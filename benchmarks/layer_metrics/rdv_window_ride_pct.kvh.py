"""Share of the messages the server took in by rendezvous that rode a
standing region of a larger size class than their own, in %
(program_counter): 100 x ``rdv_window_rides`` / ``rdv_transfers_received``
(``core/rendezvous.py`` ``RdvLink.on_complete``, the Python plane's
receiver, which the cells' server runs). A link's standing regions are one
window of the largest class it streams, so in ``kvh_mla_c8`` the messages
of a sequence's last chunk (4 to 63 pages) ride 5 MiB regions; a stream of
one message size rides none and reads 0. A program without that window
has no ``rdv_window_received`` (completions into a standing region) and
gives nothing to read."""


def read(run):
    c = run["counters"]
    if not c.get("rdv_window_received") or not c.get(
            "rdv_transfers_received"):
        return None
    return 100.0 * c.get("rdv_window_rides", 0) / c["rdv_transfers_received"]
