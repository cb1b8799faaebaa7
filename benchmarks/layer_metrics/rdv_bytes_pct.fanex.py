"""Share of the request payload that reached the server by rendezvous, the
fast path, in % (program_counter): region bytes the receiver was handed
(``native_rdv_recv_bytes`` on the C plane, ``rdv_bytes_received`` on the
Python plane; a message crosses one of the two) over the payload
acknowledged. Region bytes include the codec's header, so a stream that never
falls back reads a few thousandths over 100: a share of traffic, not of a
peak. A program that has neither counter (or moved no byte this way: the
window's counters hold only what changed) gives nothing to read.

The ``.stream`` metric's formula under ``fanex4m_c8``, where the eight
server-side links send 4 MiB one-sided while the eight client-side links
send to them: the replies' way out is ``reply_rdv_bytes_pct.fanex``."""

PLANES = ("native_rdv_recv_bytes", "rdv_bytes_received")


def read(run):
    c = run["counters"]
    if not run["payload_bytes"] or not any(k in c for k in PLANES):
        return None
    return 100.0 * sum(c.get(k, 0) for k in PLANES) / run["payload_bytes"]
