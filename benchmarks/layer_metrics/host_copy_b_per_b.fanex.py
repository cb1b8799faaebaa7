"""Host memcpy bytes per payload byte (program_counter): the ledger's
``host_copy`` over the window, server plus clients, over the payload
acknowledged. Both directions are in it: a request or a reply that fell back
to the framed ring is copied on the host on both sides.

The ``.stream`` metric's formula under ``fanex4m_c8``; the part of it billed
in the client processes is ``reply_host_copy_b_per_b.fanex``."""


def read(run):
    if not run["payload_bytes"]:
        return None
    copied = (run["server_ledger"].get("host_copy", 0)
              + run["client_ledger"].get("host_copy", 0))
    return copied / run["payload_bytes"]
