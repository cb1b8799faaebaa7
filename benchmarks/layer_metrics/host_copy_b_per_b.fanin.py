"""Host memcpy bytes per payload byte (program_counter): the ledger's
``host_copy`` over the window, server plus clients.

The ``.stream`` metric's formula under ``fanin4m_c8``: the same inbound path
(rendezvous wire, one ``device_put`` a message), with a batcher behind it."""


def read(run):
    if not run["payload_bytes"]:
        return None
    copied = (run["server_ledger"].get("host_copy", 0)
              + run["client_ledger"].get("host_copy", 0))
    return copied / run["payload_bytes"]
