"""Bytes the landing moves per payload byte (program_counter): the server
ledger's ``dma_h2d`` over the window's payload. 1.0 is the floor and what one
``device_put`` a message reads; more means a message was landed twice or a
framed one was staged. The ``.fanin`` metric's formula under
``fanex4m_c8``."""


def read(run):
    if not run["payload_bytes"]:
        return None
    return run["server_ledger"].get("dma_h2d", 0) / run["payload_bytes"]
