"""Bytes read back from the device per reply payload byte (program_counter):
the server ledger's ``dma_d2h`` over the window / the payload acknowledged
(a reply is as large as its message). 1.0 is the floor and the expected
reading: every reply byte leaves HBM once. A program that bills no
``dma_d2h`` on its reply path gives nothing to read."""


def read(run):
    moved = run["server_ledger"].get("dma_d2h")
    if not run["payload_bytes"] or moved is None:
        return None
    return moved / run["payload_bytes"]
