"""Bytes read back from the device per reply payload byte (program_counter):
the server ledger's ``dma_d2h`` over the window / the payload acknowledged (a
reply is as large as its request). The batcher bills a batch's result once an
output leaf (``FanInBatcher._complete_loop``). 1.0 is the floor and the
reading of full batches: every reply byte leaves HBM once; a batch cut short
by the timer still reads ``max_rows`` rows back, so it rises as
``batch_rows_mean`` falls. A program that bills no ``dma_d2h`` for its
batches gives nothing to read."""


def read(run):
    moved = run["server_ledger"].get("dma_d2h")
    if not run["payload_bytes"] or moved is None:
        return None
    return moved / run["payload_bytes"]
