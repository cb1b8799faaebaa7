"""Time from one consumed batch to the next, in us (program_counter): the
mean time the server spent in a call (``lens_srv_call_busy_ns`` /
``lens_srv_call_ops``: the connections' streams, each of which lasts the
window) over the batches consumed in the window (``batcher_batches``). The
calls open up to 0.25 s before the window does, so it reads 1 to 2% over
window / batches. The one number the batcher's stages are read against:
stages laid end to end (a lock-step) add up to it; in a pipeline it is
SHORTER than a connection's landing of one message plus ``batch_stack_us``
plus ``batch_run_us``. A program whose batcher counts no batch, or a window
in which no call ended, gives nothing to read."""


def read(run):
    c = run["counters"]
    batches, calls = c.get("batcher_batches"), c.get("lens_srv_call_ops")
    if not batches or not calls:
        return None
    return c.get("lens_srv_call_busy_ns", 0) / calls / batches / 1e3
