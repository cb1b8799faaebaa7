"""The batcher thread's one wait for the device, in us per batch
(program_counter): ``lens_batch_ready_busy_ns`` / ``lens_batch_ready_ops``, hop
``batch_ready`` of ``tpurpc/obs/lens.py``: ``block_until_ready`` of the stacked
batch whose rows hold credit, a child of ``batch_stack`` (``batch_stack_us``
holds it). Wall, because it is a wait: what the device needs for the stack is
about 100 us, the rest is the line to have the interpreter back. A program
without the hop gives nothing to read."""


def read(run):
    c = run["counters"]
    ops = c.get("lens_batch_ready_ops")
    if not ops:
        return None
    return c.get("lens_batch_ready_busy_ns", 0) / ops / 1e3
