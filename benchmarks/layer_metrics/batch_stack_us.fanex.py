"""One batch gathered, in us per batch (program_counter):
``lens_batch_stack_busy_ns`` / ``lens_batch_stack_ops``, hop ``batch_stack``
of ``tpurpc/obs/lens.py``: the stack program's dispatch, then, after the
consumer's dispatch (``batch_run``, taken out), the wait until the stacked
batch is ready on the device and the release of its rows' leases. On the
batcher's thread; read it against ``batch_period_us``. The ``.fanin``
metric's formula under ``fanex4m_c8``. A program without the hop gives
nothing to read."""


def read(run):
    c = run["counters"]
    ops = c.get("lens_batch_stack_ops")
    if not ops:
        return None
    return c.get("lens_batch_stack_busy_ns", 0) / ops / 1e3
