"""A batch's result awaited on the host, in us per batch (program_counter):
``lens_batch_d2h_busy_ns`` / ``lens_batch_d2h_ops``, hop ``batch_d2h`` of
``tpurpc/obs/lens.py``: ``jax.device_get`` of the consumer's result on one of
the batcher's completion threads. The read-back was asked for on the
batcher's thread before its wait for the stacked batch, so this is the wait
for what the device still had to finish (the stack, the consumer) and for
the transfer of ``max_rows`` rows, and the thread's wait for the
interpreter. Up to ``d2h_workers`` of them run side by side: it can exceed
``batch_period_us`` without being the pace. A program whose batcher read
nothing back gives nothing to read."""


def read(run):
    c = run["counters"]
    if not c.get("lens_batch_d2h_ops"):
        return None
    return c.get("lens_batch_d2h_busy_ns", 0) / c["lens_batch_d2h_ops"] / 1e3
