"""A row queued in the batcher, from ``submit`` to the dispatch of its batch,
in us per row (program_counter): ``lens_batch_wait_busy_ns`` /
``lens_batch_wait_ops``, hop ``batch_wait`` of ``tpurpc/obs/lens.py``. The
wait for the other rows of the batch (or for ``max_delay_s``), then for the
batcher's thread, which may still be stacking the batch before or be held
back by the bound on batches in flight to the host. No thread waits with
the row: its handler has yielded the row's future and gone on. The
``.fanin`` metric's formula under ``fanex4m_c8``. A program without the hop
gives nothing to read."""


def read(run):
    c = run["counters"]
    if not c.get("lens_batch_wait_ops"):
        return None
    return c.get("lens_batch_wait_busy_ns", 0) / c["lens_batch_wait_ops"] / 1e3
