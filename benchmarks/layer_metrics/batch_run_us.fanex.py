"""The consumer's dispatch, ``fn(batch, rows)``, in us per batch
(program_counter): ``lens_batch_run_busy_ns`` / ``lens_batch_run_ops``, hop
``batch_run`` of ``tpurpc/obs/lens.py``. In ``fanex4m_c8`` the consumer
returns a device array, so the stage also holds the request for its
read-back (``copy_to_host_async``, started before the wait for the stacked
batch); the read-back itself is awaited under ``batch_d2h``. Asynchronous:
not device time. On the batcher's thread, between the stack's dispatch and
the wait for it; read it against ``batch_period_us``. The ``.fanin``
metric's formula. A program without the hop gives nothing to read."""


def read(run):
    c = run["counters"]
    if not c.get("lens_batch_run_ops"):
        return None
    return c.get("lens_batch_run_busy_ns", 0) / c["lens_batch_run_ops"] / 1e3
