"""Request rows in a dispatched batch, mean over the window
(program_counter): ``batcher_rows`` / ``batcher_batches``. ``max_rows`` (8 in
``fanex4m_c8``) is a full batch; pad rows are not counted. The
``.fanin`` metric's formula under ``fanex4m_c8``, where the batcher runs
with the class's ``max_delay_s`` of 2 ms: under 8 means the timer cut
batches short. A program whose batcher counts neither gives nothing to
read."""


def read(run):
    c = run["counters"]
    if not c.get("batcher_batches"):
        return None
    return c.get("batcher_rows", 0) / c["batcher_batches"]
