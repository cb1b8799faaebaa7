"""Share of the window's batches that went out because ``max_delay_s`` ran
out, in % (program_counter): ``batcher_flush_timer`` over the four flush
reasons (``size``, ``timer``, ``drained``, ``close``). A share of batches, not
of a peak: 0 is a true reading (no batch waited out the timer), given as long
as any batch was flushed at all. The ``.fanin`` metric's formula under
``fanex4m_c8``, whose batcher keeps the class's default of 2 ms: the cell
judges it."""

REASONS = ("size", "timer", "drained", "close")


def read(run):
    c = run["counters"]
    flushed = sum(c.get(f"batcher_flush_{r}", 0) for r in REASONS)
    if not flushed:
        return None
    return 100.0 * c.get("batcher_flush_timer", 0) / flushed
