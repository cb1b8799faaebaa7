"""The CPU one batch's gather costs the batcher's thread, in us per batch
(program_counter): ``lens_batch_stack_cpu_ns`` / ``lens_batch_stack_ops``, the
thread-CPU twin of ``batch_stack_us.*``: the stack program's dispatch, the
wait for the stacked batch (``batch_ready_us``, nearly no CPU) and the release
of the rows; the consumer's dispatch is taken out on both clocks. A program
whose stages read one clock gives nothing to read."""


def read(run):
    c = run["counters"]
    # the harness's delta drops a counter that did not move, and a CPU clock
    # read for one message in N on a host where it steps in 10 ms may not
    # move: the program's count of its reads says the second clock is there
    if not c.get("lens_cpu_clock_reads") or not c.get("lens_batch_stack_ops"):
        return None
    return c.get("lens_batch_stack_cpu_ns", 0) / c["lens_batch_stack_ops"] / 1e3
