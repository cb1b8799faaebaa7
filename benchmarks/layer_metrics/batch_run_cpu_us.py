"""The CPU the consumer's dispatch costs the batcher's thread, in us per batch
(program_counter): ``lens_batch_run_cpu_ns`` / ``lens_batch_run_ops``, the
thread-CPU twin of ``batch_run_us.*`` (``fn(batch)``; in ``fanex4m_c8`` the
cut's dispatch and the asks for the read-back too). ``batch_run_us`` less this
is the thread's line for the interpreter plus what the runtime blocks on
inside the dispatches. A program whose stages read one clock gives nothing to
read."""


def read(run):
    c = run["counters"]
    # the harness's delta drops a counter that did not move, and a CPU clock
    # read for one message in N on a host where it steps in 10 ms may not
    # move: the program's count of its reads says the second clock is there
    if not c.get("lens_cpu_clock_reads") or not c.get("lens_batch_run_ops"):
        return None
    return c.get("lens_batch_run_cpu_ns", 0) / c["lens_batch_run_ops"] / 1e3
