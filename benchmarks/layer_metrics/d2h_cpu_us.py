"""The CPU a reply's read-back costs its thread, in us per reply
(program_counter): ``lens_d2h_cpu_ns`` / ``lens_d2h_ops``, the thread-CPU twin
of ``d2h_us.pingpong`` (hop ``d2h``: every device leaf's transfer started,
then each awaited). Near ``d2h_us``: the read-back is a copy the host makes;
far under it: a wait on the runtime. 0: no step of the thread's clock fell in
a clocked read-back of this window (one reply in N is clocked, and the chip
host's clock steps in 10 ms: one step is some 115 us a reply of a 15 s
window's 2,700), which says the same. A program whose stages read one clock gives
nothing to read."""


def read(run):
    c = run["counters"]
    # the harness's delta drops a counter that did not move, and a CPU clock
    # read for one message in N on a host where it steps in 10 ms may not
    # move: the program's count of its reads says the second clock is there
    if not c.get("lens_cpu_clock_reads") or not c.get("lens_d2h_ops"):
        return None
    return c.get("lens_d2h_cpu_ns", 0) / c["lens_d2h_ops"] / 1e3
