"""The CPU one reply's write costs the thread that writes it, in us per reply
(program_counter): ``lens_srv_send_cpu_ns`` / ``lens_srv_send_ops``, the
thread-CPU twin of ``srv_send_us.*``. The 4 MiB copy into the client's landing
region runs with the interpreter released and is this thread's CPU all the
same; ``srv_send_us`` less this is the line for the interpreter
(``place_return_us`` is its share after the copy). A program whose stages read
one clock gives nothing to read."""


def read(run):
    c = run["counters"]
    # the harness's delta drops a counter that did not move, and a CPU clock
    # read for one message in N on a host where it steps in 10 ms may not
    # move: the program's count of its reads says the second clock is there
    if not c.get("lens_cpu_clock_reads") or not c.get("lens_srv_send_ops"):
        return None
    return c.get("lens_srv_send_cpu_ns", 0) / c["lens_srv_send_ops"] / 1e3
