"""The CPU a landing's ``device_put`` costs its thread, in us per landing
(program_counter): ``lens_hbm_cpu_ns`` / ``lens_hbm_ops``, the thread-CPU twin
of ``hbm_place_us.*`` (hop ``hbm`` of ``tpurpc/obs/lens.py``, one op a
message). The stage is a dispatch: ``hbm_place_us`` less this is the thread's
line for the interpreter plus whatever the runtime blocks on inside the call;
a staging copy at memory speed is CPU. A program whose stages read one clock
(no ``lens_cpu_clock_reads``) gives nothing to read."""


def read(run):
    c = run["counters"]
    # the harness's delta drops a counter that did not move, and a CPU clock
    # read for one message in N on a host where it steps in 10 ms may not
    # move: the program's count of its reads says the second clock is there
    if not c.get("lens_cpu_clock_reads") or not c.get("lens_hbm_ops"):
        return None
    return c.get("lens_hbm_cpu_ns", 0) / c["lens_hbm_ops"] / 1e3
