"""The CPU the registered behavior costs its thread with one message besides
decoding it, in us per message (program_counter): (``lens_srv_handler_cpu_ns``
- ``lens_decode_cpu_ns``) / ``lens_srv_handler_ops``, the thread-CPU twin of
``srv_handler_self_us.stream`` and of ``srv_handoff_us.fanin`` / ``.fanex``
(in the fan-in cells the handler's own part is the hand-over: ``take_leases``
and ``submit``). The stage makes a dispatch in the stream cells and no
blocking call in the fan-in cells, so wall less this is the line for the
interpreter (and, in the stream cells, what the runtime blocks on). A program
whose stages read one clock gives nothing to read."""


def read(run):
    c = run["counters"]
    # the harness's delta drops a counter that did not move, and a CPU clock
    # read for one message in N on a host where it steps in 10 ms may not
    # move: the program's count of its reads says the second clock is there
    if not c.get("lens_cpu_clock_reads") or not c.get("lens_srv_handler_ops"):
        return None
    own = c.get("lens_srv_handler_cpu_ns", 0) - c.get("lens_decode_cpu_ns", 0)
    return own / c["lens_srv_handler_ops"] / 1e3
