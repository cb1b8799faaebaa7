"""The cores the server process kept busy over the window (program_counter):
d``proc_cpu_ns`` / d``proc_wall_ns``, the process's CPU clock (every thread,
Python or not) over the monotonic clock, both set by a collector of the
registry at every snapshot (``tpurpc/obs/metrics.py``). Over 1: something runs
beside the interpreter (released copies, the runtime's threads). A program
without the two counters gives nothing to read.

The interval is the one between the harness's two ``stats`` asks, before the
clients are told to go and after the last has reported: longer than the
measured window (1.7 to 15% of 15 s on the chip, PERF.md 5), and, since a
reader runs only under ``--trace 1``, it holds the profiler session: its
start, 2 s of tracing and the export at ``trace_stop``, all CPU of this
process. What an untraced server reads is in PERF.md 5, beside this."""


def read(run):
    c = run["counters"]
    wall = c.get("proc_wall_ns")
    if not wall:
        return None
    return c.get("proc_cpu_ns", 0) / wall
