"""What the observers themselves take, in % of one core over the window
(program_counter): 100 x d``obs_bg_cpu_ns`` / d``proc_wall_ns``. Every
background loop under ``tpurpc/obs/`` (the 50 Hz stage sampler, the tsdb, the
SLO loop, the watchdog, a collector) bills its own thread's CPU once a tick
(``metrics.observer_tick``); they hold the interpreter for most of it. A
program without the counters gives nothing to read.

The interval is the one between the harness's two ``stats`` asks, before the
clients are told to go and after the last has reported: longer than the
measured window (1.7 to 15% of 15 s on the chip, PERF.md 5), and, since a
reader runs only under ``--trace 1``, it holds the profiler session: its
start, 2 s of tracing and the export at ``trace_stop``, which the observers'
loops run through as they do any other second. What an untraced server reads
is in PERF.md 5, beside this."""


def read(run):
    c = run["counters"]
    wall = c.get("proc_wall_ns")
    if not wall:
        return None
    return 100.0 * c.get("obs_bg_cpu_ns", 0) / wall
