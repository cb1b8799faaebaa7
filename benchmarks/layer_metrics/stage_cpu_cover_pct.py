"""The share of the server process's CPU that its stages see, in %
(program_counter): 100 x the ``lens_<hop>_cpu_ns`` of the additive stages of
the call path and of the batcher (``srv_recv``, ``srv_handler``, ``srv_send``,
``batch_stack``, ``batch_run``, ``batch_d2h``) / d``proc_cpu_ns``. The rest is
what no stage is on: the connections' reader threads, the reaper, the
runtime's own threads, the observers (``obs_bg_cpu_pct``), and in a traced
run the profiler session itself. A program without the counters gives
nothing to read.

The interval is the one between the harness's two ``stats`` asks, before the
clients are told to go and after the last has reported: longer than the
measured window (1.7 to 15% of 15 s on the chip, PERF.md 5), and, since a
reader runs only under ``--trace 1``, it holds the profiler session: its
start, 2 s of tracing and the export at ``trace_stop``, all CPU of this
process. What an untraced server reads is in PERF.md 5, beside this."""

STAGES = ("srv_recv", "srv_handler", "srv_send", "batch_stack", "batch_run",
          "batch_d2h")


def read(run):
    c = run["counters"]
    cpu = c.get("proc_cpu_ns")
    # (a stage's counter that did not move is not in the harness's delta;
    # the count of the clock's reads says the second clock is there)
    if not cpu or not c.get("lens_cpu_clock_reads"):
        return None
    return 100.0 * sum(c.get(f"lens_{hop}_cpu_ns", 0) for hop in STAGES) / cpu
