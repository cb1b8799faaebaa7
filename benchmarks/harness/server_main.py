"""The server child: the one process that owns the chip.

Started by ``run.py`` as a user starts a server: ``tpurpc.rpc.server.Server``
with the cell's handler registered, ``GRPC_PLATFORM_TYPE`` from the
configuration, every default of ``tpurpc/utils/config.py`` left alone.
Commands come in on stdin, one line each; answers go out on stdout as
``@tag <json>`` lines (and ``@blob <n>`` followed by ``n`` raw bytes).
Only this process can trace the chip, so the profiler runs here.
"""

from __future__ import annotations

import importlib
import json
import os
import shutil
import sys
import time
import types


def say(tag: str, obj) -> None:
    sys.stdout.buffer.write(f"@{tag} {json.dumps(obj)}\n".encode())
    sys.stdout.buffer.flush()


def main() -> int:
    t_start = time.monotonic()
    spec = json.loads(sys.argv[1])
    rehearsal = spec["rehearsal_cpu"]

    from tpurpc.utils import jaxenv

    cache_dir = jaxenv.enable_compile_cache()  # before first use of jax
    import jax

    jaxenv.count_compiles()
    phases = {"import_jax_s": time.monotonic() - t_start}
    dev = jax.devices()[0]
    phases["reach_device_s"] = time.monotonic() - t_start
    want = "cpu" if rehearsal else "tpu"
    if dev.platform != want or len(jax.devices()) < spec["chips"]:
        say("fatal", f"wanted {spec['chips']} {want} device(s); jax.devices() "
            f"is {jax.devices()}: the benchmark does not fall back")
        return 3

    from tpurpc.core import _native
    from tpurpc.obs import metrics
    from tpurpc.rpc.server import Server
    from tpurpc.tpu import ledger
    from tpurpc.utils.config import get_config

    ctx = types.SimpleNamespace(
        config=spec["config"], traffic=spec["traffic"], seed=spec["seed"],
        device=dev, fault=spec.get("fault"))
    handler = importlib.import_module(
        f"benchmarks.handlers.{spec['config']['handler']}").build(ctx)
    srv = Server(max_workers=64)
    handler.register(srv)
    srv.start()
    port = srv.add_insecure_port("127.0.0.1:0")
    handler.sync()
    phases["pool_and_server_s"] = time.monotonic() - t_start

    def memory() -> dict:
        stats = dev.memory_stats() or {}
        return {k: stats.get(k) for k in ("bytes_in_use",
                                          "peak_bytes_in_use", "bytes_limit")}

    def snapshot() -> dict:
        return {"t": time.monotonic(),
                "counters": metrics.registry().counters_snapshot(),
                "ledger": ledger.snapshot(), "memory": memory(),
                "counts": handler.counts()}

    say("ready", {
        "port": port,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "platform_type": get_config().platform.name,
        "hbm_ring_size": get_config().hbm_ring_size,
        "native": _native.status(),
        "cache_dir": cache_dir,
        "server_env": {k: os.environ.get(k)
                       for k in spec["config"].get("server_env", {})},
        "pool_bytes": handler.pool_bytes,
        "startup_s": time.monotonic() - t_start,
        "phases": phases,
        "stats": snapshot()})

    trace_dir = window_span = None
    for line in sys.stdin:  # EOF means the parent is gone
        cmd, _, arg = line.strip().partition(" ")
        if cmd == "stats":
            say("stats", snapshot())
        elif cmd == "trace_start":
            from jax.profiler import ProfileOptions

            trace_dir = arg
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            t0 = time.monotonic()
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            window_span = jax.profiler.TraceAnnotation("bench.window")
            window_span.__enter__()
            say("trace_started", {"start_s": time.monotonic() - t0,
                                  "stats": snapshot()})
        elif cmd == "trace_stop":
            stats = snapshot()
            window_span.__exit__(None, None, None)
            t0 = time.monotonic()
            jax.profiler.stop_trace()
            say("trace_stopped", {"stop_s": time.monotonic() - t0,
                                  "stats": stats})
        elif cmd == "trace_reduce":
            # the reduction needs jax's ProfileData: only this process may
            # import jax, so it reads the trace; the arithmetic is the
            # benchmark's (harness/trace_reduce.py)
            from benchmarks.harness import trace_reduce

            say("trace", trace_reduce.reduce_dir(trace_dir, **json.loads(arg)))
            shutil.rmtree(trace_dir, ignore_errors=True)
        elif cmd == "audit":
            handler.sync()
            facts, blobs = handler.audit(json.loads(arg))
            say("audit", {"facts": facts, "blobs": [len(b) for b in blobs]})
            for b in blobs:
                sys.stdout.buffer.write(f"@blob {len(b)}\n".encode() + b)
            sys.stdout.buffer.flush()
        elif cmd == "free":
            handler.free()
            say("freed", {"memory": memory()})
        elif cmd == "stop":
            break
    srv.stop(grace=5)
    say("bye", {})
    return 0


if __name__ == "__main__":
    sys.exit(main())
