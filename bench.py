"""tpurpc headline benchmark: 4MB tensor streaming into jax.Array.

Mirrors the reference's large-payload bandwidth test (RDMA_BP, 128KB–4MB
payloads → 82.6 Gb/s on IB EDR, SURVEY.md §6) recast as the TPU north star:
client streams float32[1024,1024] (4 MiB) tensors over the ring transport;
the server decodes each into a ``jax.Array`` on JAX's default device (TPU
HBM) and acknowledges with total bytes.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
vs_baseline is against the reference's 82.6 Gb/s (= 10.325 GB/s) aggregate
TX bandwidth — measured on InfiniBand EDR hardware; we run whatever link the
bench host gives us (loopback shm rings here).

The benchmark needs an accelerator: where JAX finds none the run exits
non-zero and prints no result. ``TPURPC_BENCH_CPU=1`` asks for the host path
on the CPU instead; the result then says ``"jax_platform": "cpu"`` and carries
no device metric (no ``*_mfu``, no ``peak_flops``: a host matmul is not a
peak). Every number names the device it came from.

One process per chip: this parent is the client and never initialises a JAX
backend (checked after ``main()``); the server child owns the chip. The
server prints its port *before* jax backend init, then warms the backend and
prints READY; the client budgets that cold start (TPURPC_BENCH_READY_S,
default 300) outside every RPC deadline. Server stderr is captured and
surfaced on any failure. A leg that was asked to run and raised is recorded
under ``<leg>_error``, the JSON is still printed, and the exit code is 1.

Env knobs: TPURPC_BENCH_MSGS (default 96 × 4MiB), TPURPC_BENCH_PLATFORM
(default RDMA_BPEV = hybrid-wakeup ring), TPURPC_BENCH_CPU=1 (see above).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import threading
import time

BASELINE_GBPS = 82.6 / 8  # reference aggregate bandwidth, GB/s

_SERVER_CODE = r"""
import os, sys, time
import numpy as np

from tpurpc.rpc.server import Server

# Two servers (deployment guidance, round 9 / tpurpc-express): the BULK
# sink defaults to the instrumented PYTHON plane because that is where
# the rendezvous bulk-tensor path lives — payloads over the size bar move
# as ONE one-sided write into a pre-granted landing region and the codec
# aliases it in place (ISSUE 9). Same-weather A/B on this rig, 4 MiB
# tensor streams: python+rendezvous 3.6 GB/s vs native framed 1.72 vs
# python framed 0.65 — the rendezvous plane wins by ~2.1x over the
# previous default, so it IS the default; TPURPC_BENCH_SINK_NATIVE=1
# flips back to the native framed plane (the C loop does not speak the
# rendezvous control frames yet — ROADMAP item 5 territory).
srv = Server(max_workers=8,
             native_dataplane=False
             if os.environ.get("TPURPC_BENCH_SINK_NATIVE", "0") == "0"
             else None)
port = srv.add_insecure_port("127.0.0.1:0")
# Serving workers sized for PIPELINED clients (ISSUE 3): a request parks
# its pool worker inside the FanInBatcher until its batch completes, so
# max_workers caps how many requests can even REACH the batcher — 8
# workers flat-lined the depth sweep at one batch in flight. 64 covers
# 8 clients x depth 16 minus the batcher's own bounded pipeline.
srv_infer = Server(max_workers=64)
port_infer = srv_infer.add_insecure_port("127.0.0.1:0")
# Python-dataplane sink for the batch-stats probe: when the MEASURED plane
# is the native one (whose batching is C-side, invisible to the Python
# counters), the client runs one short untimed stream against this server
# so the artifact still carries real drain-batch histograms.
srv_probe = Server(max_workers=2, native_dataplane=False)
port_probe = srv_probe.add_insecure_port("127.0.0.1:0")
print("PORT", port, port_infer, port_probe, flush=True)  # bind first

# Backend bring-up OUTSIDE any RPC deadline: the client waits for READY with
# its own wall budget.
from tpurpc.utils import jaxenv
jaxenv.enable_compile_cache()  # before first use of jax
import jax
if os.environ.get("TPURPC_BENCH_CPU") == "1":
    jax.config.update("jax_platforms", "cpu")
t0 = time.time()
dev = jax.devices()[0]
if dev.platform == "cpu" and os.environ.get("TPURPC_BENCH_CPU") != "1":
    print("bench.py needs an accelerator and jax.devices() is", jax.devices(),
          "- TPURPC_BENCH_CPU=1 runs the host path on the CPU instead (no "
          "device metrics)", file=sys.stderr, flush=True)
    sys.exit(3)
x = jax.device_put(np.ones((1024, 1024), np.float32))
x.block_until_ready()
y = (x[:8, :8] + 1.0).block_until_ready()   # trivial compile warm
print("WARM", dev.platform, round(time.time() - t0, 1), file=sys.stderr,
      flush=True)

from tpurpc.jaxshim import FanInBatcher, add_tensor_method, to_jax

def consume(req_iter):
    # Bounded-depth h2d pipeline: receive/decode message k+1 while message
    # k's device_put is in flight. On ACCELERATORS the checksum accumulates
    # ON DEVICE, so the hot loop holds no d2h round trip; ONE readback
    # happens at stream end. On the CPU that device-side accumulate is
    # ~0.6 ms/message of pure op-dispatch overhead (measured on a CPU,
    # tpurpc-express round) for arrays the rendezvous path dlpack-ALIASES
    # host-side — a zero-copy numpy read is the same delivery proof at
    # ~1 µs. (Depth 3 and the on-device checksum were tuned on a link that
    # no longer exists; neither has been measured on the chip.)
    from collections import deque
    import jax.numpy as jnp
    total = 0
    on_cpu = dev.platform == "cpu"
    checksum = jnp.float32(0.0)
    checksum_f = 0.0
    inflight = deque()

    def retire(arr):
        nonlocal total, checksum, checksum_f
        arr.block_until_ready()   # bound in-flight transfers to the deque
        total += arr.nbytes       # depth
        if on_cpu:
            checksum_f += float(np.asarray(arr)[0, 0])  # zero-copy read
        else:
            checksum = checksum + arr[0, 0]  # async device-side accumulate

    for tree in req_iter:
        inflight.append(to_jax(tree["x"]))   # async dispatch -> device
        if len(inflight) > 3:
            retire(inflight.popleft())
    while inflight:
        retire(inflight.popleft())
    checksum = checksum + jnp.float32(checksum_f)
    # Batched-pipeline observability (ISSUE 1): snapshot the cumulative
    # batch histograms + wakeup counters at the end of every Sink stream.
    # Printed BEFORE the final yield so the line is flushed before the
    # client unblocks on the stream reply; the client picks the snapshot
    # matching its last timed round by ordinal.
    try:
        import json as _json

        from tpurpc.utils import stats as _st
        print("BATCHSTATS", _json.dumps({"batch": _st.batch_snapshot(),
                                         "counters": _st.counters_snapshot()}),
              flush=True)
    except Exception:
        pass
    yield {"bytes": np.int64(total), "check": np.float64(float(checksum))}

add_tensor_method(srv, "Sink", consume, kind="stream_stream")
add_tensor_method(srv_probe, "Sink", consume, kind="stream_stream")

# ---- serving flagship (BASELINE configs #4/#5): ResNet + fan-in batching --
# Full ResNet-50 @224 on an accelerator; the thin-18 stand-in where the CPU
# was asked for, so that run stays fast. fixed_bucket -> ONE compiled shape.
batcher = None
if os.environ.get("TPURPC_BENCH_SERVING", "1") == "1":
    import jax.numpy as jnp
    from tpurpc.models.resnet import (init_resnet, make_infer_fn,
                                      resnet18_thin, resnet50)

    on_accel = dev.platform not in ("cpu",)
    if on_accel:
        model, img, model_name = resnet50(dtype=jnp.bfloat16), 224, "resnet50"
    else:
        # Stand-in geometry (TPURPC_BENCH_SERVING_IMG): the CPU
        # phase exists to exercise the SERVING TRANSPORT, so the stand-in
        # must leave the transport as the bottleneck. At @64 a 1-core rig
        # is compute-bound before depth 1 even saturates (measured: the
        # idle-core ceiling for thin-18@64 is ~1.6K inf/s, which depth-1
        # serving already half-fills) and the ISSUE 3 depth sweep would
        # measure conv throughput, not pipelining. @48 keeps thin-18
        # recognizable while restoring transport-boundedness; artifacts
        # record the geometry (serving_image_size) so rounds compare
        # like-for-like (r2-r5 ran @64).
        img = int(os.environ.get("TPURPC_BENCH_SERVING_IMG", "48"))
        model, model_name = resnet18_thin(), "resnet18_thin"
    variables = init_resnet(jax.random.PRNGKey(0), model, image_size=img)
    infer = jax.jit(make_infer_fn(model))
    MAXB = int(os.environ.get("TPURPC_BENCH_SERVING_BATCH", "8"))

    def serve_fn(tree):
        return {"logits": infer(variables, tree["x"])}

    # NOTE on depth-aware flush: serve_jax wires FanInBatcher to
    # Server.inflight_requests (flush as soon as no more arrivals can
    # come). The BENCH batcher deliberately stays on timer/size-only
    # batching: under fixed_bucket (every dispatch padded+compiled at
    # max_batch) a flush heuristic misfiring in the closed-loop stagger
    # gap costs 7/8 of the compute, and cross-round serving_qps
    # comparability (r2-r5 artifacts) rides this exact configuration.
    batcher = FanInBatcher(serve_fn, max_batch=MAXB, max_delay_s=0.005,
                          fixed_bucket=True,
                          transfer_dtype=jnp.bfloat16 if on_accel else None)
    add_tensor_method(srv_infer, "Infer", batcher)
    # warm the single compiled batch shape before READY — in the dtype the
    # batcher ships (transfer_dtype), or the first request compiles again
    warm = np.zeros((MAXB, img, img, 3),
                    jnp.bfloat16 if on_accel else np.float32)
    jax.tree_util.tree_map(lambda x: x.block_until_ready(),
                           infer(variables, warm))
    # Analytic per-inference FLOPs straight from XLA's cost model (exact for
    # the compiled graph; no hand-derived constant to go stale), and a
    # device-only batched-inference rate: MFU of the *compute path* with the
    # RPC out of the picture. Serving QPS divided by the same peak gives
    # end-to-end MFU; the gap between the two is transport cost. A failure
    # here fails the run: a result without its FLOPs has no device metric.
    ca = infer.lower(variables, warm).compile().cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    flops_per_inf = float(ca["flops"]) / MAXB
    warm_dev = jax.device_put(warm)  # exclude h2d from the compute rate
    reps, t0 = 0, time.time()
    while time.time() - t0 < 1.0:
        jax.tree_util.tree_map(lambda x: x.block_until_ready(),
                               infer(variables, warm_dev))
        reps += 1
    dev_qps = reps * MAXB / (time.time() - t0)
    print("FLOPS", flops_per_inf, round(dev_qps, 1), flush=True)
    # stdout: the client parses this line (single source of model/img truth)
    print("SERVING", model_name, img, flush=True)

srv.start()
srv_infer.start()
srv_probe.start()
print("DEVKIND", getattr(dev, "device_kind", dev.platform), flush=True)
print("READY", dev.platform, ("serving" if batcher else "noserving"),
      flush=True)
srv.wait_for_termination(timeout=1200)
srv_infer.stop(grace=0)
srv_probe.stop(grace=0)
"""


class _ServerProc:
    """Bench server subprocess with line-oriented readiness + stderr capture."""

    def __init__(self, env):
        self.stderr_file = tempfile.NamedTemporaryFile(
            mode="w+", prefix="tpurpc_bench_srv_", suffix=".err", delete=False)
        self.proc = subprocess.Popen(
            [sys.executable, "-u", "-c", _SERVER_CODE],
            stdout=subprocess.PIPE, stderr=self.stderr_file, env=env,
            text=True)
        self._lines: list[str] = []
        self._cond = threading.Condition()
        self._reader = threading.Thread(target=self._drain, daemon=True)
        self._reader.start()

    def _drain(self):
        for line in self.proc.stdout:
            with self._cond:
                self._lines.append(line.strip())
                self._cond.notify_all()
        with self._cond:
            self._lines.append(None)  # EOF sentinel
            self._cond.notify_all()

    def wait_line(self, prefix: str, timeout: float):
        deadline = time.time() + timeout
        seen = 0
        with self._cond:
            while True:
                while seen < len(self._lines):
                    line = self._lines[seen]
                    seen += 1
                    if line is None:
                        raise RuntimeError(
                            f"server exited before '{prefix}'"
                            f" (rc={self.proc.poll()})\n{self.stderr_tail()}")
                    if line.startswith(prefix):
                        return line
                remain = deadline - time.time()
                if remain <= 0:
                    raise TimeoutError(
                        f"server did not print '{prefix}' within {timeout}s\n"
                        f"{self.stderr_tail()}")
                self._cond.wait(remain)

    def nth_line(self, prefix: str, n: int, timeout: float):
        """n-th (1-based) buffered line starting with ``prefix``, waiting up
        to ``timeout`` for it to arrive; on timeout/EOF falls back to the
        latest earlier match (or None). Unlike ``wait_line`` this never
        raises — it serves auxiliary observability, not readiness."""
        deadline = time.time() + timeout
        with self._cond:
            while True:
                matches = [ln for ln in self._lines
                           if ln is not None and ln.startswith(prefix)]
                if len(matches) >= n:
                    return matches[n - 1]
                eof = bool(self._lines) and self._lines[-1] is None
                remain = deadline - time.time()
                if eof or remain <= 0:
                    return matches[-1] if matches else None
                self._cond.wait(remain)

    def stderr_tail(self, n=4000) -> str:
        try:
            self.stderr_file.flush()
            with open(self.stderr_file.name) as f:
                data = f.read()
            return "--- server stderr tail ---\n" + data[-n:]
        except OSError:
            return "(server stderr unavailable)"

    def kill(self):
        self.proc.kill()
        try:
            self.proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass
        # failure paths already surfaced stderr via stderr_tail()
        try:
            self.stderr_file.close()
            os.unlink(self.stderr_file.name)
        except OSError:
            pass


def _serving_phase(port: int, model: str, img: int, platform: str = "cpu",
                   depth: "int | None" = None):
    """8-client fan-in (BASELINE config #4): concurrent image requests over
    independent connections, batched server-side into one jitted call.
    Returns (qps, model_name, n_requests); raises on failure.

    ``depth`` pins the per-client in-flight window (the ISSUE 3 sweep:
    serving_qps_by_depth at 1/4/16); None keeps the platform default +
    TPURPC_BENCH_CLIENT_DEPTH override. At depth>1 the pure-Python channel
    now pipelines too (TensorClient.call_async — stream-id demux, no
    thread per call), so the sweep is meaningful with or without
    libtpurpc.so.

    Timing starts at a barrier AFTER every client has connected and warmed
    (connection setup + first-dispatch latency excluded from the steady-state
    figure the phase exists to measure)."""
    import threading

    import numpy as np

    from tpurpc.jaxshim import TensorClient
    from tpurpc.rpc.channel import Channel

    n_clients = int(os.environ.get("TPURPC_BENCH_SERVING_CLIENTS", "8"))
    per_client = int(os.environ.get("TPURPC_BENCH_SERVING_REQS", "16"))
    image = np.random.default_rng(0).standard_normal(
        (1, img, img, 3)).astype(np.float32)
    errors: list = []
    done = [0] * n_clients
    start = threading.Barrier(n_clients + 1)

    # Serving client discipline (round 5, interleaved same-weather A/B):
    # on the CPU 8 BLOCKING clients on inline-read channels beat
    # 8 CQ-futures clients at depth 4 in 6 of 7 pairs, by 10-74% — the CQ
    # puller's wake chain costs more than pipelining recovers on one
    # shared core (the scalability profile's reader-thread result again).
    # On an ACCELERATOR the default is depth 4 + CQ, so that pipelining
    # keeps the batcher fed — chosen on a link that no longer exists and
    # not yet measured on the chip. TPURPC_BENCH_CLIENT_DEPTH overrides
    # either way.
    default_depth = "1" if platform == "cpu" else "4"
    # a malformed override must FAIL (the phase reports it), not silently
    # benchmark the platform default as if the operator's depth ran
    depth_env = (int(os.environ.get("TPURPC_BENCH_CLIENT_DEPTH",
                                    default_depth))
                 if depth is None else int(depth))

    def _make_channel():
        # NativeChannel (ctypes over libtpurpc.so) when available: the
        # closed-loop client's per-call overhead is part of the measured
        # QPS, and the native loop is ~3x the pure-Python path
        # (BASELINE.md). TPURPC_BENCH_NATIVE_CLIENT=0 opts out.
        if os.environ.get("TPURPC_BENCH_NATIVE_CLIENT", "1") == "1":
            try:
                from tpurpc.rpc.native_client import NativeChannel

                # depth 1: inline-read (round 5's same-weather winner).
                # depth>1: reader+CQ — the ISSUE 3 cross-plane A/B (python
                # and native servers, img 32 and 48) measured CQ above the
                # inline worker window at every depth>1 cell (e.g. 1310 vs
                # 1093 qps at depth 16 on the native plane): depth threads
                # on one core cost more than the CQ puller's wake chain.
                return NativeChannel("127.0.0.1", port,
                                     inline_read=depth_env <= 1,
                                     pipeline_depth=max(1, depth_env))
            except (ImportError, OSError) as exc:
                # lib missing/unbuildable: pure-Python channel, and say so
                # (serving_client_mode records what ran)
                sys.stderr.write(f"native client unavailable ({exc}); "
                                 "serving client is the Python channel\n")
        return Channel(f"127.0.0.1:{port}")

    # In-flight calls per client: >1 pipelines through the native CQ
    # futures path so the batcher sees clients*depth outstanding requests.
    # History: round 4 measured +36% at depth 4 over depth-1-with-reader;
    # round 5's wake-chain findings flipped it — depth 1 on INLINE-READ
    # channels (no reader, no CQ puller) wins by 10-29% same-weather, so
    # it is the default (the artifact's serving_client_depth records what
    # ran; r4 artifacts carry depth 4).
    depth = depth_env

    used_depth = [1] * n_clients  # what each client ACTUALLY ran
    #: channel discipline each client ACTUALLY got — depth-1 artifacts are
    #: only cross-round comparable within one mode (inline vs reader vs
    #: python differ 10-74%, the whole point of the round-5 default)
    used_mode = ["python"] * n_clients

    def client(idx: int):
        try:
            with _make_channel() as ch:
                from tpurpc.rpc.native_client import NativeChannel as _NC

                if isinstance(ch, _NC):
                    used_mode[idx] = ("native-inline" if ch.inline_read
                                      else "native-reader")
                cli = TensorClient(ch, depth=max(1, depth))
                cli.call("Infer", {"x": image}, timeout=300)  # per-conn warm
                futures_fn = None
                if depth > 1:
                    # Pipelined window, both planes (ISSUE 3): the native
                    # channel rides its CQ (reader mode) or bounded inline
                    # window; the Python channel rides PipelinedUnary —
                    # stream-id demux on the reader, no thread per call
                    # (the old .future thread-churn caveat no longer
                    # applies).
                    pl = cli.pipeline("Infer", depth=depth)
                    futures_fn = pl.call_async
                    used_depth[idx] = depth
                start.wait(timeout=600)
                if futures_fn is None:
                    for _ in range(per_client):
                        out = cli.call("Infer", {"x": image}, timeout=300)
                        assert np.asarray(out["logits"]).shape[0] == 1
                        done[idx] += 1
                else:
                    inflight = []
                    issued = 0
                    while issued < per_client or inflight:
                        while issued < per_client and len(inflight) < depth:
                            inflight.append(
                                futures_fn({"x": image}, timeout=300))
                            issued += 1
                        out = inflight.pop(0).result(timeout=300)
                        assert np.asarray(out["logits"]).shape[0] == 1
                        done[idx] += 1
        except Exception as exc:  # surfaced after join
            errors.append(exc)
            try:
                start.abort()  # never leave the main thread at the barrier
            except Exception:
                pass

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(n_clients)]
    [t.start() for t in threads]
    start.wait(timeout=600)
    t0 = time.perf_counter()
    [t.join(timeout=600) for t in threads]
    dt = time.perf_counter() - t0
    if errors:
        raise errors[0]
    if any(t.is_alive() for t in threads):
        raise TimeoutError("serving client thread still running after join "
                           "timeout; qps would be measured on a racing "
                           "partial count")
    total = sum(done)
    # one mode in practice (all clients build identically); report the set
    # defensively so a mixed run is visible rather than mislabeled
    modes = sorted(set(used_mode))
    return (total / dt, model, total, max(used_depth),
            modes[0] if len(modes) == 1 else ",".join(modes))


def _run_once(env, n_msgs: int, ready_s: float):
    import numpy as np

    from tpurpc.utils import stats as _st

    _st.reset_batch_stats()
    #: legs of this phase that were asked to run and raised: recorded, the
    #: JSON still printed, the exit code 1
    errors: dict = {}

    srv = _ServerProc(env)
    try:
        port_line = srv.wait_line("PORT", 60).split()
        port = int(port_line[1])
        port_infer = int(port_line[2]) if len(port_line) > 2 else port
        port_probe = int(port_line[3]) if len(port_line) > 3 else port
        ready = srv.wait_line("READY", ready_s)
        parts = ready.split()
        platform = parts[1]
        serving_on = len(parts) > 2 and parts[2] == "serving"

        from tpurpc.jaxshim import TensorClient
        from tpurpc.rpc.channel import Channel

        payload = np.ones((1024, 1024), np.float32)  # 4 MiB
        with Channel(f"127.0.0.1:{port}") as ch:
            cli = TensorClient(ch)

            def gen(k):
                for _ in range(k):
                    yield {"x": payload}

            # The client side of the measured-best plane (see _SERVER_CODE's
            # sink comment): the bulk stream rides the PYTHON plane, whose
            # rendezvous path one-sided-writes every 4 MiB payload into the
            # server's pre-granted landing region (tpurpc-express, ISSUE 9;
            # 3.6 vs 1.72 GB/s same-weather). TPURPC_BENCH_SINK_NATIVE=1
            # opts back to the native framed loop.
            sink_native = os.environ.get("TPURPC_BENCH_SINK_NATIVE",
                                         "0") != "0"

            # warmup RPC: decode jit + ring bring-up out of the timing.
            # It also settles the descriptor-ring adoption handshake
            # (tpurpc-pulse): steady state must show ZERO control frames.
            list(cli.duplex("Sink", gen(2), native=sink_native, timeout=300))
            ctrl0 = _ctrl_counters()

            # Calibrate HERE — after the backend bring-up, immediately
            # before the timed rounds — so the yardstick samples the same
            # host weather as the measurement.
            calib = _calibration()

            # Load-aware repetition (a shared 1-core host's noisy neighbors
            # made round-over-round deltas ±39% measurement noise). More
            # timed rounds, outlier rejection by
            # reporting the median of the FASTEST majority (trimming only
            # slow outliers — contamination on this host is always one-sided:
            # a neighbor stealing the core makes rounds slower, never
            # faster), plus best-round alongside for ceiling-spotting.
            try:
                rounds = max(1, int(os.environ.get("TPURPC_BENCH_ROUNDS",
                                                   "5")))
            except ValueError:
                rounds = 5
            dts = []
            for _ in range(rounds):
                t0 = time.perf_counter()
                replies = list(cli.duplex("Sink", gen(n_msgs),
                                          native=sink_native, timeout=600))
                dt = time.perf_counter() - t0
                total = int(np.asarray(replies[-1]["bytes"]).ravel()[0])
                assert total == n_msgs * payload.nbytes, (total, n_msgs)
                dts.append(dt)
            dts.sort()
            # fastest ceil(n/2) rounds: 3 of 5 at the default — the slow
            # tail (the only direction contamination pushes) is dropped
            kept = dts[:max(1, (len(dts) + 1) // 2)]
            dt = kept[len(kept) // 2]  # median of kept
            globals()["_LAST_STREAM_DTS"] = dts  # full sorted detail for JSON
            ctrl1 = _ctrl_counters()  # client-side delta over the rounds

        # Batch-pipeline observability (ISSUE 1): the server prints one
        # cumulative BATCHSTATS snapshot per completed Sink stream —
        # warmup is match #1, the last timed round is match rounds+1.
        batch_stats: dict = {}
        nstats = rounds + 1
        if sink_native:
            # The timed rounds rode the native C plane, whose batching isn't
            # visible to the Python counters. One short UNTIMED pass on the
            # instrumented Python plane (after the measurement) fills the
            # histograms so the artifact can still attribute throughput to
            # batch sizes; it is labeled as a probe, never the measurement.
            try:
                with Channel(f"127.0.0.1:{port_probe}") as pch:
                    list(TensorClient(pch).duplex("Sink", gen(8),
                                                  native=False, timeout=300))
                nstats += 1
                batch_stats["probe"] = "python-plane, 8 msgs, untimed"
            except Exception:
                pass
        try:
            line = srv.nth_line("BATCHSTATS", nstats, 10)
            if line:
                batch_stats["server"] = json.loads(line.split(" ", 1)[1])
        except Exception:
            pass
        # tpurpc-pulse (ISSUE 13): control-plane cost as a TRACKED series.
        # Deltas over the timed rounds — client side from registry
        # snapshots bracketing the rounds, server side from the warmup vs
        # last-round BATCHSTATS ordinals — yield control frames, forced
        # consumer wakeups (kicks) and thread parks PER BULK MESSAGE.
        ctrl_plane = None
        try:
            srv_warm = srv_end = {}
            w = srv.nth_line("BATCHSTATS", 1, 10)
            if w:
                srv_warm = (json.loads(w.split(" ", 1)[1])
                            .get("counters") or {})
            if batch_stats.get("server"):
                srv_end = batch_stats["server"].get("counters") or {}
            msgs = rounds * n_msgs

            def delta(name):
                c = ctrl1.get(name, 0) - ctrl0.get(name, 0)
                s = srv_end.get(name, 0) - srv_warm.get(name, 0)
                return c + s

            frames = delta("rdv_ctrl_frames")
            kicks = delta("ctrl_ring_kicks")
            parks = delta("wait_sleep")
            ctrl_plane = {
                "msgs": msgs,
                "ctrl_frames": frames,
                "ctrl_kicks": kicks,
                "thread_parks": parks,
                "ring_posts": delta("ctrl_ring_posts"),
                "ring_records": delta("ctrl_ring_records"),
                "ring_full_fallbacks": delta("ctrl_ring_full_fallbacks"),
                # the headline: control frames + forced consumer wakeups
                # per bulk message (≈0 in descriptor-ring steady state)
                "ctrl_wakeups_per_msg": (round((frames + kicks) / msgs, 4)
                                         if msgs else None),
                "ctrl_parks_per_msg": (round(parks / msgs, 4)
                                       if msgs else None),
            }
        except Exception as exc:
            sys.stderr.write(f"ctrl-plane delta capture failed: {exc}\n")
        try:
            from tpurpc.utils import stats as _st
            batch_stats["client"] = {"batch": _st.batch_snapshot(),
                                     "counters": _st.counters_snapshot()}
        except Exception:
            pass

        # tpurpc-lens (ISSUE 8): per-hop byte-flow waterfall for the
        # streaming path — the instrument that names the 1.72→8.5 GB/s
        # bottleneck hop (ROADMAP item 2). Client-side hops come from this
        # process's lens counters; server-side hops are scraped over the
        # introspection plane from the sink that ran the INSTRUMENTED
        # python plane (the probe port when the measured sink was native —
        # labeled, exactly like the batch-stats probe above).
        waterfall = None
        try:
            import urllib.request

            from tpurpc.obs import lens as _lens

            wf_port = port_probe if sink_native else port
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{wf_port}/debug/waterfall",
                    timeout=5) as resp:
                wf_server = json.loads(resp.read())
            wf_client = _lens.waterfall()
            waterfall = _merge_waterfalls([wf_client, wf_server])
            waterfall["plane"] = ("python-probe" if sink_native
                                  else "measured")
        except Exception as exc:
            sys.stderr.write(f"waterfall capture failed: {exc}\n")

        # tpurpc-express (ISSUE 9): the message-size sweep measuring the
        # rendezvous-vs-framed crossover (~20s; Python plane, fresh
        # channels; the main timed rounds above are untouched)
        size_sweep = None
        if os.environ.get("TPURPC_BENCH_SIZESWEEP", "1") == "1":
            try:
                size_sweep = _stream_by_size(port)
            except Exception as exc:
                sys.stderr.write(f"stream_by_size sweep failed: {exc}\n")
                errors["stream_by_size_error"] = repr(exc)

        serving = None
        extras = {"stream_dts": [round(x, 3) for x in
                                 globals().get("_LAST_STREAM_DTS", [])],
                  "calibration": calib,
                  "batch_stats": batch_stats,
                  "waterfall": waterfall,
                  "stream_by_size": size_sweep,
                  "ctrl_plane": ctrl_plane,
                  "errors": errors}
        # printed before READY, so present: the device is part of every result
        extras["device_kind"] = srv.wait_line("DEVKIND", 5).split(
            " ", 1)[1].strip()
        if serving_on:
            # Serving is a phase of the benchmark, not an extra: a failure
            # here (or a missing FLOPS line) raises and fails the run.
            # The server's SERVING line (printed before READY) is the
            # single source of truth for the model/image geometry.
            _, model, img = srv.wait_line("SERVING", 10).split()
            extras["serving_image_size"] = int(img)
            _, flops, dev_qps = srv.wait_line("FLOPS", 5).split()
            extras["model_flops_per_inference"] = float(flops)
            extras["device_infer_qps"] = float(dev_qps)
            serving = _serving_phase(port_infer, model, int(img),
                                     platform=platform)
            # Depth sweep (ISSUE 3): the same phase pinned to in-flight
            # windows 1/4/16 — the artifact's serving_qps_by_depth
            # shows what client pipelining buys the batcher.
            sweep = {}
            for d in (1, 4, 16):
                try:
                    sweep[str(d)] = round(_serving_phase(
                        port_infer, model, int(img), platform=platform,
                        depth=d)[0], 1)
                except Exception as exc:
                    sys.stderr.write(
                        f"serving depth-{d} sweep failed: {exc}\n")
                    errors[f"serving_depth_{d}_error"] = repr(exc)
            extras["serving_qps_by_depth"] = sweep
        return total / dt / 1e9, platform, serving, extras
    except Exception:
        sys.stderr.write(srv.stderr_tail() + "\n")
        raise
    finally:
        srv.kill()


def _ctrl_counters() -> dict:
    """Client-side registry snapshot of the control-plane counters the
    ctrl_wakeups_per_msg series is computed from (tpurpc-pulse)."""
    try:
        from tpurpc.obs import metrics as _metrics

        reg = _metrics.registry().metrics()
        out = {}
        for name in ("rdv_ctrl_frames", "ctrl_ring_kicks",
                     "ctrl_ring_posts", "ctrl_ring_records",
                     "ctrl_ring_full_fallbacks"):
            m = reg.get(name)
            if m is not None:
                out[name] = m.snapshot()
        from tpurpc.utils import stats as _st

        out["wait_sleep"] = _st.counters_snapshot().get("wait_sleep", 0)
        return out
    except Exception:
        return {}


def _merge_waterfalls(docs: "list[dict]") -> dict:
    """Sum hop tables from several processes (client + server side of one
    stream): bytes and busy time add, effective GB/s recomputes over the
    sums — the same merge the shard fan-out applies."""
    merged: dict = {}
    order: list = []
    for doc in docs:
        for r in (doc or {}).get("hops", ()):
            hop = r.get("hop")
            if hop not in merged:
                merged[hop] = {"hop": hop, "bytes": 0, "busy_ms": 0.0,
                               "copy_bytes": 0}
                order.append(hop)
            merged[hop]["bytes"] += int(r.get("bytes") or 0)
            merged[hop]["busy_ms"] += float(r.get("busy_ms") or 0.0)
            merged[hop]["copy_bytes"] += int(r.get("copy_bytes") or 0)
    rows = []
    for hop in order:
        r = merged[hop]
        ns = r["busy_ms"] * 1e6
        r["gbps"] = round(r["bytes"] / ns, 3) if ns else 0.0
        r["busy_ms"] = round(r["busy_ms"], 3)
        rows.append(r)
    # the lens's bottleneck rule (incl. the control-only-traffic guard: a
    # hop carrying <1% of the bulk bytes cannot be the bulk bottleneck)
    from tpurpc.obs import lens as _lens

    return {"hops": rows, "slowest_hop": _lens.slowest_hop(rows)}


def _lens_overhead(duration: "float | None" = None, pairs: int = 2) -> dict:
    """tpurpc-lens overhead gate (ISSUE 8): the continuous stage-sampling
    profiler at its DEFAULT rate (~50 Hz walking every thread stack)
    versus the same closed loop with the sampler stopped.
    ``lens_overhead_pct`` carries the <3% acceptance gate. The waterfall
    hop counters are always-on in BOTH legs (they are plain registry
    counters, priced by the obs gate since ISSUE 4) — this gate isolates
    the one genuinely new continuous cost, the sampler thread. Same
    alternation and best-draw-p50 methodology as _obs_overhead."""
    import io

    from tpurpc.bench import micro
    from tpurpc.obs import profiler
    from tpurpc.utils import stats as _st

    if duration is None:
        duration = float(os.environ.get("TPURPC_BENCH_OBS_S", "1.0"))
    prev_fast = os.environ.get("TPURPC_NATIVE_FAST_UNARY")
    os.environ["TPURPC_NATIVE_FAST_UNARY"] = "0"
    prof = profiler.get()
    srv = micro.run_server(0, max_workers=8)
    target = f"127.0.0.1:{srv.bench_port}"
    devnull = io.StringIO()
    p50s = {"off": [], "on": []}

    def leg(key, dur):
        r = micro.run_client(target, req_size=64, duration=dur, out=devnull)
        p50s[key].append(r["rtt_us"]["p50"])

    try:
        micro.run_client(target, req_size=64, duration=0.3,
                         out=devnull)  # warm: connect + first-dispatch
        for i in range(max(1, pairs)):
            legs = [("off", False), ("on", True)]
            if i % 2:
                legs.reverse()
            for key, on in legs:
                if on:
                    prof.start()
                else:
                    prof.stop()
                leg(key, duration)
    finally:
        prof.stop()  # later benches decide their own profiling
        if prev_fast is None:
            os.environ.pop("TPURPC_NATIVE_FAST_UNARY", None)
        else:
            os.environ["TPURPC_NATIVE_FAST_UNARY"] = prev_fast
        srv.stop(grace=0)
        _st.reset_batch_stats()

    def pct(on_key, off_key):
        # best-draw p50s: contamination on a shared core is one-sided (see
        # _obs_overhead.pct)
        off = min(p50s[off_key])
        on = min(p50s[on_key])
        return round((on - off) / off * 100, 2) if off else 0.0

    gate = pct("on", "off")
    return {
        "lens_overhead_pct": gate,
        "lens_overhead_gate_pct": 3.0,
        "lens_overhead_pass": gate < 3.0,
        "lens_hz": prof.hz,
        "lens_p50_us": {k: [round(x, 1) for x in sorted(v)]
                        for k, v in p50s.items()},
    }


def _obs_overhead(duration: "float | None" = None, pairs: int = 3) -> dict:
    """tpurpc-scope overhead gate (ISSUE 4): micro closed-loop RPC rate
    with telemetry FULLY ENABLED vs the default-off state, on the
    INSTRUMENTED Python plane (TPURPC_NATIVE_FAST_UNARY=0 for the gate's
    duration — letting the untraced leg ride the native C loop would
    measure the plane gap, not the telemetry).

    "Fully enabled" = every registry counter/histogram/fleet gauge live
    (they always are — unconditional and branch-free), a scraper thread
    rendering the Prometheus text at 4 Hz (~60x a production cadence)
    DURING the traffic, and tracing ACTIVE at the production sampling
    rate (TPURPC_BENCH_OBS_RATE, default 0.05 — 12x Dapper's default).
    ``obs_overhead_pct`` (positive = telemetry cost) carries the <3%
    gate. ``obs_traced100_pct`` is the informational cost of tracing
    EVERY call (a debugging mode, not an operating point): ~7 span
    records per 64-byte no-op RPC is measurable by construction.

    ON/OFF legs alternate and medians compare, so noisy-neighbor weather
    hits both sides alike."""
    import io
    import threading

    from tpurpc.bench import micro
    from tpurpc.obs import scrape, tracing
    from tpurpc.utils import stats as _st

    if duration is None:
        duration = float(os.environ.get("TPURPC_BENCH_OBS_S", "1.0"))
    rate = float(os.environ.get("TPURPC_BENCH_OBS_RATE", "0.05"))
    prev_fast = os.environ.get("TPURPC_NATIVE_FAST_UNARY")
    os.environ["TPURPC_NATIVE_FAST_UNARY"] = "0"
    srv = micro.run_server(0, max_workers=8)
    target = f"127.0.0.1:{srv.bench_port}"
    devnull = io.StringIO()
    rates = {"off": [], "on": [], "traced100": []}
    p50s = {"off": [], "on": [], "traced100": []}

    def leg(key, dur):
        stop = threading.Event()
        t = None
        if key != "off":
            def scraper():
                while not stop.is_set():
                    scrape.render_prometheus()
                    stop.wait(0.25)

            t = threading.Thread(target=scraper, daemon=True)
            t.start()
        try:
            r = micro.run_client(target, req_size=64, duration=dur,
                                 out=devnull)
            rates[key].append(r["rate_rps"])
            p50s[key].append(r["rtt_us"]["p50"])
        finally:
            stop.set()
            if t is not None:
                t.join(timeout=2)

    try:
        micro.run_client(target, req_size=64, duration=0.3,
                         out=devnull)  # warm: connect + first-dispatch
        for i in range(max(1, pairs)):
            # Alternate leg ORDER per pair: on a noisy shared core the
            # host drifts over the gate's window, and a fixed off-then-on
            # order would alias that drift into the overhead number. The
            # pairwise differencing below cancels what alternation leaves.
            tracing.force(None)
            legs = [("off", 0.0), ("on", rate)]
            if i % 2:
                legs.reverse()
            for key, r in legs:
                tracing.configure(r)
                leg(key, duration)
            tracing.force(True)  # debugging mode: every call traced
            leg("traced100", duration / 2)
            tracing.force(None)
    finally:
        tracing.force(None)
        tracing.configure(0.0)
        if prev_fast is None:
            os.environ.pop("TPURPC_NATIVE_FAST_UNARY", None)
        else:
            os.environ["TPURPC_NATIVE_FAST_UNARY"] = prev_fast
        srv.stop(grace=0)
        _st.reset_batch_stats()  # the gate's traffic must not pollute
        tracing.reset()          # the artifact's own counters/spans

    def pct(key):
        """Best-draw p50 RTT comparison. Contamination on this shared
        1-core host is ONE-SIDED (a noisy neighbor only ever slows a leg
        — the same argument behind the streaming phase's kept-fastest
        rounds and the calibration's best-of-5), so the minimum p50 of
        each config approximates its uncontended cost and the delta is
        the telemetry's own price, not the weather's."""
        off = min(p50s["off"])
        on = min(p50s[key])
        return round((on - off) / off * 100, 2) if off else 0.0

    gate = pct("on")
    return {
        "obs_overhead_pct": gate,
        "obs_overhead_gate_pct": 3.0,
        "obs_overhead_pass": gate < 3.0,
        "obs_sample_rate": rate,
        "obs_traced100_pct": pct("traced100"),
        "obs_p50_us": {k: [round(x, 1) for x in sorted(v)]
                       for k, v in p50s.items()},
        "obs_rps": {k: [round(x) for x in sorted(v)]
                    for k, v in rates.items()},
    }


def _flight_overhead(duration: "float | None" = None, pairs: int = 2) -> dict:
    """tpurpc-blackbox overhead gate (ISSUE 5): the ALWAYS-ON postmortem
    core — flight recorder emitting + stall-watchdog per-RPC registration
    and background sweeps — versus the same loop with both suppressed.
    ``flight_overhead_pct`` carries the <3% acceptance gate. By design the
    recorder emits on state EDGES only (a healthy closed loop produces
    near-zero events), so the measured cost is the watchdog's dict
    store/delete per RPC plus the suppressed-emit branch.

    ``tail_capture_pct`` is the INFORMATIONAL cost of tail-based trace
    capture (every RPC gets a provisional span buffer; spans are recorded
    and then dropped for healthy calls) — it is a separately-toggleable
    feature (TPURPC_TRACE_TAIL=0) and is reported, not gated: its price is
    the same ballpark as obs_traced100_pct, paid to guarantee a span tree
    for every pathological call at sample rate 0.

    Tail capture is held in its default-ON state for BOTH flight legs so
    the flight delta isolates the recorder+watchdog; the tail legs then
    toggle only tail capture with recorder+watchdog on. Same alternation
    and best-draw-p50 methodology as _obs_overhead."""
    import io

    from tpurpc.bench import micro
    from tpurpc.obs import flight, tracing, watchdog
    from tpurpc.utils import stats as _st

    if duration is None:
        duration = float(os.environ.get("TPURPC_BENCH_OBS_S", "1.0"))
    prev_fast = os.environ.get("TPURPC_NATIVE_FAST_UNARY")
    os.environ["TPURPC_NATIVE_FAST_UNARY"] = "0"
    srv = micro.run_server(0, max_workers=8)
    target = f"127.0.0.1:{srv.bench_port}"
    devnull = io.StringIO()
    p50s = {"off": [], "on": [], "tail_off": [], "tail_on": []}
    wd = watchdog.get()

    def leg(key, dur):
        r = micro.run_client(target, req_size=64, duration=dur, out=devnull)
        p50s[key].append(r["rtt_us"]["p50"])

    try:
        tracing.force(None)
        tracing.configure(0.0)
        micro.run_client(target, req_size=64, duration=0.3,
                         out=devnull)  # warm: connect + first-dispatch
        for i in range(max(1, pairs)):
            legs = [("off", False), ("on", True)]
            if i % 2:
                legs.reverse()
            for key, enabled in legs:
                flight.RECORDER.enabled = enabled
                wd.enabled = enabled
                leg(key, duration)
            # tail capture A/B (informational): recorder+watchdog stay on
            tail_legs = [("tail_off", False), ("tail_on", None)]
            if i % 2:
                tail_legs.reverse()
            for key, mode in tail_legs:
                tracing.tail(mode)
                leg(key, duration / 2)
    finally:
        flight.RECORDER.enabled = True
        wd.enabled = True
        wd.reset()
        tracing.tail(None)
        tracing.force(None)
        tracing.configure(0.0)
        if prev_fast is None:
            os.environ.pop("TPURPC_NATIVE_FAST_UNARY", None)
        else:
            os.environ["TPURPC_NATIVE_FAST_UNARY"] = prev_fast
        srv.stop(grace=0)
        _st.reset_batch_stats()
        tracing.reset()

    def pct(on_key, off_key):
        # best-draw p50s: contamination on a shared core is one-sided (see
        # _obs_overhead.pct) — the minimum of each leg approximates its
        # uncontended cost
        off = min(p50s[off_key])
        on = min(p50s[on_key])
        return round((on - off) / off * 100, 2) if off else 0.0

    gate = pct("on", "off")
    return {
        "flight_overhead_pct": gate,
        "flight_overhead_gate_pct": 3.0,
        "flight_overhead_pass": gate < 3.0,
        "tail_capture_pct": pct("tail_on", "tail_off"),
        "flight_p50_us": {k: [round(x, 1) for x in sorted(v)]
                          for k, v in p50s.items()},
    }


def _proto_verify_overhead(duration: "float | None" = None,
                           pairs: int = 4) -> dict:
    """tpurpc-proof overhead gate (ISSUE 12): the LIVE protocol verifier
    (``TPURPC_VERIFY_PROTOCOL=1`` — every flight event checked against
    the declared machines as it is recorded) versus the same loop with no
    verifier installed. ``proto_verify_overhead_pct`` carries the <3%
    acceptance gate. By design the cost rides the flight recorder's
    edges-not-traffic economy: a healthy closed loop emits near-zero
    events, so the verifier's per-event machine step is almost never
    taken — the measured cost is one global load + None check per emit.
    Same alternation and best-draw-p50 methodology as _obs_overhead."""
    import io

    from tpurpc.analysis import protocol
    from tpurpc.bench import micro
    from tpurpc.utils import stats as _st

    if duration is None:
        duration = float(os.environ.get("TPURPC_BENCH_OBS_S", "1.0"))
    prev_fast = os.environ.get("TPURPC_NATIVE_FAST_UNARY")
    os.environ["TPURPC_NATIVE_FAST_UNARY"] = "0"
    srv = micro.run_server(0, max_workers=8)
    target = f"127.0.0.1:{srv.bench_port}"
    devnull = io.StringIO()
    p50s = {"off": [], "on": []}
    verifier = None

    def leg(key, dur):
        r = micro.run_client(target, req_size=64, duration=dur, out=devnull)
        p50s[key].append(r["rtt_us"]["p50"])

    try:
        micro.run_client(target, req_size=64, duration=0.3,
                         out=devnull)  # warm: connect + first-dispatch
        for i in range(max(1, pairs)):
            legs = [("off", False), ("on", True)]
            if i % 2:
                legs.reverse()
            for key, enabled in legs:
                if enabled:
                    verifier = protocol.install_live()
                else:
                    protocol.uninstall_live()
                leg(key, duration)
    finally:
        protocol.uninstall_live()
        if prev_fast is None:
            os.environ.pop("TPURPC_NATIVE_FAST_UNARY", None)
        else:
            os.environ["TPURPC_NATIVE_FAST_UNARY"] = prev_fast
        srv.stop(grace=0)
        _st.reset_batch_stats()

    off = min(p50s["off"])
    on = min(p50s["on"])
    gate = round((on - off) / off * 100, 2) if off else 0.0
    return {
        "proto_verify_overhead_pct": gate,
        "proto_verify_overhead_gate_pct": 3.0,
        "proto_verify_overhead_pass": gate < 3.0,
        "proto_verify_events_checked": (verifier.checked if verifier
                                        else 0),
        "proto_verify_violations": (len(verifier.violations) if verifier
                                    else 0),
        "proto_verify_p50_us": {k: [round(x, 1) for x in sorted(v)]
                                for k, v in p50s.items()},
    }


def _argus_overhead(duration: "float | None" = None, pairs: int = 3) -> dict:
    """tpurpc-argus overhead gate (ISSUE 14): the whole detect loop armed
    — tsdb sampler on a 4 Hz grain (4x the production 1 s default), the
    SLO evaluator ticking at 4 Hz over a declared (never-firing)
    objective, and a fleet collector polling the serving port's /metrics
    + /debug/slo + /debug/flight + /traces at 4 Hz over real HTTP —
    versus the same closed loop with all three stopped.
    ``argus_overhead_pct`` carries the <3% acceptance gate;
    ``tsdb_resident_bytes`` records the history plane's bounded memory
    (informational — fixed by construction: preallocated rings x series
    cap). Same alternation and best-draw-p50 methodology as
    _obs_overhead: the sampler/evaluator/collector are background
    cadences, so their cost shows up as closed-loop RTT contention."""
    import io

    from tpurpc.bench import micro
    from tpurpc.obs import slo as _slo
    from tpurpc.obs import tsdb as _tsdb
    from tpurpc.obs.collector import FleetCollector
    from tpurpc.utils import stats as _st

    if duration is None:
        duration = float(os.environ.get("TPURPC_BENCH_OBS_S", "1.0"))
    prev_fast = os.environ.get("TPURPC_NATIVE_FAST_UNARY")
    os.environ["TPURPC_NATIVE_FAST_UNARY"] = "0"
    srv = micro.run_server(0, max_workers=8)
    target = f"127.0.0.1:{srv.bench_port}"
    devnull = io.StringIO()
    p50s = {"off": [], "on": []}

    # the armed plane: 4 Hz sampler over the REAL registry, an evaluator
    # with an objective that never fires (no trip/page noise in the timed
    # window), a collector process-alike polling over loopback HTTP
    db = _tsdb.Tsdb(fine_s=0.25)
    ev = _slo.SloEvaluator(eval_s=0.25, tsdb=db)
    ev.declare(_slo.SloObjective(
        "bench-guard", latency_ms=60_000.0, target_pct=50.0,
        windows=[(2.0, 8.0, 1e9)]))
    col = FleetCollector([target], poll_s=0.25)

    def leg(key, dur):
        if key == "on":
            db.start()
            ev.start()
            col.start()
        try:
            r = micro.run_client(target, req_size=64, duration=dur,
                                 out=devnull)
            p50s[key].append(r["rtt_us"]["p50"])
        finally:
            if key == "on":
                col.stop()
                ev.stop()
                db.stop()

    try:
        micro.run_client(target, req_size=64, duration=0.3,
                         out=devnull)  # warm: connect + first-dispatch
        for i in range(max(1, pairs)):
            legs = ["off", "on"]
            if i % 2:
                legs.reverse()
            for key in legs:
                leg(key, duration)
    finally:
        col.stop()
        ev.stop()
        db.stop()
        if prev_fast is None:
            os.environ.pop("TPURPC_NATIVE_FAST_UNARY", None)
        else:
            os.environ["TPURPC_NATIVE_FAST_UNARY"] = prev_fast
        srv.stop(grace=0)
        _st.reset_batch_stats()

    off = min(p50s["off"])
    on = min(p50s["on"])
    gate = round((on - off) / off * 100, 2) if off else 0.0
    return {
        "argus_overhead_pct": gate,
        "argus_overhead_gate_pct": 3.0,
        "argus_overhead_pass": gate < 3.0,
        "argus_sampler_hz": 4.0,
        "tsdb_resident_bytes": db.resident_bytes(),
        "tsdb_series": len(db.series()),
        "argus_p50_us": {k: [round(x, 1) for x in sorted(v)]
                         for k, v in p50s.items()},
    }


def _diagnose_overhead(duration: "float | None" = None,
                       pairs: int = 3) -> dict:
    """tpurpc-oracle overhead gate (ISSUE 20): the causal diagnosis
    engine armed — a tsdb sampler feeding the fine windows at 4 Hz plus
    a background querier running the FULL ``diagnose_doc`` pipeline
    (symptom scan, change-point detection over every series, all rules'
    collect+score, noisy-OR combination) at 4 Hz — versus the same
    closed loop with both stopped. ``diagnose_overhead_pct`` carries the
    <3% acceptance gate. The engine is pull-only (the `diag` lint rule
    enforces read-only evidence collection), so its cost is pure reader
    contention on the planes' locks — exactly what this gate prices.
    Same alternation and best-draw-p50 methodology as _obs_overhead."""
    import io
    import threading

    from tpurpc.bench import micro
    from tpurpc.obs import diagnose as _dz
    from tpurpc.obs import tsdb as _tsdb
    from tpurpc.utils import stats as _st

    if duration is None:
        duration = float(os.environ.get("TPURPC_BENCH_OBS_S", "1.0"))
    prev_fast = os.environ.get("TPURPC_NATIVE_FAST_UNARY")
    os.environ["TPURPC_NATIVE_FAST_UNARY"] = "0"
    srv = micro.run_server(0, max_workers=8)
    target = f"127.0.0.1:{srv.bench_port}"
    devnull = io.StringIO()
    p50s = {"off": [], "on": []}
    runs = {"n": 0}

    db = _tsdb.Tsdb(fine_s=0.25)
    stop_ev = threading.Event()
    worker = {"t": None}

    def query_loop():
        while not stop_ev.wait(0.25):
            try:
                _dz.diagnose_doc({})
                runs["n"] += 1
            except Exception:
                pass

    def leg(key, dur):
        if key == "on":
            db.start()
            stop_ev.clear()
            worker["t"] = threading.Thread(target=query_loop, daemon=True)
            worker["t"].start()
        try:
            r = micro.run_client(target, req_size=64, duration=dur,
                                 out=devnull)
            p50s[key].append(r["rtt_us"]["p50"])
        finally:
            if key == "on":
                stop_ev.set()
                if worker["t"] is not None:
                    worker["t"].join(timeout=2.0)
                db.stop()

    try:
        micro.run_client(target, req_size=64, duration=0.3,
                         out=devnull)  # warm: connect + first-dispatch
        for i in range(max(1, pairs)):
            legs = ["off", "on"]
            if i % 2:
                legs.reverse()
            for key in legs:
                leg(key, duration)
    finally:
        stop_ev.set()
        db.stop()
        if prev_fast is None:
            os.environ.pop("TPURPC_NATIVE_FAST_UNARY", None)
        else:
            os.environ["TPURPC_NATIVE_FAST_UNARY"] = prev_fast
        srv.stop(grace=0)
        _st.reset_batch_stats()

    off = min(p50s["off"])
    on = min(p50s["on"])
    gate = round((on - off) / off * 100, 2) if off else 0.0
    return {
        "diagnose_overhead_pct": gate,
        "diagnose_overhead_gate_pct": 3.0,
        "diagnose_overhead_pass": gate < 3.0,
        "diagnose_queries_run": runs["n"],
        "diagnose_p50_us": {k: [round(x, 1) for x in sorted(v)]
                            for k, v in p50s.items()},
    }


def _fleet_bench() -> dict:
    """tpurpc-fleet benches (ISSUE 6), in-process, seconds each:

    * ``fleet_qps`` — 3-server aggregate behind ``round_robin`` with a
      depth-8 pipelined client (the N-backend serving posture);
    * ``fleet_p99_degraded_pct`` — p99 latency with ONE slow replica,
      hedging on vs. off. The acceptance claim: hedging improves the
      degraded p99 ≥ 2x while total attempt amplification stays under the
      hedging policy's bound (no retry storm) — tail latency under
      contention is what the RPC layer owes the fleet (arXiv:1804.01138);
    * ``shed_curve`` — goodput/shed/p99 vs. offered load on an
      admission-gated server, plus the same worst offered load UNGATED:
      the gate trips before collapse (accepted-call p99 holds while the
      ungated leg queues).

    All servers run the Python plane (``native_dataplane=False``) and
    clients pin ``tpurpc_native=False`` — the features under test (load
    reports, hedging, admission) live there."""
    import threading

    from tpurpc.rpc.channel import Channel, HedgingPolicy
    from tpurpc.rpc.server import (AdmissionGate, Server,
                                   unary_unary_rpc_method_handler)

    def spawn(n, delay_of=None, max_workers=32, admission=None):
        rigs = []
        for i in range(n):
            srv = Server(max_workers=max_workers, admission=admission,
                         native_dataplane=False)
            calls = [0]
            d = delay_of(i) if delay_of else 0.0

            def handler(req, ctx, _c=calls, _d=d):
                _c[0] += 1
                if _d:
                    time.sleep(_d)
                return req

            srv.add_method("/fb.S/Echo",
                           unary_unary_rpc_method_handler(handler))
            port = srv.add_insecure_port("127.0.0.1:0")
            srv.start()
            rigs.append((srv, port, calls))
        return rigs

    def stop_all(rigs):
        for srv, _, _ in rigs:
            srv.stop(grace=0)

    out: dict = {}

    # -- fleet_qps: 3-server aggregate --------------------------------------
    rigs = spawn(3)
    try:
        addrs = ",".join(f"127.0.0.1:{p}" for _, p, _ in rigs)
        with Channel(f"ipv4:{addrs}", lb_policy="round_robin") as ch:
            pipe = ch.unary_unary("/fb.S/Echo",
                                  tpurpc_native=False).pipeline(depth=8)
            t_end = time.monotonic() + 0.3  # warm
            while time.monotonic() < t_end:
                pipe.call_async(b"w", timeout=10).result(10)
            n = 0
            t0 = time.monotonic()
            futs = []
            while time.monotonic() - t0 < 2.0:
                futs.append(pipe.call_async(b"x", timeout=10))
                if len(futs) >= 64:
                    for f in futs:
                        f.result(10)
                        n += 1
                    futs = []
            for f in futs:
                f.result(10)
                n += 1
            dt = time.monotonic() - t0
            pipe.close()
        out["fleet_qps"] = round(n / dt, 1)
        out["fleet_servers"] = 3
        per_server = [c[0] for _, _, c in rigs]
        out["fleet_qps_spread"] = per_server
    finally:
        stop_all(rigs)

    # -- fleet_p99_degraded_pct: one slow replica, hedging on vs off --------
    SLOW_S = 0.04
    N_CALLS = 120
    hp = HedgingPolicy(max_attempts=3, hedging_delay=0.008)
    rigs = spawn(3, delay_of=lambda i: SLOW_S if i == 0 else 0.0)
    try:
        addrs = ",".join(f"127.0.0.1:{p}" for _, p, _ in rigs)

        def leg(hedging):
            with Channel(f"ipv4:{addrs}", lb_policy="round_robin",
                         hedging_policy=hedging) as ch:
                mc = ch.unary_unary("/fb.S/Echo", tpurpc_native=False)
                for _ in range(6):
                    mc(b"w", timeout=10)  # warm every subchannel
                before = sum(c[0] for _, _, c in rigs)
                lats = []
                for _ in range(N_CALLS):
                    t0 = time.perf_counter()
                    mc(b"x", timeout=10)
                    lats.append((time.perf_counter() - t0) * 1000)
                time.sleep(SLOW_S + 0.05)  # cancelled losers finish counting
                attempts = sum(c[0] for _, _, c in rigs) - before
            lats.sort()
            return lats[max(0, int(len(lats) * 0.99) - 1)], attempts

        p99_off, attempts_off = leg(None)
        p99_on, attempts_on = leg(hp)
        out["fleet_p99_degraded_pct"] = {
            "slow_replica_s": SLOW_S,
            "calls": N_CALLS,
            "p99_ms_hedging_off": round(p99_off, 2),
            "p99_ms_hedging_on": round(p99_on, 2),
            "improvement_x": round(p99_off / p99_on, 2) if p99_on else None,
            "attempts_off": attempts_off,
            "attempts_on": attempts_on,
            # amplification must stay under the policy's hard bound — the
            # no-retry-storm half of the acceptance criterion
            "attempt_amplification": round(attempts_on / N_CALLS, 3),
            "amplification_bound": hp.max_attempts,
        }
    finally:
        stop_all(rigs)

    # -- shed_curve: goodput vs offered load through the admission gate -----
    HANDLER_S = 0.004
    gate = AdmissionGate(8, soft_limit=6)
    rigs = spawn(1, delay_of=lambda i: HANDLER_S, max_workers=8,
                 admission=gate)
    try:
        _, port, _ = rigs[0]

        def offered_leg(depth, target_port, leg_s=1.0):
            """One pipelined client whose WINDOW is the offered
            concurrency — a single issuing thread, so the 1-core host's
            client-side scheduling noise doesn't masquerade as server
            collapse (32 closed-loop threads measured the scheduler, not
            the gate)."""
            ok = [0]
            shed = [0]
            lat_ok: list = []
            lk = threading.Lock()
            with Channel(f"127.0.0.1:{target_port}") as ch:
                pipe = ch.unary_unary("/fb.S/Echo",
                                      tpurpc_native=False).pipeline(
                                          depth=depth)
                stop_at = time.monotonic() + leg_s
                t0 = time.monotonic()

                def issue():
                    t_req = time.perf_counter()
                    fut = pipe.call_async(b"x", timeout=10)

                    def done(f):
                        if f.exception() is None:
                            ok[0] += 1
                            with lk:
                                lat_ok.append(
                                    (time.perf_counter() - t_req) * 1000)
                        else:
                            shed[0] += 1

                    fut.add_done_callback(done)
                    return fut

                pending = []
                while time.monotonic() < stop_at:
                    pending.append(issue())
                    if len(pending) >= depth * 2:
                        for f in pending:
                            try:
                                f.result(10)
                            except Exception:
                                pass
                        pending = []
                for f in pending:
                    try:
                        f.result(10)
                    except Exception:
                        pass
                dt = time.monotonic() - t0
                pipe.close()
            lat_ok.sort()
            p99 = (lat_ok[max(0, int(len(lat_ok) * 0.99) - 1)]
                   if lat_ok else None)
            return {"offered_depth": depth,
                    "goodput_qps": round(ok[0] / dt, 1),
                    "shed_per_s": round(shed[0] / dt, 1),
                    "p99_ok_ms": round(p99, 2) if p99 else None}

        curve = [offered_leg(n, port) for n in (4, 8, 16, 32)]
        out["shed_curve"] = curve
        out["shed_rejected_total"] = gate.rejected
        # the ungated comparison at the worst offered load: same handler,
        # no gate — queueing latency the gate exists to cut off
        ungated = spawn(1, delay_of=lambda i: HANDLER_S, max_workers=8)
        try:
            out["shed_nogate_worst"] = offered_leg(32, ungated[0][1])
        finally:
            stop_all(ungated)
        goodputs = [c["goodput_qps"] for c in curve]
        peak = max(goodputs)
        out["shed_curve_noncollapse"] = round(
            min(goodputs[goodputs.index(peak):]) / peak, 3) if peak else None
    finally:
        stop_all(rigs)
    return out


#: tpurpc-manycore (ISSUE 7) — the sharded serving rig. The model is a
#: NUMPY matmul stand-in built pre-fork (plain arrays are fork-safe,
#: copy-on-write; an XLA client is not — that is why shard workers stay
#: jax-free here, and the artifact names the stand-in). Workers are full
#: per-core servers: own poller, rings (auto-scaled per shard), pool.
_SHARD_SERVER_CODE = r"""
import os, sys
import numpy as np
from tpurpc.jaxshim.service import add_tensor_method
from tpurpc.rpc.server import Server
from tpurpc.rpc.shard import ShardedServer

IMG = int(os.environ.get("TPURPC_BENCH_CORES_IMG", "48"))
WORKERS = int(sys.argv[1])

rng = np.random.default_rng(0)
W1 = rng.standard_normal((IMG * IMG * 3, 128)).astype(np.float32) * 0.01
W2 = rng.standard_normal((128, 10)).astype(np.float32) * 0.1

def model(tree):
    x = np.asarray(tree["x"], dtype=np.float32)
    x = x.reshape(x.shape[0], -1)
    return {"logits": np.maximum(x @ W1, 0.0) @ W2}

def build(shard_id):
    srv = Server(max_workers=32)
    add_tensor_method(srv, "Infer", model)
    return srv

sup = ShardedServer(build, workers=WORKERS, listener="reuseport").start()
print("PORT", sup.port, flush=True)
print("READY", flush=True)
sys.stdin.readline()
sup.stop()
"""

#: closed-loop client PROCESS (not thread): on a multi-core rig the load
#: generators must scale past the GIL too, or the sweep measures the
#: client's one core instead of the server's N.
_SHARD_CLIENT_CODE = r"""
import sys, time
import numpy as np
from tpurpc.jaxshim import TensorClient
from tpurpc.rpc.channel import Channel

port, depth, dur, img = (int(sys.argv[1]), int(sys.argv[2]),
                         float(sys.argv[3]), int(sys.argv[4]))
image = np.random.default_rng(0).standard_normal(
    (1, img, img, 3)).astype(np.float32)
with Channel(f"127.0.0.1:{port}") as ch:
    cli = TensorClient(ch, depth=max(1, depth))
    out = cli.call("Infer", {"x": image}, timeout=120)  # warm this conn
    assert np.asarray(out["logits"]).shape[0] == 1
    print("READY", flush=True)
    sys.stdin.readline()  # GO
    n = 0
    end = time.perf_counter() + dur
    if depth <= 1:
        while time.perf_counter() < end:
            cli.call("Infer", {"x": image}, timeout=120)
            n += 1
    else:
        pl = cli.pipeline("Infer", depth=depth)
        inflight = []
        while time.perf_counter() < end:
            while len(inflight) < depth:
                inflight.append(pl.call_async({"x": image}, timeout=120))
            inflight.pop(0).result(timeout=120)
            n += 1
        for f in inflight:
            f.result(timeout=120)
            n += 1
    print("DONE", n, flush=True)
"""


def _shard_cell(env, workers: int, n_clients: int, depth: int,
                duration_s: float, img: int) -> float:
    """One sweep cell: a sharded server subprocess + ``n_clients`` client
    processes released on a barrier; returns aggregate QPS."""
    srv = subprocess.Popen(
        [sys.executable, "-u", "-c", _SHARD_SERVER_CODE, str(workers)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, env=env, text=True)
    clients = []
    try:
        port = None
        deadline = time.time() + 60
        while time.time() < deadline:
            line = srv.stdout.readline()
            if not line:
                raise RuntimeError(
                    f"shard server died: {srv.stderr.read()[-800:]}")
            if line.startswith("PORT"):
                port = int(line.split()[1])
            if line.startswith("READY"):
                break
        if port is None:
            raise TimeoutError("shard server never reported PORT")
        clients = [subprocess.Popen(
            [sys.executable, "-u", "-c", _SHARD_CLIENT_CODE, str(port),
             str(depth), str(duration_s), str(img)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, env=env, text=True)
            for _ in range(n_clients)]
        for c in clients:
            line = c.stdout.readline()
            if not line.startswith("READY"):
                raise RuntimeError(
                    f"shard client died: {c.stderr.read()[-800:]}")
        t0 = time.perf_counter()
        for c in clients:  # the GO barrier: one newline each
            c.stdin.write("\n")
            c.stdin.flush()
        total = 0
        for c in clients:
            line = c.stdout.readline()
            if not line.startswith("DONE"):
                raise RuntimeError(
                    f"shard client failed: {c.stderr.read()[-800:]}")
            total += int(line.split()[1])
        dt = time.perf_counter() - t0
        return total / dt
    finally:
        for c in clients:
            c.kill()
        try:
            srv.stdin.write("\n")
            srv.stdin.flush()
            srv.wait(timeout=10)
        except (OSError, subprocess.TimeoutExpired):
            srv.kill()


def _shard_bench() -> dict:
    """tpurpc-manycore (ISSUE 7): ``serving_qps_by_cores`` — aggregate QPS
    vs. worker count (1/2/4 per-core shard processes behind one
    SO_REUSEPORT port), plus the PR 3 depth sweep re-run WITH sharding.

    Methodology notes the artifact must carry:

    * ``cores_requested`` vs ``cores_achieved`` per cell, exactly like
      PR 3's concurrency probes — on a 1-core rig every worker count
      timeshares one core, so the sweep is expected ~flat there and the
      ≥2.5x@4 acceptance gate only APPLIES where ``cores_achieved >= 4``;
    * clients are PROCESSES (closed-loop, depth-4, barrier-released), so
      on a multi-core rig the load generation scales past the GIL too;
    * the model is a numpy matmul stand-in built pre-fork (fork-safe,
      jax-free workers) — this measures the SERVING PATH's core scaling,
      which is the thing sharding changes.
    """
    cpus = _cores_available()
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.dirname(os.path.abspath(__file__))
                         + os.pathsep + env.get("PYTHONPATH", ""))
    img = int(os.environ.get("TPURPC_BENCH_CORES_IMG", "48"))
    dur = float(os.environ.get("TPURPC_BENCH_CORES_S", "2.5"))
    n_clients = int(os.environ.get("TPURPC_BENCH_CORES_CLIENTS", "4"))
    out: dict = {}
    by_cores = {}
    achieved = {}
    for workers in (1, 2, 4):
        qps = _shard_cell(env, workers, n_clients, depth=4,
                          duration_s=dur, img=img)
        by_cores[str(workers)] = round(qps, 1)
        achieved[str(workers)] = min(workers, cpus)
    out["serving_qps_by_cores"] = by_cores
    out["serving_by_cores_requested"] = [1, 2, 4]
    out["serving_by_cores_achieved"] = achieved
    out["serving_by_cores_clients"] = n_clients
    out["serving_by_cores_model"] = (
        f"numpy relu-matmul stand-in @{img} (jax-free shard workers; "
        "fork-safe)")
    ratio = (by_cores["4"] / by_cores["1"]) if by_cores["1"] else 0.0
    out["serving_by_cores_scaling_x4"] = round(ratio, 2)
    # the acceptance gate (≥2.5x at 4 workers) binds on multi-core rigs;
    # elsewhere the honest record is requested-vs-achieved + a note
    out["serving_by_cores_gate"] = {
        "target_x": 2.5,
        "applicable": cpus >= 4,
        "pass": (ratio >= 2.5) if cpus >= 4 else None,
    }
    if cpus < 4:
        out["serving_by_cores_note"] = (
            f"{cpus}-core rig: all worker counts timeshare "
            f"{cpus} core(s), so the sweep is ~flat by physics (same "
            "regime as the PR 3 depth sweep); the machinery is validated "
            "here, the scaling claim binds on a multi-core rig — "
            "cores_achieved records the truth per cell")
    # the PR 3 depth sweep, re-run with sharding enabled: once the serving
    # core has headroom (multi-core rigs), depth should stop being flat
    sharded_workers = min(4, max(2, cpus))
    sweep = {}
    for depth in (1, 4, 16):
        qps = _shard_cell(env, sharded_workers, n_clients, depth=depth,
                          duration_s=dur, img=img)
        sweep[str(depth)] = round(qps, 1)
    out["serving_qps_by_depth_sharded"] = sweep
    out["serving_by_depth_sharded_workers"] = sharded_workers
    return out


def _gen_bench() -> dict:
    """tpurpc-cadence benches (ISSUE 10), in-process, ~15s total:

    * ``gen_tokens_per_s`` — aggregate decode goodput under a mixed
      interactive/batch closed-loop client set (the continuous-batching
      serving posture: many concurrent per-token streams, one device
      batch);
    * ``gen_ttft_ms`` — time-to-first-token at light load (p50) and at
      the heaviest offered load (interactive p99): the number the SLO
      classes exist to protect;
    * ``gen_shed_curve`` — goodput / sheds / per-class TTFT vs offered
      load (concurrent streaming clients), with the graceful-degradation
      acceptance recorded on file: goodput past saturation holds >= 0.75
      of peak, the batch class sheds FIRST, and interactive TTFT p99 at
      the worst load stays bounded vs the light-load baseline.

    The model is the deterministic numpy toy with a 1 ms step stand-in
    (named in ``gen_model``): the bench measures the SCHEDULER + streaming
    transport — join/leave churn, per-token flushes, shed behavior — not
    model FLOPs, exactly like the fleet bench measures the RPC layer.

    1-core caveat (the PR 3/PR 6 lesson, again): every offered-load
    client is a closed-loop thread SHARING the serving core, so the
    heaviest legs measure client-side scheduling pressure as well as the
    server — the sweep stops at 24 clients and ``gen_note`` says so."""
    import threading

    from tpurpc.jaxshim.generate import ToyDecodeModel
    from tpurpc.obs import watchdog as _wd
    from tpurpc.rpc.channel import Channel
    from tpurpc.rpc.status import RpcError, StatusCode
    from tpurpc.serving import GenerationClient, serve_generation

    STEP_S = 0.001
    MAX_TOKENS = 24

    def leg(n_clients: int, leg_s: float = 1.2) -> dict:
        """One offered-load cell: ``n_clients`` closed-loop streaming
        clients (alternating interactive/batch) against a FRESH server,
        so no EWMA/queue state leaks between cells."""
        model = ToyDecodeModel(step_delay_s=STEP_S)
        srv, port, sched = serve_generation(
            model, max_batch=8, max_waiting=8, batch_shed_depth=4)
        lock = threading.Lock()
        stats = {"tokens": 0, "streams": 0,
                 "sheds": {"interactive": 0, "batch": 0},
                 "ttft_ms": {"interactive": [], "batch": []}}
        stop_at = [0.0]
        # barrier-released start (the _shard_bench discipline): channel
        # dialing happens OUTSIDE the measured window, or the big legs pay
        # their ramp-up inside the goodput denominator
        start = threading.Barrier(n_clients + 1)

        def client(slo: str):
            with Channel(f"127.0.0.1:{port}") as ch:
                gen = GenerationClient(ch)
                list(gen.generate([1], max_tokens=1, timeout=20))  # dial
                start.wait(30)
                while time.monotonic() < stop_at[0]:
                    t0 = time.perf_counter()
                    try:
                        it = iter(gen.call([7, 7], max_tokens=MAX_TOKENS,
                                           slo=slo, timeout=20))
                        next(it)
                        ttft = (time.perf_counter() - t0) * 1000
                        n = 1 + sum(1 for _ in it)
                    except RpcError as exc:
                        if exc.code() is StatusCode.UNAVAILABLE:
                            with lock:
                                stats["sheds"][slo] += 1
                            # a well-behaved shed client honors pushback
                            md = dict(exc.trailing_metadata() or ())
                            pb = int(md.get("tpurpc-pushback-ms", 25))
                            time.sleep(min(pb, 200) / 1000)
                            continue
                        raise
                    with lock:
                        stats["tokens"] += n
                        stats["streams"] += 1
                        stats["ttft_ms"][slo].append(ttft)

        try:
            stop_at[0] = time.monotonic() + 3600  # armed after the barrier
            threads = [threading.Thread(
                target=client,
                args=("interactive" if i % 2 == 0 else "batch",))
                for i in range(n_clients)]
            for t in threads:
                t.start()
            start.wait(60)
            t0 = time.monotonic()
            stop_at[0] = t0 + leg_s
            for t in threads:
                t.join(leg_s + 30)
            dt = time.monotonic() - t0
        finally:
            srv.stop(grace=0)
            sched.close()

        def p(q, xs):
            if not xs:
                return None
            xs = sorted(xs)
            return round(xs[max(0, int(len(xs) * q) - 1)], 2)

        return {
            "offered_clients": n_clients,
            "goodput_tokens_per_s": round(stats["tokens"] / dt, 1),
            "streams_per_s": round(stats["streams"] / dt, 1),
            "shed_per_s_interactive": round(
                stats["sheds"]["interactive"] / dt, 1),
            "shed_per_s_batch": round(stats["sheds"]["batch"] / dt, 1),
            "ttft_p50_ms_interactive": p(0.5,
                                         stats["ttft_ms"]["interactive"]),
            "ttft_p99_ms_interactive": p(0.99,
                                         stats["ttft_ms"]["interactive"]),
            "ttft_p99_ms_batch": p(0.99, stats["ttft_ms"]["batch"]),
            "avg_step_batch": round(
                sched.tokens_out / max(1, sched.steps), 2),
        }

    out: dict = {}
    # the watchdog's default 1s bar reads a healthy-but-queued token
    # stream as a stall and logs a flight replay per trip MID-MEASUREMENT
    # — silence it for the bench window (the decode-step attribution has
    # its own smoke + tests)
    wd = _wd.get()
    wd_was = wd.enabled
    wd.enabled = False
    try:
        light = leg(2)
        curve = [light] + [leg(n) for n in (4, 8, 16, 24)]
    finally:
        wd.enabled = wd_was
    out["gen_shed_curve"] = curve
    out["gen_note"] = (
        "1-core rig: offered-load clients share the serving core, so the "
        "heaviest legs include client-side scheduling cost; see "
        "ARCHITECTURE.md §19")
    goodputs = [c["goodput_tokens_per_s"] for c in curve]
    peak = max(goodputs)
    out["gen_tokens_per_s"] = peak
    out["gen_model"] = (f"toy affine-hash decode, step stand-in "
                        f"{STEP_S * 1000:.0f}ms, {MAX_TOKENS} tokens/stream")
    worst = curve[-1]
    out["gen_ttft_ms"] = {
        "light_p50": light["ttft_p50_ms_interactive"],
        "light_p99": light["ttft_p99_ms_interactive"],
        "worst_load_interactive_p99": worst["ttft_p99_ms_interactive"],
        "worst_load_batch_p99": worst["ttft_p99_ms_batch"],
    }
    # graceful degradation, on file: goodput past the peak never collapses
    # below 0.75x peak...
    past_peak = goodputs[goodputs.index(peak):]
    out["gen_shed_noncollapse"] = round(min(past_peak) / peak, 3) \
        if peak else None
    # ...the batch class absorbs the shedding first...
    sheds_i = sum(c["shed_per_s_interactive"] for c in curve)
    sheds_b = sum(c["shed_per_s_batch"] for c in curve)
    out["gen_batch_sheds_first"] = bool(sheds_b > sheds_i)
    out["gen_sheds_per_s_by_class"] = {"interactive": round(sheds_i, 1),
                                       "batch": round(sheds_b, 1)}
    # ...and interactive TTFT at the worst load stays bounded (record the
    # ratio; the acceptance eyeball is "held while batch sheds first")
    if light["ttft_p99_ms_interactive"] and \
            worst["ttft_p99_ms_interactive"]:
        out["gen_ttft_inflation_x"] = round(
            worst["ttft_p99_ms_interactive"]
            / max(0.01, light["ttft_p99_ms_interactive"]), 2)
    return out


def _odyssey_overhead(pairs: int = 7, phase_s: float = 0.8) -> dict:
    """tpurpc-odyssey gate (ISSUE 15): journey tracing + per-sequence
    accounting ON (the default posture: ledger per sequence, per-token
    ITL at the stream edge, per-step cost shares, journey spans into the
    tail buffer) vs OFF (``odyssey.force(False)``).

    Methodology: ONE long-lived decode scheduler fed in-process by
    closed-loop submitters carrying trace contexts and account keys (the
    exact PR 10 gen-bench decode regime at full token rate), with the
    odyssey gate toggled between adjacent PHASES and the gate computed
    as the MEDIAN of paired adjacent-phase diffs. In-process rather than
    over RPC because the toggle changes ONLY decode-loop-side work —
    the transport face passes trace/account identically in both states
    — while end-to-end closed-loop legs on this shared 1-core box swing
    ±5% with host weather, drowning a ~1% signal (the RPC-path tokens/s
    trajectory still rides ``_gen_bench`` with odyssey at its default
    ON). ``odyssey_overhead_pct < 3%`` is the acceptance gate; the on
    phases also record ``gen_itl_p99_us`` (the first token-latency
    series in the perf trajectory) and per-account accounting totals."""
    import threading

    from tpurpc.jaxshim.generate import ToyDecodeModel
    from tpurpc.obs import odyssey as _ody
    from tpurpc.obs import tracing as _tracing
    from tpurpc.obs import watchdog as _wd
    from tpurpc.serving.scheduler import DecodeScheduler

    STEP_S = 0.001
    MAX_TOKENS = 24
    N_FEEDERS = 10
    ACCOUNTS = ("bench-acct-a", "bench-acct-b")

    model = ToyDecodeModel(step_delay_s=STEP_S)
    sched = DecodeScheduler(model, max_batch=8, max_waiting=32,
                            name="ody-bench")
    stop = [False]

    def feeder(i: int):
        while not stop[0]:
            ctx = _tracing.maybe_sample()  # the api face's trace source
            try:
                st = sched.submit([7, 7], max_tokens=MAX_TOKENS,
                                  trace=ctx, account=ACCOUNTS[i % 2])
            except Exception:
                time.sleep(0.005)
                continue
            try:
                for _ in st:
                    pass
            except Exception:
                pass

    wd = _wd.get()
    wd_was = wd.enabled
    wd.enabled = False
    deltas: list = []
    rates = {"off": [], "on": []}

    def phase(on: bool) -> float:
        _ody.force(on)
        n0 = sched.tokens_out
        t0 = time.monotonic()
        time.sleep(phase_s)
        dt = time.monotonic() - t0
        return (sched.tokens_out - n0) / dt

    try:
        threads = [threading.Thread(target=feeder, args=(i,), daemon=True)
                   for i in range(N_FEEDERS)]
        for t in threads:
            t.start()
        time.sleep(0.5)  # ramp, untimed
        for i in range(max(2, pairs)):
            if i % 2 == 0:
                off = phase(False)
                on = phase(True)
            else:
                on = phase(True)
                off = phase(False)
            rates["off"].append(off)
            rates["on"].append(on)
            if off > 0:
                deltas.append((off - on) / off * 100)
    finally:
        stop[0] = True
        _ody.force(None)
        wd.enabled = wd_was
        sched.close()
        for t in threads:
            t.join(10)
    deltas.sort()
    gate = deltas[len(deltas) // 2] if deltas else 0.0
    out = {
        "odyssey_overhead_pct": round(gate, 2),
        "odyssey_tokens_per_s": {
            "off": round(sorted(rates["off"])[len(rates["off"]) // 2], 1),
            "on": round(sorted(rates["on"])[len(rates["on"]) // 2], 1)},
        "odyssey_overhead_note": (
            "median of paired adjacent on/off phase diffs on one live "
            "decode loop; per-hook microcost ~0.4us/token (one ITL list "
            "append; hist+roll flush in 64-token batches) + ~1.8us/step "
            "(cost shares) + ~11us/seq (ledger lifecycle)"),
    }
    # the first token-latency series on file: rolling p99 ITL from the
    # on legs (µs), plus what the accounting plane attributed per account
    itl = _ody.itl_p99_us("interactive")
    if itl is not None:
        out["gen_itl_p99_us"] = round(itl, 1)
    accts = _ody.accounts_snapshot()
    out["odyssey_accounts"] = {
        name: {"seqs": int(b["seqs"]), "tokens": int(b["tokens"]),
               "step_us": round(b["step_us"], 1)}
        for name, b in sorted(accts.items()) if name in ACCOUNTS}
    return out


def _disagg_bench() -> dict:
    """tpurpc-keystone benches (ISSUE 11), in-process, ~15s total:

    * ``disagg_tokens_per_s`` / ``disagg_ttft_ms_p50`` vs the colocated
      PR 10 baseline (``disagg_baseline_*``): the same step stand-in and
      client count, once through ``serve_generation`` (prefill+decode in
      one scheduler) and once split prefill-tier -> decode-tier with the
      KV shipped over block grants — the cost of disaggregation on this
      1-core rig is on file, not guessed;
    * ``disagg_migration_blackout_ms`` — a live stream is migrated
      between two decode servers mid-generation; blackout is the worst
      inter-token gap, reported against the median healthy gap;
    * ``disagg_prefix_sweep`` — repeated-prompt fractions 0 / 0.5 / 0.9:
      measured prefix-cache hit rate and mean KV bytes shipped per
      request (a hit ships exactly one 16 B entry).
    """
    import numpy as _np

    from tpurpc.jaxshim.generate import ToyDecodeModel
    from tpurpc.obs import watchdog as _wd
    from tpurpc.rpc.channel import Channel
    from tpurpc.serving import (DisaggClient, GenerationClient, migrate,
                                serve_decode, serve_generation,
                                serve_prefill)

    STEP_S = 0.001
    N_CLIENTS = 4
    TOKENS = 48
    PROMPT = [7] * 24

    def drive(make_gen, n_clients=N_CLIENTS, tokens=TOKENS) -> dict:
        lock = threading.Lock()
        stats = {"tokens": 0, "ttft": []}
        start = threading.Barrier(n_clients + 1)

        def client():
            gen = make_gen()
            start.wait(30)
            for _ in range(3):
                t0 = time.perf_counter()
                n = 0
                for _tok in gen(PROMPT, tokens):
                    if n == 0:
                        ttft = (time.perf_counter() - t0) * 1000
                    n += 1
                with lock:
                    stats["tokens"] += n
                    stats["ttft"].append(ttft)

        threads = [threading.Thread(target=client)
                   for _ in range(n_clients)]
        for t in threads:
            t.start()
        start.wait(60)
        t0 = time.monotonic()
        for t in threads:
            t.join(60)
        dt = time.monotonic() - t0
        ttfts = sorted(stats["ttft"])
        return {
            "tokens_per_s": round(stats["tokens"] / dt, 1),
            "ttft_ms_p50": round(ttfts[len(ttfts) // 2], 2)
            if ttfts else None,
        }

    out: dict = {}
    wd = _wd.get()
    wd_was = wd.enabled
    wd.enabled = False
    try:
        # -- colocated baseline (PR 10 posture) --------------------------
        srv, port, sched = serve_generation(
            ToyDecodeModel(step_delay_s=STEP_S), max_batch=8)
        chans = []
        try:
            def mk():
                ch = Channel(f"127.0.0.1:{port}")
                chans.append(ch)
                cli = GenerationClient(ch)
                return lambda p, n: cli.generate(p, max_tokens=n,
                                                 timeout=30)
            base = drive(mk)
        finally:
            for ch in chans:
                ch.close()
            srv.stop(grace=0)
            sched.close()
        out["disagg_baseline_tokens_per_s"] = base["tokens_per_s"]
        out["disagg_baseline_ttft_ms_p50"] = base["ttft_ms_p50"]

        # -- disaggregated: prefill tier -> decode tier ------------------
        d_srv, d_port, d_sched, d_state = serve_decode(
            ToyDecodeModel(step_delay_s=STEP_S), max_batch=8,
            kv_blocks=512, block_bytes=1024)
        d_ch = Channel(f"127.0.0.1:{d_port}")
        p_srv, p_port, p_state = serve_prefill(
            ToyDecodeModel(), d_ch, f"127.0.0.1:{d_port}")
        clis = []
        try:
            def mkd():
                ch = Channel(f"127.0.0.1:{p_port}")
                chans.append(ch)
                cli = DisaggClient(ch, f"127.0.0.1:{d_port}")
                clis.append(cli)
                return lambda p, n: cli.generate(p, max_tokens=n,
                                                 timeout=30)
            dis = drive(mkd)
            out["disagg_tokens_per_s"] = dis["tokens_per_s"]
            out["disagg_ttft_ms_p50"] = dis["ttft_ms_p50"]
            out["disagg_prefix_hits_under_load"] = \
                d_state.mgr.prefix_hits

            # -- prefix-cache hit-rate sweep -----------------------------
            sweep = []
            rng = _np.random.default_rng(11)
            for frac in (0.0, 0.5, 0.9):
                hits0 = d_state.mgr.prefix_hits
                ship0 = p_state.shipped_bytes
                reqs = 20
                cli = clis[0]
                hot = [3] * 64
                for i in range(reqs):
                    p = hot if rng.random() < frac else \
                        [int(x) for x in rng.integers(1, 250, 64)]
                    list(cli.generate(p, max_tokens=2, timeout=30))
                sweep.append({
                    "repeat_fraction": frac,
                    "hit_rate": round(
                        (d_state.mgr.prefix_hits - hits0) / reqs, 2),
                    "mean_ship_bytes": round(
                        (p_state.shipped_bytes - ship0) / reqs, 1),
                })
            out["disagg_prefix_sweep"] = sweep
        finally:
            for cli in clis:
                cli.close()
            for ch in chans:
                try:
                    ch.close()
                except Exception:
                    pass
            p_srv.stop(grace=0)
            p_state.close()
            d_srv.stop(grace=0)
            d_sched.close()
            d_state.close()
            d_state.mgr.close()
            d_ch.close()

        # -- migration blackout ------------------------------------------
        a_srv, a_port, a_sched, a_state = serve_decode(
            ToyDecodeModel(step_delay_s=STEP_S), name="migA",
            kv_blocks=256, block_bytes=1024)
        b_srv, b_port, b_sched, b_state = serve_decode(
            ToyDecodeModel(step_delay_s=STEP_S), name="migB",
            kv_blocks=256, block_bytes=1024)
        a_ch = Channel(f"127.0.0.1:{a_port}")
        mp_srv, mp_port, mp_state = serve_prefill(
            ToyDecodeModel(), a_ch, f"127.0.0.1:{a_port}")
        mp_ch = Channel(f"127.0.0.1:{mp_port}")
        b_ch = Channel(f"127.0.0.1:{b_port}")
        cli = DisaggClient(mp_ch, f"127.0.0.1:{a_port}")
        try:
            stamps: list = []

            def stream():
                for _ in cli.generate([5] * 8, max_tokens=400,
                                      timeout=60):
                    stamps.append(time.perf_counter())

            t = threading.Thread(target=stream)
            t.start()
            while a_sched.running_depth() == 0 and t.is_alive():
                time.sleep(0.005)
            time.sleep(0.05)
            migrate(a_state, b_ch, f"127.0.0.1:{b_port}")
            t.join(60)
            gaps = [(b - a) * 1000
                    for a, b in zip(stamps, stamps[1:])]
            if gaps:
                gaps_sorted = sorted(gaps)
                out["disagg_migration_blackout_ms"] = round(max(gaps), 2)
                out["disagg_migration_median_gap_ms"] = round(
                    gaps_sorted[len(gaps_sorted) // 2], 3)
                out["disagg_migration_tokens"] = len(stamps)
        finally:
            cli.close()
            mp_srv.stop(grace=0)
            mp_state.close()
            a_srv.stop(grace=0)
            b_srv.stop(grace=0)
            a_sched.close()
            b_sched.close()
            a_state.close()
            b_state.close()
            a_state.mgr.close()
            b_state.mgr.close()
            for ch in (mp_ch, a_ch, b_ch):
                ch.close()
    finally:
        wd.enabled = wd_was
    out["disagg_note"] = (
        "toy 1ms-step stand-in: the bench measures the handoff/"
        "re-attach/migration machinery, not model FLOPs. Even on this "
        "1-core rig disagg tokens/s beats colocated — prefill leaves "
        "the decode loop thread (colocated prefill stalls the step "
        "loop between boundaries) — while TTFT pays the extra "
        "prefill-hop round trip; real fleets also scale the tiers "
        "independently")
    return out


def _hive_bench() -> dict:
    """tpurpc-hive (ISSUE 16): connection-scale curves — live p99 and
    resident bytes per connection as the PARKED fleet ramps 1k → 10k →
    50k pairs (1% of each level stays active; a fixed 32-connection
    driver set is what's timed, so the curve isolates the cost of parked
    mass rather than traffic mix). Gates: p99 with the 50k-level fleet
    parked within 25% of the 100-connection baseline, and <= 4 KiB
    resident per parked pair (the ring + status regions must live in the
    shared RingPool, not the pair).

    Loopback connections cost ~10 fds each, so RLIMIT_NOFILE caps the
    achievable fleet on most rigs — every level records target vs
    achieved and the artifact says loudly when it was capped."""
    import resource

    import tpurpc.core.pair as _pair

    drivers_n = 32
    msg = b"\xa5" * 256
    _pair.RingPool.reset()
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if soft < hard:
        try:
            resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))
            soft = hard
        except (ValueError, OSError):
            pass
    cap = max(drivers_n + 8, (soft - 200) // 10)

    def pump(a, b):
        for p in (a, b):
            try:
                if p.drain_notifications():
                    p.kick()
            except Exception:
                pass

    def park_all(conns):
        now = time.monotonic()
        for a, b in conns:
            a.maybe_park(now, 0.0)
            b.maybe_park(now, 0.0)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            pending = [(a, b) for a, b in conns
                       if not (a._parked and b._parked)]
            if not pending:
                return
            now = time.monotonic()
            for a, b in pending:
                pump(a, b)
                if not a._parked:
                    a.maybe_park(now, 0.0)
                if not b._parked:
                    b.maybe_park(now, 0.0)

    def drive_p99(drivers, samples=1500):
        lats = []
        deadline = time.monotonic() + 8
        while len(lats) < samples and time.monotonic() < deadline:
            for a, b in drivers:
                t0 = time.perf_counter()
                sent = 0
                while sent < len(msg):
                    sent += b.send([msg[sent:]])
                    pump(a, b)
                got = 0
                while got < len(msg):
                    got += len(a.recv() or b"")
                    pump(a, b)
                lats.append(time.perf_counter() - t0)
        lats.sort()
        return lats[min(len(lats) - 1, int(len(lats) * 0.99))]

    fleet = []      # (a, b) conns beyond the driver set
    out = {"hive_fd_limit": soft, "hive_conn_cap": cap,
           "hive_levels": []}
    try:
        drivers = [_pair.create_loopback_pair(ring_size=4096)
                   for _ in range(drivers_n)]
        # 100-connection baseline: drivers + 68 idle live connections
        fleet = [_pair.create_loopback_pair(ring_size=4096)
                 for _ in range(100 - drivers_n)]
        drive_p99(drivers, samples=300)  # warmup: byte-code/alloc caches
        base_p99 = drive_p99(drivers)
        out["hive_baseline_conns"] = 100
        out["hive_baseline_p99_us"] = round(base_p99 * 1e6, 1)
        for target_pairs in (1000, 10_000, 50_000):
            want_conns = min(target_pairs // 2, cap)
            while len(fleet) + drivers_n < want_conns:
                fleet.append(_pair.create_loopback_pair(ring_size=4096))
            park_all(fleet)
            parked = [p for a, b in fleet for p in (a, b) if p._parked]
            resident = (max(p.resident_bytes_est() for p in parked)
                        if parked else 0)
            p99 = drive_p99(drivers)
            stats = _pair.RingPool.get().stats()
            level = {
                "target_pairs": target_pairs,
                "parked_pairs": len(parked),
                "fd_capped": want_conns < target_pairs // 2,
                "live_p99_us": round(p99 * 1e6, 1),
                "p99_vs_baseline_pct": round(100 * p99 / base_p99, 1),
                "resident_bytes_per_parked_pair": resident,
                "ring_pool_free_mib": round(stats["free_bytes"] / 2**20, 2),
            }
            out["hive_levels"].append(level)
        last = out["hive_levels"][-1]
        out["hive_p99_gate_pct"] = last["p99_vs_baseline_pct"]
        out["hive_p99_gate_ok"] = last["p99_vs_baseline_pct"] <= 125.0
        out["hive_resident_gate_ok"] = (
            last["resident_bytes_per_parked_pair"] <= 4096)
        if last["fd_capped"]:
            out["hive_note"] = (
                f"fd limit {soft} caps the fleet at {cap} connections "
                f"({2 * cap} pairs) — the 50k level measured the capped "
                f"fleet; the per-pair resident + p99 curves are the claim, "
                f"not the absolute count")
    finally:
        for a, b in drivers + fleet:
            try:
                a.destroy()
                b.destroy()
            except Exception:
                pass
        _pair.RingPool.reset()
    return out


_NATIVE_LEG_CODE = r"""
import json, statistics, sys, time

mode, msgs, rounds = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
from tpurpc.obs import native_obs
from tpurpc.rpc import native_client
from tpurpc.rpc.channel import Channel
from tpurpc.rpc.server import Server, stream_stream_rpc_method_handler

kw = {} if mode.startswith("native") else {"native_dataplane": False}
srv = Server(max_workers=4, **kw)
def total(req_iter, ctx):
    n = 0
    for m in req_iter:
        n += len(m)
    yield str(n).encode()
srv.add_method("/natbench.S/Sink", stream_stream_rpc_method_handler(total))
port = srv.add_insecure_port("127.0.0.1:0")
srv.start()
payload = b"\xa5" * (4 << 20)
opts = {} if mode.startswith("native") else {"tpurpc_native": False}
with Channel(f"127.0.0.1:{port}") as ch:
    mc = ch.stream_stream("/natbench.S/Sink", **opts)
    # warmup settles the capability hello + standing grants — the first
    # big send legitimately races the hello and frames
    list(mc(iter([payload, payload]), timeout=60))
    c0 = native_client.rdv_counters() or {}
    o0 = native_obs.counters()
    gbps = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        out = list(mc(iter([payload] * msgs), timeout=300))
        dt = time.perf_counter() - t0
        assert out[-1] == str(msgs * len(payload)).encode(), out
        gbps.append(msgs * len(payload) / dt / 1e9)
    c1 = native_client.rdv_counters() or {}
    o1 = native_obs.counters()
srv.stop(grace=1)
delta = {k: c1.get(k, 0) - c0.get(k, 0) for k in c1}
print("RESULT " + json.dumps({
    "gbps": round(statistics.median(gbps), 3),
    "gbps_rounds": [round(g, 3) for g in sorted(gbps)],
    "counters_delta": delta,
    "obs_delta": {k: o1.get(k, 0) - o0.get(k, 0) for k in o1},
    "total_msgs": rounds * msgs,
}), flush=True)
"""


def _native_bench(env) -> dict:
    """tpurpc-ironclad (ISSUE 18): the native-plane A/B — ``stream_4MiB``
    over (a) native client+server with rendezvous (the default ladder),
    (b) native forced framed (size bar pushed above every payload — same
    code path, zero offers, the honest framed control leg), and (c) the
    Python plane with rendezvous (the PR 7 headline path) — same weather:
    one run, sequential legs bracketed by a fresh memcpy yardstick.

    Emits the native plane's ``ctrl_wakeups_per_msg`` (process-global C
    counters: forced consumer kicks + framed control ops per message,
    ≈0 in the ring-borne steady state) and ``native_stream_vs_memcpy_pct``
    with the ≥80% gate BINDING wherever the rig has ≥2 cores; the honest
    ``applicable: false`` + note survives only on true 1-core rigs, where
    sender memcpy and receiver deliver timeshare one hart. Each leg is a
    fresh subprocess so the env knobs and the process-global counters
    start clean.

    tpurpc-xray rides the same run: ``native_ctrl_wakeups_per_msg`` is
    derived from the scraped shm metrics table (one vocabulary with
    /metrics and the tsdb), and a fourth leg with ``TPURPC_NATIVE_OBS=0``
    prices the instrument itself — ``native_obs_overhead_pct`` with the
    <3% gate every other telemetry layer already answers to."""
    cpus = _cores_available()
    msgs = int(os.environ.get("TPURPC_BENCH_NATIVE_MSGS", "48"))
    rounds = int(os.environ.get("TPURPC_BENCH_NATIVE_ROUNDS", "5"))
    lenv = dict(env)
    lenv["GRPC_PLATFORM_TYPE"] = "RDMA_BPEV"  # ring platform: C adoption
    lenv["JAX_PLATFORMS"] = "cpu"  # jax-free legs: they must not want the chip

    def leg(mode, extra=None):
        e = dict(lenv)
        if extra:
            e.update(extra)
        p = subprocess.run(
            [sys.executable, "-u", "-c", _NATIVE_LEG_CODE, mode,
             str(msgs), str(rounds)],
            env=e, capture_output=True, text=True, timeout=240)
        lines = [ln for ln in p.stdout.splitlines()
                 if ln.startswith("RESULT ")]
        if p.returncode != 0 or not lines:
            raise RuntimeError(
                f"native bench leg {mode} failed: {p.stderr[-800:]}")
        return json.loads(lines[0][len("RESULT "):])

    out: dict = {}
    yard = _calibration().get("memcpy_gbps_best")  # same-weather yardstick
    rdv = leg("native_rdv")
    framed = leg("native_framed",
                 {"TPURPC_RENDEZVOUS_MIN_KB": str(1 << 20)})
    py = leg("python_rdv")
    d = rdv["counters_delta"]
    n = rdv["total_msgs"]
    out["native_stream_4MiB_gbps"] = rdv["gbps"]
    out["native_framed_4MiB_gbps"] = framed["gbps"]
    out["python_rdv_4MiB_gbps"] = py["gbps"]
    if framed["gbps"]:
        out["native_rdv_vs_framed_x"] = round(rdv["gbps"] / framed["gbps"],
                                              2)
    if py["gbps"]:
        out["native_vs_python_x"] = round(rdv["gbps"] / py["gbps"], 2)
    # the control-plane claim, C-side: kicks + framed control ops per bulk
    # message across the native leg's timed window (client AND server —
    # the counters are process-global, so ≈0 is the stronger statement).
    # tpurpc-xray: derived from the SCRAPED obs table — the same slots
    # /metrics, the tsdb, and tools/top read — so the bench artifact and
    # the live scrape can never tell different stories; the PR 18 ledger
    # carries the number only when the plane is off.
    od = rdv.get("obs_delta") or {}
    src = od if od else d
    out["native_ctrl_wakeups_per_msg"] = round(
        (src.get("ctrl_kicks", 0) + src.get("ctrl_frames", 0)) / n, 4)
    out["native_ctrl_wakeups_source"] = ("obs_table" if od else
                                         "rdv_ledger")
    out["native_rdv_fallbacks"] = d.get("rdv_fallback", 0)
    out["native_host_copy_bytes_per_msg"] = round(
        d.get("host_copy_bytes", 0) / n, 1)
    if yard:
        out["native_memcpy_gbps"] = yard
        pct = round(100 * rdv["gbps"] / yard, 1)
        out["native_stream_vs_memcpy_pct"] = pct
        # the ISSUE 18 flip: the 80% gate BINDS wherever ≥2 cores let the
        # receiver's deliver run beside the sender's memcpy
        out["native_stream_vs_memcpy_gate"] = {
            "target_pct": 80.0,
            "applicable": cpus >= 2,
            "pass": (pct >= 80.0) if cpus >= 2 else None,
        }
        if cpus < 2:
            out["native_stream_vs_memcpy_note"] = (
                "1-core rig: sender memcpy and receiver deliver timeshare "
                "one hart, so the ceiling is 1/(t_memcpy + t_consume) "
                "regardless of control-plane cost; "
                "native_ctrl_wakeups_per_msg (≈0) and the rdv-vs-framed "
                "A/B carry the native-plane claim here")
    # tpurpc-xray (ISSUE 19): the observability plane's own price — the
    # SAME native+rdv leg with TPURPC_NATIVE_OBS=0 (the C side reads it
    # at first use, so a fresh subprocess is the honest off state; the
    # rdv_write timing bracket is behind enabled(), keeping the off leg
    # free of clock reads too). Best-draw comparison: contamination on a
    # shared rig is one-sided, so max-of-rounds approximates each leg's
    # uncontended throughput and the delta is the instrument's cost.
    obsoff = leg("native_rdv", {"TPURPC_NATIVE_OBS": "0"})
    out["native_obs_off_4MiB_gbps"] = obsoff["gbps"]
    best_on = max(rdv["gbps_rounds"] or [rdv["gbps"]])
    best_off = max(obsoff["gbps_rounds"] or [obsoff["gbps"]])
    if best_off:
        pct = round(100.0 * (best_off - best_on) / best_off, 2)
        out["native_obs_overhead_pct"] = pct
        out["native_obs_overhead_gate_pct"] = 3.0
        out["native_obs_overhead_pass"] = pct < 3.0
    if cpus >= 2:
        # delivery-shard A/B: decode/deliver off the receive hart is only
        # a win when there is a second hart to take it
        noshard = leg("native_rdv", {"TPURPC_NATIVE_DELIVERY": "0"})
        out["native_noshard_4MiB_gbps"] = noshard["gbps"]
        if noshard["gbps"]:
            out["native_delivery_shard_speedup_x"] = round(
                rdv["gbps"] / noshard["gbps"], 2)
    else:
        out["native_delivery_shard_note"] = (
            "1-core rig: the delivery shard is auto-off (a queue handoff "
            "to the only hart); its A/B binds on ≥2-core rigs and the "
            "≥2.5x@4-core serving gate lives in serving_by_cores_gate")
    out["native_bench_method"] = {
        "payload_mib": 4, "msgs_per_round": msgs, "rounds": rounds,
        "stat": "median of rounds", "handler": "bytes sink (jax-free)",
        "rounds_sorted": {"native_rdv": rdv["gbps_rounds"],
                          "native_framed": framed["gbps_rounds"],
                          "python_rdv": py["gbps_rounds"],
                          "native_obs_off": obsoff["gbps_rounds"]},
    }
    return out


def _stream_by_size(port: int) -> dict:
    """tpurpc-express (ISSUE 9): message-size sweep 64 KiB → 16 MiB on the
    Python plane, rendezvous ON vs OFF (the size bar pushed above every
    payload), recording GB/s per cell and the measured crossover — so the
    TPURPC_RENDEZVOUS_MIN_KB default is a number this artifact justifies,
    not a guess. Each leg is budgeted by bytes, keeps the whole sweep to
    ~20 s, and reuses one channel per mode so steady-state (standing
    landing regions pre-granted) is what's measured."""
    import numpy as np

    from tpurpc.jaxshim import TensorClient
    from tpurpc.rpc.channel import Channel

    sizes = [64 << 10, 256 << 10, 1 << 20, 4 << 20, 16 << 20]
    budget = 96 << 20  # bytes per cell
    out: dict = {"sizes_kib": [s >> 10 for s in sizes],
                 "rendezvous_gbps": [], "framed_gbps": []}
    saved = os.environ.get("TPURPC_RENDEZVOUS_MIN_KB")
    try:
        for mode in ("rendezvous", "framed"):
            if mode == "framed":
                # push the size bar above every payload: same code path,
                # zero offers — the honest framed control leg
                os.environ["TPURPC_RENDEZVOUS_MIN_KB"] = str(1 << 20)
            elif saved is not None:
                os.environ["TPURPC_RENDEZVOUS_MIN_KB"] = saved
            else:
                os.environ.pop("TPURPC_RENDEZVOUS_MIN_KB", None)
            with Channel(f"127.0.0.1:{port}") as ch:
                cli = TensorClient(ch)
                for size in sizes:
                    # 2-D: the Sink handler's checksum reads arr[0, 0]
                    payload = np.ones((size // 1024, 256), np.float32)
                    msgs = max(4, budget // payload.nbytes)

                    def gen(k, p=payload):
                        for _ in range(k):
                            yield {"x": p}

                    # warm: jit + (rendezvous mode) standing grants
                    list(cli.duplex("Sink", gen(2), native=False,
                                    timeout=120))
                    t0 = time.perf_counter()
                    replies = list(cli.duplex("Sink", gen(msgs),
                                              native=False, timeout=300))
                    dt = time.perf_counter() - t0
                    import numpy as _np

                    total = int(_np.asarray(
                        replies[-1]["bytes"]).ravel()[0])
                    assert total == msgs * payload.nbytes
                    out[f"{mode}_gbps"].append(round(total / dt / 1e9, 2))
    finally:
        if saved is not None:
            os.environ["TPURPC_RENDEZVOUS_MIN_KB"] = saved
        else:
            os.environ.pop("TPURPC_RENDEZVOUS_MIN_KB", None)
    crossover = None
    for size, r, f in zip(sizes, out["rendezvous_gbps"],
                          out["framed_gbps"]):
        if r > f:
            crossover = size
            break
    out["crossover_bytes"] = crossover
    out["note"] = ("crossover = smallest message size where the "
                   "rendezvous plane beats the framed path; the "
                   "TPURPC_RENDEZVOUS_MIN_KB default (256) should sit at "
                   "or below it")
    return out


def _cores_available() -> int:
    try:
        return len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        return os.cpu_count() or 1


def _calibration() -> dict:
    """Tiny host-speed probes so round-over-round artifacts are comparable
    across noisy-neighbor weather: a memcpy-bandwidth
    probe (the streaming path is memcpy-bound on a CPU) and a
    single-thread matmul probe. Best-of-5 each — the best draw approximates
    the uncontended host; the best/mean ratio (≤1; «1 = contended) exposes
    contamination during the calibration itself."""
    import numpy as np

    out: dict = {}
    try:
        src = np.ones(32 * 1024 * 1024 // 8, np.float64)  # 32 MiB
        dst = np.empty_like(src)
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            np.copyto(dst, src)
            ts.append(time.perf_counter() - t0)
        out["memcpy_gbps_best"] = round(src.nbytes / min(ts) / 1e9, 2)
        out["memcpy_best_over_mean"] = round(min(ts) / (sum(ts) / len(ts)), 3)
        a = np.ones((384, 384), np.float32)
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            (a @ a).sum()
            ts.append(time.perf_counter() - t0)
        gflop = 2 * 384**3 / 1e9
        out["matmul_gflops_best"] = round(gflop / min(ts), 1)
    except Exception as exc:  # calibration is metadata, never a failure
        out["error"] = repr(exc)
    return out


def main() -> None:
    os.environ.setdefault("GRPC_PLATFORM_TYPE",
                          os.environ.get("TPURPC_BENCH_PLATFORM", "RDMA_BPEV"))
    os.environ.setdefault("GRPC_RDMA_RING_BUFFER_SIZE_KB", "32768")

    n_msgs = int(os.environ.get("TPURPC_BENCH_MSGS", "96"))
    # Budget for the server's cold start: backend bring-up plus the
    # ResNet-50 compile, outside every RPC deadline.
    ready_s = float(os.environ.get("TPURPC_BENCH_READY_S", "300"))

    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.dirname(os.path.abspath(__file__)) +
                         os.pathsep + env.get("PYTHONPATH", ""))

    try:
        load_start = os.getloadavg()
    except OSError:
        load_start = None

    # No accelerator and no TPURPC_BENCH_CPU=1: the server exits, this
    # raises, the run ends non-zero with no result. There is no second try.
    gbps, platform, serving, extras = _run_once(env, n_msgs, ready_s)

    out = {
        "metric": "stream_4MiB_tensors_to_jax_Array",
        "value": round(gbps, 3),
        "unit": "GB/s",
        "vs_baseline": round(gbps / BASELINE_GBPS, 3),
        "jax_platform": platform,
        "device_kind": extras["device_kind"],
    }
    out.update(extras["errors"])
    # Host-weather provenance: 1/5/15-min load
    # at start AND end brackets the measurement window; the calibration
    # probes give a host-speed yardstick to normalize cross-round deltas.
    try:
        load_end = os.getloadavg()
    except OSError:
        load_end = None
    if load_start is not None:
        out["host_load"] = {"start": [round(x, 2) for x in load_start],
                            "end": [round(x, 2) for x in load_end]
                            if load_end else None}
    out["calibration"] = extras.get("calibration", {})
    # tpurpc-scope overhead gate (ISSUE 4): telemetry fully on vs off,
    # micro closed-loop, medians of alternated legs; <3% is the contract.
    if os.environ.get("TPURPC_BENCH_OBS", "1") == "1":
        try:
            out.update(_obs_overhead())
        except Exception as exc:  # the gate is auxiliary: report, don't fail
            sys.stderr.write(f"obs overhead gate failed: {exc}\n")
            out["obs_overhead_error"] = repr(exc)
        # tpurpc-blackbox flight-recorder gate (ISSUE 5): recorder+watchdog
        # always-on vs suppressed; <3% is the acceptance contract.
        try:
            out.update(_flight_overhead())
        except Exception as exc:
            sys.stderr.write(f"flight overhead gate failed: {exc}\n")
            out["flight_overhead_error"] = repr(exc)
        # tpurpc-lens (ISSUE 8): continuous stage profiler at default Hz
        # vs stopped; <3% is the acceptance contract.
        try:
            out.update(_lens_overhead())
        except Exception as exc:
            sys.stderr.write(f"lens overhead gate failed: {exc}\n")
            out["lens_overhead_error"] = repr(exc)
        # tpurpc-proof (ISSUE 12): live protocol verifier on vs off;
        # <3% is the acceptance contract (edges-not-traffic economy).
        try:
            out.update(_proto_verify_overhead())
        except Exception as exc:
            sys.stderr.write(f"proto verify overhead gate failed: {exc}\n")
            out["proto_verify_overhead_error"] = repr(exc)
        # tpurpc-argus (ISSUE 14): tsdb sampler + slo evaluator + a 4 Hz
        # collector polling the serving port, on vs off; <3% gate plus
        # the informational tsdb_resident_bytes bound.
        try:
            out.update(_argus_overhead())
        except Exception as exc:
            sys.stderr.write(f"argus overhead gate failed: {exc}\n")
            out["argus_overhead_error"] = repr(exc)
        # tpurpc-oracle (ISSUE 20): the full diagnosis pipeline querying
        # at 4 Hz (change-point scan + every rule) vs idle; <3% gate —
        # asking "why" must cost nothing measurable.
        try:
            out.update(_diagnose_overhead())
        except Exception as exc:
            sys.stderr.write(f"diagnose overhead gate failed: {exc}\n")
            out["diagnose_overhead_error"] = repr(exc)
    # tpurpc-fleet (ISSUE 6): fleet_qps / fleet_p99_degraded_pct (hedging
    # on-vs-off with one slow replica) / shed_curve (admission gate vs
    # offered load). In-process, ~10s total.
    if os.environ.get("TPURPC_BENCH_FLEET", "1") == "1":
        try:
            out.update(_fleet_bench())
        except Exception as exc:
            sys.stderr.write(f"fleet bench failed: {exc}\n")
            out["fleet_bench_error"] = repr(exc)
    # tpurpc-manycore (ISSUE 7): serving QPS vs. shard-worker count (1/2/4
    # per-core processes, one SO_REUSEPORT port) + the depth sweep re-run
    # under sharding; cores_requested/achieved recorded like PR 3's
    # concurrency probes. ~35s, jax-free subprocesses.
    if os.environ.get("TPURPC_BENCH_CORES", "1") == "1":
        try:
            out.update(_shard_bench())
        except Exception as exc:
            sys.stderr.write(f"shard bench failed: {exc}\n")
            out["shard_bench_error"] = repr(exc)
    # tpurpc-cadence (ISSUE 10): continuous-batching generation serving —
    # tokens/s + TTFT vs offered load, and the shed-curve saturation sweep
    # proving graceful degradation. In-process, ~15s, jax-free.
    if os.environ.get("TPURPC_BENCH_GEN", "1") == "1":
        try:
            out.update(_gen_bench())
        except Exception as exc:
            sys.stderr.write(f"gen bench failed: {exc}\n")
            out["gen_bench_error"] = repr(exc)
        # tpurpc-odyssey (ISSUE 15): journey tracing + per-sequence cost
        # accounting on vs off under the gen bench; <3% gate, plus the
        # first token-latency series (gen_itl_p99_us) and the
        # per-account accounting totals.
        try:
            out.update(_odyssey_overhead())
        except Exception as exc:
            sys.stderr.write(f"odyssey overhead gate failed: {exc}\n")
            out["odyssey_overhead_error"] = repr(exc)
    # tpurpc-keystone (ISSUE 11): disaggregated prefill/decode vs the
    # colocated baseline, migration blackout, prefix-cache hit sweep.
    # In-process, ~15s, jax-free.
    if os.environ.get("TPURPC_BENCH_DISAGG", "1") == "1":
        try:
            out.update(_disagg_bench())
        except Exception as exc:
            sys.stderr.write(f"disagg bench failed: {exc}\n")
            out["disagg_bench_error"] = repr(exc)
    # tpurpc-hive (ISSUE 16): the connection-scale plane — live p99 +
    # resident bytes/connection as the parked fleet ramps 1k → 10k → 50k
    # pairs (fd-budget capped, loudly). In-process, ~15s, jax-free.
    if os.environ.get("TPURPC_BENCH_HIVE", "1") == "1":
        try:
            out.update(_hive_bench())
        except Exception as exc:
            sys.stderr.write(f"hive bench failed: {exc}\n")
            out["hive_bench_error"] = repr(exc)
    # tpurpc-ironclad (ISSUE 18): the native-plane A/B — stream_4MiB over
    # native+rdv vs native-framed vs python+rdv, same weather, with the
    # native ctrl_wakeups_per_msg and the memcpy gate binding on ≥2 cores.
    if os.environ.get("TPURPC_BENCH_NATIVE", "1") == "1":
        try:
            out.update(_native_bench(env))
        except Exception as exc:
            sys.stderr.write(f"native bench failed: {exc}\n")
            out["native_bench_error"] = repr(exc)
    if extras.get("stream_dts"):
        out["stream_round_secs"] = extras["stream_dts"]  # sorted; median used
    # tpurpc-express (ISSUE 9): the headline stream vs the SAME-WEATHER
    # memcpy yardstick (the acceptance ratio), plus the size sweep with the
    # measured rendezvous-vs-framed crossover
    yard = out.get("calibration", {}).get("memcpy_gbps_best")
    if yard:
        out["memcpy_gbps"] = yard  # the same-weather yardstick, tracked
        out["stream_4MiB_vs_memcpy_pct"] = round(100 * gbps / yard, 1)
    # tpurpc-pulse (ISSUE 13): control-plane cost per bulk message — the
    # ~0.6 ms/msg of wakeups ARCHITECTURE §18 described in prose is now a
    # tracked series.  ctrl_wakeups_per_msg = control frames + forced
    # consumer wakeups (kicks) per message, ≈0 with the descriptor-ring
    # plane in steady state; thread_parks carries the residual fd-level
    # parks (framed acks, poll-slice expiries) for context.
    cp = extras.get("ctrl_plane")
    if cp:
        out["ctrl_wakeups_per_msg"] = cp.get("ctrl_wakeups_per_msg")
        out["ctrl_parks_per_msg"] = cp.get("ctrl_parks_per_msg")
        out["ctrl_plane"] = cp
    if yard and _cores_available() < 2:
        # Gate context (PR 7 precedent): stream ≥ 80% of the burst-memcpy
        # yardstick requires the RECEIVER's per-message work (decode,
        # delivery, jax materialization) to run on a core the sender's
        # memcpy is not using.  On a 1-core rig both processes share the
        # hart, so the ceiling is 1/(t_memcpy + t_consume) regardless of
        # control-plane cost — the 80% gate binds on ≥2-core hosts; the
        # recorded pct and the A/B vs TPURPC_CTRL_RING=0 carry the
        # control-plane claim here.
        out["stream_vs_memcpy_applicable"] = False
        out["stream_vs_memcpy_note"] = (
            "1-core rig: sender memcpy and receiver decode/deliver share "
            "one hart; ctrl_wakeups_per_msg (≈0) and the ring-off A/B are "
            "the control-plane evidence")
    if extras.get("stream_by_size"):
        out["stream_by_size"] = extras["stream_by_size"]
        out["rendezvous_crossover_bytes"] = extras["stream_by_size"].get(
            "crossover_bytes")
    # tpurpc-lens (ISSUE 8): the streaming phase's per-hop waterfall — the
    # next PR finds ROADMAP item 2's bottleneck hop ON FILE here.
    if extras.get("waterfall"):
        wf = extras["waterfall"]
        out["waterfall_gbps_by_hop"] = {
            r["hop"]: r["gbps"] for r in wf["hops"]}
        out["waterfall_slowest_hop"] = wf.get("slowest_hop")
        out["waterfall_plane"] = wf.get("plane")
        out["waterfall_detail"] = wf["hops"]
    # Batched receive pipeline (ISSUE 1): messages moved per receive-drain
    # wakeup, and how often waiters were satisfied inside the busy window
    # vs parked on fds. The drain happens on whichever side RECEIVES the
    # bulk stream — the server for Sink — so prefer its histogram; the
    # client-side one covers the ack path. A zero-count histogram means the
    # measured plane was the native one (C-side batching, not instrumented
    # by the Python counters) — the field is still emitted so rounds are
    # comparable.
    bs = extras.get("batch_stats") or {}
    hist = {"count": 0, "mean": 0.0, "p50": 0, "p99": 0, "side": None}
    for side in ("server", "client"):
        h = ((bs.get(side) or {}).get("batch") or {}).get("ring_drain")
        if h and h.get("count"):
            hist = dict(h, side=side)
            break
    out["batch_msgs_per_wakeup"] = hist
    merged: dict = {}
    for side in ("server", "client"):
        for name, v in ((bs.get(side) or {}).get("counters") or {}).items():
            merged[name] = merged.get(name, 0) + v
    waits = (merged.get("wait_spin_hit", 0) + merged.get("wait_spin_miss", 0)
             + merged.get("wait_spin_skipped", 0))
    out["poller_spin_sleep"] = {
        "spin_hit": merged.get("wait_spin_hit", 0),
        "spin_miss": merged.get("wait_spin_miss", 0),
        "spin_skipped": merged.get("wait_spin_skipped", 0),
        "sleep": merged.get("wait_sleep", 0),
        # fraction of waits satisfied inside the busy window (hit / all
        # wait entries); None when nothing waited (pure native plane)
        "spin_ratio": (round(merged.get("wait_spin_hit", 0) / waits, 4)
                       if waits else None),
    }
    out["batch_stats"] = bs  # full per-side detail for round-over-round
    if serving is not None:
        # BASELINE configs #4/#5 (8-client fan-in batching into a ResNet
        # server); the reference publishes no figure, so no vs_baseline.
        qps, model, total, used_depth, used_mode = serving
        out["serving_qps"] = round(qps, 1)
        out["serving_model"] = model
        if extras.get("serving_image_size"):
            # stand-in geometry provenance: r2-r5 ran the thin-18 stand-in
            # @64 (compute-bound on 1-core rigs); r6+ runs @48 so the
            # serving phase measures the transport — compare like-for-like
            out["serving_image_size"] = extras["serving_image_size"]
        out["serving_requests"] = total
        # config provenance: the depth AND channel discipline the phase
        # ACTUALLY ran (depth-1 artifacts are only comparable within one
        # mode — native-inline vs native-reader vs python differ 10-74%);
        # r1-r2 ran depth-1 reader/python, r4 depth-4 CQ
        out["serving_client_depth"] = used_depth
        out["serving_client_mode"] = used_mode
        if extras.get("serving_qps_by_depth"):
            # in-flight-window sweep (ISSUE 3): same phase at depth 1/4/16
            out["serving_qps_by_depth"] = extras["serving_qps_by_depth"]
            if platform == "cpu":
                # Measured context the sweep MUST carry on this rig: with
                # client+server+model sharing ONE core, depth-1 already
                # runs the core at 0% idle (/proc/stat during steady
                # state), so pipelining has no idle latency to convert
                # into throughput and the sweep is expected ~flat. Depth
                # pays off where depth-1 leaves the serving core waiting —
                # any multi-core host. Without this note a flat
                # sweep reads as a pipelining bug; it is host physics.
                out["serving_depth_note"] = (
                    "1-core rig: depth-1 saturates the shared core "
                    "(0% idle measured) — sweep flat by physics, see "
                    "ARCHITECTURE.md §12")
        flops = extras["model_flops_per_inference"]
        dev_qps = extras["device_infer_qps"]
        out["model_flops_per_inference"] = flops
        out["device_infer_qps"] = dev_qps
        if platform != "cpu":
            # MFU = achieved model FLOP/s ÷ the chip's published peak (an
            # unknown device_kind raises: no peak is assumed). Two flavors:
            # serving_mfu has the whole RPC pipeline in it; device_mfu is
            # the compute path alone (batched, weights+pixels already in
            # HBM) — the gap between them is transport cost. A CPU run
            # carries neither: a host matmul is not a peak.
            peak, peak_src = _peak_flops(extras["device_kind"])
            out["peak_flops"] = peak
            out["peak_flops_source"] = peak_src
            out["serving_mfu"] = round(qps * flops / peak, 8)
            out["device_mfu"] = round(dev_qps * flops / peak, 6)
    # one process per chip: the client side must never have initialised a
    # JAX backend (the in-process legs above claim to be jax-free)
    xb = sys.modules.get("jax._src.xla_bridge")
    out["parent_jax_backend_initialised"] = bool(
        xb is not None and xb.backends_are_initialized())
    print(json.dumps(out))
    failed = sorted(k for k in out if k.endswith("_error"))
    if failed or out["parent_jax_backend_initialised"]:
        sys.stderr.write(f"bench.py: failed: {failed or 'parent touched jax'}"
                         "\n")
        sys.exit(1)


#: bf16 dense-matmul peak of one chip, FLOP/s, keyed by the ``device_kind``
#: JAX reports, each with its source. A kind that is not here is an error:
#: add the row, with its source, when the benchmark first meets the chip.
_PEAK_FLOPS = {
    "TPU v4": (275e12, "Google Cloud documentation, 'TPU v4': 275 TFLOP/s "
                       "bf16 per chip"),
    "TPU v5 lite": (197e12, "Google Cloud documentation, 'TPU v5e': 197 "
                            "TFLOP/s bf16 per chip"),
    "TPU v5": (459e12, "Google Cloud documentation, 'TPU v5p': 459 TFLOP/s "
                       "bf16 per chip"),
    "TPU v6 lite": (918e12, "Google Cloud documentation, 'TPU v6e': 918 "
                            "TFLOP/s bf16 per chip"),
}


def _peak_flops(device_kind: str) -> "tuple[float, str]":
    """(published peak FLOP/s, its source) for the MFU denominator."""
    try:
        return _PEAK_FLOPS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r}: add it to "
            "_PEAK_FLOPS with its source") from None


if __name__ == "__main__":
    main()
