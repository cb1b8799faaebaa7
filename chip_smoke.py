#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that tpurpc still starts on the chip.

    python3 chip_smoke.py                 # needs a TPU; exits non-zero without
    python3 chip_smoke.py --rehearsal-cpu # tiny CPU dress rehearsal, never a pass

Drives the system's main path once, through the entry points a user calls
(``Server``, ``add_tensor_method``, ``FanInBatcher``; ``TensorClient`` over
``Channel``), at the full width of the one model the repo serves:

1. *tensor RPC into HBM* — ``GRPC_PLATFORM_TYPE=RDMA_TPU``, a ``device=True``
   stream: seeded random ``float32[1024,1024]`` (4 MiB) tensors plus sizes that
   do not divide the 16 MiB device ring, enough to lap its credit window four
   times a pass. Every message lands directly: one ``device_put`` to its
   final array under the ring's credit, no device program
   (``hbm_place_msgs`` = messages, ``dma_h2d`` = payload, ``dma_d2d`` = 0).
   The handler insists every leaf is a ``jax.Array`` on the device, folds a
   position-weighted checksum on the device and reads it back once.
2. *serving* — ResNet-50, 1000 classes, 224x224x3, bf16, random weights from a
   seed, behind ``FanInBatcher(max_batch=8, fixed_bucket=True,
   transfer_dtype=bf16)``; 8 connections; each reply is compared with the same
   jitted function called directly in the server on the same image.
3. *to_jax* — a second server under ``RDMA_BPEV`` (``bench.py``'s platform),
   started after the first has exited and freed the chip: ``codec.to_jax`` on a
   rendezvous-landed view, a framed view and a read-only view must all land on
   the TPU.

One process per chip: this parent is the client and never imports jax (it
says so at exit); each server is a child that owns the chip for its life and
is stopped cleanly. Nothing here catches a failure and carries on: any check
that fails or phase that raises ends the run with a non-zero exit and no
result line. The last line of a passing run is one JSON object with exactly
these keys, ``{"ok": true, "device": {"platform": "tpu", "kind": "...",
"count": 1}}``; the line before it, ``summary {...}``, carries the counts of
every leg and ends with ``"claim": null`` (also written to
``chiprun_out/chip_smoke/summary.json``).
"""

from __future__ import annotations

import argparse
import json
import os
import queue
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

#: the run at full width, and the CPU dress rehearsal (KiB rings, the thin
#: ResNet) that exists so tests can drive this very script
REAL = dict(
    ring_kb=None,              # the default device ring (16 MiB), asserted
    rdv_min_kb=None,           # the default rendezvous bar (256 KiB)
    shapes=[(1024, 1024),      # 4 MiB: BASELINE config 3
            (768, 1024),       # 3 MiB: no divisor of the ring
            (1000, 1001),      # 4,004,000 B: leaves every later offset
            (640, 1024)],      #   off every power of two
    small=(100, 100),          # 40 KB: under the bar, rides the framed path
    laps=4,
    model="resnet50", image=224, classes=1000,
    clients=8, requests=4,
)
REHEARSAL = dict(
    ring_kb=64, rdv_min_kb=4,
    shapes=[(64, 64), (48, 64), (50, 50), (40, 64)],
    small=(10, 10),
    laps=4,
    model="resnet18_thin", image=32, classes=10,
    clients=8, requests=2,
)


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# what both sides must agree on
# ---------------------------------------------------------------------------

def checksum_np(x) -> int:
    """Position-weighted sum of ``x``'s 32-bit words, mod 2**32: a misplaced
    or stale byte changes it, and uint32 arithmetic is exact on both sides
    (the server's ``fold`` is the same sum in jnp)."""
    import numpy as np

    u = np.ascontiguousarray(x).view(np.uint32).ravel()
    w = np.arange(u.size, dtype=np.uint32) * np.uint32(2) + np.uint32(1)
    return int((u * w).sum(dtype=np.uint32))


def fold_np(acc: int, x) -> int:
    return (acc * 31 + checksum_np(x)) & 0xFFFFFFFF


def make_image(cfg: dict, client: int, req: int):
    """A seeded image whose statistics differ from every other one's, so two
    images never share logits and a reply from the wrong row shows."""
    import numpy as np

    k = client * cfg["requests"] + req
    rng = np.random.default_rng(1000 + k)
    img = rng.standard_normal((1, cfg["image"], cfg["image"], 3))
    return (img * (0.5 + 0.25 * (k % 7)) + (k % 5 - 2) * 0.5).astype(
        np.float32)


# ---------------------------------------------------------------------------
# the server child: owns the chip for its whole life
# ---------------------------------------------------------------------------

def serve(role: str, rehearsal: bool) -> None:
    cfg = REHEARSAL if rehearsal else REAL
    t_start = time.monotonic()

    def say(tag: str, obj) -> None:
        print(f"@{tag} {json.dumps(obj)}", flush=True)

    from tpurpc.utils import jaxenv

    cache_dir = jaxenv.enable_compile_cache()  # before first use of jax

    def cache_entries() -> int:
        return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    entries_before = cache_entries()
    import importlib.metadata as md

    import jax
    import jax.numpy as jnp
    import numpy as np

    jaxenv.count_compiles()
    dev = jax.devices()[0]
    want = "cpu" if rehearsal else "tpu"
    if dev.platform != want:
        say("fatal", f"no {want} device: jax.devices() is {jax.devices()} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); "
            "this script does not fall back")
        sys.exit(3)

    from tpurpc.core import _native
    from tpurpc.jaxshim import FanInBatcher, add_tensor_method, to_jax
    from tpurpc.obs import metrics
    from tpurpc.rpc.server import Server
    from tpurpc.tpu import ledger
    from tpurpc.utils.config import get_config

    @jax.jit
    def fold(acc, x):
        u = jax.lax.bitcast_convert_type(x, jnp.uint32).ravel()
        w = jax.lax.iota(jnp.uint32, u.size) * jnp.uint32(2) + jnp.uint32(1)
        return acc * jnp.uint32(31) + jnp.sum(u * w, dtype=jnp.uint32)

    def on_chip(x, what: str):
        if not isinstance(x, jax.Array) or x.devices() != {dev}:
            where = x.devices() if isinstance(x, jax.Array) else type(x)
            raise RuntimeError(f"{what} is on {where}, not on {dev}")
        return x

    # the host-ring server keeps to the Python loop as bench.py's bulk sink
    # does: that is the plane whose rendezvous path lands payloads in place
    srv = Server(max_workers=64,
                 native_dataplane=None if role == "ring" else False)
    extra = {}                                  # role-specific @ready facts
    seen = {"writable_views": 0, "views": 0}    # what leg 3's wire handed over

    if role == "ring":
        # -- leg 1: wire -> TpuRingEndpoint -> HbmRing -> lease-backed array
        def sink(trees):
            acc, n, nbytes = jnp.uint32(0), 0, 0
            for tree in trees:
                x = on_chip(tree["x"], "device=True request leaf")
                acc = fold(acc, x)
                n += 1
                nbytes += x.nbytes
            yield {"check": np.uint32(int(acc)),  # the one readback
                   "n": np.int64(n), "bytes": np.int64(nbytes)}

        add_tensor_method(srv, "Sink", sink, kind="stream_stream",
                          device=True)

        # -- leg 1, outbound: one reply that is a device array
        bump = jax.jit(lambda x: x + jnp.float32(1))

        def bumped(tree):
            return {"y": on_chip(bump(on_chip(tree["x"], "request leaf")),
                                 "reply leaf")}

        add_tensor_method(srv, "Bump", bumped, device=True)

        # -- leg 2: the serving stack exactly as bench.py wires it
        from tpurpc.models import resnet

        model = getattr(resnet, cfg["model"])(num_classes=cfg["classes"],
                                              dtype=jnp.bfloat16)
        t0 = time.monotonic()
        variables = resnet.init_resnet(jax.random.PRNGKey(0), model,
                                       image_size=cfg["image"])
        infer = jax.jit(resnet.make_infer_fn(model))
        max_batch = 8

        def serve_fn(tree):
            return {"logits": infer(variables, on_chip(tree["x"], "batch"))}

        batcher = FanInBatcher(serve_fn, max_batch=max_batch,
                               max_delay_s=0.005, fixed_bucket=True,
                               transfer_dtype=jnp.bfloat16)
        add_tensor_method(srv, "Infer", batcher)

        def direct(tree):
            """The reference: the SAME jitted function on the same image,
            no batcher — row 0 of a zero-padded batch of the same shape
            and dtype the batcher dispatches, so nothing recompiles."""
            image = np.asarray(tree["x"]).astype(jnp.bfloat16)
            batch = np.pad(image, [(0, max_batch - 1), (0, 0), (0, 0),
                                   (0, 0)])
            out = infer(variables, jax.device_put(batch))
            return {"logits": np.asarray(jax.device_get(out))[:1]}

        add_tensor_method(srv, "InferDirect", direct)
        # warm the one compiled shape through both doors, before READY
        zero = {"x": np.zeros((1, cfg["image"], cfg["image"], 3),
                              np.float32)}
        warm = np.asarray(batcher(zero)["logits"])
        check(warm.shape == (1, cfg["classes"]), f"warm logits {warm.shape}")
        direct(zero)
        extra["model_setup_s"] = round(time.monotonic() - t0, 2)
        extra["ring_capacity"] = get_config().hbm_ring_size
    else:
        # -- leg 3: codec.to_jax on what a host-ring platform hands over
        def tojax(trees):
            acc, n = jnp.uint32(0), 0
            for tree in trees:
                view = tree["x"]
                check(isinstance(view, np.ndarray), f"host view {type(view)}")
                seen["views"] += 1
                seen["writable_views"] += bool(view.flags.writeable)
                acc = fold(acc, on_chip(to_jax(view), "to_jax(wire view)"))
                frozen = view.view()
                frozen.setflags(write=False)
                acc = fold(acc, on_chip(to_jax(frozen),
                                        "to_jax(read-only view)"))
                n += 1
            yield {"check": np.uint32(int(acc)), "n": np.int64(n)}

        add_tensor_method(srv, "ToJax", tojax, kind="stream_stream")

    srv.start()
    port = srv.add_insecure_port("127.0.0.1:0")

    def snapshot() -> dict:
        out = {"counters": metrics.registry().counters_snapshot(),
               "ledger": ledger.snapshot(),
               "cache_entries": cache_entries()}
        if role == "ring":
            out["batches"] = batcher.batches_run
            out["rows"] = batcher.rows_run
        else:
            out["seen"] = dict(seen)
        return out

    say("ready", {
        "port": port,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "versions": {"python": sys.version.split()[0],
                     "jax": jax.__version__,
                     "jaxlib": md.version("jaxlib"),
                     "libtpu": md.version("libtpu"),
                     "flax": md.version("flax"),
                     "numpy": np.__version__},
        "platform_type": get_config().platform.name,
        "native": _native.status(),
        "native_server_loop": getattr(srv, "_native_dp", None) is not None,
        "cache_dir": cache_dir,
        "cache_entries_before": entries_before,
        "startup_s": round(time.monotonic() - t_start, 2),
        **extra,
        "stats": snapshot(),
    })
    for line in sys.stdin:  # the parent's commands; EOF means it is gone
        cmd = line.strip()
        if cmd == "stats":
            say("stats", snapshot())
        elif cmd == "stop":
            break
    srv.stop(grace=5)
    if role == "ring":
        batcher.close()
    say("bye", {"cache_entries": cache_entries()})


# ---------------------------------------------------------------------------
# the parent: the client, and never a jax process
# ---------------------------------------------------------------------------

class ServerChild:
    """One server process: line-oriented commands in, ``@tag json`` out,
    stderr to a file under OUT_DIR that is shown when something fails."""

    def __init__(self, role: str, platform_type: str, rehearsal: bool,
                 env: dict):
        self.role = role
        env = dict(env, GRPC_PLATFORM_TYPE=platform_type)
        if rehearsal:
            env["JAX_PLATFORMS"] = "cpu"  # the one place a platform is set:
            # behind --rehearsal-cpu, and every line of it says so
        self.err_path = os.path.join(OUT_DIR, f"server_{role}.stderr")
        self._err = open(self.err_path, "w")
        argv = [sys.executable, "-u", os.path.abspath(__file__),
                "--serve", role]
        if rehearsal:
            argv.append("--rehearsal-cpu")
        self.proc = subprocess.Popen(
            argv, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=self._err, env=env, text=True, cwd=HERE)
        self._lines: "queue.Queue[str | None]" = queue.Queue()
        threading.Thread(target=self._pump, daemon=True).start()

    def _pump(self) -> None:
        for line in self.proc.stdout:
            self._lines.put(line.rstrip("\n"))
        self._lines.put(None)

    def expect(self, tag: str, timeout: float):
        deadline = time.monotonic() + timeout
        while True:
            try:
                line = self._lines.get(timeout=max(0.0, deadline
                                                   - time.monotonic()))
            except queue.Empty:
                raise SmokeFailure(
                    f"server {self.role}: no @{tag} within {timeout:.0f}s\n"
                    f"{self.stderr_tail()}") from None
            if line is None:
                raise SmokeFailure(
                    f"server {self.role} exited (rc={self.proc.wait()}) "
                    f"before @{tag}\n{self.stderr_tail()}")
            if line.startswith("@fatal "):
                raise SmokeFailure(f"server {self.role}: "
                                   f"{json.loads(line[7:])}")
            if line.startswith(f"@{tag} "):
                return json.loads(line[len(tag) + 2:])

    def command(self, cmd: str) -> None:
        self.proc.stdin.write(cmd + "\n")
        self.proc.stdin.flush()

    def stats(self) -> dict:
        self.command("stats")
        return self.expect("stats", 60)

    def stop(self) -> dict:
        """Clean shutdown, waited for: the chip is free when this returns."""
        self.command("stop")
        bye = self.expect("bye", 60)
        try:
            rc = self.proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"server {self.role} did not exit after "
                               f"@bye\n{self.stderr_tail()}") from None
        check(rc == 0, f"server {self.role} exited rc={rc}\n"
              f"{self.stderr_tail()}")
        return bye

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self._err.close()

    def stderr_tail(self, n: int = 3000) -> str:
        self._err.flush()
        with open(self.err_path, errors="replace") as f:
            return f"--- {self.err_path} (tail) ---\n" + f.read()[-n:]


def use_platform(platform_type: str) -> None:
    """Point THIS process's transport at ``platform_type``: the client picks
    the wire (a client left on the default TCP reaches a ``device=True``
    method over a connection with no device ring). The config is a
    process-wide singleton read once, so re-read it."""
    from tpurpc.utils import config

    os.environ["GRPC_PLATFORM_TYPE"] = platform_type
    config.set_config(None)


def delta(after: dict, before: dict, group: str) -> dict:
    return {k: v - before[group].get(k, 0)
            for k, v in after[group].items() if v != before[group].get(k, 0)}


def plan_pass(cfg: dict, capacity: int, floor: int):
    """One pass of the tensor leg: the shapes in rotation until ``laps`` ring
    capacities are nearly full, then one filler that ends the pass EXACTLY on
    a multiple of the capacity — the replay then meets every offset again,
    so what the warm-up pass compiled is all the measured pass needs.
    Returns ``(shapes, wrapped)``: how many spans cross the credit window's
    edge is arithmetic (the sizes do not divide it)."""
    total, used, shapes, i = cfg["laps"] * capacity, 0, [], 0
    biggest = max(4 * a * b for a, b in cfg["shapes"])
    while total - used >= biggest + floor:
        shapes.append(cfg["shapes"][i % len(cfg["shapes"])])
        used += 4 * shapes[-1][0] * shapes[-1][1]
        i += 1
    shapes.append(((total - used) // 4,))
    wrapped, off = 0, 0
    for shape in shapes:
        n = 4 * shape[0] * (shape[1] if len(shape) > 1 else 1)
        wrapped += off % capacity + n > capacity
        off += n
    check(off == total, f"pass plan ends at {off}, not {total}")
    return shapes, wrapped


def tensor_leg(say, cfg, srv: ServerChild, port: int, capacity: int,
               on_tpu: bool) -> dict:
    import numpy as np

    from tpurpc.jaxshim import TensorClient
    from tpurpc.rpc.channel import Channel
    from tpurpc.tpu import ledger

    floor = (cfg["rdv_min_kb"] or 256) << 10  # all of it rides rendezvous
    shapes, wrapped = plan_pass(cfg, capacity, floor)
    payload = sum(4 * int(np.prod(s)) for s in shapes)
    check(wrapped >= 2, f"plan wraps only {wrapped} spans")
    say(f"tensor leg: {len(shapes)} messages/pass, {payload} B = "
        f"{payload // capacity} laps of the {capacity} B device ring, "
        f"{wrapped} spans wrap; sizes {sorted({4 * int(np.prod(s)) for s in shapes})}")
    out = {"messages_per_pass": len(shapes), "payload_bytes": payload,
           "wrapped_spans_per_pass": wrapped}

    def run_pass(cli, seed: int, these) -> None:
        rng = np.random.default_rng(seed)
        msgs = [rng.standard_normal(s, dtype=np.float32) for s in these]
        want = 0
        for m in msgs:
            want = fold_np(want, m)
        (reply,) = list(cli.duplex("Sink", ({"x": m} for m in msgs),
                                   timeout=600))
        got = int(np.asarray(reply["check"]).ravel()[0])
        check(int(np.asarray(reply["n"]).ravel()[0]) == len(msgs)
              and int(np.asarray(reply["bytes"]).ravel()[0])
              == sum(m.nbytes for m in msgs), f"server counted {reply}")
        check(got == want, f"device checksum {got:#010x} != numpy's "
              f"{want:#010x} (seed {seed}): bytes were misplaced")

    with Channel(f"127.0.0.1:{port}") as ch:
        cli = TensorClient(ch)
        # pass 1 builds the handler's program for every shape; pass 2, NEW
        # data of the same shapes, is the one the counters are read across
        for name, seed in (("warm-up", 11), ("measured", 12)):
            s0, t0, sent0 = srv.stats(), time.monotonic(), ledger.snapshot()
            run_pass(cli, seed, shapes)
            wall, s1 = time.monotonic() - t0, srv.stats()
            c, led = delta(s1, s0, "counters"), delta(s1, s0, "ledger")
            # the one-sided write is the SENDER's movement: this process's
            led["rdma_write"] = (ledger.snapshot()["rdma_write"]
                                 - sent0["rdma_write"])
            landed = c.get("hbm_place_msgs", 0)
            say(f"  {name} pass: checksum ok, {wall:.2f}s wall; landed "
                f"{landed}; ledger dma_h2d={led.get('dma_h2d', 0)} "
                f"dma_d2d={led.get('dma_d2d', 0)} "
                f"rdma_write={led.get('rdma_write', 0)} "
                f"host_copy={led.get('host_copy', 0)} "
                f"zero_copy={led.get('zero_copy', 0)}; "
                f"programs built {c.get('xla_compiles', 0)} "
                f"({c.get('xla_compile_ms', 0)} ms; persistent cache "
                f"{c.get('xla_cache_hits', 0)} hits / "
                f"{c.get('xla_cache_misses', 0)} misses)")
            out[name] = {"wall_s": round(wall, 3), "landed": landed,
                         "ledger": led,
                         "compiles": c.get("xla_compiles", 0),
                         "compile_ms": c.get("xla_compile_ms", 0)}
        # the measured pass, held to account
        check(c.get("xla_compiles", 0) == 0,
              f"{c.get('xla_compiles')} programs were built after warm-up")
        check(led.get("dma_h2d", 0) == payload,
              f"dma_h2d {led.get('dma_h2d')} != payload {payload}")
        check(led["rdma_write"] >= payload,
              f"rendezvous carried {led['rdma_write']} of {payload} B")
        check(led.get("host_copy", 0) < floor,
              f"host_copy {led.get('host_copy')} B: a payload was copied on "
              f"the host (control frames alone stay under {floor})")
        # every message lands in its final array by its one transfer, and
        # nothing else is counted
        check(landed == len(shapes),
              f"hbm_place_msgs {landed}, want {len(shapes)} messages")
        check(not led.get("dma_d2d"),
              f"dma_d2d {led.get('dma_d2d')} B moved on the device")
        check(not c.get("tensor_device_degraded"),
              "device=True degraded to the host decode")
        # one reply out of HBM: read back once, billed once, bit-exact
        m = np.random.default_rng(14).standard_normal(shapes[0],
                                                      dtype=np.float32)
        cli.call("Bump", {"x": m}, timeout=600)  # builds the one program
        s0 = srv.stats()
        y = np.array(cli.call("Bump", {"x": m}, timeout=600)["y"])
        s1 = srv.stats()
        c, led = delta(s1, s0, "counters"), delta(s1, s0, "ledger")
        check(y.tobytes() == (m + np.float32(1)).tobytes(),
              "the reply read out of device memory is not x + 1")
        got = {k: led.get(k, 0) for k in ("dma_d2h", "dma_d2h_ops",
                                          "zero_copy", "zero_copy_ops")
               if led.get(k, 0)}
        # a host backend aliases the reply where it lies; a chip reads it
        # back once and bills nothing else
        want = ({"dma_d2h": m.nbytes, "dma_d2h_ops": 1} if on_tpu else
                {"zero_copy": m.nbytes, "zero_copy_ops": 1})
        check(got == want and c.get("lens_d2h_ops", 0) == int(on_tpu),
              f"a reply of {m.nbytes} B billed {got} with "
              f"{c.get('lens_d2h_ops', 0)} d2h stage(s), want {want}")
        check(c.get("rdv_bytes_sent", 0) >= m.nbytes,
              f"the reply left framed: rdv_bytes_sent {c.get('rdv_bytes_sent')}")
        say(f"  reply out of device memory ({m.nbytes} B): bit-exact, "
            f"ledger {got}, by rendezvous {c.get('rdv_bytes_sent', 0)} B")
        out["reply"] = {"bytes": m.nbytes, "ledger": got}
        # and once below the rendezvous bar: the framed path into the ring
        s0 = srv.stats()
        run_pass(cli, 13, [cfg["small"]] * 4)
        c = delta(srv.stats(), s0, "counters")
        check(c.get("hbm_place_msgs", 0) == 4, f"framed placements {c}")
        say(f"  framed ({4 * int(np.prod(cfg['small']))} B x4): checksum ok")
    return out


def serving_leg(say, cfg, srv: ServerChild, port: int) -> dict:
    import numpy as np

    from tpurpc.jaxshim import TensorClient
    from tpurpc.rpc.channel import Channel

    n_clients, n_reqs = cfg["clients"], cfg["requests"]
    replies: dict = {}
    errors: list = []
    go = threading.Barrier(n_clients)

    def client(idx: int) -> None:
        try:
            with Channel(f"127.0.0.1:{port}") as ch:
                cli = TensorClient(ch)
                go.wait(timeout=120)  # arrive together: batches can fill
                for r in range(n_reqs):
                    out = cli.call("Infer", {"x": make_image(cfg, idx, r)},
                                   timeout=300)
                    replies[idx, r] = np.asarray(out["logits"], np.float32)
        except BaseException as exc:  # re-raised by the main thread below
            errors.append(exc)
            go.abort()

    s0, t0 = srv.stats(), time.monotonic()
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    wall = time.monotonic() - t0
    if errors:
        raise errors[0]
    check(not any(t.is_alive() for t in threads), "a client never finished")
    s1 = srv.stats()
    c = delta(s1, s0, "counters")
    batches, rows = s1["batches"] - s0["batches"], s1["rows"] - s0["rows"]
    check(len(replies) == n_clients * n_reqs and rows == len(replies),
          f"{len(replies)} replies, {rows} rows served")
    check(batches < rows, f"{batches} batches for {rows} rows: no dispatched "
          "batch had more than one real row")
    check(c.get("xla_compiles", 0) == 0,
          f"{c.get('xla_compiles')} programs were built during the requests")

    # each reply against the same jitted function called directly
    worst = 0.0
    with Channel(f"127.0.0.1:{port}") as ch:
        cli = TensorClient(ch)
        direct = {key: np.asarray(cli.call(
            "InferDirect", {"x": make_image(cfg, *key)},
            timeout=300)["logits"], np.float32) for key in replies}
    check(not delta(srv.stats(), s1, "counters").get("xla_compiles"),
          "the direct reference recompiled: not the batcher's program")
    for key, got in replies.items():
        check(got.shape == (1, cfg["classes"]) and np.isfinite(got).all(),
              f"reply {key}: shape {got.shape}, finite "
              f"{np.isfinite(got).all()}")
        want = direct[key]
        tol = 2e-2 * max(1.0, float(np.abs(want).max()))  # bf16: 8 bits
        err = float(np.abs(got - want).max())
        worst = max(worst, err / tol)
        check(err <= tol, f"reply {key} is {err:.4g} from the direct call "
              f"(tolerance {tol:.4g})")
        nearest = min(direct, key=lambda k: float(np.abs(got - direct[k])
                                                   .max()))
        check(nearest == key, f"reply {key} matches image {nearest}'s "
              "logits: the caller got another caller's row")
    say(f"serving leg: {len(replies)} replies from {n_clients} connections "
        f"in {batches} batches ({rows} real rows), all finite "
        f"[1,{cfg['classes']}], each nearest to and within bf16 tolerance of "
        f"its own direct call (worst {worst:.2f} of tolerance); programs "
        f"built during requests 0; {wall:.2f}s wall")
    return {"wall_s": round(wall, 3), "replies": len(replies),
            "batches": batches, "rows": rows,
            "worst_error_over_tolerance": round(worst, 3)}


def tojax_leg(say, cfg, srv: ServerChild, port: int, on_tpu: bool) -> dict:
    import numpy as np

    from tpurpc.jaxshim import TensorClient
    from tpurpc.rpc.channel import Channel
    from tpurpc.tpu import ledger

    rng = np.random.default_rng(21)
    msgs = [rng.standard_normal(s, dtype=np.float32)
            for s in [cfg["shapes"][0]] * 4 + [cfg["small"]] * 4]
    want = 0
    for m in msgs:
        want = fold_np(fold_np(want, m), m)  # writable view, then read-only
    payload = sum(m.nbytes for m in msgs)
    s0, t0, sent0 = srv.stats(), time.monotonic(), ledger.snapshot()
    with Channel(f"127.0.0.1:{port}") as ch:
        # native=False: the instrumented Python plane, as bench.py's
        # headline stream rides it (the C loop keeps its own ledger)
        (reply,) = list(TensorClient(ch).duplex(
            "ToJax", ({"x": m} for m in msgs), native=False, timeout=600))
    wall, s1 = time.monotonic() - t0, srv.stats()
    landed = ledger.snapshot()["rdma_write_ops"] - sent0["rdma_write_ops"]
    got = int(np.asarray(reply["check"]).ravel()[0])
    check(got == want, f"to_jax checksum {got:#010x} != {want:#010x}")
    led, c = delta(s1, s0, "ledger"), delta(s1, s0, "counters")
    seen = {k: s1["seen"][k] - s0["seen"][k] for k in s1["seen"]}
    check(landed == 4, f"{landed} of 4 large messages rode rendezvous")
    if on_tpu:  # every to_jax is one h2d; nothing may claim an alias
        check(led.get("dma_h2d", 0) == 2 * payload
              and not led.get("zero_copy"),
              f"to_jax on tpu billed {led}, want dma_h2d={2 * payload}")
    say(f"to_jax leg: {len(msgs)} wire views ({seen['writable_views']} "
        f"writable; 4 rendezvous-landed, 4 framed) + their read-only twins "
        f"all on the device, checksum ok; ledger dma_h2d="
        f"{led.get('dma_h2d', 0)} zero_copy={led.get('zero_copy', 0)}; "
        f"programs built {c.get('xla_compiles', 0)}; {wall:.2f}s wall")
    return {"wall_s": round(wall, 3), "views": seen, "ledger": led}


def build_native(say) -> dict:
    """The data plane, from what git would commit: never trust a
    ``libtpurpc.so`` that was lying around. The artifact is named after the
    digest of ``native/src`` and built on a miss; ``TPURPC_NATIVE_LIB``
    points every process of this run at it."""
    import shutil

    from tpurpc.core import _native

    if shutil.which("g++") is None:
        os.environ["TPURPC_NATIVE"] = "0"
        say("DATA PLANE: PYTHON — g++ is not on PATH, libtpurpc.so was not "
            "built; the pure-Python ring ops ran instead")
        return {"plane": "python", "why": "g++ not on PATH"}
    digest = _native.sources_digest()
    path = os.path.join(OUT_DIR, f"libtpurpc-{digest[:16]}.so")
    built = not os.path.exists(path)
    if built:
        t0 = time.monotonic()
        _native.build_from_sources(path)
        say(f"data plane: built {os.path.relpath(path, HERE)} from "
            f"native/src in {time.monotonic() - t0:.1f}s")
    else:
        say(f"data plane: {os.path.relpath(path, HERE)} matches native/src "
            f"(sha256 {digest[:16]}), built by an earlier run")
    os.environ["TPURPC_NATIVE_LIB"] = path
    status = _native.status()
    check(status["plane"] == "native", f"fresh build did not load: {status}")
    return dict(status, built_this_run=built, sources_sha256=digest[:16])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal-cpu", action="store_true",
                    help="tiny CPU dress rehearsal (what the tests run); "
                         "proves nothing about the chip and says so")
    ap.add_argument("--serve", choices=("ring", "hostring"),
                    help=argparse.SUPPRESS)  # the server child's entry
    args = ap.parse_args()
    sys.path.insert(0, HERE)
    if args.serve:
        serve(args.serve, args.rehearsal_cpu)
        return 0

    rehearsal = args.rehearsal_cpu
    cfg = REHEARSAL if rehearsal else REAL
    label = "[REHEARSAL on cpu — not a chip result]" if rehearsal else "[tpu]"

    def say(msg: str) -> None:
        print(f"{label} {msg}", flush=True)

    t_run = time.monotonic()
    try:
        import tpurpc  # noqa: F401
    except ImportError as exc:
        raise SmokeFailure(f"not in a tpurpc checkout: {exc}") from None
    os.makedirs(OUT_DIR, exist_ok=True)
    native = build_native(say)
    # the rehearsal's KiB sizes, for this process (the sender decides what
    # rides rendezvous) and the servers alike; the real run sets neither
    for key, val in (("TPURPC_HBM_RING_SIZE_KB", cfg["ring_kb"]),
                     ("TPURPC_RENDEZVOUS_MIN_KB", cfg["rdv_min_kb"])):
        if val is not None:
            os.environ[key] = str(val)
    env = dict(os.environ, PYTHONPATH=HERE + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    result: dict = {"legs": {}}
    children = []
    try:
        # -- server 1: RDMA_TPU, tensor leg + serving leg --------------------
        use_platform("RDMA_TPU")
        srv = ServerChild("ring", "RDMA_TPU", rehearsal, env)
        children.append(srv)
        ready = srv.expect("ready", 900)
        device = ready["device"]
        say(f"device: platform={device['platform']} "
            f"kind={device['kind']!r} count={device['count']}; "
            + " ".join(f"{k}={v}" for k, v in ready["versions"].items()))
        check(rehearsal or device["platform"] == "tpu", f"device {device}")
        check(ready["native"]["plane"] == native["plane"],
              f"server runs {ready['native']}, the parent {native}")
        say(f"server 1 (GRPC_PLATFORM_TYPE=RDMA_TPU -> "
            f"{ready['platform_type']}): ring ops {ready['native']['plane']}"
            f", C server loop {'on' if ready['native_server_loop'] else 'off'}"
            f" (the device-ring platform is served by the Python loop); up "
            f"in {ready['startup_s']}s, model set-up "
            f"{ready['model_setup_s']}s; set-up built "
            f"{ready['stats']['counters'].get('xla_compiles', 0)} programs "
            f"in {ready['stats']['counters'].get('xla_compile_ms', 0)} ms "
            f"(persistent cache {ready['cache_dir']}: "
            f"{ready['cache_entries_before']} entries before, "
            f"{ready['stats']['counters'].get('xla_cache_hits', 0)} hits / "
            f"{ready['stats']['counters'].get('xla_cache_misses', 0)} "
            "misses)")
        check(rehearsal or ready["ring_capacity"] == 16 << 20,
              f"device ring is {ready['ring_capacity']} B, not the default")
        result["legs"]["tensor"] = tensor_leg(
            say, cfg, srv, ready["port"], ready["ring_capacity"],
            device["platform"] == "tpu")
        result["legs"]["serving"] = serving_leg(say, cfg, srv, ready["port"])
        bye = srv.stop()
        say(f"server 1 stopped cleanly; compile cache now holds "
            f"{bye['cache_entries']} entries")
        setup1 = ready["stats"]["counters"]

        # -- server 2: RDMA_BPEV, after the first freed the chip -------------
        use_platform("RDMA_BPEV")
        srv2 = ServerChild("hostring", "RDMA_BPEV", rehearsal, env)
        children.append(srv2)
        ready2 = srv2.expect("ready", 300)
        check(ready2["device"] == device, f"second server on "
              f"{ready2['device']}")
        say(f"server 2 (RDMA_BPEV) took the chip over in "
            f"{ready2['startup_s']}s: ring ops {ready2['native']['plane']}, "
            f"C server loop {'on' if ready2['native_server_loop'] else 'off'}")
        result["legs"]["to_jax"] = tojax_leg(
            say, cfg, srv2, ready2["port"], device["platform"] == "tpu")
        bye2 = srv2.stop()
        say("server 2 stopped cleanly")
    finally:
        for child in children:
            child.kill()

    check("jax" not in sys.modules,
          "the client process imported jax: one process per chip is broken")
    say("parent never imported jax")
    wall = time.monotonic() - t_run
    say(f"all legs passed in {wall:.1f}s wall")
    summary = {
        **({"rehearsal_passed": True} if rehearsal else {}),
        "versions": ready["versions"],
        "data_plane": native,
        "setup": {"startup_s": ready["startup_s"],
                  "model_setup_s": ready["model_setup_s"],
                  "compiles": setup1.get("xla_compiles", 0),
                  "compile_ms": setup1.get("xla_compile_ms", 0),
                  "cache_dir": ready["cache_dir"],
                  "cache_entries": [ready["cache_entries_before"],
                                    bye["cache_entries"],
                                    bye2["cache_entries"]]},
        **result,
        "wall_s": round(wall, 1),
        "claim": None,
    }
    say(f"summary {json.dumps(summary)}")
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    # the result line: exactly these keys, the device as JAX reported it in
    # the server. A rehearsal is never "ok": it ran on a CPU.
    print(json.dumps({"ok": not rehearsal, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
