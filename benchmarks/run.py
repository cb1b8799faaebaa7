#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Looks the cell up in ``BENCHMARK.json``, loads its configuration
(``benchmarks/configs/<config>.json``) and traffic mix
(``benchmarks/traffic/<traffic>.json``), and imports by the names those files
give the handler (``handlers/``), the traffic kind (``traffic_kinds/``), the
plain reference (``configs/``) and, in a traced run, the cell's per-layer
readers (``layer_metrics/``). A later cell is new files plus new entries; no
file here needs an edit.

Processes: this parent (numpy only, never jax: it says so at exit), one server
child that owns the chip, one client child per connection. Needs a TPU and
``g++``; without either it exits non-zero and prints no result. The last line
of standard output is the result, one JSON object; the numbers that decide
``correct`` come last in it, each beside its limit, and are the last lines of
standard error too.

``--rehearsal-cpu`` is the same code at the KiB sizes the configuration file
gives under ``rehearsal_cpu``, on the CPU: every line says so, no metric is
printed, and its last line can not be read as a result.
"""

from __future__ import annotations

T_START = __import__("time").monotonic()  # set-up counts from here

import argparse
import importlib
import importlib.util
import json
import os
import queue
import shutil
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE = os.path.join(ROOT, ".bench_cache")
FIRST_RUN_S = 1100      # a first run compiles, and builds the data plane
REHEARSAL = "[REHEARSAL on cpu - not a chip result]"


class BenchFailure(Exception):
    """The run can give no result (no chip, no g++, a child that died)."""


class Child:
    """A child process speaking ``@tag <json>`` lines (and ``@blob <n>`` +
    raw bytes) on stdout, commands on stdin; stderr to a file that is shown
    when something fails."""

    def __init__(self, name: str, argv: list[str], env: dict):
        self.name = name
        os.makedirs(os.path.join(CACHE, "logs"), exist_ok=True)
        self.err_path = os.path.join(CACHE, "logs", f"{name}.stderr")
        self._err = open(self.err_path, "w")
        self.proc = subprocess.Popen(
            argv, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=self._err, env=env, cwd=ROOT)
        self._items: "queue.Queue" = queue.Queue()
        threading.Thread(target=self._pump, daemon=True).start()

    def _pump(self) -> None:
        out = self.proc.stdout
        for raw in iter(out.readline, b""):
            line = raw.decode(errors="replace").rstrip("\n")
            if line.startswith("@blob "):
                self._items.put(("blob", out.read(int(line[6:]))))
            elif line.startswith("@"):
                tag, _, body = line[1:].partition(" ")
                self._items.put((tag, body))
        self._items.put((None, None))

    def expect(self, tag: str, timeout: float):
        deadline = time.monotonic() + timeout
        while True:
            try:
                got, body = self._items.get(
                    timeout=max(0.0, deadline - time.monotonic()))
            except queue.Empty:
                raise BenchFailure(f"{self.name}: no @{tag} within "
                                   f"{timeout:.0f}s\n{self.tail()}") from None
            if got is None:
                raise BenchFailure(
                    f"{self.name} exited (rc={self.proc.wait()}) before "
                    f"@{tag}\n{self.tail()}")
            if got == "fatal":
                raise BenchFailure(f"{self.name}: {json.loads(body)}")
            if got == tag:
                return body if tag == "blob" else json.loads(body)

    def command(self, line: str) -> None:
        self.proc.stdin.write(line.encode() + b"\n")
        self.proc.stdin.flush()

    def ask(self, line: str, tag: str, timeout: float = 120):
        self.command(line)
        return self.expect(tag, timeout)

    def finish(self, timeout: float = 60) -> int:
        try:
            return self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            raise BenchFailure(f"{self.name} did not exit\n"
                               f"{self.tail()}") from None

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        self._err.close()

    def tail(self, n: int = 3000) -> str:
        self._err.flush()
        with open(self.err_path, errors="replace") as f:
            return f"--- {self.err_path} (tail) ---\n" + f.read()[-n:]


def build_native(say) -> dict:
    """The data plane from what git would commit, named after the digest of
    ``native/src`` and built on a miss (as ``chip_smoke.py`` does); every
    process of the run is pointed at it. No ``g++``: the run fails, it does
    not measure the other plane."""
    from tpurpc.core import _native

    if shutil.which("g++") is None:
        raise BenchFailure("g++ is not on PATH: libtpurpc.so cannot be "
                           "built, and the Python data plane is not what "
                           "these cells measure")
    digest = _native.sources_digest()
    path = os.path.join(CACHE, "native", f"libtpurpc-{digest[:16]}.so")
    built = not os.path.exists(path)
    if built:
        t0 = time.monotonic()
        _native.build_from_sources(path)
        say(f"data plane: built {os.path.relpath(path, ROOT)} in "
            f"{time.monotonic() - t0:.1f}s")
    os.environ["TPURPC_NATIVE_LIB"] = path
    status = _native.status()
    if status["plane"] != "native":
        raise BenchFailure(f"fresh build did not load: {status}")
    return dict(status, built_this_run=built)


def load_reader(name: str):
    path = os.path.join(HERE, "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.layer_metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(m: dict, cell: str, group: str) -> list[dict]:
    """The entries of ``end_to_end`` or ``per_layer`` that ``cell`` reports:
    those that list it under ``workloads``, and those with no such key (a
    per-layer metric without one goes wherever the metric it moves goes)."""
    e2e = {e["name"]: e.get("workloads") for e in m["end_to_end"]}
    out = []
    for e in m[group]:
        listed = e.get("workloads")
        if listed is None and group == "per_layer":
            listed = e2e[e["moves"]]
        if listed is None or cell in listed:
            out.append(e)
    return out


def find_traffic(name: str) -> dict:
    try:
        with open(os.path.join(HERE, "traffic", name + ".json")) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchFailure(
            f"no traffic mix benchmarks/traffic/{name}.json") from None


def delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if isinstance(v, (int, float)) and v != before.get(k, 0)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal-cpu", action="store_true",
                    help="KiB sizes on the CPU; proves nothing about the "
                         "chip and says so on every line")
    ap.add_argument("--fault", default=None,
                    help="plant a fault in the handler (tests and the "
                         "control only; the result must read correct=false)")
    args = ap.parse_args()
    rehearsal = args.rehearsal_cpu
    label = REHEARSAL if rehearsal else "[bench]"

    def say(msg: str) -> None:
        print(f"{label} {msg}", flush=True)

    sys.path.insert(0, ROOT)
    try:
        import tpurpc  # noqa: F401
    except ImportError as exc:
        raise BenchFailure(f"not in a tpurpc checkout: {exc}") from None
    from benchmarks.harness import stats

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    cell = next((w for w in m["workloads"] if w["name"] == args.workload),
                None)
    if cell is None:
        raise BenchFailure(f"no cell {args.workload!r} in BENCHMARK.json")
    entry = next(c for c in m["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    traffic = find_traffic(cell["traffic"])
    env_extra = {}
    if rehearsal:
        small = config["rehearsal_cpu"]
        env_extra = dict(small.get("env", {}), JAX_PLATFORMS="cpu")
        config = {**config, **{k: v for k, v in small.items() if k != "env"}}
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    reference = importlib.import_module(
        f"benchmarks.configs.{config['reference']}")
    conns = int(traffic["connections"])
    msg_bytes = int(config["message"]["bytes"])

    native = build_native(say)
    setup = {"parent_and_native_s": time.monotonic() - T_START}
    os.environ.update(env_extra)
    os.environ["GRPC_PLATFORM_TYPE"] = config["platform_type"]
    env = dict(os.environ, PYTHONPATH=ROOT + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    spec = {"config": config, "traffic": traffic, "seed": args.seed,
            "chips": cell["chips"], "rehearsal_cpu": rehearsal,
            "fault": args.fault}
    children: list[Child] = []
    try:
        server_env = config.get("server_env", {})
        server = Child("server", [sys.executable, "-u", os.path.join(
            HERE, "harness", "server_main.py"), json.dumps(spec)],
            dict(env, **server_env))
        children.append(server)
        clients = []
        for c in range(conns):
            cspec = dict(spec, conn=c, seconds=args.seconds)
            clients.append(Child(f"client{c}", [
                sys.executable, "-u",
                os.path.join(HERE, "harness", "client_main.py"),
                json.dumps(cspec)], env))
            children.append(clients[-1])
        ready = server.expect("ready", FIRST_RUN_S)
        setup["server_ready_s"] = time.monotonic() - T_START
        setup["server_phases"] = ready["phases"]
        device = ready["device"]
        say(f"server up in {ready['startup_s']:.1f}s on {device}; "
            f"{ready['platform_type']}, data plane {ready['native']['plane']}"
            f" (parent: {native['plane']}, built this run: "
            f"{native['built_this_run']}), device ring "
            f"{ready['hbm_ring_size']} B, pool {ready['pool_bytes']} B, "
            f"compile cache {ready['cache_dir']}; environment from the "
            f"configuration's server_env: {ready['server_env']}")
        if ready["server_env"] != server_env:
            raise BenchFailure(f"the server runs with {ready['server_env']},"
                               f" not the configuration's {server_env}")
        if ready["native"]["plane"] != "native":
            raise BenchFailure(f"server data plane: {ready['native']}")
        if ready["hbm_ring_size"] != config["expect"]["hbm_ring_bytes"]:
            raise BenchFailure(
                f"the device ring is {ready['hbm_ring_size']} B, not the "
                f"{config['expect']['hbm_ring_bytes']} B the configuration "
                "expects of the program's defaults")
        for cl in clients:
            cl.command(f"port {ready['port']}")
        for cl in clients:
            got = cl.expect("ready", FIRST_RUN_S)
            if (got["native"]["plane"] != "native"
                    or got["platform_type"] != ready["platform_type"]):
                raise BenchFailure(f"{cl.name}: {got}")
        setup["clients_warm_s"] = time.monotonic() - T_START
        before = server.ask("stats", "stats")
        peak = before["memory"]["peak_bytes_in_use"]
        if peak is not None and peak > 1.5 * ready["pool_bytes"]:
            raise BenchFailure(
                f"device peak {peak} B after warm-up with a pool of "
                f"{ready['pool_bytes']} B: the donated pool write copied it")
        warm = before["counters"]
        say(f"set-up so far, seconds from the parent's start: {setup}")
        say(f"warm-up done: {warm.get('xla_compiles', 0)} programs built or "
            f"loaded in {warm.get('xla_compile_ms', 0)} ms (persistent cache "
            f"{warm.get('xla_cache_hits', 0)} hits / "
            f"{warm.get('xla_cache_misses', 0)} misses)")

        # -- the measured window ------------------------------------------------
        t0 = time.monotonic() + 0.25
        setup_s = t0 - T_START
        for cl in clients:
            cl.command(f"go {t0!r}")
        traced = None
        if args.trace:
            trace_dir = os.path.join(CACHE, "trace", cell["name"])
            offset = min(float(traffic.get("trace_offset_s", 2.0)),
                         args.seconds / 4)
            length = min(float(traffic.get("trace_seconds", 2.0)),
                         args.seconds / 2)
            time.sleep(max(0.0, t0 + offset - time.monotonic()))
            started = server.ask(f"trace_start {trace_dir}", "trace_started")
            time.sleep(length)
            stopped = server.ask("trace_stop", "trace_stopped", 300)
            traced = {"start_s": started["start_s"],
                      "stop_s": stopped["stop_s"],
                      "counters": delta(stopped["stats"]["counters"],
                                        started["stats"]["counters"]),
                      "messages": sum(stopped["stats"]["counts"])
                      - sum(started["stats"]["counts"])}
        results = [cl.expect("result", args.seconds + 300) for cl in clients]
        after = server.ask("stats", "stats")

        # -- what the window left in the pool ------------------------------------
        counts = after["counts"]
        sample = reference.plan_sample(config, traffic, args.seed, counts)
        audit = server.ask("audit " + json.dumps(sample), "audit", 300)
        blobs = [server.expect("blob", 120) for _ in audit["blobs"]]
        freed = server.ask("free", "freed")
        trace = None
        if traced:
            trace = server.ask("trace_reduce " + json.dumps(
                {"chips": cell["chips"], "require_device": not rehearsal}),
                "trace", 300)
        server.command("stop")
        server.expect("bye", 60)
        for ch in children:
            rc = ch.finish()
            if rc != 0:
                raise BenchFailure(f"{ch.name} exited rc={rc}\n{ch.tail()}")
    finally:
        for ch in children:
            ch.kill()

    # -- the reference, and the numbers compared -----------------------------------
    t_ref = time.monotonic()
    warmed = int(traffic["warmup_messages"])
    acked = [warmed + r["acked"] for r in results]
    compared = reference.check(config, traffic, args.seed, audit["facts"],
                               sample, blobs, acked)
    limits = dict(reference.LIMITS)
    counters = delta(after["counters"], before["counters"])
    compared["rpc_failed"] = sum(r["failed"] for r in results)
    compared["compiles_in_window"] = counters.get("xla_compiles", 0)
    compared["degraded_leaves"] = counters.get("tensor_device_degraded", 0)
    limits.update(rpc_failed=0, compiles_in_window=0, degraded_leaves=0)
    correct = all(compared[k] <= limits[k] for k in compared)
    ref_s = time.monotonic() - t_ref

    attempted = sum(r["attempted"] for r in results)
    failed = compared["rpc_failed"]
    messages = sum(r["acked"] for r in results)
    payload = messages * msg_bytes
    first, last = stats.window(results)
    server_ledger = delta(after["ledger"], before["ledger"])
    client_ledger: dict = {}
    for r in results:
        for k, v in r["ledger"].items():
            client_ledger[k] = client_ledger.get(k, 0) + v
    paths = {k: v for k, v in counters.items()
             if k.startswith(("hbm_place_", "hbm_view_"))
             and not k.endswith(("msgs", "bytes"))}
    say(f"window {last - first:.3f}s: {messages} messages of {msg_bytes} B "
        f"acknowledged on {conns} connection(s), {failed} failed; generator "
        f"late by at most {max(r['late_s'] for r in results) * 1e3:.2f} ms, "
        f"{sum(r['bank_copies'] for r in results)} messages sent as copies "
        f"because the client still held their bank entry; "
        f"ring paths {paths} (hbm_place_scatter "
        f"{counters.get('hbm_place_scatter', 0)}, hbm_view_window "
        f"{counters.get('hbm_view_window', 0)}); server ledger "
        f"{server_ledger}; clients' ledger {client_ledger}")
    say(f"device memory: peak {after['memory']['peak_bytes_in_use']} B in "
        f"the window, {freed['memory']['bytes_in_use']} B after the pool was "
        f"freed; reference and comparison took {ref_s:.2f}s; errors "
        f"{[r['error'] for r in results if r.get('error')]}")
    if "jax" in sys.modules:
        raise BenchFailure("the parent imported jax")

    values: dict[str, float] = {}
    if not args.trace:
        values["setup_s"] = setup_s
        if config["rpc"] == "stream_stream":
            values["hbm_gbytes_s"] = stats.rate(payload, results) / 1e9
        else:
            lat = stats.latencies_ms(results)
            values["calls_s"] = stats.rate(messages, results)
            values["call_p50_ms"] = stats.percentile(lat, 50)
            values["call_p95_ms"] = stats.percentile(lat, 95)
        wanted = cell_metrics(m, cell["name"], "end_to_end")
    else:
        if device["kind"] not in peaks and not rehearsal:
            raise BenchFailure(f"no peaks for device kind {device['kind']!r} "
                               "in benchmarks/peaks.json")
        run = {"cell": cell["name"], "payload_bytes": payload,
               "messages": messages, "server_ledger": server_ledger,
               "client_ledger": client_ledger, "counters": counters,
               "peaks": peaks.get(device["kind"]),
               "trace": dict(trace, messages=traced["messages"],
                             payload_bytes=traced["messages"] * msg_bytes)}
        say(f"trace: start {traced['start_s']:.2f}s, stop "
            f"{traced['stop_s']:.2f}s, {trace['trace_bytes']} B; busy "
            f"{trace['busy_s']:.4f}s of {trace['window_s']:.4f}s; longest "
            f"idle gap {trace['longest_gap_s'] * 1e3:.2f} ms; "
            f"{traced['messages']} messages placed in it")
        wanted = cell_metrics(m, cell["name"], "per_layer")
        for e in wanted:
            got = load_reader(e["name"])(run)
            if got is not None:
                values[e["name"]] = got
    metrics = {e["name"]: {"value": values[e["name"]], "unit": e["unit"]}
               for e in wanted if e["name"] in values}

    compared_out = {k: {"value": compared[k], "limit": limits[k]}
                    for k in compared}
    if rehearsal:
        say(f"metrics are not printed in a rehearsal ({sorted(metrics)})")
        line = {"rehearsal_cpu": True, "correct": False,
                "would_be_correct": correct, "attempted": attempted,
                "failed": failed, "compared": compared_out}
    else:
        dev = dict(device,
                   memory_peak_bytes=after["memory"]["peak_bytes_in_use"])
        line = {"correct": correct, "attempted": attempted, "failed": failed,
                "metrics": metrics, "device": dev}
        if trace:
            dev.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
            line["breakdown"] = {"device_ops": trace["device_ops"],
                                 "idle_gaps": trace["idle_gaps"]}
        line["compared"] = compared_out
    for k, v in compared_out.items():
        print(f"{label} compared {k} = {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchFailure as exc:
        print(f"benchmark failed, no result: {exc}", file=sys.stderr)
        sys.exit(1)
