"""gRPC-wire-path sweep: stock grpcio clients against the tpurpc h2 server.

VERDICT r3 next-round #4: every committed fast number rides tpurpc's lean
native framing, but the reference's numbers all INCLUDE chttp2+HPACK
(``/root/reference/src/core/ext/transport/chttp2/transport/
chttp2_transport.cc:1624`` sits in its hot path) — so the wire-compat path
(``tpurpc/wire/grpc_h2.py``, from-scratch h2+HPACK in Python) needs its own
measured row, and an honest same-host comparison against grpcio↔grpcio
(grpcio's server is the C core; ours is Python — the gap IS the price of a
pure-Python h2 server).

Cells: {tpurpc-h2-server, grpcio-server} × {unary, streaming} × sizes,
stock grpcio client throughout. One fresh server subprocess per cell.

    python -m tpurpc.bench.wire --sizes 64,65536 --duration 3
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

_TPURPC_SERVER = """
import tpurpc.rpc as rpc
srv = rpc.Server(max_workers=8)
srv.add_method("/wire.Bench/Echo",
               rpc.unary_unary_rpc_method_handler(lambda r, c: bytes(r),
                                                  inline=True))
def _echo_stream(req_iter, ctx):
    for m in req_iter:
        yield bytes(m)
srv.add_method("/wire.Bench/EchoStream",
               rpc.stream_stream_rpc_method_handler(_echo_stream))
print("PORT", srv.add_insecure_port("127.0.0.1:0"), flush=True)
srv.start()
srv.wait_for_termination(timeout=600)
"""

_GRPCIO_SERVER = """
import grpc
from concurrent import futures

class H(grpc.GenericRpcHandler):
    def service(self, hcd):
        if hcd.method == "/wire.Bench/Echo":
            return grpc.unary_unary_rpc_method_handler(lambda r, c: bytes(r))
        if hcd.method == "/wire.Bench/EchoStream":
            def es(req_iter, ctx):
                for m in req_iter:
                    yield bytes(m)
            return grpc.stream_stream_rpc_method_handler(es)
        return None

srv = grpc.server(futures.ThreadPoolExecutor(max_workers=8))
srv.add_generic_rpc_handlers((H(),))
port = srv.add_insecure_port("127.0.0.1:0")
print("PORT", port, flush=True)
srv.start()
srv.wait_for_termination(timeout=600)
"""


def _run_client(port: int, size: int, duration: float,
                streaming: bool) -> dict:
    """Closed-loop stock-grpcio client (in-process: grpcio's client is the
    C core; its overhead is part of every reference measurement too)."""
    import grpc

    payload = b"x" * size
    lat = []
    with grpc.insecure_channel(f"127.0.0.1:{port}") as ch:
        if streaming:
            import queue as _q
            import threading as _t

            sendq: "_q.Queue" = _q.Queue(maxsize=1)
            stop = _t.Event()

            def gen():
                while not stop.is_set():
                    item = sendq.get()
                    if item is None:
                        return
                    yield item

            mc = ch.stream_stream("/wire.Bench/EchoStream")
            call = mc(gen())
            # warm
            sendq.put(payload)
            next(iter([next(iter(call))]))
            t_end = time.perf_counter() + duration
            while time.perf_counter() < t_end:
                t0 = time.perf_counter()
                sendq.put(payload)
                next(iter(call))
                lat.append(time.perf_counter() - t0)
            stop.set()
            sendq.put(None)
            call.cancel()
        else:
            mc = ch.unary_unary("/wire.Bench/Echo")
            mc(payload, timeout=30)  # warm
            t_end = time.perf_counter() + duration
            while time.perf_counter() < t_end:
                t0 = time.perf_counter()
                mc(payload, timeout=30)
                lat.append(time.perf_counter() - t0)
    lat.sort()
    n = len(lat)
    total = sum(lat)
    return {
        "rpcs": n,
        "rate_rps": round(n / total, 1) if total else 0.0,
        "rtt_us": {
            "mean": round(total / n * 1e6, 1),
            "p50": round(lat[n // 2] * 1e6, 1),
            "p99": round(lat[min(n - 1, int(n * 0.99))] * 1e6, 1),
        },
    }


def run_cell(server_kind: str, size: int, duration: float,
             streaming: bool) -> dict:
    env = dict(os.environ)
    env.setdefault("GRPC_PLATFORM_TYPE", "TCP")  # the wire path IS tcp+h2
    code = _TPURPC_SERVER if server_kind == "tpurpc" else _GRPCIO_SERVER
    srv = subprocess.Popen([sys.executable, "-u", "-c", code],
                           stdout=subprocess.PIPE, text=True, env=env)
    try:
        line = srv.stdout.readline()
        if not line.startswith("PORT"):
            raise RuntimeError(f"server failed: {line!r} (rc={srv.poll()})")
        port = int(line.split()[1])
        out = _run_client(port, size, duration, streaming)
        out.update({"server": server_kind, "size": size,
                    "streaming": streaming})
        return out
    finally:
        srv.kill()
        srv.wait()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default="64,65536")
    ap.add_argument("--duration", type=float, default=3.0)
    args = ap.parse_args(argv)
    sizes = [int(s) for s in args.sizes.split(",")]

    rows = []
    for server_kind in ("tpurpc", "grpcio"):
        for streaming in (False, True):
            for size in sizes:
                cell = run_cell(server_kind, size, args.duration, streaming)
                print(json.dumps(cell), flush=True)
                rows.append(cell)
    print(f"\n{'server':<8} {'mode':<10} {'size':>7} {'RPC/s':>9} "
          f"{'p50us':>8} {'p99us':>8}")
    for r in rows:
        print(f"{r['server']:<8} "
              f"{'streaming' if r['streaming'] else 'unary':<10} "
              f"{r['size']:>7} {r['rate_rps']:>9.0f} "
              f"{r['rtt_us']['p50']:>8.1f} {r['rtt_us']['p99']:>8.1f}")


if __name__ == "__main__":
    main()
