"""tpurpc-lens smoke for the verification gate (tools/check.sh, ISSUE 8).

Runs a short burst of streaming (ring-plane tensor duplex) + serving
(unary echo) traffic in-process, plus one tiny SUBPROCESS member, and
asserts the three lens faces work end to end:

* the stage-tagged sampling profiler attributes samples to >=3 known
  stages (and the unattributed share stays under the 20% bar);
* ``/debug/waterfall`` reports EVERY declared hop, nonzero bytes on each
  hop the traffic crosses, and names a slowest hop;
* ``python -m tpurpc.tools.timeline`` against this process + the
  subprocess emits a Perfetto-loadable chrome-trace JSON with >=2 named
  process lanes, rebased on per-process clock anchors.

~15s (jax on cpu pays the import). Exit 0 on success; any assertion or
exception exits 1 with the reason.

    python -m tpurpc.tools.lens_smoke
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
import urllib.request

os.environ.setdefault("GRPC_PLATFORM_TYPE", "RDMA_BPEV")
os.environ.setdefault("TPURPC_LENS_HZ", "200")  # smoke: sample fast
os.environ.setdefault("JAX_PLATFORMS", "cpu")

#: the hops a ring-plane tensor duplex, unary echoes and one HbmRing
#: landing move bytes through
_DRIVEN_HOPS = ("device", "send_ring", "wire", "rendezvous", "ctrl",
                "peer_ring", "decode", "hbm", "jax_array", "srv_recv",
                "srv_queue", "srv_handler", "hbm_view")

_PEER_CODE = r"""
import sys, time
from tpurpc.obs import tracing
from tpurpc.rpc.server import Server, unary_unary_rpc_method_handler

tracing.force(True)
srv = Server(max_workers=2)
srv.add_method("/lens/Echo",
               unary_unary_rpc_method_handler(lambda req, ctx: bytes(req)))
port = srv.add_insecure_port("127.0.0.1:0")
srv.start()
print("PORT", port, flush=True)
time.sleep(float(sys.argv[1]))
"""


def run() -> int:
    import numpy as np

    from tpurpc.jaxshim import TensorClient
    from tpurpc.obs import lens, profiler, tracing
    from tpurpc.rpc.channel import Channel
    from tpurpc.rpc.server import Server, unary_unary_rpc_method_handler
    from tpurpc.tpu.hbm_ring import HbmRing

    # -- local member: streaming + serving on the instrumented plane ------
    from tpurpc.jaxshim.service import add_tensor_method

    srv = Server(max_workers=8, native_dataplane=False)
    add_tensor_method(srv, "Sink", _sink, kind="stream_stream")
    srv.add_method("/lens/Echo",
                   unary_unary_rpc_method_handler(
                       lambda req, ctx: bytes(req)))
    port = srv.add_insecure_port("127.0.0.1:0")
    srv.start()
    assert profiler.get().running(), "Server.start did not start the sampler"
    tracing.force(True)

    peer = subprocess.Popen([sys.executable, "-u", "-c", _PEER_CODE, "60"],
                            stdout=subprocess.PIPE, text=True)
    try:
        peer_port = int(peer.stdout.readline().split()[1])

        payload = np.ones((512, 512), np.float32)  # 1 MiB

        def gen(k):
            for _ in range(k):
                yield {"x": payload}

        with Channel(f"127.0.0.1:{port}") as ch:
            cli = TensorClient(ch)
            deadline = time.monotonic() + 10.0
            rounds = 0
            while True:
                replies = list(cli.duplex("Sink", gen(24), native=False,
                                          timeout=60))
                total = int(np.asarray(replies[-1]["bytes"]).ravel()[0])
                assert total == 24 * payload.nbytes, (total, rounds)
                rounds += 1
                snap = profiler.snapshot()
                named = [s for s in snap["stages"]
                         if s in profiler.STAGES and snap["stages"][s] > 0]
                if len(named) >= 3 and rounds >= 2:
                    break
                if time.monotonic() > deadline:
                    break
            mc = ch.unary_unary("/lens/Echo")
            for i in range(32):
                assert mc(b"e%d" % i, timeout=10) == b"e%d" % i
        with Channel(f"127.0.0.1:{peer_port}") as ch2:
            mc2 = ch2.unary_unary("/lens/Echo")
            for i in range(8):
                assert mc2(b"p%d" % i, timeout=10) == b"p%d" % i

        # the hbm device hop: a real HbmRing landing and its lease-backed
        # array
        ring = HbmRing(1 << 16)
        lease = ring.land(np.arange(4096, dtype=np.uint8), np.uint8, (4096,))
        assert lease.array.shape == (4096,)
        lease.release()

        # -- face 1: profiler names >=3 known stages ----------------------
        snap = profiler.snapshot()
        named = sorted(s for s in snap["stages"]
                       if s in profiler.STAGES and snap["stages"][s] > 0)
        assert len(named) >= 3, \
            f"profiler named only {named} over {snap['samples']} samples"
        assert snap["attributed_pct"] >= 80.0, \
            f"unattributed share too high: {snap}"
        assert snap["top_stacks"], "no collapsed stacks collected"

        # -- face 2: waterfall reports every declared hop -----------------
        wf = _get_json(port, "/debug/waterfall")
        by_hop = {r["hop"]: r for r in wf["hops"]}
        assert tuple(by_hop) == lens.HOP_NAMES, by_hop.keys()
        # every hop this smoke's traffic crosses; the hops appended since
        # for paths it does not drive (the native plane, device replies,
        # the fan-in batcher, a refused or released sender) stay idle here
        # and have their own tests
        idle = [h for h in _DRIVEN_HOPS if by_hop[h]["bytes"] == 0]
        assert not idle, f"hops with zero bytes after traffic: {idle}"
        assert all("cpu_ms" in r for r in wf["hops"]), wf["hops"][0]
        assert wf["slowest_hop"] in by_hop, wf["slowest_hop"]
        assert "ledger" in wf, "copy ledger not folded into the waterfall"
        text = _get_text(port, "/debug/waterfall?text=1")
        assert "slowest" in text, text

        # profile served on the serving port too (+collapsed)
        prof = _get_json(port, "/debug/profile")
        assert prof["samples"] > 0 and prof["stage_pct"], prof
        assert _get_text(port, "/debug/profile?collapsed=1").strip(), \
            "empty collapsed-stack export"

        # -- face 3: timeline tool over both members ----------------------
        out = os.path.join(tempfile.mkdtemp(prefix="tpurpc-lens-"),
                           "timeline.json")
        from tpurpc.tools import timeline as tl

        rc = tl.main([f"127.0.0.1:{port}", f"127.0.0.1:{peer_port}",
                      "-o", out])
        assert rc == 0, f"timeline tool exit {rc}"
        with open(out, encoding="utf-8") as f:
            doc = json.load(f)  # valid JSON is the Perfetto bar
        lanes = [e for e in doc["traceEvents"]
                 if e.get("ph") == "M" and e.get("name") == "process_name"]
        assert len(lanes) >= 2, f"{len(lanes)} process lane(s)"
        spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert spans, "timeline carries no span/sample events"
        assert not doc["otherData"]["unanchored"], \
            f"members exported no clock anchor: {doc['otherData']}"
        # rebased timestamps must be non-negative and sane (< 1 day span)
        ts = [e["ts"] for e in doc["traceEvents"] if "ts" in e]
        assert min(ts) >= 0 and max(ts) - min(ts) < 86_400e6, \
            (min(ts), max(ts))

        print(f"lens smoke OK: stages {named}, "
              f"attributed {snap['attributed_pct']}%, "
              f"slowest hop {wf['slowest_hop']}, "
              f"timeline {len(lanes)} lanes / {len(spans)} events")
        return 0
    finally:
        tracing.force(None)
        peer.kill()
        srv.stop(0)


def _sink(req_iter):
    import numpy as np

    from tpurpc.jaxshim import to_jax

    total = 0
    for tree in req_iter:
        arr = to_jax(tree["x"])
        total += arr.nbytes
    yield {"bytes": np.int64(total)}


def _get_json(port: int, path: str) -> dict:
    return json.loads(_get_text(port, path))


def _get_text(port: int, path: str) -> str:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=10) as resp:
        return resp.read().decode("utf-8", "replace")


def main() -> int:
    try:
        return run()
    except AssertionError as exc:
        print(f"lens smoke FAILED: {exc}", file=sys.stderr)
        return 1
    except Exception as exc:  # noqa: BLE001 — smoke: any failure is a fail
        import traceback

        traceback.print_exc()
        print(f"lens smoke FAILED: {exc!r}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    raise SystemExit(main())
