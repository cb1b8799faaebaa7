"""tpurpc-lens (ISSUE 8): waterfall hops, stage profiler, clock-anchored
timeline, shard fan-out of the new routes, concurrent-scrape safety.

The profiler tests drive ``sample_once`` with SYNTHETIC frames so the
stage attribution is deterministic; the scrape/shard tests run real
servers (the routes exist to be curled)."""

import itertools
import json
import socket
import threading
import time

import pytest

from tpurpc.obs import lens, metrics, profiler, tracing
from tpurpc.obs.profiler import StageProfiler


def _http_get(port, path, timeout=5.0):
    with socket.create_connection(("127.0.0.1", port), timeout=timeout) as s:
        s.settimeout(timeout)
        s.sendall(f"GET {path} HTTP/1.0\r\n\r\n".encode())
        buf = bytearray()
        while True:
            chunk = s.recv(65536)
            if not chunk:
                break
            buf += chunk
    head, _, body = bytes(buf).partition(b"\r\n\r\n")
    return int(head.split(None, 2)[1]), body


# ---------------------------------------------------------------------------
# waterfall hop registry + export
# ---------------------------------------------------------------------------

def test_hop_counters_known_hops_only():
    b, ns, cp = lens.hop_counters("wire")
    assert b.name == "lens_wire_bytes"
    with pytest.raises(ValueError):
        lens.hop_counters("warp-drive")


def test_waterfall_rates_and_slowest_hop():
    b, ns, cp = lens.hop_counters("send_ring")
    b0 = b.snapshot()
    b.inc(10_000_000)
    ns.inc(1_000_000)  # 10 MB in 1 ms = 10 GB/s on top of whatever was there
    doc = lens.waterfall()
    row = next(r for r in doc["hops"] if r["hop"] == "send_ring")
    # bounded, not exact: other live machinery (pollers, lingering
    # connections from earlier tests) may bump the process-global counter
    # between our snapshots
    assert b0 + 10_000_000 <= row["bytes"] <= b.snapshot()
    # the rate is DEFINED as bytes/busy_ns of one snapshot pair (both
    # fields are rounded for export: compare loosely)
    assert row["gbps"] == pytest.approx(
        row["bytes"] / (row["busy_ms"] * 1e6), rel=0.05, abs=0.002)
    assert doc["slowest_hop"] in {r["hop"] for r in doc["hops"]}
    assert "ledger" in doc
    # hop order is the declared data-flow order
    assert tuple(r["hop"] for r in doc["hops"]) == lens.HOP_NAMES


def test_waterfall_text_rendering_flags_slowest():
    slow_b, slow_ns, _ = lens.hop_counters("decode")
    # enough BYTES to clear the 1%-of-bulk-traffic share bar (a hop that
    # moved a negligible share cannot be the bulk flow's bottleneck) while
    # pathologically slow: must win the argmin
    slow_b.inc(500_000_000)
    slow_ns.inc(50_000_000_000_000)
    txt = lens.render_text()
    assert "slowest" in txt and "decode" in txt


def test_slowest_hop_ignores_control_only_traffic():
    """tpurpc-express: once bulk payloads ride the rendezvous hop, the
    framed wire hop carries only control frames — a few KB at low rates —
    and its low GB/s must NOT name it the bottleneck of the bulk flow."""
    rows = [
        {"hop": "wire", "bytes": 20_000, "busy_ms": 10.0, "gbps": 0.002},
        {"hop": "rendezvous", "bytes": 500_000_000, "busy_ms": 100.0,
         "gbps": 5.0},
        {"hop": "decode", "bytes": 480_000_000, "busy_ms": 60.0,
         "gbps": 8.0},
    ]
    assert lens.slowest_hop(rows) == "rendezvous"
    # ... but with comparable byte shares the true argmin wins as before
    rows[0] = {"hop": "wire", "bytes": 400_000_000, "busy_ms": 400.0,
               "gbps": 1.0}
    assert lens.slowest_hop(rows) == "wire"


def test_streaming_hops_account_ring_traffic():
    """A ring write/read round trip lands bytes in send_ring AND peer_ring
    with nonzero busy time."""
    from tpurpc.core.ring import RingReader, RingWriter

    sb, sn, sc = lens.hop_counters("send_ring")
    rb, rn, rc = lens.hop_counters("peer_ring")
    s0, r0 = sb.snapshot(), rb.snapshot()
    buf = bytearray(4096)

    def place(off, data):
        buf[off:off + len(data)] = bytes(data)

    w = RingWriter(4096, place)
    payload = b"z" * 1500
    w.writev([payload])
    reader = RingReader(buf)
    out = reader.read(4096)
    assert out == payload
    assert sb.snapshot() - s0 == 1500
    assert rb.snapshot() - r0 == 1500
    assert sc.snapshot() >= 1500  # ring bytes move by host memcpy: copies
    assert sn.snapshot() > 0 and rn.snapshot() > 0


# ---------------------------------------------------------------------------
# stage profiler: deterministic classification via synthetic frames
# ---------------------------------------------------------------------------

class _Code:
    def __init__(self, filename, name):
        self.co_filename = filename
        self.co_name = name


class _Frame:
    def __init__(self, filename, name, back=None):
        self.f_code = _Code(filename, name)
        self.f_back = back


def _stack(*frames):
    """Build a frame chain from (filename, funcname) outermost-first;
    returns the INNERMOST frame (what sys._current_frames yields)."""
    top = None
    for filename, name in frames:
        top = _Frame(filename, name, back=top)
    return top


_RING = "/x/tpurpc/core/ring.py"  # matches the registered basename markers


def test_classify_innermost_marker_wins():
    # innermost→outermost walk: drain_into (ring-read) shadows the outer
    # server dispatch frame
    f = _stack(("/x/tpurpc/rpc/server.py", "_run_handler"),
               (_RING, "drain_into"))
    stage, parts = StageProfiler.classify(f)
    assert stage == "ring-read"
    assert parts[-1].endswith("drain_into")  # leaf-last collapsed stack


def test_classify_stdlib_park_attributes_to_outer_tpurpc_frame():
    # a batcher thread parked in threading.Condition.wait: the stdlib
    # frame carries no marker, the outer jaxshim frame names the stage
    import tpurpc.jaxshim.service  # noqa: F401 — registers its markers

    f = _stack(("/x/tpurpc/jaxshim/service.py", "_loop"),
               ("/usr/lib/python3/threading.py", "wait"))
    stage, _ = StageProfiler.classify(f)
    assert stage == "batcher"


def test_classify_unattributed_vs_other():
    in_tree = profiler._TPURPC_DIR + "/rpc/mystery.py"
    stage, _ = StageProfiler.classify(_stack((in_tree, "enigma")))
    assert stage == "unattributed"
    stage, _ = StageProfiler.classify(
        _stack(("/usr/lib/python3/selectors.py", "select")))
    assert stage == "other"


def test_sample_once_aggregates_and_bounds():
    p = StageProfiler(hz=50)
    frames = {
        1: _stack((_RING, "writev")),
        2: _stack((_RING, "drain_into")),
        3: _stack(("/usr/lib/python3/queue.py", "get")),
    }
    for _ in range(10):
        p.sample_once(frames=frames, now_ns=123)
    assert p.samples == 30
    assert p.stages["ring-write"] == 10
    assert p.stages["ring-read"] == 10
    assert p.stages["other"] == 10
    snap = p.snapshot()
    # `other` is excluded from the attribution denominator
    assert snap["attributed_pct"] == 100.0
    assert snap["stage_pct"]["ring-write"] == 50.0
    assert len(p.recent) == 30
    collapsed = p.collapsed_text()
    assert "ring:writev 10" in collapsed


def test_sampler_thread_runs_and_stops():
    p = StageProfiler(hz=200)
    p.start()
    try:
        deadline = time.monotonic() + 5
        while p.samples == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert p.samples > 0
    finally:
        p.stop()
    assert not p.running()
    n = p.samples
    time.sleep(0.05)
    assert p.samples == n  # genuinely stopped


def test_register_stages_keys_by_basename():
    profiler.register_stages("/weird/path/fake_lens_mod.py",
                             {"fake_fn": "codec"})
    assert profiler.markers()[("fake_lens_mod.py", "fake_fn")] == "codec"
    stage, _ = StageProfiler.classify(
        _stack(("/other/prefix/fake_lens_mod.py", "fake_fn")))
    assert stage == "codec"


# ---------------------------------------------------------------------------
# clock anchor + timeline rebasing (the pinned-skew satellite)
# ---------------------------------------------------------------------------

def test_chrome_trace_carries_clock_anchor():
    doc = tracing.chrome_trace()
    a = doc["clock_anchor"]
    assert abs(a["mono_ns"] - time.monotonic_ns()) < 5e9
    assert abs(a["wall_ns"] - time.time_ns()) < 5e9  # tpr: allow(wallclock)
    assert a["uncertainty_ns"] >= 0 and a["pid"] > 0


def test_timeline_rebase_pinned_math():
    from tpurpc.tools.timeline import rebase_ns

    anchor = {"mono_ns": 1_000_000, "wall_ns": 500_000_000}
    # mono 1.5ms = wall 500.5ms; epoch 500ms -> 500us on the shared axis
    assert rebase_ns(1_500_000, anchor, 500_000_000) == pytest.approx(500.0)
    # no anchor: raw monotonic passes through (flagged upstream)
    assert rebase_ns(2_000, None, 0) == pytest.approx(2.0)


def test_timeline_aligns_two_processes_with_known_skew():
    """Two fake processes whose monotonic epochs differ by exactly 7s:
    events that happened at the SAME wall instant must land at the same
    rebased timestamp, and lanes stay distinct."""
    from tpurpc.tools.timeline import build_timeline

    wall = 1_700_000_000_000_000_000
    skew_ns = 7_000_000_000

    def member(target, mono_anchor, ev_mono_ns):
        return {
            "target": target,
            "traces": {
                "traceEvents": [
                    {"ph": "X", "name": "spanA", "cat": "tpurpc",
                     "ts": ev_mono_ns / 1e3, "dur": 10.0,
                     "pid": 1, "tid": 1},
                ],
                "displayTimeUnit": "ms",
                "clock_anchor": {"pid": 1, "mono_ns": mono_anchor,
                                 "wall_ns": wall},
            },
            "flight": {"events": []},
            "profile": {},
            "metrics": "",
        }

    # proc A: event 1ms after its anchor. proc B: its monotonic clock is
    # 7s AHEAD (started later), same wall anchor instant, event also 1ms
    # after the anchor — the two events are wall-simultaneous.
    a = member("a:1", 10_000_000, 10_000_000 + 1_000_000)
    b = member("b:1", 10_000_000 + skew_ns,
               10_000_000 + skew_ns + 1_000_000)
    doc = build_timeline([a, b])
    spans = [e for e in doc["traceEvents"]
             if e.get("ph") == "X" and e["name"] == "spanA"]
    assert len(spans) == 2
    assert spans[0]["ts"] == pytest.approx(spans[1]["ts"], abs=1e-6)
    assert spans[0]["pid"] != spans[1]["pid"]  # distinct lanes
    assert not doc["otherData"]["unanchored"]
    names = [e["args"]["name"] for e in doc["traceEvents"]
             if e.get("ph") == "M" and e.get("name") == "process_name"]
    assert names == ["a:1", "b:1"]


def test_timeline_unanchored_member_is_flagged_not_dropped():
    from tpurpc.tools.timeline import build_timeline

    doc = build_timeline([{
        "target": "old:1",
        "traces": {"traceEvents": [
            {"ph": "X", "name": "s", "ts": 5.0, "dur": 1.0,
             "pid": 1, "tid": 1}]},
        "flight": None, "profile": None, "metrics": "",
    }])
    assert doc["otherData"]["unanchored"] == ["old:1"]
    assert any(e.get("ph") == "X" for e in doc["traceEvents"])


def test_merge_waterfalls_sums_and_recomputes_rate():
    import bench

    a = {"hops": [{"hop": "wire", "bytes": 1_000_000, "busy_ms": 1.0,
                   "copy_bytes": 0}]}
    b = {"hops": [{"hop": "wire", "bytes": 3_000_000, "busy_ms": 1.0,
                   "copy_bytes": 100}]}
    m = bench._merge_waterfalls([a, b])
    row = m["hops"][0]
    assert row["bytes"] == 4_000_000 and row["copy_bytes"] == 100
    assert row["gbps"] == pytest.approx(2.0, rel=0.01)  # 4MB / 2ms
    assert m["slowest_hop"] == "wire"


# ---------------------------------------------------------------------------
# scrape routes + concurrent-scraper hammering (satellite 3)
# ---------------------------------------------------------------------------

@pytest.fixture
def echo_server():
    from tpurpc.rpc.server import Server, unary_unary_rpc_method_handler

    srv = Server(max_workers=8)
    srv.add_method("/lens/Echo",
                   unary_unary_rpc_method_handler(
                       lambda req, ctx: bytes(req)))
    port = srv.add_insecure_port("127.0.0.1:0")
    srv.start()
    yield srv, port
    srv.stop(0)


def test_profile_and_waterfall_routes(echo_server):
    _srv, port = echo_server
    status, body = _http_get(port, "/debug/profile")
    assert status == 200
    doc = json.loads(body)
    assert doc["enabled"] and doc["hz"] > 0
    status, body = _http_get(port, "/debug/waterfall")
    assert status == 200
    doc = json.loads(body)
    assert tuple(r["hop"] for r in doc["hops"]) == lens.HOP_NAMES
    status, body = _http_get(port, "/debug/waterfall?text=1")
    assert status == 200 and b"GB/s" in body
    status, _body = _http_get(port, "/debug/profile?collapsed=1")
    assert status == 200


def test_lens_off_switch_disables_profile_route(echo_server, monkeypatch):
    _srv, port = echo_server
    monkeypatch.setenv("TPURPC_LENS", "0")
    try:
        status, body = _http_get(port, "/debug/profile")
        assert status == 200
        assert json.loads(body) == {"enabled": False,
                                    "reason": "TPURPC_LENS=0"}
    finally:
        monkeypatch.delenv("TPURPC_LENS", raising=False)


def test_concurrent_scrapers_vs_pipelined_traffic(echo_server):
    """N scraper threads hammer /metrics + /debug/profile +
    /debug/waterfall on the SERVING port while depth-4 pipelined traffic
    runs: no exception anywhere, no torn Prometheus output, and the
    scrape cost lands in the scrape_us histogram."""
    from tpurpc.rpc.channel import Channel
    from tpurpc.tools.top import parse_prometheus

    _srv, port = echo_server
    scrape_us = metrics.histogram("scrape_us", kind="latency")
    count0 = scrape_us.snapshot()["count"]
    errors = []
    stop = threading.Event()
    scrapes = {"n": 0}

    def scraper(k):
        paths = ["/metrics", "/debug/profile", "/debug/waterfall"]
        try:
            while not stop.is_set():
                path = paths[scrapes["n"] % len(paths)]
                status, body = _http_get(port, path)
                assert status == 200, (path, status)
                if path == "/metrics":
                    m = parse_prometheus(body.decode())
                    # a torn exposition drops whole families: the core
                    # series must be present in EVERY scrape
                    assert ("tpurpc_ring_msgs_read", "") in m, "torn scrape"
                else:
                    json.loads(body)  # torn JSON would raise
                scrapes["n"] += 1
        except Exception as exc:  # noqa: BLE001 — recorded, test asserts
            errors.append((k, repr(exc)))

    threads = [threading.Thread(target=scraper, args=(k,), daemon=True)
               for k in range(3)]
    [t.start() for t in threads]
    try:
        with Channel(f"127.0.0.1:{port}") as ch:
            pl = ch.unary_unary("/lens/Echo").pipeline(depth=4)
            for round_ in range(6):
                futs = [pl.call_async(b"m%d" % i, timeout=20)
                        for i in range(16)]
                for i, f in enumerate(futs):
                    assert f.result(20) == b"m%d" % i
    finally:
        stop.set()
        [t.join(timeout=10) for t in threads]
    assert not errors, errors
    assert scrapes["n"] >= 6, "scrapers barely ran"
    # the scrape cost is accounted where it runs — the scrape_us histogram
    got = metrics.histogram("scrape_us", kind="latency").snapshot()
    assert got["count"] >= count0 + scrapes["n"]
    assert got["p50"] > 0


# ---------------------------------------------------------------------------
# shard fan-out of /traces, /debug/profile, /debug/waterfall (satellite 1)
# ---------------------------------------------------------------------------

def _build_traced(shard_id):
    import tpurpc.rpc as tps
    from tpurpc.obs import tracing as _tracing

    _tracing.force(True)
    srv = tps.Server(max_workers=4)
    srv.add_method("/lens/Who", tps.unary_unary_rpc_method_handler(
        lambda req, ctx: str(shard_id).encode()))
    return srv


def test_trace_on_non_answering_shard_appears_in_merged_view():
    """The satellite-1 regression: a sampled span born on shard k must be
    visible in GET /traces on the serving port no matter which worker
    answers the scrape — plus the new /debug/profile and /debug/waterfall
    fan-outs carry every live worker."""
    import tpurpc.rpc as tps
    from tpurpc.rpc.shard import ShardedServer

    sup = ShardedServer(_build_traced, workers=2,
                        listener="reuseport").start()
    tracing.force(True)  # client roots propagate; each serving worker
    try:                 # records its half of the span tree
        seen = set()
        deadline = time.monotonic() + 30
        while len(seen) < 2 and time.monotonic() < deadline:
            with tps.Channel(f"127.0.0.1:{sup.port}") as ch:
                seen.add(bytes(ch.unary_unary("/lens/Who")(
                    b"x", timeout=20)).decode())
        assert seen == {"0", "1"}, seen

        def merged_traces():
            status, body = _http_get(sup.port, "/traces")
            assert status == 200
            return json.loads(body)

        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            doc = merged_traces()
            span_pids = {e["pid"] for e in doc.get("traceEvents", ())
                         if e.get("ph") == "X"}
            if span_pids >= {0, 1}:
                break
            time.sleep(0.25)
        # BOTH workers' spans are in the one merged doc — whichever shard
        # answered, the other one's spans crossed the fan-out
        assert span_pids >= {0, 1}, (span_pids, doc.get("shards"))
        assert set(doc["clock_anchors"]) == {"0", "1"}

        status, body = _http_get(sup.port, "/debug/profile")
        assert status == 200
        prof = json.loads(body)
        assert set(prof["shards"]) == {"0", "1"}, prof.get("shards")
        assert prof["samples"] >= 0 and prof["enabled"]

        status, body = _http_get(sup.port, "/debug/waterfall")
        assert status == 200
        wf = json.loads(body)
        assert set(wf["shards"]) == {"0", "1"}
        assert tuple(r["hop"] for r in wf["hops"]) == lens.HOP_NAMES
    finally:
        tracing.force(None)
        sup.stop()


# ---------------------------------------------------------------------------
# lens.stage (ISSUE 26): counters always, profiler spans when jax is here
# ---------------------------------------------------------------------------

def _hop(name):
    snap = metrics.registry().counters_snapshot()
    return {k: snap[f"lens_{name}_{k}"]
            for k in ("bytes", "busy_ns", "ops", "copy_bytes")}


@pytest.mark.parametrize("raises", [False, True])
def test_stage_bumps_bytes_busy_ops_once_per_exit(raises):
    before = _hop("jax_array")
    try:
        with lens.stage("jax_array", 100) as st:
            st.copy = 7
            time.sleep(0.002)
            if raises:
                raise RuntimeError("the body failed")
    except RuntimeError:
        assert raises
    after = _hop("jax_array")
    assert after["ops"] == before["ops"] + 1
    assert after["bytes"] == before["bytes"] + 100
    assert after["copy_bytes"] == before["copy_bytes"] + 7
    assert after["busy_ns"] - before["busy_ns"] >= 2_000_000


def test_stage_begin_end_pair_and_exclude():
    before = _hop("srv_handler")
    st = lens.stage("srv_handler", 5).begin()
    time.sleep(0.02)
    st.exclude(15_000_000, 0)  # a sibling's 15 ms ran inside this interval
    dt = st.end()
    after = _hop("srv_handler")
    assert after["ops"] == before["ops"] + 1
    assert after["busy_ns"] - before["busy_ns"] == dt
    assert 4_000_000 <= dt < 20_000_000


# -- two clocks a stage (ISSUE 39): thread CPU beside wall ----------------------
# No timing assert a loaded runner can break: a sleep's wall has a floor and
# its CPU a ceiling far below it; a spin is driven by the thread's own clock.

@pytest.fixture(autouse=True)
def every_message_clocked(monkeypatch):
    """One message in N reads the CPU clock: N pinned to 1, so that a test
    can assert on the ``cpu_ns`` of the one stage it runs. And this thread
    as one that was never given a message, whatever an earlier test left
    on it."""
    monkeypatch.setattr(lens, "_CPU_EVERY", 1)
    for name in ("ids", "clocked"):
        lens._tls.__dict__.pop(name, None)


def _clocks(name):
    snap = metrics.registry().counters_snapshot()
    return {k: snap[f"lens_{name}_{k}"] for k in ("busy_ns", "cpu_ns", "ops")}


def _since(name, before):
    after = _clocks(name)
    return {k: after[k] - before[k] for k in after}


def _spin_cpu(ns):
    """Burn ``ns`` of THIS thread's CPU, by its own clock."""
    until = time.thread_time_ns() + ns
    while time.thread_time_ns() < until:
        pass


def test_a_stage_that_sleeps_reads_wall_and_nearly_no_cpu():
    before = _clocks("jax_array")
    with lens.stage("jax_array") as st:
        time.sleep(0.05)
    got = _since("jax_array", before)
    assert got["ops"] == 1
    assert got["busy_ns"] >= 50_000_000
    assert got["cpu_ns"] == st.cpu_ns < 25_000_000


def test_a_stage_that_spins_reads_its_threads_cpu():
    before = _clocks("jax_array")
    with lens.stage("jax_array") as st:
        _spin_cpu(10_000_000)
    got = _since("jax_array", before)
    assert got["cpu_ns"] == st.cpu_ns >= 10_000_000
    # on a core at most all of the time
    assert got["busy_ns"] >= got["cpu_ns"] - 1_000_000


def test_a_stage_reads_its_own_threads_cpu_and_no_other():
    """A thread that burns CPU beside a sleeping stage is not billed to it:
    the second clock is the calling thread's, not the process's."""
    stop = threading.Event()

    def burn():
        while not stop.is_set():
            pass

    t = threading.Thread(target=burn, daemon=True)
    t.start()
    try:
        before = _clocks("jax_array")
        with lens.stage("jax_array"):
            time.sleep(0.05)
        got = _since("jax_array", before)
    finally:
        stop.set()
        t.join(5)
    assert not t.is_alive()
    assert got["busy_ns"] >= 50_000_000 and got["cpu_ns"] < 25_000_000


def test_exclude_takes_a_sibling_out_on_both_clocks():
    before = {h: _clocks(h) for h in ("srv_handler", "srv_send")}
    outer = lens.stage("srv_handler").begin()
    _spin_cpu(5_000_000)
    inner = lens.stage("srv_send").begin()
    _spin_cpu(10_000_000)
    dt = inner.end()
    outer.exclude(dt, inner.cpu_ns)
    outer.end()
    handler = _since("srv_handler", before["srv_handler"])
    send = _since("srv_send", before["srv_send"])
    assert send["cpu_ns"] == inner.cpu_ns >= 10_000_000
    assert send["busy_ns"] == dt
    # the outer stage keeps its own 5 ms and none of the sibling's 10
    assert 5_000_000 <= handler["cpu_ns"] == outer.cpu_ns < 10_000_000
    assert handler["busy_ns"] >= 5_000_000


def test_call_stages_stay_additive_on_both_clocks():
    """``CallStages.send_end`` inside an open ``srv_handler``: the send's
    CPU is the send's, not the handler's too."""
    before = {h: _clocks(h) for h in ("srv_handler", "srv_send")}
    stages = lens.CallStages(lambda: None)
    stages.handle(0)
    tx = stages.send_begin()
    _spin_cpu(10_000_000)
    stages.send_end(tx)
    stages.handled()
    assert _since("srv_send", before["srv_send"])["cpu_ns"] >= 10_000_000
    assert _since("srv_handler", before["srv_handler"])["cpu_ns"] < 10_000_000


def test_begin_and_end_across_a_generators_yield():
    """What the call path does: a stage opened before a ``yield`` and ended
    after it, on the thread that drives the generator."""
    def behavior():
        st = lens.stage("srv_handler", 3).begin()
        _spin_cpu(2_000_000)
        yield "reply"
        _spin_cpu(2_000_000)
        st.end()
        yield st

    before = _clocks("srv_handler")
    gen = behavior()
    assert next(gen) == "reply"
    time.sleep(0.02)       # the consumer's own time, inside the interval
    st = next(gen)
    got = _since("srv_handler", before)
    assert got["ops"] == 1
    assert got["busy_ns"] >= 20_000_000
    assert 4_000_000 <= got["cpu_ns"] == st.cpu_ns < got["busy_ns"]


def test_a_message_is_clocked_whole_or_not_at_all(monkeypatch):
    """Where reading the thread's clock is dear, one message in N reads it:
    the stage that opens the message on its thread decides (its hop's every
    N-th), the stages nested under it inherit that, a clocked stage bills
    its CPU times N and an unclocked one reads no clock at all."""
    monkeypatch.setattr(lens, "_CPU_EVERY", 4)
    monkeypatch.setitem(lens._OPENED, "srv_recv", itertools.count())
    reads = metrics.counter("lens_cpu_clock_reads")
    call = next(lens._CALL_IDS)
    billed = []
    for seq in range(6):
        before = {h: _clocks(h) for h in ("srv_recv", "decode", "hbm")}
        r0 = reads.snapshot()
        with lens.stage("srv_recv", call=call, seq=seq) as outer:
            with lens.stage("decode") as mid:
                _spin_cpu(2_000_000)
                with lens.stage("hbm") as inner:      # inherits too
                    _spin_cpu(1_000_000)
        got = {h: _since(h, before[h]) for h in before}
        billed.append((outer.cpu_ns, mid.cpu_ns, inner.cpu_ns,
                       reads.snapshot() - r0, got))
    for k in (0, 4):
        outer_cpu, mid_cpu, inner_cpu, n_reads, got = billed[k]
        assert n_reads == 6
        assert outer_cpu >= mid_cpu >= 3_000_000 > inner_cpu >= 1_000_000
        assert got["decode"]["cpu_ns"] == 4 * mid_cpu
        assert got["hbm"]["cpu_ns"] == 4 * inner_cpu
        assert got["srv_recv"]["cpu_ns"] == 4 * outer_cpu
    for k in (1, 2, 3, 5):
        outer_cpu, mid_cpu, inner_cpu, n_reads, got = billed[k]
        assert (outer_cpu, mid_cpu, inner_cpu, n_reads) == (0, 0, 0, 0)
        assert all(g["cpu_ns"] == 0 and g["ops"] == 1 and g["busy_ns"] > 0
                   for g in got.values())
    # the same pair again (a batch's second stage) opens no new message: it
    # counts nothing (a count would raise here) and inherits the last
    # decision, which was not to read
    monkeypatch.setattr(lens, "_clocked", lambda hop: 1 / 0)
    r0 = reads.snapshot()
    with lens.stage("batch_run", call=call, seq=5) as again:
        pass
    assert reads.snapshot() - r0 == 0 and again.cpu_ns == 0


def test_every_nth_opening_of_a_hop_is_clocked_whoever_opens_it(monkeypatch):
    """``_clocked``: a hop's every N-th opening, its first among them, and
    exactly so when threads open it at once (the count is one step of the
    interpreter: none is lost and none counted twice)."""
    monkeypatch.setattr(lens, "_CPU_EVERY", 7)
    monkeypatch.setitem(lens._OPENED, "jax_array", itertools.count())
    assert [lens._clocked("jax_array") for _ in range(15)] == [
        k % 7 == 0 for k in range(15)]
    monkeypatch.setitem(lens._OPENED, "jax_array", itertools.count())
    tally = []

    def open_many():
        tally.append(sum(lens._clocked("jax_array") for _ in range(2_500)))

    threads = [threading.Thread(target=open_many) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert sum(tally) == -(-10_000 // 7)
    # a thread that was never given a message decides a stage at a time
    out = []

    def bare():
        monkeypatch.setitem(lens._OPENED, "jax_array", itertools.count())
        for _ in range(8):
            with lens.stage("jax_array") as st:
                _spin_cpu(200_000)
            out.append(st.cpu_ns > 0)

    t = threading.Thread(target=bare)
    t.start()
    t.join(30)
    assert out == [True] + [False] * 6 + [True]


def test_account_bumps_no_cpu():
    before = _clocks("srv_call")
    lens.account("srv_call", 7_000_000, 11)
    assert _since("srv_call", before) == {
        "busy_ns": 7_000_000, "cpu_ns": 0, "ops": 1}


def test_every_waterfall_row_has_cpu_ms_and_the_text_a_column():
    before = _clocks("jax_array")
    with lens.stage("jax_array", 1 << 20):
        _spin_cpu(3_000_000)
    got = _since("jax_array", before)
    assert 3_000_000 <= got["cpu_ns"] <= got["busy_ns"]
    doc = lens.waterfall()
    assert all("cpu_ms" in r for r in doc["hops"])
    row = next(r for r in doc["hops"] if r["hop"] == "jax_array")
    assert row["cpu_ms"] >= (before["cpu_ns"] + 3_000_000) / 1e6 - 0.001
    text = lens.render_text(doc).splitlines()
    assert text[0].split()[:6] == ["hop", "GB/s", "MiB", "busy_ms", "cpu_ms",
                                   "copy_MiB"]
    # which messages read the second clock and what a read costs here, and
    # the observers' own share, in the document and under the table
    assert doc["cpu_clock"]["every"] == 1 and doc["cpu_clock"]["reads"] >= 2
    assert set(doc["observers"]) == {"cpu_ms", "ticks", "us_a_tick"}
    assert text[-1].startswith("observers: ") and "us a tick" in text[-1]
    # a document of a member that predates the column still renders
    old = {"hops": [{k: v for k, v in r.items() if k != "cpu_ms"}
                    for r in doc["hops"]], "slowest_hop": None}
    assert "jax_array" in lens.render_text(old)


def test_the_process_clocks_advance_with_every_snapshot():
    a = metrics.registry().counters_snapshot()
    _spin_cpu(2_000_000)
    b = metrics.registry().counters_snapshot()
    assert b["proc_wall_ns"] - a["proc_wall_ns"] >= 2_000_000
    assert b["proc_cpu_ns"] - a["proc_cpu_ns"] >= 2_000_000
    now = time.monotonic_ns()
    assert 0 <= now - b["proc_wall_ns"] < 60_000_000_000


def _observers():
    """Each background loop under ``obs/``, as (start, stop): private
    instances at a period short enough to tick within the test; the
    watchdog's sweeper has no stop, so it is the process's own (a sweep
    every 0.25 s, as under any server)."""
    from tpurpc.obs import collector, slo, tsdb, watchdog

    sampler = StageProfiler(hz=200)
    historian = tsdb.Tsdb(fine_s=0.01)
    pager = slo.SloEvaluator(eval_s=0.01, tsdb=historian)
    fleet = collector.FleetCollector([], poll_s=0.01)
    return {
        "profiler": (sampler.start, sampler.stop),
        "tsdb": (historian.start, historian.stop),
        "slo": (pager.start, pager.stop),
        "watchdog": (watchdog.get()._ensure_thread, lambda: None),
        "collector": (fleet.start, fleet.stop),
    }


@pytest.mark.parametrize("loop", ["profiler", "tsdb", "slo", "watchdog",
                                  "collector"])
def test_every_observer_loop_bills_its_own_ticks(loop):
    ticks = metrics.counter("obs_bg_ticks")
    cpu = metrics.counter("obs_bg_cpu_ns")
    start, stop = _observers()[loop]
    t0, c0 = ticks.snapshot(), cpu.snapshot()
    start()
    try:
        deadline = time.monotonic() + 10
        while ticks.snapshot() < t0 + 2 and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        stop()
    assert ticks.snapshot() >= t0 + 2
    assert cpu.snapshot() > c0
    # the operator's view of the pair: GET /debug/waterfall
    seen = lens.waterfall()["observers"]
    assert seen["ticks"] >= t0 + 2
    assert seen["cpu_ms"] > 0 and seen["us_a_tick"] > 0


def test_every_hop_has_an_ops_counter_and_the_list_only_grows():
    snap = metrics.registry().counters_snapshot()
    for hop in lens.HOP_NAMES:
        assert f"lens_{hop}_ops" in snap
        assert f"lens_{hop}_cpu_ns" in snap
    # ISSUE 39 appended its two hops at the end and moved nothing
    assert lens.HOP_NAMES[-3:] == ("srv_reply_wait", "batch_ready",
                                   "place_return")
    assert lens.HOP_NAMES.index("srv_reply_wait") == 25
    assert lens.HOP_NAMES[:12] == (
        "device", "send_ring", "wire", "rendezvous", "ctrl", "native_send",
        "native_recv", "native_rdv", "peer_ring", "decode", "hbm",
        "jax_array")
    with pytest.raises(KeyError):
        lens.stage("warp-drive").begin().end()


def test_nested_stages_keep_parent_over_the_sum_of_its_children():
    """`decode` over `hbm_credit`, `hbm` and `hbm_view`, on one thread, with
    a placement that has to wait for credit."""
    import numpy as np

    from tpurpc.jaxshim import codec
    from tpurpc.tpu import HbmRing
    from tpurpc.tpu.endpoint import decode_tree_to_ring

    hops = ("decode", "hbm_credit", "hbm", "hbm_view")
    before = {h: _hop(h) for h in hops}
    ring = HbmRing(1 << 16)
    wire = codec.encode_tree_bytes({"x": np.arange(10240, dtype=np.float32)})
    _, first = decode_tree_to_ring(ring, bytearray(wire))  # 40 KiB of 64

    def release_soon():
        time.sleep(0.05)
        for lease in first:
            lease.release()

    t = threading.Thread(target=release_soon)
    t.start()
    _, second = decode_tree_to_ring(ring, bytearray(wire), timeout=10)
    t.join(timeout=10)
    assert not t.is_alive()
    for lease in second:
        lease.release()
    d = {h: {k: _hop(h)[k] - before[h][k] for k in before[h]} for h in hops}
    assert d["decode"]["ops"] == 2 and d["hbm"]["ops"] == 2
    assert d["hbm_view"]["ops"] == 2
    assert d["hbm_credit"]["ops"] == 1  # only the placement that blocked
    assert d["hbm_credit"]["busy_ns"] >= 30_000_000
    assert d["decode"]["busy_ns"] >= (d["hbm_credit"]["busy_ns"]
                                      + d["hbm"]["busy_ns"]
                                      + d["hbm_view"]["busy_ns"])
    assert d["hbm"]["bytes"] == d["hbm"]["copy_bytes"] == 2 * 40960
    assert d["decode"]["bytes"] == d["hbm_view"]["bytes"] == 2 * 40960


def test_direct_landing_is_one_op_of_each_landing_stage():
    """A message landed is one `decode` over one `hbm` and one `hbm_view`,
    whatever its leaf count: the three readers of the landing keep an
    additive split."""
    import numpy as np

    from tpurpc.jaxshim import codec
    from tpurpc.tpu import HbmRing
    from tpurpc.tpu.endpoint import decode_tree_to_ring

    hops = ("decode", "hbm_credit", "hbm", "hbm_view")
    ring = HbmRing(1 << 16)
    tree = {"x": np.arange(8192, dtype=np.float32), "y": np.ones(5, np.int32)}
    wire = bytearray(codec.encode_tree_bytes(tree))
    before = {h: _hop(h) for h in hops}
    _, leases = decode_tree_to_ring(ring, wire)
    d = {h: {k: _hop(h)[k] - before[h][k] for k in before[h]} for h in hops}
    for lease in leases:
        lease.release()
    payload = 8192 * 4 + 5 * 4
    assert [d[h]["ops"] for h in hops] == [1, 0, 1, 1]
    assert d["decode"]["busy_ns"] >= (d["hbm"]["busy_ns"]
                                      + d["hbm_view"]["busy_ns"]) > 0
    assert d["hbm"]["bytes"] == d["hbm"]["copy_bytes"] == payload
    assert d["decode"]["bytes"] == d["hbm_view"]["bytes"] == payload


def test_stage_in_a_process_without_jax_leaves_it_unimported():
    import os
    import subprocess
    import sys

    script = (
        "import sys\n"
        "from tpurpc.obs import lens, metrics\n"
        "with lens.stage('srv_recv', 3, call=1, seq=0):\n"
        "    with lens.stage('decode', 3):\n"
        "        pass\n"
        "snap = metrics.registry().counters_snapshot()\n"
        "assert snap['lens_srv_recv_ops'] == 1, snap\n"
        "assert snap['lens_decode_bytes'] == 3, snap\n"
        "assert 'jax' not in sys.modules\n"
        "print('NOJAX-OK')\n")
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=here + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=120)
    assert res.returncode == 0 and "NOJAX-OK" in res.stdout, res.stderr


def _device_stream_server(monkeypatch, fn):
    """A `device=True` stream method on the plane that has a device ring
    (GRPC_PLATFORM_TYPE=RDMA_TPU is served by rpc/server.py: the native
    server plane does not adopt it)."""
    from tpurpc.jaxshim import add_tensor_method
    from tpurpc.rpc.server import Server
    from tpurpc.utils import config as config_mod

    monkeypatch.setenv("GRPC_PLATFORM_TYPE", "RDMA_TPU")
    config_mod.set_config(None)
    srv = Server(max_workers=4)
    add_tensor_method(srv, "Sink", fn, kind="stream_stream", device=True)
    srv.start()
    return srv, srv.add_insecure_port("127.0.0.1:0")


def test_profiler_trace_holds_the_stage_spans_of_a_device_stream(
        monkeypatch, tmp_path):
    """One device stream under a CPU `jax.profiler` trace: the handler's
    thread line holds `tpurpc.srv_recv`, `tpurpc.decode`, `tpurpc.hbm`,
    `tpurpc.hbm_view` and `tpurpc.srv_handler` inside the test's own window
    span, each carrying `call` and a `seq` that rises with the messages."""
    import glob
    import re

    import jax
    import numpy as np

    from tpurpc.jaxshim import TensorClient
    from tpurpc.rpc.channel import Channel

    def consume(trees):
        n = 0
        for t in trees:
            t["x"].block_until_ready()
            n += 1
        yield {"n": np.int64(n)}

    srv, port = _device_stream_server(monkeypatch, consume)
    x = np.ones(4096, np.float32)
    try:
        with Channel(f"127.0.0.1:{port}") as ch:
            client = TensorClient(ch)
            list(client.duplex("Sink", iter([{"x": x}] * 2), timeout=60))
            jax.profiler.start_trace(str(tmp_path))
            with jax.profiler.TraceAnnotation("test.window"):
                replies = list(client.duplex(
                    "Sink", iter([{"x": x}] * 6), timeout=60))
            jax.profiler.stop_trace()
        assert int(np.asarray(replies[0]["n"]).ravel()[0]) == 6
    finally:
        srv.stop(grace=0)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    window = None
    lines = {}
    for plane in data.planes:
        # thread lines by position: two threads may share a line name
        for nth, line in enumerate(plane.lines):
            for ev in line.events:
                name = ev.name.split("#")[0]
                if name == "test.window":
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif name.startswith("tpurpc."):
                    stats = dict(ev.stats)
                    text = ev.name + " " + " ".join(
                        f"{k}={v}" for k, v in stats.items())
                    seq = int(re.search(r"seq=(\d+)", text).group(1))
                    call = int(re.search(r"call=(\d+)", text).group(1))
                    lines.setdefault((plane.name, nth), []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns, name,
                         call, seq))
    assert window is not None
    want = {"tpurpc.srv_recv", "tpurpc.decode", "tpurpc.hbm",
            "tpurpc.hbm_view", "tpurpc.srv_handler"}
    handler_lines = [evs for evs in lines.values()
                     if want <= {e[2] for e in evs}]
    assert len(handler_lines) == 1, {k: {e[2] for e in v}
                                     for k, v in lines.items()}
    evs = sorted(handler_lines[0])
    assert all(window[0] <= e[0] and e[1] <= window[1] for e in evs)
    assert len({e[3] for e in evs}) == 1 and evs[0][3] > 0  # one call
    by_name = {n: [e for e in evs if e[2] == n] for n in want}
    assert [e[4] for e in by_name["tpurpc.srv_handler"]] == list(range(6))
    assert [e[4] for e in by_name["tpurpc.srv_recv"]] == list(range(7))
    for h, d in zip(by_name["tpurpc.srv_handler"],
                    by_name["tpurpc.decode"]):
        assert h[0] <= d[0] and d[1] <= h[1] and h[4] == d[4]  # nests
    for d, child in zip(by_name["tpurpc.decode"] * 2,
                        by_name["tpurpc.hbm"] + by_name["tpurpc.hbm_view"]):
        assert d[0] <= child[0] and child[1] <= d[1] and d[4] == child[4]


def test_python_plane_counts_what_a_message_waits_for_its_handler(
        monkeypatch):
    """`srv_queue` ops = messages taken; its time grows when the handler
    sleeps between messages, and the top-level stages cover the call."""
    import numpy as np

    from tpurpc.jaxshim import TensorClient
    from tpurpc.rpc.channel import Channel

    def slow(trees):
        n = 0
        for _ in trees:
            time.sleep(0.02)
            n += 1
        yield {"n": np.int64(n)}

    hops = ("srv_queue", "srv_recv", "srv_handler", "srv_send", "srv_call")
    srv, port = _device_stream_server(monkeypatch, slow)
    before = {h: _hop(h) for h in hops}
    x = np.ones(1024, np.float32)
    try:
        with Channel(f"127.0.0.1:{port}") as ch:
            list(TensorClient(ch).duplex("Sink", iter([{"x": x}] * 10),
                                         timeout=60))
    finally:
        srv.stop(grace=0)
    d = {h: {k: _hop(h)[k] - before[h][k] for k in before[h]} for h in hops}
    assert d["srv_queue"]["ops"] == d["srv_handler"]["ops"] == 10
    assert d["srv_recv"]["ops"] == 11  # ten messages and the stream's end
    assert d["srv_send"]["ops"] == 1 and d["srv_call"]["ops"] == 1
    # ten messages sent at once, taken one per 20 ms: they wait their turn
    assert d["srv_queue"]["busy_ns"] >= 5 * 20_000_000
    assert d["srv_handler"]["busy_ns"] >= 10 * 20_000_000
    staged = sum(d[h]["busy_ns"] for h in ("srv_recv", "srv_handler",
                                           "srv_send"))
    assert 0.9 * d["srv_call"]["busy_ns"] <= staged <= d["srv_call"]["busy_ns"]
