"""tpurpc-xray: the Python face of the C observability plane (ISSUE 19).

The merged-flight contract (``tpurpc/obs/native_obs.py`` + the
``flight.snapshot`` merge): the C core's shm flight ring and metrics
table surface through the SAME consumers the Python plane feeds —
one monotonic timeline with lane tags, protocol conformance over the
merged stream, ``native_*`` registry series into the tsdb, postfork
remapping in forked shard workers, and a clean off switch that leaves
the PR 18 ``tpr_rdv_counters`` ledger ABI untouched.
"""

import json
import os
import subprocess
import sys

import pytest

import tpurpc.rpc as rpc
from tpurpc.rpc.channel import Channel

from tests.conftest import requires_native_lib  # noqa: E402

pytestmark = requires_native_lib

PY_PAYLOAD = bytes(512) * 4096  # 2 MiB: over the py-plane rdv floor
NATIVE_PAYLOAD = bytes(range(256)) * 4096  # 1 MiB on the C plane


@pytest.fixture
def ring_platform(monkeypatch):
    monkeypatch.setenv("GRPC_PLATFORM_TYPE", "RDMA_BPEV")
    from tpurpc.utils import config as config_mod

    config_mod.set_config(None)
    yield
    config_mod.set_config(None)


@pytest.fixture
def obs_plane(ring_platform):
    """Fresh C + py flight state; skips when the .so was built with the
    plane compiled out or disabled in this environment."""
    from tpurpc.obs import flight, native_obs

    if not native_obs.available():
        pytest.skip("native obs plane not available in this process")
    flight.RECORDER.reset()
    native_obs.reset()
    yield native_obs
    flight.RECORDER.reset()


def test_the_plane_outlives_a_reload_of_the_library(obs_plane):
    """``_native.reset_for_tests()`` (a test that ran the Python data plane
    in this worker before) hands out a new handle: the plane declares its
    signatures on it again, and still reads the region's name as bytes."""
    from tpurpc.core import _native

    assert obs_plane.available()
    _native.reset_for_tests()
    assert _native.load() is not None
    assert obs_plane.available()
    assert isinstance(obs_plane.counters(), dict) and obs_plane.counters()


def _run_child(script, **env_extra):
    """``script`` in a fresh interpreter on the ring platform: for what the C
    side reads once, at first use."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, GRPC_PLATFORM_TYPE="RDMA_BPEV",
               JAX_PLATFORMS="cpu", **env_extra)
    env["PYTHONPATH"] = here + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=180)


def _totaling_server():
    srv = rpc.Server(max_workers=4)

    def total(req_iter, ctx):
        n = 0
        for m in req_iter:
            n += len(m)
        yield str(n).encode()

    srv.add_method("/nobs.S/Total",
                   rpc.stream_stream_rpc_method_handler(total))
    port = srv.add_insecure_port("127.0.0.1:0")
    srv.start()
    return srv, port


def _cross_plane_exchange():
    """One native-plane leg and one py-plane leg on the same wire, so the
    merged flight carries BOTH lanes."""
    srv, port = _totaling_server()
    try:
        assert srv._native_dp is not None, "server adoption did not engage"
        with Channel(f"127.0.0.1:{port}") as ch:
            mc = ch.stream_stream("/nobs.S/Total")
            list(mc(iter([b"warm"]), timeout=30))
            out = list(mc(iter([NATIVE_PAYLOAD]), timeout=60))
            assert out[-1] == str(len(NATIVE_PAYLOAD)).encode(), out
            mc_py = ch.stream_stream("/nobs.S/Total", tpurpc_native=False)
            out = list(mc_py(iter([PY_PAYLOAD]), timeout=60))
            assert out[-1] == str(len(PY_PAYLOAD)).encode(), out
    finally:
        srv.stop(grace=1)


def test_merged_snapshot_two_lanes_one_timeline(obs_plane):
    """Cross-plane calls produce ONE time-ordered flight view: C records
    lane-tagged ``native`` on n* entities, py records tagged ``py``,
    interleaved on the shared CLOCK_MONOTONIC axis."""
    from tpurpc.obs import flight

    _cross_plane_exchange()
    snap = flight.snapshot()
    stamps = [e["t_ns"] for e in snap]
    assert stamps == sorted(stamps), "merged timeline out of order"
    native = [e for e in snap if e.get("lane") == "native"]
    py = [e for e in snap if e.get("lane") == "py"]
    assert native, "C plane contributed nothing to the merge"
    assert py, "python lane lost its tag in the merge"
    assert all(e["entity"].startswith("n") for e in native), native[:5]
    # the C rendezvous evidence arrives whole and in causal order
    evs = [e["event"] for e in native]
    for name in ("rdv-offer", "rdv-claim", "rdv-complete"):
        assert name in evs, (name, evs)
    assert evs.index("rdv-offer") < evs.index("rdv-claim") \
        < evs.index("rdv-complete")


def test_merged_snapshot_replays_through_protocol_machines(obs_plane):
    """The C plane emits the SAME event vocabulary the protocol machines
    were built for: the merged dump replays with zero violations, and the
    dump file round-trips through the offline checker."""
    from tpurpc.analysis import protocol
    from tpurpc.obs import flight

    _cross_plane_exchange()
    snap = flight.snapshot()
    assert any(e.get("lane") == "native" for e in snap)
    violations = protocol.check_events(snap, strict=False)
    assert violations == [], violations[:5]
    # and as a dump FILE (the TPURPC_FLIGHT_DUMP / CI-artifact path)
    path = "/tmp/_tpurpc_test_native_obs_dump.json"
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"events": snap}, f)
    try:
        total, violations = protocol.check_dump(path, strict=False)
        assert total == len(snap)
        assert violations == [], violations[:5]
    finally:
        os.unlink(path)


def test_counters_scrape_registry_and_tsdb_pickup(obs_plane):
    """The metrics table reaches every layered consumer: the raw dict,
    the registry mirror (``native_*``), /metrics rendering, and tsdb
    history — all without the C hot path seeing Python."""
    from tpurpc.obs import metrics as metrics_mod
    from tpurpc.obs import scrape, tsdb
    from tpurpc.rpc import native_client

    _cross_plane_exchange()
    tab = obs_plane.counters()
    assert tab["rdv_send_bytes"] >= len(NATIVE_PAYLOAD), tab
    assert tab["emitted"] > 0 and tab["conn_up"] >= 1, tab
    assert set(tab) == set(obs_plane.METRIC_NAMES)
    # registry mirror: externally-owned totals, assigned not inc()ed
    assert obs_plane.sync_registry() is True
    reg = metrics_mod.registry()
    assert reg.counter("native_rdv_send_bytes").value == \
        tab["rdv_send_bytes"]
    assert "tpurpc_native_rdv_send_bytes" in scrape.render_prometheus()
    # tsdb: one sampler tick picks the mirror up as history
    db = tsdb.Tsdb(fine_s=0.05)
    db.sample_once()
    kinds = db.series()
    assert kinds.get("native_rdv_send_bytes") == "counter", kinds
    assert kinds.get("native_dlv_depth") == "gauge", kinds
    pts = db.window("native_rdv_send_bytes", 60.0)
    assert pts and pts[-1][1] >= len(NATIVE_PAYLOAD), pts
    # the PR 18 rdv ledger rides alongside, not underneath: both ABIs
    # answer, from independent storage
    led = native_client.rdv_counters()
    assert led is not None
    assert set(led) == set(native_client.RDV_COUNTER_NAMES)


def test_postfork_reset_attaches_fresh_region(obs_plane):
    """A forked shard worker must NOT keep writing into the parent's shm
    region: postfork_reset drops the inherited mapping, the C side builds
    its own region under a new name, and the parent's stays intact."""
    parent_name = obs_plane._lib().tpr_obs_shm_name().decode()
    assert parent_name
    r, w = os.pipe()
    pid = os.fork()
    if pid == 0:
        status = 1
        try:
            os.close(r)
            obs_plane.postfork_reset()
            child_name = obs_plane._lib().tpr_obs_shm_name().decode()
            doc = {"name": child_name,
                   "available": obs_plane.available(),
                   "emitted": obs_plane.counters().get("emitted", -1)}
            os.write(w, json.dumps(doc).encode())
            os.close(w)
            status = 0
        finally:
            os._exit(status)
    os.close(w)
    try:
        raw = b""
        while True:
            chunk = os.read(r, 4096)
            if not chunk:
                break
            raw += chunk
    finally:
        os.close(r)
        _, code = os.waitpid(pid, 0)
    assert os.waitstatus_to_exitcode(code) == 0
    doc = json.loads(raw)
    assert doc["available"] is True
    assert doc["name"] and doc["name"] != parent_name, doc
    assert doc["emitted"] == 0, doc  # fresh table, not the parent's totals
    # the parent keeps its region AND its mapping (staleness probe holds)
    assert obs_plane._lib().tpr_obs_shm_name().decode() == parent_name
    assert obs_plane.available()


def test_off_switch_leaves_rdv_ledger_abi_intact(ring_platform):
    """TPURPC_NATIVE_OBS=0 (read by the C side at first use, hence the
    subprocess): the plane reports unavailable, the flight snapshot grows
    no lane tags, and the PR 18 ``tpr_rdv_counters`` ledger still answers
    — observability off must not degrade the data plane's own telemetry."""
    script = """
import json
from tpurpc.obs import flight, native_obs
import tpurpc.rpc as rpc
from tpurpc.rpc.channel import Channel
from tpurpc.rpc import native_client

srv = rpc.Server(max_workers=2)

def total(req_iter, ctx):
    yield str(sum(len(m) for m in req_iter)).encode()

srv.add_method("/off.S/Total", rpc.stream_stream_rpc_method_handler(total))
port = srv.add_insecure_port("127.0.0.1:0")
srv.start()
payload = bytes(512) * 4096
try:
    with Channel(f"127.0.0.1:{port}") as ch:
        mc = ch.stream_stream("/off.S/Total")
        assert list(mc(iter([payload]), timeout=60))[-1] == \\
            str(len(payload)).encode()
        mc_py = ch.stream_stream("/off.S/Total", tpurpc_native=False)
        assert list(mc_py(iter([payload]), timeout=60))[-1] == \\
            str(len(payload)).encode()
finally:
    srv.stop(grace=1)
assert not native_obs.available()
assert native_obs.counters() == {}
assert native_obs.records() == []
snap = flight.snapshot()
assert snap, "py recorder must still record with the plane off"
assert all("lane" not in e for e in snap), "lane tags leaked"
led = native_client.rdv_counters()
assert led is not None
assert set(led) == set(native_client.RDV_COUNTER_NAMES)
assert native_client.rdv_counters_reset() is True
print("OFFSWITCH-OK")
"""
    res = _run_child(script, TPURPC_NATIVE_OBS="0")
    assert res.returncode == 0, (res.stdout, res.stderr)
    assert "OFFSWITCH-OK" in res.stdout


# ---------------------------------------------------------------------------
# ISSUE 26: the queue counted in C, refusals in the table, one instant for
# all counters, the region unlinked on a clean exit, the watchdog on progress
# ---------------------------------------------------------------------------

def _sleepy_server(nap_s):
    srv = rpc.Server(max_workers=4)

    def total(req_iter, ctx):
        n = 0
        for m in req_iter:
            n += len(m)
            if nap_s:
                import time

                time.sleep(nap_s)
        yield str(n).encode()

    srv.add_method("/nobs.S/Total",
                   rpc.stream_stream_rpc_method_handler(total))
    port = srv.add_insecure_port("127.0.0.1:0")
    srv.start()
    return srv, port


def _native_counters():
    from tpurpc.obs import metrics as metrics_mod

    snap = metrics_mod.registry().counters_snapshot()  # no explicit sync
    return {k[len("native_"):]: v for k, v in snap.items()
            if k.startswith("native_")}


def _stream(port, messages):
    with Channel(f"127.0.0.1:{port}") as ch:
        out = list(ch.stream_stream("/nobs.S/Total")(iter(messages),
                                                     timeout=60))
    return int(out[-1])


def test_counters_snapshot_is_current_with_no_explicit_sync(obs_plane):
    _cross_plane_exchange()
    tab = obs_plane.counters()
    got = _native_counters()
    assert tab["rdv_send_bytes"] >= len(NATIVE_PAYLOAD)
    for name in ("rdv_send_bytes", "rdv_recv_bytes", "conn_up",
                 "srv_queue_msgs"):
        assert got[name] == tab[name] > 0, (name, got, tab)


def test_srv_queue_counts_every_message_and_its_wait(obs_plane):
    """`srv_queue_msgs` = messages a handler took off `call->pending`;
    `srv_queue_ns` grows when the handler sleeps between messages while the
    client has already sent the next ones."""
    from tpurpc.obs import lens, metrics as metrics_mod

    waits = {}
    for nap_s in (0.0, 0.03):
        srv, port = _sleepy_server(nap_s)
        try:
            assert srv._native_dp is not None
            before = _native_counters()
            handled = metrics_mod.registry().counters_snapshot()[
                "lens_srv_handler_ops"]
            assert _stream(port, [b"x" * 1000] * 8) == 8000
            after = _native_counters()
        finally:
            srv.stop(grace=1)
        assert after["srv_queue_msgs"] - before["srv_queue_msgs"] == 8
        assert metrics_mod.registry().counters_snapshot()[
            "lens_srv_handler_ops"] - handled == 8  # the same plane's stages
        waits[nap_s] = after["srv_queue_ns"] - before["srv_queue_ns"]
    # eight sent at once, one taken per 30 ms: they wait their turn
    assert waits[0.03] >= 4 * 30_000_000 > waits[0.0]
    assert "srv_recv" in lens.HOP_NAMES


def test_rdv_refused_moves_when_the_landing_pool_is_exhausted(ring_platform):
    """A 1 MiB landing pool cannot lease the region a 1 MiB payload needs:
    the receiver refuses the offer, the payload falls back to the framed
    path and still arrives, and the metric table says so (the budget is read
    once by the C side, hence the subprocess)."""
    script = r"""
import tpurpc.rpc as rpc
from tpurpc.obs import metrics
from tpurpc.rpc.channel import Channel

srv = rpc.Server(max_workers=2)

def total(req_iter, ctx):
    yield str(sum(len(m) for m in req_iter)).encode()

srv.add_method("/ref.S/Total", rpc.stream_stream_rpc_method_handler(total))
port = srv.add_insecure_port("127.0.0.1:0")
srv.start()
assert srv._native_dp is not None
payload = bytes(range(256)) * 4096
try:
    with Channel(f"127.0.0.1:{port}") as ch:
        mc = ch.stream_stream("/ref.S/Total")
        list(mc(iter([b"warm"]), timeout=30))  # the links negotiate
        out = list(mc(iter([payload] * 3), timeout=60))
    assert out[-1] == str(3 * len(payload)).encode(), out
finally:
    srv.stop(grace=1)
snap = metrics.registry().counters_snapshot()
assert snap["native_rdv_refused"] >= 1, snap
assert snap["native_rdv_recv_bytes"] == 0, snap
print("REFUSED-OK", snap["native_rdv_refused"])
"""
    res = _run_child(script, TPURPC_RENDEZVOUS_POOL_MB="1")
    assert res.returncode == 0 and "REFUSED-OK" in res.stdout, \
        res.stdout + res.stderr[-2000:]


def test_obs_region_is_unlinked_on_a_clean_exit(ring_platform):
    """A server process that starts, serves and stops leaves no
    ``/dev/shm/tpr_*`` of its own behind: the C side unlinks the region's
    name at exit (every server used to leave 176,368 bytes there)."""
    script = r"""
import os
import tpurpc.rpc as rpc
from tpurpc.obs import native_obs
from tpurpc.rpc.channel import Channel

srv = rpc.Server(max_workers=2)
srv.add_method("/bye.S/Echo", rpc.unary_unary_rpc_method_handler(
    lambda req, ctx: req))
port = srv.add_insecure_port("127.0.0.1:0")
srv.start()
with Channel(f"127.0.0.1:{port}") as ch:
    assert ch.unary_unary("/bye.S/Echo")(b"hi", timeout=30) == b"hi"
assert native_obs.available()
name = native_obs._lib().tpr_obs_shm_name().decode()
assert os.path.exists("/dev/shm/" + name)
srv.stop(grace=1)
print("REGION", name)
"""
    res = _run_child(script)
    assert res.returncode == 0, res.stderr[-2000:]
    name = res.stdout.split("REGION", 1)[1].split()[0]
    assert name.startswith("tpr_")
    assert not os.path.exists("/dev/shm/" + name)


@pytest.mark.parametrize("platform,native", [("TCP", False),
                                             ("RDMA_BPEV", True)])
def test_watchdog_bars_a_stream_on_progress_not_age(monkeypatch, platform,
                                                    native):
    """On both server planes: a healthy 30-message stream that outlives the
    stall floor several times over does not trip; one whose sender goes quiet
    mid-way does."""
    import threading
    import time

    from tpurpc.obs import metrics as metrics_mod
    from tpurpc.obs import watchdog
    from tpurpc.utils import config as config_mod

    monkeypatch.setenv("GRPC_PLATFORM_TYPE", platform)
    config_mod.set_config(None)
    wd = watchdog.get()
    saved = (wd.enabled, wd.min_stall_s, wd.sweep_s)
    wd.enabled, wd.min_stall_s, wd.sweep_s = True, 0.25, 0.05
    wd.reset()  # no history of the method: the floor is the bar
    trips = metrics_mod.counter("watchdog_trips")
    srv, port = _sleepy_server(0.0)
    try:
        assert (srv._native_dp is not None) == native

        def paced(n, gap_s, hold=None):
            for i in range(n):
                if hold is not None and i == n // 2:
                    hold.wait(timeout=10)
                time.sleep(gap_s)
                yield b"m" * 100

        t0, before = time.monotonic(), trips.snapshot()
        assert _stream(port, paced(30, 0.03)) == 3000
        assert time.monotonic() - t0 > 3 * wd.min_stall_s
        assert trips.snapshot() == before, wd.snapshot()
        hold = threading.Event()
        threading.Timer(4 * wd.min_stall_s, hold.set).start()
        assert _stream(port, paced(6, 0.0, hold)) == 600
        assert trips.snapshot() > before
    finally:
        srv.stop(grace=1)
        wd.enabled, wd.min_stall_s, wd.sweep_s = saved
        wd.reset()
