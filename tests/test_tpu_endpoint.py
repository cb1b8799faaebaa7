"""Platform.TPU transport: TpuRingEndpoint dispatch, device-ring decode,
lease-gated credit, end-to-end tensor RPC with ledger-proven copy accounting.

The north-star path (BASELINE.json): wire bytes → frame assembly (host) →
device-ring placement → lease-backed jax.Array, with host-memcpy = 0 after
assembly. Reference analogs: creation path ``rdma_bp_posix.cc:706-796``,
receive drain ``ring_buffer.cc:122-191``.
"""

import threading

import numpy as np
import pytest

from tpurpc.jaxshim import TensorClient, add_tensor_method, codec
from tpurpc.rpc.channel import Channel
from tpurpc.rpc.server import Server
from tpurpc.tpu import HbmRing, ledger
from tpurpc.tpu.endpoint import (DeviceMessage, TpuRingEndpoint,
                                 decode_tensor_to_ring, decode_tree_to_ring)

from tests.test_tpu import _moved, _path_counters as _paths


def _tpu_server(monkeypatch, fn, kind="unary_unary", device=True,
                platform="TPU"):
    monkeypatch.setenv("GRPC_PLATFORM_TYPE", platform)
    # Re-arm the config singleton AFTER the env change: a straggler thread
    # from the previous test (server teardown, bootstrap) can rebuild the
    # singleton in the window between the autouse fixture's reset and this
    # setenv, silently pinning the whole test to the default TCP platform.
    from tpurpc.utils import config as config_mod

    config_mod.set_config(None)
    srv = Server(max_workers=4)
    add_tensor_method(srv, "Call", fn, kind=kind, device=device)
    srv.start()
    port = srv.add_insecure_port("127.0.0.1:0")
    return srv, port


@pytest.fixture(params=["alias", "direct"])
def landing(request, monkeypatch):
    """Both landings of the decode path: ``alias`` is a CPU ring by default
    (bytes into the ring, dlpack views of them); ``direct`` is a ring whose
    views cannot alias it, as on every TPU, reached here by
    ``TPURPC_DLPACK_VIEW=0`` (each ring reads it once, as it is made)."""
    if request.param == "direct":
        monkeypatch.setenv("TPURPC_DLPACK_VIEW", "0")
    return request.param


# -- decode-to-ring units -----------------------------------------------------

def test_decode_tensor_to_ring_zero_host_copy():
    """The DeserializeToDevice step itself moves no bytes host-side."""
    x = np.arange(2048, dtype=np.float32)
    wire = bytearray(codec.encode_tensor_bytes(x))
    ring = HbmRing(1 << 16)
    with ledger.track() as w:
        lease, end = decode_tensor_to_ring(ring, wire)
    assert w["host_copy"] == 0
    assert w["dma_h2d"] == x.nbytes
    assert w["dma_d2d"] >= x.nbytes  # in-ring landing + view materialization
    assert end == len(wire)
    with lease as arr:
        assert arr.shape == (2048,)
        np.testing.assert_array_equal(np.asarray(arr), x)


def test_decode_tree_to_ring_roundtrip_and_release():
    tree = {"w": np.ones((16, 16), np.float32),
            "b": np.arange(16, dtype=np.int32)}
    wire = codec.encode_tree_bytes(tree)
    ring = HbmRing(1 << 16)
    out, leases = decode_tree_to_ring(ring, wire)
    np.testing.assert_array_equal(np.asarray(out["w"]), tree["w"])
    np.testing.assert_array_equal(np.asarray(out["b"]), tree["b"])
    assert ring.stats()["live_spans"] == 2
    for lease in leases:
        lease.release()
    st = ring.stats()
    assert st["live_spans"] == 0 and st["writable"] == st["capacity"]


def _trees():
    import ml_dtypes

    rng = np.random.default_rng(5)
    return {
        "one_leaf": {"x": rng.standard_normal((32, 48)).astype(np.float32)},
        "forty_leaves": {f"l{i:02d}": rng.integers(
            0, 255, size=(i + 1, 3), dtype=np.uint8) for i in range(40)},
        "empty_leaf": {"a": np.zeros((0, 4), np.float32),
                       "b": np.arange(6, dtype=np.int32)},
        "zero_d_leaf": {"s": np.float32(2.5), "v": np.ones(3, np.float16)},
        "bfloat16": {"h": rng.standard_normal((16, 8)).astype(
            ml_dtypes.bfloat16)},
    }


@pytest.mark.parametrize("case", list(_trees()))
def test_decode_tree_lands_directly_where_no_view_can_alias(monkeypatch,
                                                            case):
    """The TPU's landing, on the CPU: one transfer per message, each leaf
    its final array (values, dtype, shape as the host decode gives them),
    nothing moved on the device, nothing copied on the host."""
    import jax

    monkeypatch.setenv("TPURPC_DLPACK_VIEW", "0")
    tree = _trees()[case]
    wire = bytearray(codec.encode_tree_bytes(tree))
    want = codec.decode_tree(bytes(wire))
    payload = sum(v.nbytes for v in want.values())
    ring = HbmRing(1 << 16)
    before = _paths()
    with ledger.track() as w:
        out, leases = decode_tree_to_ring(ring, wire)
    wire[:] = bytes(len(wire))  # the wire buffer is reused; the arrays stay
    assert len(leases) == len(tree)
    for key, ref in want.items():
        leaf = out[key]
        assert isinstance(leaf, jax.Array) and leaf.devices() == {ring.device}
        assert leaf.dtype == ref.dtype and leaf.shape == ref.shape, key
        np.testing.assert_array_equal(np.asarray(leaf), ref)
    assert w["dma_h2d"] == payload and w["dma_h2d_ops"] == 1
    assert w["dma_d2d"] == w["host_copy"] == w["zero_copy"] == 0
    assert _moved(before) == {
        "hbm_place_direct": len(tree), "hbm_view_direct": len(tree),
        "hbm_place_msgs": len(tree), "hbm_place_bytes": payload}
    assert ring.stats()["tail"] == payload
    for lease in leases:
        assert not lease.aliased
        lease.release()
    st = ring.stats()
    assert st["live_spans"] == 0 and st["head"] == st["tail"]


def test_decode_tensor_lands_directly_where_no_view_can_alias(monkeypatch):
    monkeypatch.setenv("TPURPC_DLPACK_VIEW", "0")
    x = np.arange(2048, dtype=np.float32).reshape(2, 1024)
    wire = bytearray(codec.encode_tensor_bytes(x))
    ring = HbmRing(1 << 16)
    before = _paths()
    with ledger.track() as w:
        lease, end = decode_tensor_to_ring(ring, wire)
    assert end == len(wire)
    assert (w["dma_h2d"], w["dma_d2d"], w["host_copy"]) == (x.nbytes, 0, 0)
    assert _moved(before) == {
        "hbm_place_direct": 1, "hbm_view_direct": 1, "hbm_place_msgs": 1,
        "hbm_place_bytes": x.nbytes}
    with lease as arr:
        assert arr.shape == x.shape and arr.dtype == x.dtype
        assert arr.devices() == {ring.device}
        np.testing.assert_array_equal(np.asarray(arr), x)
    assert ring.writable() == ring.capacity


def test_ring_credit_blocks_until_lease_release(landing):
    """An unreleased lease back-pressures placement (flow control), and a
    release from another thread unblocks a waiting place()."""
    x = np.zeros(3000, np.uint8)
    wire = bytearray(codec.encode_tensor_bytes(x))
    ring = HbmRing(1 << 12)  # 4 KiB: one message in flight
    lease, _ = decode_tensor_to_ring(ring, wire)
    with pytest.raises(BufferError):
        decode_tensor_to_ring(ring, wire, timeout=0.05)
    t = threading.Timer(0.1, lease.release)
    t.start()
    lease2, _ = decode_tensor_to_ring(ring, wire, timeout=5)  # blocks, then ok
    lease2.release()
    t.join()


def test_oversized_payload_rejected():
    ring = HbmRing(1 << 12)
    wire = bytearray(codec.encode_tensor_bytes(np.zeros(8192, np.uint8)))
    with pytest.raises(BufferError):
        decode_tensor_to_ring(ring, wire, timeout=0.05)


def test_empty_tensors_no_span_collision():
    """Consecutive zero-size leaves must not collide on the (off, 0) span key
    (reviewer finding: shared _live entry corrupted lease counts)."""
    tree = {"a": np.zeros((0,), np.float32), "b": np.zeros((0,), np.float64),
            "c": np.arange(4, dtype=np.int32)}
    ring = HbmRing(1 << 12)
    out, leases = decode_tree_to_ring(ring, codec.encode_tree_bytes(tree))
    assert out["a"].shape == (0,) and out["b"].shape == (0,)
    np.testing.assert_array_equal(np.asarray(out["c"]), tree["c"])
    for lease in leases:
        lease.release()  # must not KeyError
    st = ring.stats()
    assert st["live_spans"] == 0 and st["writable"] == st["capacity"]


def test_corrupt_trailer_releases_leases(landing):
    """A poison trailer must return every taken lease (reviewer finding:
    leaked credit = one-peer DoS on the connection's ring)."""
    tree = {"x": np.ones(64, np.float32)}
    wire = bytearray(codec.encode_tree_bytes(tree))
    wire[-3:] = b"\xff\xff\xff"  # corrupt the JSON treedef trailer
    ring = HbmRing(1 << 12)
    with pytest.raises(Exception):
        decode_tree_to_ring(ring, wire)
    st = ring.stats()
    assert st["live_spans"] == 0 and st["writable"] == st["capacity"]


def test_misfit_header_returns_every_byte_of_credit(landing):
    """A header whose dtype does not fit ``nbytes`` (the sender writes it):
    the decode raises, alone or as the middle leaf of a tree, and the ring
    has all of its credit back."""
    ring = HbmRing(1 << 12)
    rec = bytearray(codec.encode_tensor_bytes(np.arange(10, dtype=np.uint8)))
    rec[4] = codec._DTYPE_TO_CODE[np.dtype(np.float32)]  # 10 B of float32
    with pytest.raises(Exception):
        decode_tensor_to_ring(ring, rec)
    tree = {"a": np.ones(8, np.float32), "b": np.arange(10, dtype=np.uint8),
            "c": np.ones(8, np.float32)}
    wire = bytearray(codec.encode_tree_bytes(tree))
    at = wire.index(bytes(rec[:4]), wire.index(bytes(rec[:4])) + 1)
    assert wire[at + 4] == codec._DTYPE_TO_CODE[np.dtype(np.uint8)]
    wire[at + 4] = rec[4]
    with pytest.raises(Exception):
        decode_tree_to_ring(ring, wire)
    st = ring.stats()
    assert st["live_spans"] == 0 and st["writable"] == st["capacity"], st


def test_tree_larger_than_ring_fails_fast():
    """A tree that can never fit must raise immediately, not stall a worker
    the full place timeout waiting on its own leases (reviewer finding)."""
    import time

    tree = {"a": np.zeros(3000, np.uint8), "b": np.zeros(3000, np.uint8)}
    ring = HbmRing(1 << 12)  # 4 KiB < 6 KB total
    t0 = time.monotonic()
    with pytest.raises(BufferError, match="capacity"):
        decode_tree_to_ring(ring, codec.encode_tree_bytes(tree))
    assert time.monotonic() - t0 < 1.0
    assert ring.stats()["live_spans"] == 0


# -- endpoint dispatch --------------------------------------------------------

@pytest.mark.parametrize("spelling", ["TPU", "RDMA_TPU"])
def test_factory_dispatches_tpu_endpoint(monkeypatch, spelling):
    """GRPC_PLATFORM_TYPE=TPU|RDMA_TPU yields TpuRingEndpoint on both sides
    (the import that was a ModuleNotFoundError in round 1)."""
    monkeypatch.setenv("GRPC_PLATFORM_TYPE", spelling)
    from tpurpc.utils import config as config_mod

    config_mod.set_config(None)  # see _tpu_server: straggler-thread rebuild
    from tpurpc.core.endpoint import EndpointListener, connect_endpoint

    got = []
    ev = threading.Event()

    def on_ep(ep):
        got.append(ep)
        ev.set()

    lst = EndpointListener("127.0.0.1", 0, on_ep)
    try:
        cli = connect_endpoint("127.0.0.1", lst.port)
        assert ev.wait(10)
        assert isinstance(cli, TpuRingEndpoint)
        assert isinstance(got[0], TpuRingEndpoint)
        cli.write(b"ping")
        assert got[0].read(16, timeout=5) == b"ping"
        cli.close()
        got[0].close()
    finally:
        lst.close()


# -- end-to-end tensor RPC on the TPU platform --------------------------------

def test_e2e_device_tensor_rpc(monkeypatch, landing):
    """GRPC_PLATFORM_TYPE=TPU end to end: handler receives ring-backed device
    arrays, decode adds no host copies beyond frame assembly."""
    import jax

    seen = {}
    before = _paths()

    def fn(tree):
        seen["type"] = type(tree["x"])
        return {"y": tree["x"] * 2}

    srv, port = _tpu_server(monkeypatch, fn)
    try:
        x = np.arange(1024, dtype=np.float32).reshape(32, 32)
        with Channel(f"127.0.0.1:{port}") as ch:
            out = TensorClient(ch).call("Call", {"x": x}, timeout=30)
        np.testing.assert_array_equal(np.asarray(out["y"]), x * 2)
        assert issubclass(seen["type"], jax.Array)
        took = {"alias": {"hbm_place_update": 1, "hbm_view_alias": 1},
                "direct": {"hbm_place_direct": 1, "hbm_view_direct": 1}}
        assert _moved(before) == {**took[landing], "hbm_place_msgs": 1,
                                  "hbm_place_bytes": x.nbytes}
    finally:
        srv.stop(grace=0)


def test_e2e_rpc_ledger_shows_zero_copy_views(monkeypatch):
    """VERDICT r4 next #3 done-criterion: an end-to-end RPC on the emulated
    TPU platform whose ledger shows zero_copy > 0 and NO view-side d2d for
    eligible (aligned, unwrapped) leaves — the only d2d ops in the window
    are the per-leaf landing writes, so every request view was an alias."""
    import jax

    seen = {}

    def fn(tree):
        seen["arrays"] = [tree["a"], tree["b"]]
        return {"y": tree["a"] + 1}

    srv, port = _tpu_server(monkeypatch, fn)
    try:
        # 4 KiB float32 leaves: span offsets 0 and 4096 on a fresh ring —
        # aligned, unwrapped, dlpack-eligible
        a = np.arange(1024, dtype=np.float32)
        b = np.ones(1024, np.float32)
        with Channel(f"127.0.0.1:{port}") as ch:
            cli = TensorClient(ch)
            with ledger.track() as w:
                out = cli.call("Call", {"a": a, "b": b}, timeout=30)
        np.testing.assert_array_equal(np.asarray(out["y"]), a + 1)
        assert issubclass(type(seen["arrays"][0]), jax.Array)
        # both request leaves were viewed as ALIASES (zero_copy, no
        # materialization) and the whole tree landed as ONE batched
        # placement (place_many: one h2d + one donated update per tree,
        # not per leaf): view-side d2d == 0, so exactly one d2d op total
        assert w["zero_copy"] >= a.nbytes + b.nbytes, w.delta
        assert w["dma_d2d_ops"] == 1, w.delta  # the batch landing write ONLY
        assert w["dma_h2d_ops"] == 1, w.delta  # one packed h2d per tree
    finally:
        srv.stop(grace=0)


def test_e2e_concurrent_passthrough_echo_no_alias_corruption(monkeypatch):
    """Round-5 serialize-then-release ordering: a device handler returning
    an ALIASED request leaf verbatim must serialize it before the lease
    releases — otherwise a concurrent RPC's in-place placement could
    overwrite the span mid-serialization and corrupt the reply silently
    (reviewer finding, round 5). Hammer two concurrent echo streams with
    distinct payloads and verify every reply byte-exactly."""
    def fn(tree):
        return {"y": tree["x"]}  # passthrough: the alias itself

    srv, port = _tpu_server(monkeypatch, fn)
    errors = []
    try:
        # ONE channel: both workers' RPCs multiplex one connection and so
        # share one receive ring — the only topology where a concurrent
        # placement can reuse a just-released span under a late serializer
        with Channel(f"127.0.0.1:{port}") as ch:
            cli = TensorClient(ch)

            def worker(seed):
                try:
                    rng = np.random.default_rng(seed)
                    for _ in range(30):
                        x = rng.standard_normal(1024).astype(np.float32)
                        out = cli.call("Call", {"x": x}, timeout=30)
                        np.testing.assert_array_equal(np.asarray(out["y"]), x)
                except Exception as exc:
                    errors.append(exc)

            ts = [threading.Thread(target=worker, args=(s,)) for s in (1, 2)]
            [t.start() for t in ts]
            [t.join(timeout=120) for t in ts]
            assert not errors, errors
    finally:
        srv.stop(grace=0)


def test_e2e_client_device_response(monkeypatch):
    """call_device: the RESPONSE lands in the client connection's device ring
    and comes back as a lease-holding DeviceMessage."""
    def fn(tree):
        return {"y": np.asarray(tree["x"]) + 1}

    srv, port = _tpu_server(monkeypatch, fn)
    try:
        x = np.arange(256, dtype=np.float32)
        with Channel(f"127.0.0.1:{port}") as ch:
            cli = TensorClient(ch)
            msg = cli.call_device("Call", {"x": x}, timeout=30)
            assert isinstance(msg, DeviceMessage)
            ring = ch.device_ring()
            assert ring is not None and ring.stats()["live_spans"] == 1
            with msg as tree:
                np.testing.assert_array_equal(np.asarray(tree["y"]), x + 1)
            assert ring.stats()["live_spans"] == 0  # credit returned
    finally:
        srv.stop(grace=0)


def test_e2e_streaming_rolling_credit(monkeypatch, landing):
    """A device-mode stream longer than the ring holds only one message's
    leases at a time (rolling release as the handler advances)."""
    monkeypatch.setenv("TPURPC_HBM_RING_SIZE_KB", "64")  # 64 KiB device ring
    before = _paths()

    def consume(trees):
        total = 0
        for t in trees:
            total += int(np.asarray(t["x"]).sum())
        yield {"total": np.int64(total)}

    srv, port = _tpu_server(monkeypatch, consume, kind="stream_stream")
    try:
        x = np.ones(4096, np.float32)  # 16 KiB per message, 8 messages
        with Channel(f"127.0.0.1:{port}") as ch:
            replies = list(TensorClient(ch).duplex(
                "Call", iter([{"x": x}] * 8), timeout=60))
        assert int(np.asarray(replies[0]["total"]).ravel()[0]) == 8 * 4096
        assert _moved(before)["hbm_view_" + landing] == 8
    finally:
        srv.stop(grace=0)


def test_device_method_falls_back_off_platform(monkeypatch):
    """device=True on a TCP transport degrades to the host decode — and
    says so: the handler sees numpy, the degrade counter moves."""
    from tpurpc.obs import metrics

    seen = []

    def fn(tree):
        seen.append(type(tree["x"]))
        return {"y": np.asarray(tree["x"]) * 3}

    degraded = metrics.counter("tensor_device_degraded")
    before = degraded.snapshot()
    srv, port = _tpu_server(monkeypatch, fn, platform="TCP")
    try:
        x = np.arange(64, dtype=np.float32)
        with Channel(f"127.0.0.1:{port}") as ch:
            out = TensorClient(ch).call("Call", {"x": x}, timeout=30)
            np.testing.assert_array_equal(np.asarray(out["y"]), x * 3)
            assert ch.device_ring() is None
        assert seen == [np.ndarray]
        assert degraded.snapshot() == before + 1
    finally:
        srv.stop(grace=0)


def test_e2e_wrapped_spans_take_pallas_consume(monkeypatch):
    """A long device-mode stream through a SMALL ring forces spans across
    the wrap point; every wrapped view must go through the fused Pallas
    consume kernel (counted) and every payload must decode exactly —
    the kernel exercised by the full transport→ring→lease path."""
    monkeypatch.setenv("TPURPC_HBM_RING_SIZE_KB", "32")  # tiny: wrap often

    import tpurpc.ops as ops_pkg
    from tpurpc.ops.ring_window import ring_window as real_ring_window

    calls = {"n": 0}

    def counting(*a, **kw):
        calls["n"] += 1
        return real_ring_window(*a, **kw)

    monkeypatch.setattr(ops_pkg, "ring_window", counting)

    rng = np.random.default_rng(11)
    payloads = [rng.standard_normal(1500).astype(np.float32)
                for _ in range(12)]  # 6 KiB each through a 32 KiB ring

    def consume(trees):
        acc = 0.0
        for t in trees:
            acc += float(np.asarray(t["x"]).sum())
        yield {"total": np.float64(acc)}

    srv, port = _tpu_server(monkeypatch, consume, kind="stream_stream")
    try:
        with Channel(f"127.0.0.1:{port}") as ch:
            replies = list(TensorClient(ch).duplex(
                "Call", iter([{"x": p} for p in payloads]), timeout=60))
        want = sum(float(p.sum()) for p in payloads)
        got = float(np.asarray(replies[0]["total"]).ravel()[0])
        assert abs(got - want) < 1e-3 * max(1.0, abs(want))
        assert calls["n"] >= 1, "stream never crossed the wrap point"
    finally:
        srv.stop(grace=0)
