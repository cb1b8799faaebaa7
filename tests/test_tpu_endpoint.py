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

from tests.test_tpu import _landed, _moved


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


# -- decode-to-ring units -----------------------------------------------------

def test_decode_tensor_to_ring_zero_host_copy():
    """The DeserializeToDevice step itself moves no bytes host-side."""
    x = np.arange(2048, dtype=np.float32)
    wire = bytearray(codec.encode_tensor_bytes(x))
    ring = HbmRing(1 << 16)
    with ledger.track() as w:
        lease, end = decode_tensor_to_ring(ring, wire)
    assert w["host_copy"] == 0
    assert w["dma_h2d"] == x.nbytes and w["dma_h2d_ops"] == 1
    assert w["dma_d2d"] == w["zero_copy"] == 0  # the one transfer, no more
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
def test_decode_tree_lands_directly_where_no_view_can_alias(case):
    """The landing: one transfer per message, each leaf its final array
    (values, dtype, shape as the host decode gives them), nothing moved on
    the device, nothing copied on the host."""
    import jax

    tree = _trees()[case]
    wire = bytearray(codec.encode_tree_bytes(tree))
    want = codec.decode_tree(bytes(wire))
    payload = sum(v.nbytes for v in want.values())
    ring = HbmRing(1 << 16)
    before = _landed()
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
    assert _moved(before) == {"hbm_place_msgs": len(tree),
                              "hbm_place_bytes": payload}
    assert ring.stats()["tail"] == payload
    for lease in leases:
        lease.release()
    st = ring.stats()
    assert st["live_spans"] == 0 and st["head"] == st["tail"]


def test_decode_tensor_lands_directly_where_no_view_can_alias():
    x = np.arange(2048, dtype=np.float32).reshape(2, 1024)
    wire = bytearray(codec.encode_tensor_bytes(x))
    ring = HbmRing(1 << 16)
    before = _landed()
    with ledger.track() as w:
        lease, end = decode_tensor_to_ring(ring, wire)
    assert end == len(wire)
    assert (w["dma_h2d"], w["dma_d2d"], w["host_copy"]) == (x.nbytes, 0, 0)
    assert _moved(before) == {"hbm_place_msgs": 1,
                              "hbm_place_bytes": x.nbytes}
    with lease as arr:
        assert arr.shape == x.shape and arr.dtype == x.dtype
        assert arr.devices() == {ring.device}
        np.testing.assert_array_equal(np.asarray(arr), x)
    assert ring.writable() == ring.capacity


def test_ring_credit_blocks_until_lease_release():
    """An unreleased lease back-pressures the landing (flow control), and a
    release from another thread unblocks a waiting one."""
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
    """Consecutive zero-size leaves share an offset and must not collide on
    it: they hold no span and free nobody's."""
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


def test_corrupt_trailer_releases_leases():
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


def test_misfit_header_returns_every_byte_of_credit():
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

def test_e2e_device_tensor_rpc(monkeypatch):
    """GRPC_PLATFORM_TYPE=TPU end to end: handler receives ring-backed device
    arrays, decode adds no host copies beyond frame assembly."""
    import jax

    seen = {}
    before = _landed()

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
        assert _moved(before) == {"hbm_place_msgs": 1,
                                  "hbm_place_bytes": x.nbytes}
    finally:
        srv.stop(grace=0)


def test_e2e_concurrent_passthrough_echo_no_alias_corruption(monkeypatch):
    """A device handler returning a request leaf verbatim: the reply is
    the request's own bytes whatever lands on the connection meanwhile (a
    landed array is a snapshot, and the reply is serialized inside the lease
    window). Hammer two concurrent echo streams with distinct payloads and
    verify every reply byte-exactly."""
    def fn(tree):
        return {"y": tree["x"]}  # passthrough: the landed array itself

    srv, port = _tpu_server(monkeypatch, fn)
    errors = []
    try:
        # ONE channel: both workers' RPCs multiplex one connection and so
        # share one receive ring and its credit
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


def test_e2e_streaming_rolling_credit(monkeypatch):
    """A device-mode stream longer than the ring holds only one message's
    leases at a time (rolling release as the handler advances)."""
    monkeypatch.setenv("TPURPC_HBM_RING_SIZE_KB", "64")  # 64 KiB device ring
    before = _landed()

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
        assert _moved(before) == {"hbm_place_msgs": 8,
                                  "hbm_place_bytes": 8 * x.nbytes}
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


def _record_rings(monkeypatch):
    """Every device ring a connection makes from here on, in order."""
    import tpurpc.tpu.endpoint as endpoint_mod

    made = []

    def make(capacity):
        made.append(HbmRing(capacity))
        return made[-1]

    monkeypatch.setattr(endpoint_mod, "HbmRing", make)
    return made


@pytest.mark.parametrize("connections", [1, 8])
def test_e2e_mixed_sizes_lap_the_credit_window(monkeypatch, connections):
    """chip_smoke.py's tensor leg at its rehearsal sizes, over RPC: four
    sizes that do not divide a 64 KiB window, enough messages to lap it four
    times on each connection, a reply per message. Every message lands by
    its one transfer, nothing moves on the device, and every window ends
    empty."""
    import jax

    import chip_smoke

    cfg = chip_smoke.REHEARSAL
    monkeypatch.setenv("TPURPC_HBM_RING_SIZE_KB", str(cfg["ring_kb"]))
    monkeypatch.setenv("TPURPC_RENDEZVOUS_MIN_KB", str(cfg["rdv_min_kb"]))
    capacity = cfg["ring_kb"] << 10
    shapes, wrapped = chip_smoke.plan_pass(cfg, capacity,
                                           cfg["rdv_min_kb"] << 10)
    sizes = {4 * int(np.prod(s)) for s in shapes}
    assert wrapped >= 2 and any(capacity % n for n in sizes)
    made = _record_rings(monkeypatch)

    def sums(trees):
        for tree in trees:
            x = tree["x"]
            assert isinstance(x, jax.Array)
            yield {"check": np.uint32(chip_smoke.checksum_np(np.asarray(x))),
                   "bytes": np.int64(x.nbytes)}

    srv, port = _tpu_server(monkeypatch, sums, kind="stream_stream")
    errors = []

    def client(seed):
        try:
            rng = np.random.default_rng(seed)
            msgs = [rng.standard_normal(s, dtype=np.float32) for s in shapes]
            with Channel(f"127.0.0.1:{port}") as ch:
                replies = list(TensorClient(ch).duplex(
                    "Call", ({"x": m} for m in msgs), timeout=120))
            assert len(replies) == len(msgs)
            for m, r in zip(msgs, replies):
                assert int(np.asarray(r["bytes"]).ravel()[0]) == m.nbytes
                assert (int(np.asarray(r["check"]).ravel()[0])
                        == chip_smoke.checksum_np(m))
        except BaseException as exc:
            errors.append(exc)

    before = _landed()
    try:
        with ledger.track() as w:
            ts = [threading.Thread(target=client, args=(40 + i,))
                  for i in range(connections)]
            [t.start() for t in ts]
            [t.join(timeout=180) for t in ts]
            assert not any(t.is_alive() for t in ts)
        assert not errors, errors
    finally:
        srv.stop(grace=0)
    messages, payload = connections * len(shapes), connections * 4 * capacity
    assert _moved(before) == {"hbm_place_msgs": messages,
                              "hbm_place_bytes": payload}
    assert w["dma_h2d"] == payload and w["dma_h2d_ops"] == messages
    assert w["dma_d2d"] == 0
    assert len(made) == connections
    for ring in made:
        st = ring.stats()
        assert st["head"] == st["tail"] == 4 * capacity and not st["live_spans"]


def test_e2e_held_leases_time_out_the_landing_that_no_longer_fits(
        monkeypatch):
    """Two calls on one connection hold 48 of its window's 64 KiB for as
    long as their handler runs: the third call's landing waits its timeout,
    raises ``BufferError`` in the server, and the call ends UNKNOWN. Once
    the handler lets go the window is whole again."""
    import functools

    import tpurpc.tpu.endpoint as endpoint_mod
    from tpurpc.rpc.status import RpcError, StatusCode

    monkeypatch.setenv("TPURPC_HBM_RING_SIZE_KB", "64")
    made = _record_rings(monkeypatch)
    monkeypatch.setattr(
        endpoint_mod, "decode_tree_to_ring",
        functools.partial(endpoint_mod.decode_tree_to_ring, timeout=0.3))
    entered, let_go = threading.Semaphore(0), threading.Event()

    def hold(tree):
        entered.release()
        assert let_go.wait(60)
        return {"n": np.int64(tree["x"].nbytes)}

    srv, port = _tpu_server(monkeypatch, hold)
    x = np.ones(6144, np.float32)  # 24 KiB: two fit, a third does not
    got = []
    try:
        with Channel(f"127.0.0.1:{port}") as ch:
            cli = TensorClient(ch)

            def call():
                got.append(cli.call("Call", {"x": x}, timeout=60))

            ts = [threading.Thread(target=call) for _ in range(2)]
            [t.start() for t in ts]
            assert entered.acquire(timeout=30) and entered.acquire(timeout=30)
            (ring,) = made
            assert ring.stats()["live_spans"] == 2
            with pytest.raises(RpcError) as err:
                cli.call("Call", {"x": x}, timeout=60)
            assert err.value.code() == StatusCode.UNKNOWN
            assert "HBM ring full" in err.value.details()
            assert ring.stats()["tail"] == 2 * x.nbytes  # nothing was claimed
            let_go.set()
            [t.join(timeout=60) for t in ts]
            assert not any(t.is_alive() for t in ts)
        assert [int(np.asarray(r["n"]).ravel()[0]) for r in got] == [
            x.nbytes] * 2
        st = ring.stats()
        assert st["head"] == st["tail"] == 2 * x.nbytes
    finally:
        let_go.set()
        srv.stop(grace=0)
