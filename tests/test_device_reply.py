"""The outbound leg of a ``device=True`` tensor method (ISSUE 28): a reply's
device leaves leave through ONE function, ``tpu/serialize.tree_from_device``.

Every expectation is computed with plain numpy from seeded data. The CPU has
no device whose memory the host cannot address, so the ``device`` cases make
``serialize._on_device`` say so of every ``jax.Array`` (the transfer calls are
the real ones: ``copy_to_host_async`` and ``np.asarray`` work on any backend),
and the order-of-transfers test uses leaves that record what is asked of
them. The request path bills ``dma_h2d`` alone, so ``zero_copy`` and
``dma_d2h`` in a window are the serializer's.
"""

import queue

import numpy as np
import pytest

from tpurpc.jaxshim import TensorClient, add_tensor_method, codec
from tpurpc.obs import metrics
from tpurpc.rpc.channel import Channel
from tpurpc.rpc.server import Server
from tpurpc.tpu import ledger, serialize

KINDS = ("unary_unary", "unary_stream", "stream_stream")
MESSAGES = 5          # per call, where the kind streams
FAN_OUT = 3           # replies to one request (unary_stream)


def _d2h():
    snap = metrics.registry().counters_snapshot()
    return {k: snap.get(f"lens_d2h_{k}", 0) for k in ("ops", "bytes")}


def _server(monkeypatch, fn, kind, backend="device"):
    monkeypatch.setenv("GRPC_PLATFORM_TYPE", "RDMA_TPU")
    from tpurpc.utils import config as config_mod

    config_mod.set_config(None)
    if backend == "device":
        import jax

        monkeypatch.setattr(serialize, "_on_device",
                            lambda x: isinstance(x, jax.Array))
    srv = Server(max_workers=4)
    add_tensor_method(srv, "Call", fn, kind=kind, device=True)
    srv.start()
    return srv, srv.add_insecure_port("127.0.0.1:0")


def _server_ring(srv):
    from tpurpc.core.endpoint import device_ring_of

    (conn,) = srv._connections
    return device_ring_of(conn.endpoint)


def _pingpong(cli, messages):
    """Strict ping-pong on one duplex stream: message ``k + 1`` goes out
    when reply ``k`` is in. Returns copies of the replies."""
    todo = queue.Queue()
    pending = iter(messages)

    def requests():
        while True:
            tree = todo.get()
            if tree is None:
                return
            yield tree

    todo.put(next(pending))
    out = []
    for reply in cli.duplex("Call", requests(), timeout=60):
        out.append(np.array(reply["y"]))
        del reply
        todo.put(next(pending, None))
    return out


def _answer(x):
    import jax.numpy as jnp

    return jnp.asarray(x) * 2 + 1   # exact in float32: one rounding, as numpy


def _method(kind):
    """``(fn, replies expected of one request x)``, the reply a device
    array computed from the request's device array."""
    if kind == "unary_unary":
        return (lambda tree: {"y": _answer(tree["x"])},
                lambda x: [x * 2 + 1])
    if kind == "unary_stream":
        def fan(tree):
            for k in range(FAN_OUT):
                yield {"y": _answer(tree["x"]) + k}
        return fan, lambda x: [x * 2 + 1 + k for k in range(FAN_OUT)]

    def each(trees):
        for tree in trees:
            yield {"y": _answer(tree["x"])}
    return each, lambda x: [x * 2 + 1]


@pytest.mark.parametrize("backend", ["device", "host"])
@pytest.mark.parametrize("kind", KINDS)
def test_device_array_replies_bit_exact_and_billed_once(monkeypatch, kind,
                                                        backend):
    """Every kind of ``device=True`` method answering with device arrays:
    each reply bit-exact against numpy; the ledger exact: ``dma_d2h`` = the
    reply bytes on a device backend, ``zero_copy`` = the reply bytes on a
    host backend, never both; one ``d2h`` op a reply, on a device backend
    alone."""
    fn, expected = _method(kind)
    rng = np.random.default_rng([28, KINDS.index(kind)])
    xs = [rng.standard_normal((64, 48)).astype(np.float32)
          for _ in range(MESSAGES)]
    want = [r.astype(np.float32) for x in xs for r in expected(x)]
    srv, port = _server(monkeypatch, fn, kind, backend)
    try:
        with Channel(f"127.0.0.1:{port}") as ch:
            cli = TensorClient(ch)
            before = _d2h()
            with ledger.track() as w:
                if kind == "unary_unary":
                    got = [np.array(cli.call("Call", {"x": x},
                                             timeout=30)["y"]) for x in xs]
                elif kind == "unary_stream":
                    got = [np.array(r["y"]) for x in xs
                           for r in cli.stream("Call", {"x": x}, timeout=30)]
                else:
                    got = _pingpong(cli, ({"x": x} for x in xs))
            after = _d2h()
    finally:
        srv.stop(grace=0)
    assert len(got) == len(want)
    for g, r in zip(got, want):
        assert g.dtype == r.dtype and g.shape == r.shape
        assert g.tobytes() == r.tobytes()
    reply_bytes = sum(r.nbytes for r in want)
    if backend == "device":
        assert w["dma_d2h"] == reply_bytes, w.delta
        assert w["dma_d2h_ops"] == len(want), w.delta
        assert w["zero_copy"] == 0, w.delta
        assert after["ops"] - before["ops"] == len(want)
        assert after["bytes"] - before["bytes"] == reply_bytes
    else:
        assert w["zero_copy"] == reply_bytes, w.delta
        assert w["dma_d2h"] == 0, w.delta
        assert after == before
    assert w["dma_h2d"] == sum(x.nbytes for x in xs), w.delta


class _Leaf:
    """A leaf that lives on a device as far as the serializer can tell, and
    writes down what is asked of it."""

    def __init__(self, host, log, k):
        self._host, self._log, self._k = host, log, k
        self.nbytes, self.dtype, self.shape = (host.nbytes, host.dtype,
                                               host.shape)

    def devices(self):
        class _Dev:
            platform = "tpu"
        return {_Dev()}

    def copy_to_host_async(self):
        self._log.append(("start", self._k))

    def __array__(self, dtype=None, copy=None):
        self._log.append(("await", self._k))
        return self._host


DTYPES = ("float32", "int8", "uint16", "int32", "float16", "bool", "uint8",
          "float64")


def test_forty_leaf_tree_starts_every_transfer_before_awaiting_one():
    rng = np.random.default_rng(2840)
    log: list = []
    hosts, tree = [], {"layers": [], "step": np.int32(7)}
    for k in range(40):
        dt = np.dtype(DTYPES[k % len(DTYPES)])
        shape = (int(rng.integers(1, 9)), int(rng.integers(1, 33)))
        host = (rng.integers(0, 2, shape).astype(dt) if dt == np.bool_
                else (rng.standard_normal(shape) * 50).astype(dt))
        hosts.append(host)
        tree["layers"].append({"w": _Leaf(host, log, k)})
    before = _d2h()
    with ledger.track() as w:
        segs = serialize.tree_from_device(tree)
    after = _d2h()
    starts = [i for i, (what, _) in enumerate(log) if what == "start"]
    awaits = [i for i, (what, _) in enumerate(log) if what == "await"]
    assert len(starts) == 40 and max(starts) < min(awaits)
    assert sorted(k for what, k in log if what == "await") == list(range(40))
    assert w["dma_d2h"] == sum(h.nbytes for h in hosts), w.delta
    assert w["dma_d2h_ops"] == 40
    assert w["zero_copy"] == 4 and w["host_copy"] == 0, w.delta   # `step`
    assert after["ops"] - before["ops"] == 1          # one stage a reply
    assert after["bytes"] - before["bytes"] == w["dma_d2h"]
    # the gather list aliases the transfers' landing buffers: no join
    payloads = [s for s in segs if isinstance(s, memoryview)]
    assert len(payloads) == 41
    for host in hosts:
        assert any(np.shares_memory(np.frombuffer(s, np.uint8), host)
                   for s in payloads if s.nbytes == host.nbytes)
    back = codec.decode_tree(b"".join(bytes(s) for s in segs))
    assert int(np.asarray(back["step"]).ravel()[0]) == 7
    for host, layer in zip(hosts, back["layers"]):
        assert layer["w"].dtype == host.dtype
        assert layer["w"].tobytes() == host.tobytes()


def test_host_leaves_are_aliased_and_never_staged():
    """numpy leaves and host-backend arrays: no ``d2h`` op, no ``dma_d2h``,
    no ``host_copy``; the payload segments are the leaves' own memory."""
    import jax.numpy as jnp

    a = np.arange(4096, dtype=np.float32)
    b = jnp.arange(512, dtype=jnp.int32)
    before = _d2h()
    with ledger.track() as w:
        segs = serialize.tree_from_device({"a": a, "b": b})
    assert _d2h() == before
    assert w["dma_d2h"] == 0 and w["host_copy"] == 0, w.delta
    assert w["zero_copy"] == a.nbytes + b.nbytes
    assert any(isinstance(s, memoryview) and s.nbytes == a.nbytes
               and np.shares_memory(np.frombuffer(s, np.uint8), a)
               for s in segs)
    back = codec.decode_tree(b"".join(bytes(s) for s in segs))
    assert back["a"].tobytes() == a.tobytes()
    assert back["b"].tobytes() == np.arange(512, dtype=np.int32).tobytes()


@pytest.mark.parametrize("kind", ["unary_unary", "stream_stream"])
def test_passthrough_reply_is_read_inside_the_lease(monkeypatch, kind):
    """``return {"y": tree["x"]}`` for 1,000 round trips: every reply is
    serialized before its request's lease goes back, every reply bit-exact,
    and afterwards the server's ring holds no span and all its credit."""
    from tpurpc.tpu import hbm_ring

    rounds = 1000
    events: list = []
    real_ser, real_release = (serialize.tree_from_device,
                              hbm_ring.HbmLease.release)

    def ser(tree):
        out = real_ser(tree)
        events.append("ser")
        return out

    def release(self):
        events.append("rel")
        return real_release(self)

    monkeypatch.setattr(serialize, "tree_from_device", ser)
    monkeypatch.setattr(hbm_ring.HbmLease, "release", release)
    if kind == "unary_unary":
        def fn(tree):
            return {"y": tree["x"]}
    else:
        def fn(trees):
            for tree in trees:
                yield {"y": tree["x"]}
    rng = np.random.default_rng(281)
    bank = [rng.standard_normal(256).astype(np.float32) for _ in range(8)]

    def message(k):
        x = bank[k % len(bank)].copy()
        x[0] = k
        return x

    srv, port = _server(monkeypatch, fn, kind)
    try:
        with Channel(f"127.0.0.1:{port}") as ch:
            cli = TensorClient(ch)
            before = _d2h()
            if kind == "unary_unary":
                got = [np.array(cli.call("Call", {"x": message(k)},
                                         timeout=30)["y"])
                       for k in range(rounds)]
            else:
                got = _pingpong(cli, ({"x": message(k)}
                                      for k in range(rounds)))
            ring = _server_ring(srv)
            stats = ring.stats()
    finally:
        srv.stop(grace=0)
    assert len(got) == rounds
    for k, g in enumerate(got):
        assert g.tobytes() == message(k).tobytes()
    assert events == ["ser", "rel"] * rounds
    assert stats["live_spans"] == 0
    assert stats["writable"] == stats["capacity"]
    assert _d2h()["ops"] - before["ops"] == rounds
