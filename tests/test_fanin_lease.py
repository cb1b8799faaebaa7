"""The lease hand-over of ``add_tensor_method(device=True,
kind="stream_stream")`` (ISSUE 33), alone and end to end: a stream handler
takes a message's leases with the message and passes them to a
``FanInBatcher``; what is taken is not released by the iterator's advance or
by the end of the call, what is not taken still is; over ``RDMA_TPU`` on the
CPU eight connections stream KiB messages over the rendezvous bar into one
batcher and every row comes out of a batch bit-exact."""

import threading

import numpy as np
import pytest

from tpurpc.jaxshim import FanInBatcher, add_tensor_method, codec
from tpurpc.jaxshim.service import (DeviceRequests, TensorClient,
                                    _device_decoder)
from tpurpc.rpc.channel import Channel
from tpurpc.rpc.server import Server
from tpurpc.tpu import ledger
from tpurpc.tpu.hbm_ring import HbmRing


class Ctx:
    def __init__(self, ring):
        self.device_ring = ring


def wire(k, words=256):
    return bytearray(codec.encode_tree_bytes(
        {"x": np.full(words, k, np.float32)}))


def live(ring):
    """Spans whose credit is out (a released span behind one that is still
    out waits in the ring's table: it is not counted)."""
    with ring._lock:
        return sum(not released for _, released in ring._live.values())


@pytest.mark.parametrize("taken", [(), (0,), (1,), (0, 1), (0, 1, 2)])
def test_taken_leases_are_the_takers_and_the_rest_roll(taken):
    """Three messages of 1 KiB through one call's decoder; the handler takes
    those in ``taken``. The rolling rule and ``finish`` release the others
    and only the others; the taker's releases bring the window back whole."""
    ring = HbmRing(4096)
    decode, finish, take = _device_decoder(Ctx(ring))
    held = {}
    for k in range(3):
        tree = decode(wire(k))
        assert float(np.asarray(tree["x"])[0]) == k
        # the message before went back unless it was taken
        assert live(ring) == 1 + len(held)
        if k in taken:
            held[k] = take()
            assert len(held[k]) == 1 and take() == []  # a second take: none
    finish()
    assert live(ring) == len(held)
    finish()                                   # idempotent
    for k in sorted(held, reverse=True):      # any order: spans wait in turn
        for lease in held[k]:
            lease.release()
    st = ring.stats()
    assert st["head"] == st["tail"] == 3 * 1024 and not st["live_spans"]


def test_device_requests_is_the_iterator_it_replaces():
    ring = HbmRing(4096)
    decode, finish, take = _device_decoder(Ctx(ring))
    reqs = DeviceRequests((wire(k) for k in range(3)), decode, take)
    assert iter(reqs) is reqs
    got = [float(np.asarray(t["x"])[0]) for t in reqs]
    assert got == [0.0, 1.0, 2.0] and next(reqs, None) is None
    assert live(ring) == 1           # the last message's, until the call ends
    finish()
    assert live(ring) == 0


def test_without_a_device_ring_there_is_nothing_to_take():
    class Plain:
        device_ring = None

    decode, finish, take = _device_decoder(Plain())
    reqs = DeviceRequests([wire(5)], decode, take)
    tree = next(reqs)
    assert isinstance(tree["x"], np.ndarray) and reqs.take_leases() == []
    finish()


# -- end to end ------------------------------------------------------------------------

CONNS, EACH, SHAPE = 8, 24, (32, 32)   # 4 KiB messages, 2 KiB rendezvous bar


def message(conn, seq):
    x = np.random.default_rng([conn, seq]).standard_normal(
        SHAPE, dtype=np.float32)
    x.reshape(-1).view(np.uint32)[:2] = (seq, conn)
    return x


@pytest.fixture
def fanin_server(monkeypatch):
    import jax

    monkeypatch.setenv("GRPC_PLATFORM_TYPE", "RDMA_TPU")
    monkeypatch.setenv("TPURPC_HBM_RING_SIZE_KB", "16")
    monkeypatch.setenv("TPURPC_RENDEZVOUS_MIN_KB", "2")
    from tpurpc.utils import config as config_mod

    config_mod.set_config(None)
    log = []                       # (rows, batch as numpy), in batch order

    def consume(batch, rows):
        log.append((int(rows), np.asarray(batch["x"])))

    batcher = FanInBatcher(consume, max_batch=8, max_delay_s=0.05,
                           fixed_bucket=True, occupancy=True)
    kinds = []

    def put(trees):
        futures = []
        for tree in trees:
            x = tree["x"]
            kinds.append(isinstance(x, jax.Array))
            futures.append(batcher.submit(
                {"x": x}, leases=trees.take_leases(), one_row=True))
        for f in futures:
            f.result(60)
        yield {"n": np.int64(len(futures))}

    srv = Server(max_workers=16)
    add_tensor_method(srv, "Put", put, kind="stream_stream", device=True)
    srv.start()
    port = srv.add_insecure_port("127.0.0.1:0")
    try:
        yield port, log, kinds
    finally:
        srv.stop(grace=0)
        batcher.close()


def test_eight_connections_into_one_batcher_over_rdma_tpu(fanin_server):
    port, log, kinds = fanin_server
    errors = []

    def client(c):
        try:
            with Channel(f"127.0.0.1:{port}") as ch:
                (reply,) = TensorClient(ch).duplex(
                    "Put", ({"x": message(c, k)} for k in range(EACH)),
                    timeout=120)
            assert int(np.asarray(reply["n"]).ravel()[0]) == EACH
        except BaseException as exc:
            errors.append(exc)

    before = ledger.snapshot()
    ts = [threading.Thread(target=client, args=(c,)) for c in range(CONNS)]
    [t.start() for t in ts]
    [t.join(180) for t in ts]
    assert not any(t.is_alive() for t in ts) and not errors, errors
    moved = {k: v - before[k] for k, v in ledger.snapshot().items()}
    payload = CONNS * EACH * 4096
    # one landing a message, one gather a batch: each payload byte once each
    assert moved["dma_h2d"] == payload and moved["dma_d2d"] == payload
    assert moved["dma_h2d_ops"] == CONNS * EACH
    assert moved["dma_d2d_ops"] == len(log)
    assert all(kinds) and len(kinds) == CONNS * EACH
    # the log is a valid interleaving, and every row is its message
    nxt = [0] * CONNS
    for rows, batch in log:
        assert 1 <= rows <= 8 and batch.shape == (8,) + SHAPE
        assert not batch[rows:].any()
        for row in batch[:rows]:
            seq, conn = (int(w) for w in row.reshape(-1).view(np.uint32)[:2])
            assert seq == nxt[conn]
            nxt[conn] += 1
            assert row.tobytes() == message(conn, seq).tobytes()
    assert nxt == [EACH] * CONNS
