"""A stream handler may answer with a future (ISSUE 36): ``rpc/server.py``
``_DeferredReplies`` and the tensor shim's pass-through.

Replies leave in yield order whatever order (and on whatever threads) the
futures resolve; a client that stops sending until it is answered is
answered; a failed future fails the call once, with its error, and every
lease goes back once; cancel and deadline hold with replies pending; and a
handler that yields plain messages writes the frames it always wrote,
through the loop it always ran."""

import importlib
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from tpurpc.jaxshim import FanInBatcher, add_tensor_method
from tpurpc.jaxshim.service import TensorClient
from tpurpc.obs import metrics
from tpurpc.rpc import frame as fr
from tpurpc.rpc.channel import Channel
from tpurpc.rpc.server import Server
from tpurpc.rpc.status import AbortError, RpcError, StatusCode

# ``tpurpc.rpc.server`` the module (the package also exports a function of
# that name)
server_mod = importlib.import_module("tpurpc.rpc.server")


def counters(*names):
    snap = metrics.registry().counters_snapshot()
    return [snap.get(n, 0) for n in names]


def serve(fn, **kw):
    srv = Server(max_workers=16)
    add_tensor_method(srv, "M", fn, kind="stream_stream", **kw)
    srv.start()
    return srv, srv.add_insecure_port("127.0.0.1:0")


def msg(k, words=16):
    return {"x": np.full(words, k, np.float32)}


def pipelined(port, n, depth, timeout=30):
    """Send ``n`` requests, at most ``depth`` unanswered: a client that stops
    sending until it is answered. Returns the replies' first words."""
    window = threading.Semaphore(depth)

    def requests():
        for k in range(n):
            assert window.acquire(timeout=timeout), "never answered"
            yield msg(k)

    got = []
    with Channel(f"127.0.0.1:{port}") as ch:
        for reply in TensorClient(ch).duplex("M", requests(),
                                             timeout=timeout):
            got.append(int(np.ravel(reply["y"])[0]))
            window.release()
    return got


def resolver(order, threads, group=8):
    """A handler that answers each request with a future and resolves every
    ``group`` of them in ``order`` ("reverse", "forward", "shuffled") from
    ``threads`` threads at once; the stragglers at the stream's end."""

    def settle(batch):
        if order == "reverse":
            batch = batch[::-1]
        elif order == "shuffled":
            batch = [batch[i] for i in np.random.default_rng(
                len(batch)).permutation(len(batch))]
        shares = [batch[i::threads] for i in range(threads)]

        def work(share):
            for fut, x in share:
                time.sleep(0.0005)
                fut.set_result({"y": x + 1})

        for share in shares:
            threading.Thread(target=work, args=(share,), daemon=True).start()

    def handler(trees):
        batch = []
        for tree in trees:
            fut = Future()
            batch.append((fut, np.array(tree["x"])))
            if len(batch) == group:
                settle(batch)
                batch = []
            yield fut
        settle(batch)

    return handler


@pytest.mark.parametrize("order,threads", [
    ("reverse", 1), ("reverse", 4), ("shuffled", 3), ("forward", 2)])
def test_replies_leave_in_yield_order(order, threads):
    deferred0, overtaken0 = counters("srv_replies_deferred",
                                     "srv_replies_overtaken")
    srv, port = serve(resolver(order, threads))
    try:
        got = pipelined(port, 50, 8)
    finally:
        srv.stop(grace=1)
    assert got == list(range(1, 51))
    deferred, overtaken, waits = counters(
        "srv_replies_deferred", "srv_replies_overtaken",
        "lens_srv_reply_wait_ops")
    assert deferred - deferred0 == 50 and waits >= 50
    if order == "reverse" and threads == 1:
        # every group's last future resolves first: all but one a group
        # (six of 8 and the stragglers' 2) were resolved before an earlier
        assert overtaken - overtaken0 == 6 * 7 + 1
    elif order != "forward":
        assert overtaken > overtaken0


@pytest.mark.parametrize("depth", [1, 2, 8])
def test_a_client_that_waits_for_its_answer_is_answered(depth):
    """The batcher's own shape at every client depth: rows resolve when a
    batch of 4 is full or a 20 ms timer runs out, on the batcher's threads,
    while the handler thread is parked in ``next(requests)``."""
    batcher = FanInBatcher(lambda b: {"y": np.asarray(b["x"]) + 1},
                           max_batch=4, max_delay_s=0.02)

    def handler(trees):
        for tree in trees:
            yield batcher.submit({"x": np.array(tree["x"])[None]})

    srv, port = serve(handler)
    try:
        got = pipelined(port, 13, depth)
    finally:
        srv.stop(grace=1)
        batcher.close()
    assert got == list(range(1, 14))


def test_a_plain_response_after_a_future_keeps_its_place():
    held = []

    def handler(trees):
        for k, tree in enumerate(trees):
            x = np.array(tree["x"])
            if k % 3 == 0:
                fut = Future()
                held.append((fut, x))
                yield fut
            else:
                yield {"y": x + 1}
            if len(held) == 2:
                for fut, x in held[::-1]:
                    fut.set_result({"y": x + 1})
                held.clear()
        for fut, x in held:
            fut.set_result({"y": x + 1})

    srv, port = serve(handler)
    try:
        assert pipelined(port, 20, 8) == list(range(1, 21))
    finally:
        srv.stop(grace=1)


@pytest.mark.parametrize("error,code,text", [
    (ValueError("row 5 is poisoned"), StatusCode.UNKNOWN, "row 5"),
    (AbortError(StatusCode.RESOURCE_EXHAUSTED, "no slot"),
     StatusCode.RESOURCE_EXHAUSTED, "no slot"),
    ("cancel", StatusCode.UNKNOWN, ""),
])
def test_a_failed_future_fails_the_call_once(error, code, text):
    """Replies before the failed one arrive, nothing after it does, the
    status is the future's, and the handler's generator is unwound (once)
    although its thread was parked in ``next(requests)``."""
    unwound = []
    trailers = []
    real = server_mod._ServerConnection._send_trailers

    def spy(self, st, status, *a, **kw):
        trailers.append(status)
        return real(self, st, status, *a, **kw)

    def handler(trees):
        try:
            for k, tree in enumerate(trees):
                fut = Future()
                if k != 5:
                    fut.set_result({"y": np.array(tree["x"]) + 1})
                elif error == "cancel":
                    fut.cancel()
                else:
                    fut.set_exception(error)
                yield fut
        finally:
            unwound.append(True)

    srv, port = serve(handler)
    got = []
    window = threading.Semaphore(8)

    def requests():
        for k in range(40):
            if not window.acquire(timeout=10):
                return
            yield msg(k)

    try:
        server_mod._ServerConnection._send_trailers = spy
        with Channel(f"127.0.0.1:{port}") as ch:
            with pytest.raises(RpcError) as exc:
                for reply in TensorClient(ch).duplex("M", requests(),
                                                     timeout=20):
                    got.append(int(reply["y"][0]))
                    window.release()
        deadline = time.monotonic() + 5
        while not unwound and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        server_mod._ServerConnection._send_trailers = real
        srv.stop(grace=1)
    assert got == [1, 2, 3, 4, 5]
    assert exc.value.code() == code and text in (exc.value.details() or "")
    assert unwound == [True] and trailers == [code]


def test_a_failed_batch_returns_every_lease_once(monkeypatch):
    """``tests/test_fanin_lease.py``'s rule with a consumer that answers:
    over ``RDMA_TPU`` on the CPU a ``device=True`` stream yields its rows'
    futures; the consumer fails the third batch. The call fails once, with
    the consumer's error, and the connection's credit window comes back
    whole: taken leases by the batcher, the rest by the call's end."""
    monkeypatch.setenv("GRPC_PLATFORM_TYPE", "RDMA_TPU")
    monkeypatch.setenv("TPURPC_HBM_RING_SIZE_KB", "16")
    monkeypatch.setenv("TPURPC_RENDEZVOUS_MIN_KB", "2")
    from tpurpc.utils import config as config_mod

    config_mod.set_config(None)
    rings, batches = [], []

    def consume(batch, rows):
        batches.append(int(rows))
        if len(batches) == 3:
            raise RuntimeError("the consumer fell over")
        return {"y": batch["x"] + 1}

    batcher = FanInBatcher(consume, max_batch=4, max_delay_s=0.02,
                           fixed_bucket=True, occupancy=True)

    def handler(trees):
        for tree in trees:
            yield batcher.submit({"x": tree["x"]},
                                 leases=trees.take_leases(), one_row=True)

    srv = Server(max_workers=8)
    add_tensor_method(srv, "M", handler, kind="stream_stream", device=True)
    real = server_mod.ServerContext.device_ring.fget
    monkeypatch.setattr(
        server_mod.ServerContext, "device_ring",
        property(lambda self: rings.append(real(self)) or rings[-1]))
    srv.start()
    port = srv.add_insecure_port("127.0.0.1:0")
    got = []
    window = threading.Semaphore(4)

    def requests():
        for k in range(64):
            if not window.acquire(timeout=10):
                return
            yield {"x": np.full((32, 32), k, np.float32)}

    try:
        with Channel(f"127.0.0.1:{port}") as ch:
            with pytest.raises(RpcError) as exc:
                for reply in TensorClient(ch).duplex("M", requests(),
                                                     timeout=30):
                    assert reply["y"].shape == (32, 32)
                    got.append(int(reply["y"][0, 0]))
                    window.release()
        assert exc.value.code() == StatusCode.UNKNOWN
        assert "fell over" in exc.value.details()
        assert got == list(range(1, len(got) + 1)) and len(got) >= 4
        ring = next(r for r in rings if r is not None)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            st = ring.stats()
            if st["head"] == st["tail"] and not st["live_spans"]:
                break
            time.sleep(0.02)
        st = ring.stats()
        assert st["head"] == st["tail"] and not st["live_spans"]
    finally:
        srv.stop(grace=0)
        batcher.close()


def test_cancel_with_replies_pending():
    """The client walks away with futures open: the handler's generator is
    unwound, nothing is written for futures resolved after, and no thread
    is left holding the stream."""
    open_futures, unwound = [], threading.Event()

    def handler(trees):
        try:
            for tree in trees:
                fut = Future()
                open_futures.append((fut, np.array(tree["x"])))
                yield fut
        finally:
            unwound.set()

    srv, port = serve(handler)
    try:
        with Channel(f"127.0.0.1:{port}") as ch:
            hold = threading.Event()

            def requests():
                for k in range(3):
                    yield msg(k)
                hold.wait(10)

            call = TensorClient(ch).duplex("M", requests(), timeout=30)
            deadline = time.monotonic() + 5
            while len(open_futures) < 3 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert len(open_futures) == 3
            call.cancel()
            hold.set()
            assert unwound.wait(5)
        sends = counters("lens_srv_send_ops")[0]
        for fut, x in open_futures:
            fut.set_result({"y": x + 1})      # nobody is listening
        time.sleep(0.1)
        assert counters("lens_srv_send_ops")[0] == sends
    finally:
        srv.stop(grace=1)


def test_deadline_with_replies_pending():
    """A reply that never resolves: the call ends DEADLINE_EXCEEDED at its
    deadline, after the replies that were ready, not at the handler's
    pleasure."""
    never = []

    def handler(trees):
        for k, tree in enumerate(trees):
            fut = Future()
            if k < 2:
                fut.set_result({"y": np.array(tree["x"]) + 1})
            else:
                never.append(fut)
            yield fut

    srv, port = serve(handler)
    got = []
    t0 = time.monotonic()
    try:
        with Channel(f"127.0.0.1:{port}") as ch:
            with pytest.raises(RpcError) as exc:
                for reply in TensorClient(ch).duplex(
                        "M", (msg(k) for k in range(3)), timeout=1.0):
                    got.append(int(reply["y"][0]))
    finally:
        srv.stop(grace=1)
    assert exc.value.code() == StatusCode.DEADLINE_EXCEEDED
    assert got == [1, 2] and len(never) == 1
    assert 0.8 < time.monotonic() - t0 < 8


class _Frames:
    """What a connection's writer was asked to put on the wire for its data
    streams: ``(type, flags, payload bytes)`` in order."""

    def __init__(self, monkeypatch):
        self.sent = []
        real = fr.FrameWriter.send

        def send(writer, ftype, flags, stream_id, payload=b"", **kw):
            if ftype in (fr.MESSAGE, fr.TRAILERS) and writer._coalesce:
                segs = payload if isinstance(payload, (list, tuple)) else [
                    payload]
                body = b"".join(bytes(memoryview(s).cast("B")) for s in segs)
                if ftype == fr.TRAILERS:
                    body = body.split(b"tpurpc-load")[0]  # the load report
                self.sent.append((ftype, flags, body))
            return real(writer, ftype, flags, stream_id, payload, **kw)

        monkeypatch.setattr(fr.FrameWriter, "send", send)


def plain(trees):
    for tree in trees:
        yield {"y": np.array(tree["x"]) + 1}


def futured(trees):
    for tree in trees:
        fut = Future()
        fut.set_result({"y": np.array(tree["x"]) + 1})
        yield fut


@pytest.mark.parametrize("device", [False, True])
def test_plain_messages_take_the_plain_path_and_futures_write_the_same_frames(
        monkeypatch, device):
    """A handler that yields plain messages never meets the ordered queue
    (constructing one fails the test), and a handler that yields the same
    answers as futures puts byte-identical MESSAGE frames and the same
    trailers on the wire."""
    frames = _Frames(monkeypatch)
    made = []
    real_init = server_mod._DeferredReplies.__init__

    def init(self, *a, **kw):
        made.append(True)
        real_init(self, *a, **kw)

    monkeypatch.setattr(server_mod._DeferredReplies, "__init__", init)
    wire = {}
    for name, fn in (("plain", plain), ("futured", futured)):
        del frames.sent[:]
        srv, port = serve(fn, device=device)
        try:
            assert pipelined(port, 9, 4) == list(range(1, 10))
        finally:
            srv.stop(grace=1)
        wire[name] = [f for f in frames.sent]
        if name == "plain":
            assert not made
    assert made == [True]
    assert [f[0] for f in wire["plain"]] == [fr.MESSAGE] * 9 + [fr.TRAILERS]
    assert wire["plain"] == wire["futured"]


def test_the_handler_thread_parks_at_the_stream_bound(monkeypatch):
    """At most ``stream_queue_depth`` responses wait in the queue: beyond it
    the handler's thread parks in the push, as it would in a blocking send,
    and goes on when the head is written."""
    monkeypatch.setenv("TPURPC_STREAM_QUEUE_DEPTH", "3")
    from tpurpc.utils import config as config_mod

    config_mod.set_config(None)
    if config_mod.get_config().stream_queue_depth != 3:
        pytest.skip("stream_queue_depth is not set from the environment")
    futures, yielded = [], []

    def handler(trees):
        for tree in trees:
            fut = Future()
            futures.append((fut, np.array(tree["x"])))
            yield fut
            yielded.append(len(futures))

    srv, port = serve(handler)
    got = []
    try:
        with Channel(f"127.0.0.1:{port}") as ch:
            replies = TensorClient(ch).duplex(
                "M", (msg(k) for k in range(6)), timeout=20)
            deadline = time.monotonic() + 5
            while len(futures) < 4 and time.monotonic() < deadline:
                time.sleep(0.01)
            time.sleep(0.2)
            # three queued, the fourth's push parked: no fifth request taken
            assert len(futures) == 4 and yielded == [1, 2, 3]
            for k in range(6):
                while len(futures) <= k:
                    time.sleep(0.005)
                fut, x = futures[k]
                fut.set_result({"y": x + 1})
            got = [int(r["y"][0]) for r in replies]
    finally:
        srv.stop(grace=1)
    assert got == [1, 2, 3, 4, 5, 6]
