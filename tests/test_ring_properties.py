"""Property-based differential tests: the ring protocol and the device
ring's credit window vs simple oracles, under randomized operation sequences.

SURVEY §7 stage 4 prescribes porting the ring *math* as a formally-tested
state machine — these are the law: a FIFO byte-queue model for the pair
protocol (any divergence is a framing/credit bug), and a deque of spans for
the credit window over device landings.
"""

import collections
import threading
import time

import numpy as np
import pytest
from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from tpurpc.core.pair import LocalDomain, create_loopback_pair

_SETTINGS = dict(max_examples=40, deadline=None,
                 suppress_health_check=[HealthCheck.too_slow])


@settings(**_SETTINGS)
@given(st.lists(st.integers(min_value=0, max_value=3000), min_size=1,
                max_size=30),
       st.randoms(use_true_random=False))
def test_pair_fifo_differential(sizes, rnd):
    """Random message sizes pumped through a 4KB ring == a FIFO byte queue:
    same bytes, same order, regardless of wraps/partials/credit timing."""
    a, b = create_loopback_pair(ring_size=4096, domain=LocalDomain())
    try:
        sent = bytearray()
        got = bytearray()
        payloads = [bytes([i % 256]) * n for i, n in enumerate(sizes)]
        total = sum(len(p) for p in payloads)
        pi, off = 0, 0
        stall = 0
        while len(got) < total and stall < 10000:
            # writer side: push as much of the current payload as accepted
            if pi < len(payloads):
                p = payloads[pi]
                if off < len(p) or len(p) == 0:
                    n = a.send([p], off)
                    off += n
                if off >= len(p):
                    sent.extend(p)
                    pi += 1
                    off = 0
            # reader side: sometimes drain, sometimes not (credit jitter)
            if rnd.random() < 0.7:
                chunk = b.recv(max_bytes=rnd.randrange(1, 5000))
                got.extend(chunk)
                if not chunk:
                    stall += 1
                else:
                    stall = 0
            else:
                stall += 1
        # final drain
        deadline = 10000
        while len(got) < total and deadline:
            got.extend(b.recv())
            deadline -= 1
        assert bytes(got) == bytes(b"".join(payloads))
    finally:
        a.destroy()
        b.destroy()


jax = pytest.importorskip("jax")


class _Window:
    """The credit window's reference: a deque of ``[off, n, released]``."""

    def __init__(self, capacity):
        self.capacity, self.head, self.tail = capacity, 0, 0
        self.spans = collections.deque()

    def stats(self):
        return {"capacity": self.capacity, "head": self.head,
                "tail": self.tail, "live_spans": len(self.spans),
                "writable": self.capacity - (self.tail - self.head)}

    def land(self, sizes):
        """``"over"`` (raises at once), ``"full"`` (blocks, then raises),
        or the ``(off, n)`` spans claimed."""
        if sum(sizes) > self.capacity:
            return "over"
        if sum(sizes) > self.stats()["writable"]:
            return "full"
        out = []
        for n in sizes:
            if n:
                self.spans.append([self.tail, n, False])
            out.append((self.tail, n))
            self.tail += n
        return out

    def release(self, off, n):
        for span in self.spans:
            span[2] |= bool(n) and span[0] == off
        while self.spans and self.spans[0][2]:
            self.head += self.spans.popleft()[1]


_LEAF_DTYPES = [np.dtype(np.uint8), np.dtype(np.int16), np.dtype(np.float32)]


def _leaf(rng, max_bytes):
    """One ``(payload, dtype, shape)`` leaf and the array it must land as:
    empty and 0-d leaves among them."""
    dt = _LEAF_DTYPES[rng.integers(len(_LEAF_DTYPES))]
    kind = rng.integers(10)
    if kind == 0:
        shape = (0, 3)
    elif kind == 1:
        shape = ()
    else:
        n = int(rng.integers(1, max(2, max_bytes // dt.itemsize)))
        shape = (2, n // 2) if kind == 2 and n % 2 == 0 else (n,)
    x = rng.integers(0, 100, size=shape).astype(dt)
    return (bytearray(x.tobytes()), dt, shape), x


@pytest.mark.parametrize("capacity", [1 << 12, 1 << 16], ids=["4K", "64K"])
@pytest.mark.parametrize("seed", range(16))
def test_credit_window_follows_its_model(seed, capacity):
    """Seeded schedules against the reference, step by step: batches of 1 to
    8 leaves, releases out of order, a landing that times out, one that a
    release from another thread wakes, a misfit leaf mid-batch. After every
    step the ring's ``stats()`` are the model's."""
    from tpurpc.tpu import HbmRing

    rng = np.random.default_rng(1000 * capacity + seed)
    ring, model = HbmRing(capacity), _Window(capacity)
    held = []  # (lease, (off, n)) not yet released

    def batch(max_bytes):
        made = [_leaf(rng, max_bytes) for _ in range(rng.integers(1, 9))]
        return [leaf for leaf, _ in made], [x for _, x in made]

    def landed(leases, spans, want):
        assert len(leases) == len(spans) == len(want)
        for lease, span, x in zip(leases, spans, want):
            assert lease.array.dtype == x.dtype and lease.array.shape == x.shape
            np.testing.assert_array_equal(np.asarray(lease.array), x)
            held.append((lease, span))

    def land(leaves, want, timeout=None):
        verdict = model.land([x.nbytes for x in want])
        t0 = time.monotonic()
        if isinstance(verdict, str):
            match = "capacity" if verdict == "over" else "ring full"
            with pytest.raises(BufferError, match=match):
                ring.land_many(leaves, timeout)
            waited = time.monotonic() - t0
            if verdict == "over" or timeout is None:
                assert waited < 1  # raised at once
            else:
                assert waited >= 0.9 * timeout  # blocked first
        else:
            landed(ring.land_many(leaves, timeout), verdict, want)
        return verdict

    def release(i):
        lease, span = held.pop(i)
        lease.release()
        model.release(*span)

    def misfit():
        leaves, _ = batch(capacity // 16)
        bad = (bytearray(10), np.dtype(np.float32), (2,))  # 10 B of float32
        leaves.insert(int(rng.integers(len(leaves) + 1)), bad)
        with pytest.raises(ValueError):
            ring.land_many(leaves, timeout=5)

    def fill():
        """Land until the window refuses one more leaf of a quarter."""
        x = np.arange(capacity // 4, dtype=np.uint8)
        while land([(x, x.dtype, x.shape)], [x]) != "full":
            pass

    for _ in range(40):
        op = rng.integers(10)
        if op < 5:
            land(*batch(capacity // 4))
        elif op < 8 and held:
            release(int(rng.integers(len(held))))
        elif op == 8:
            misfit()
        else:
            land(*batch(capacity // 4), timeout=0.0)
        assert ring.stats() == model.stats()

    # a full window: a landing times out, and changes nothing
    fill()
    x = np.arange(capacity // 4, dtype=np.uint8)
    assert land([(x, x.dtype, x.shape)], [x], timeout=0.03) == "full"
    misfit()
    assert ring.stats() == model.stats()
    # ... and one that waits is woken by releases made on another thread, out
    # of order; it lands once the head has passed enough of them
    order = rng.permutation(len(held))
    leases, spans = zip(*held)
    held.clear()
    waker = threading.Timer(
        0.05, lambda: [leases[i].release() for i in order])
    waker.start()
    t0 = time.monotonic()
    got = ring.land_many([(x, x.dtype, x.shape)], timeout=30)
    assert time.monotonic() - t0 >= 0.04
    waker.join(timeout=30)
    assert not waker.is_alive()
    for i in order:
        model.release(*spans[i])
    landed(got, model.land([x.nbytes]), [x])
    assert ring.stats() == model.stats()
    while held:
        release(int(rng.integers(len(held))))
    assert ring.stats() == model.stats()
    assert ring.head == ring.tail == model.tail and ring.writable() == capacity
