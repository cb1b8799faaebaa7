"""ring_scatter Pallas kernel vs numpy oracle (interpret mode, CPU).

The write twin of test_ring_window: every wrap phase, the clamp case (start
inside the last 9 rows), masks at payload edges, and preservation of
untouched ring bytes. On real TPU hardware the same kernel runs with
interpret=False (chip validation is part of the bench round).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from tpurpc.ops.ring_scatter import ring_scatter, ring_scatter_reference

CAP = 16384  # 32 rows of 128 u32 lanes = 2x the 18-row minimum


def _rng(seed):
    return np.random.default_rng(seed)


def _check(cap, start, n, seed=0):
    import jax.numpy as jnp

    r = _rng(seed)
    ring0 = r.integers(0, 256, cap, dtype=np.uint8)
    payload = r.integers(0, 256, n, dtype=np.uint8)
    want = ring_scatter_reference(ring0, payload, start)
    buf = jnp.asarray(ring0)
    pay = jnp.asarray(payload)
    got = np.asarray(ring_scatter(buf, pay, start, interpret=True))
    np.testing.assert_array_equal(got, want)


def test_no_wrap_aligned():
    _check(CAP, 0, 4096)


def test_no_wrap_misaligned_start():
    _check(CAP, 4 * 37, 4096, seed=1)


def test_wrap_crossing():
    _check(CAP, CAP - 2048, 8192, seed=2)


def test_start_in_last_nine_rows_clamp():
    # start within the final 9 rows of the ring: the kernel's row clamp +
    # exact pre-wrap mask is what keeps window A inside the ring
    _check(CAP, CAP - 4 * 100, 4096, seed=3)


def test_tiny_payload_one_word():
    _check(CAP, 4 * 1001, 4, seed=4)


def test_payload_not_block_multiple():
    # 4-byte-aligned but not a multiple of the (8,128) block: the padded
    # tail must NOT be written into the ring
    _check(CAP, 4 * 513, 4 * 300, seed=5)


def test_full_capacity_payload():
    _check(CAP, 4 * 77, CAP, seed=6)


def test_wrap_exactly_at_end():
    _check(CAP, CAP - 4096, 4096, seed=7)  # lands flush, no wrap


def test_untouched_bytes_preserved():
    import jax.numpy as jnp

    r = _rng(8)
    ring0 = r.integers(0, 256, CAP, dtype=np.uint8)
    payload = r.integers(0, 256, 512, dtype=np.uint8)
    start = 4 * 613
    got = np.asarray(ring_scatter(jnp.asarray(ring0), jnp.asarray(payload),
                                  start, interpret=True))
    # the written span
    np.testing.assert_array_equal(got[start:start + 512], payload)
    # everything else identical
    mask = np.ones(CAP, bool)
    mask[start:start + 512] = False
    np.testing.assert_array_equal(got[mask], ring0[mask])


def test_sequential_places_accumulate():
    """Back-to-back placements (the ring's real usage) compose correctly,
    including across the wrap."""
    import jax.numpy as jnp

    r = _rng(9)
    ring = r.integers(0, 256, CAP, dtype=np.uint8)
    want = ring.copy()
    buf = jnp.asarray(ring)
    off = CAP - 3000
    for i, n in enumerate((1024, 2048, 512, 4096)):
        payload = r.integers(0, 256, n, dtype=np.uint8)
        want = ring_scatter_reference(want, payload, off)
        buf = ring_scatter(buf, jnp.asarray(payload), off, interpret=True)
        off = (off + n) % CAP
    np.testing.assert_array_equal(np.asarray(buf), want)


def test_shape_guards():
    import jax.numpy as jnp

    buf = jnp.zeros((CAP,), jnp.uint8)
    with pytest.raises(ValueError):
        ring_scatter(buf, jnp.zeros((10,), jnp.uint8), 0, interpret=True)
    with pytest.raises(ValueError):
        ring_scatter(buf, jnp.zeros((8,), jnp.uint8), 2, interpret=True)
    with pytest.raises(ValueError):
        ring_scatter(jnp.zeros((4096,), jnp.uint8),
                     jnp.zeros((8,), jnp.uint8), 0, interpret=True)
    # zero-length payload: identity, no kernel
    out = ring_scatter(buf, jnp.zeros((0,), jnp.uint8), 0, interpret=True)
    assert out.shape == (CAP,)


def test_hbm_ring_place_uses_kernel():
    """HbmRing.place routes a wrapped span through ring_scatter and its view
    through ring_window — the path counters say so — and the bytes
    round-trip."""
    from tpurpc.obs import metrics
    from tpurpc.tpu.hbm_ring import HbmRing

    ring = HbmRing(16384)
    r = _rng(10)
    before = metrics.registry().counters_snapshot()
    # advance near the end so the next placement wraps
    spans = []
    for n in (8192, 4096):
        payload = r.integers(0, 256, n, dtype=np.uint8).tobytes()
        spans.append((ring.place(payload), payload))
    for (off, n), payload in spans:
        lease = ring.view(off, n)
        got = np.asarray(lease.array)
        np.testing.assert_array_equal(got, np.frombuffer(payload, np.uint8))
        lease.release()
    # wrap case: head advanced, place 8KB crossing the 16KB boundary
    payload = r.integers(0, 256, 8192, dtype=np.uint8).tobytes()
    off, n = ring.place(payload)
    assert (off & (16384 - 1)) + n > 16384  # really wraps
    with ring.view(off, n) as arr:
        np.testing.assert_array_equal(
            np.asarray(arr), np.frombuffer(payload, np.uint8))
    after = metrics.registry().counters_snapshot()
    moved = {k: after[k] - before.get(k, 0) for k in after
             if k.startswith(("hbm_place_", "hbm_view_"))
             and after[k] != before.get(k, 0)}
    assert moved["hbm_place_update"] == 2 and moved["hbm_place_scatter"] == 1
    assert moved["hbm_view_window"] == 1
    assert "hbm_place_split" not in moved and "hbm_view_concat" not in moved
