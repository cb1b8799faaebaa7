"""Pallas kernel: contiguous window of a wrapped device ring, in one pass.

The consume path of the HBM receive ring (``tpurpc/tpu/hbm_ring.py``,
reference analog ``ring_buffer.cc:122-191`` — whose ``Read`` memcpys out of
the host ring) needs ``out[i] = ring[(head + i) mod capacity]`` for a span
that may cross the wrap point. Expressed in jax ops that is
``dynamic_slice + dynamic_slice + concatenate``; this module does it as ONE
Pallas kernel, blocked over the output.

TPU-compatible formulation (validated on a real v5e chip AND in interpret
mode against a numpy oracle): the ring lives in ``ANY`` (HBM) as a
``(rows, 128)`` uint32 matrix; each program async-DMAs two 9-row windows
into VMEM scratch — the (row-clamped) source window at the block's start
and the wrap window at row 0 — then combines them with *flat rolls*
decomposed into supported 2-D ops:

    flat_roll(X, s)[r, c] = X[r + s//C + (c + s%C >= C), (c + s%C) % C]
                          = where(lane < C - s%C,
                                  roll(roll(X, -s//C, 0), -s%C, 1),
                                  roll(roll(X, -s//C - 1, 0), -s%C, 1))

Out-of-window rows rolled in are garbage but only land on lanes the final
pre/post-wrap select discards (proved in the per-case comments below).

Alignment contract: offsets/lengths multiple of 4 bytes (uint32 lanes),
ring capacity ≥ 9·512 bytes. The caller falls back to the jax-op chain
otherwise.
"""

from __future__ import annotations

import functools

import numpy as np

import jax

from tpurpc.ops.layout import bytes_to_words, words_to_bytes

#: lanes per row (TPU vector lane width)
_C = 128
#: output rows per program: (8, 128) is the minimal uint32 tile
_R = 8
#: scratch rows: 9 valid rows (8 + 1 for sub-row shifts) padded so row
#: rolls up to 16 never wrap back into valid rows
_SCRATCH_ROWS = 32


def _flat_roll_neg(x, s, lanes):
    """first _R rows of flat_roll(x, -s): out[i] = x_flat[i + s], for
    lanes where i + s stays inside x's valid leading rows."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    rr = s // _C
    t = s % _C
    y1 = pltpu.roll(x, -rr, axis=0)
    y2 = pltpu.roll(x, -(rr + 1), axis=0)
    a1 = pltpu.roll(y1, -t, axis=1)
    a2 = pltpu.roll(y2, -t, axis=1)
    return jnp.where(lanes < _C - t, a1, a2)


def _flat_roll_pos(x, s, lanes):
    """first _R rows of flat_roll(x, +s): out[i] = x_flat[i - s], valid on
    lanes with i >= s (the rest roll in discarded garbage)."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    rr = s // _C
    t = s % _C
    y1 = pltpu.roll(x, rr, axis=0)
    y2 = pltpu.roll(x, rr + 1, axis=0)
    b1 = pltpu.roll(y1, t, axis=1)
    b2 = pltpu.roll(y2, t, axis=1)
    return jnp.where(lanes >= t, b1, b2)


def _kernel(head_ref, buf_ref, out_ref, scr_a, scr_b, sem_a, sem_b,
            *, rows: int):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    capacity_words = rows * _C
    block = _R * _C
    pid = pl.program_id(0)
    p1 = jax.lax.rem(head_ref[0] + pid * block, capacity_words)
    row1 = p1 // _C
    row1c = jnp.minimum(row1, rows - (_R + 1))   # clamp: 9 rows must fit
    d_rows = row1 - row1c
    # pre-wrap length for this block; only the (at most one) block whose
    # window crosses the wrap ever selects from window B
    pre = capacity_words - p1
    # window A: 9 rows from the (clamped) source start; covers the
    # pre-wrap part of the block at flat offset s = d_rows*C + p1%C < 9C
    cp_a = pltpu.make_async_copy(
        buf_ref.at[pl.dslice(row1c, _R + 1), :],
        scr_a.at[pl.dslice(0, _R + 1), :], sem_a)
    cp_a.start()

    # window B: 9 rows from ring start; covers the post-wrap part. Skipped
    # for non-crossing blocks (the common case) — its lanes would be fully
    # discarded, so the DMA would be pure wasted bandwidth.
    @pl.when(pre < block)
    def _copy_wrap_window():
        cp_b = pltpu.make_async_copy(
            buf_ref.at[pl.dslice(0, _R + 1), :],
            scr_b.at[pl.dslice(0, _R + 1), :], sem_b)
        cp_b.start()
        cp_b.wait()

    cp_a.wait()

    lanes = jax.lax.broadcasted_iota(jnp.int32, (_SCRATCH_ROWS, _C), 1)
    flat = (jax.lax.broadcasted_iota(jnp.int32, (_SCRATCH_ROWS, _C), 0) * _C
            + lanes)
    s_a = d_rows * _C + p1 % _C
    a = _flat_roll_neg(scr_a[...], s_a, lanes)
    # when pre >= block, B is never selected and its (stale-scratch,
    # garbage-rolled) lanes are discarded by the select below
    b = _flat_roll_pos(scr_b[...], jax.lax.rem(pre, capacity_words), lanes)
    out_ref[...] = jnp.where(flat < pre, a, b)[:_R]


@functools.partial(jax.jit, static_argnames=("n_words", "interpret"))
def _ring_window_impl(buf_u8, head_word, *, n_words: int, interpret: bool):
    """One compiled dispatch: uint8→uint32 bitcast, the pallas gather, and
    the uint32→uint8 bitcast all under this jit (tpurpc.ops.layout says why
    the bitcasts are spelt the way they are)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    buf_words = bytes_to_words(buf_u8)
    rows = buf_words.shape[0]
    block = _R * _C
    padded = ((n_words + block - 1) // block) * block
    grid = (padded // block,)
    out = pl.pallas_call(
        functools.partial(_kernel, rows=rows),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # head word index
            pl.BlockSpec(memory_space=pl.ANY),      # ring stays in HBM
        ],
        out_specs=pl.BlockSpec((_R, _C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded // _C, _C), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((_SCRATCH_ROWS, _C), jnp.uint32),
                        pltpu.VMEM((_SCRATCH_ROWS, _C), jnp.uint32),
                        pltpu.SemaphoreType.DMA,
                        pltpu.SemaphoreType.DMA],
        interpret=interpret,
        name="tpurpc_ring_window",  # what a device trace calls the kernel
    )(head_word, buf_words)
    return words_to_bytes(out)[:4 * n_words]


def ring_window(buf, head: int, n: int, *, interpret: bool = False):
    """``out[i] = buf[(head + i) mod capacity]`` as one fused kernel.

    ``buf``: 1-D device uint8 array, power-of-two length ≥ 4608 bytes.
    ``head``/``n`` must be multiples of 4 (uint32 lanes). Returns a uint8
    array of length ``n``. Raises ValueError on shapes the kernel can't
    take — callers fall back to the jax-op chain.
    """
    import jax.numpy as jnp

    capacity = buf.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.uint8)
    if capacity % 4 or head % 4 or n % 4:
        raise ValueError("ring_window needs 4-byte alignment")
    if capacity // 4 < (_R + 1) * _C:
        raise ValueError("ring smaller than the kernel's 9-row DMA window")
    if n > capacity:
        raise ValueError(f"window {n} exceeds capacity {capacity}")
    head_word = jnp.asarray([(head // 4) % (capacity // 4)], jnp.int32)
    return _ring_window_impl(buf, head_word, n_words=n // 4,
                             interpret=interpret)


def ring_window_reference(buf: np.ndarray, head: int, n: int) -> np.ndarray:
    """Numpy oracle for the kernel's contract."""
    capacity = buf.shape[0]
    idx = (head + np.arange(n)) % capacity
    return np.asarray(buf)[idx]
