"""Pallas kernel: land a payload into a wrapped device ring, in one pass.

The write twin of :mod:`tpurpc.ops.ring_window` (VERDICT r2 next#6): the
place path of the HBM receive ring needs

    ring[(start + i) mod capacity] = payload[i]        for i < n

which in jax ops is a donated ``dynamic_update_slice`` — TWO dispatches when
the span wraps (``hbm_ring.py place``), and the wrap case rebinds the
donated buffer twice. This kernel does the whole landing as ONE aliased
pallas_call: the NIC-placement-write of the north star
(``ring_buffer.cc:261-330`` GetWriteRequests' wrap-split is the host-side
analog this replaces).

Formulation (same validated machinery as ring_window — 2-D row-granular
DMAs with dynamic row offsets + flat rolls decomposed into ``pltpu.roll``):
the ring is a ``(rows, 128)`` uint32 matrix in ``ANY`` (HBM); each program
owns one (8,128) payload block and read-modify-writes the ≤2 nine-row ring
windows its bytes land in:

  window A (dest span start):  in-DMA 9 rows -> merge
      ``where(s <= flat < s + lim_pre, payload_flat[flat - s], old)``
      with ``s = dest offset within the window`` -> out-DMA 9 rows back
  window B (ring rows 0..9, wrap only): merge
      ``where(flat < lim_post, payload_flat[flat + pre], old)`` -> out-DMA

Rows the payload doesn't touch are preserved by the RMW; masks are exact,
so garbage lanes rolled in from the zero-padded payload tile are always
discarded (same proof shape as ring_window's selects).

Correctness depends on the TPU grid executing sequentially (it does: grid
iterations are a loop on a core; interpret mode likewise) — adjacent
programs' windows share boundary rows, and program i+1's in-DMA must see
program i's out-DMA. Both DMAs are awaited inside each program.

Alignment contract: start/length multiples of 4 bytes; capacity a power of
two ≥ 2·9·512 bytes (windows A and B must never overlap). Callers fall
back to the dynamic_update_slice chain otherwise.
"""

from __future__ import annotations

import functools

import numpy as np

import jax

from tpurpc.ops.layout import bytes_to_words, words_to_bytes
from tpurpc.ops.ring_window import (_C, _R, _SCRATCH_ROWS, _flat_roll_neg,
                                    _flat_roll_pos)


def _kernel(start_ref, payload_ref, buf_ref, out_ref, scr, sem_in, sem_out,
            *, rows: int, n_words: int):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del buf_ref  # aliased with out_ref: out_ref starts as the ring's
    # contents (input_output_aliases) and is both RMW source and target
    capacity_words = rows * _C
    block = _R * _C
    pid = pl.program_id(0)
    base = pid * block                        # payload flat offset of block
    q = jax.lax.rem(start_ref[0] + base, capacity_words)
    row1 = q // _C
    row1c = jnp.minimum(row1, rows - (_R + 1))  # clamp: 9 rows must fit
    d_rows = row1 - row1c
    s = d_rows * _C + q % _C                  # dest offset inside window A
    pre = capacity_words - q                  # words before the wrap point
    valid = jnp.minimum(block, n_words - base)  # real payload words here
    lanes = jax.lax.broadcasted_iota(jnp.int32, (_SCRATCH_ROWS, _C), 1)
    flat = (jax.lax.broadcasted_iota(jnp.int32, (_SCRATCH_ROWS, _C), 0) * _C
            + lanes)
    # zero-padded payload tile: rolled-in rows beyond the 8 real ones are
    # zeros, and the exact masks below discard them anyway
    pad = jnp.zeros((_SCRATCH_ROWS - _R, _C), jnp.uint32)
    ptile = jnp.concatenate([payload_ref[...], pad], axis=0)

    # -- window A: the destination span's start ------------------------------
    cp_in = pltpu.make_async_copy(
        out_ref.at[pl.dslice(row1c, _R + 1), :],
        scr.at[pl.dslice(0, _R + 1), :], sem_in)
    cp_in.start()
    cp_in.wait()
    shifted = _flat_roll_pos(ptile, s, lanes)   # shifted[f] = payload[f - s]
    lim_pre = jnp.minimum(valid, pre)
    merged = jnp.where((flat >= s) & (flat < s + lim_pre), shifted, scr[...])
    scr[...] = merged
    cp_out = pltpu.make_async_copy(
        scr.at[pl.dslice(0, _R + 1), :],
        out_ref.at[pl.dslice(row1c, _R + 1), :], sem_out)
    cp_out.start()
    cp_out.wait()

    # -- window B: ring start (only when this block crosses the wrap) --------
    @pl.when(pre < valid)
    def _wrap_window():
        cp2_in = pltpu.make_async_copy(
            out_ref.at[pl.dslice(0, _R + 1), :],
            scr.at[pl.dslice(0, _R + 1), :], sem_in)
        cp2_in.start()
        cp2_in.wait()
        back = _flat_roll_neg(ptile, pre, lanes)  # back[f] = payload[f + pre]
        merged_b = jnp.where(flat < valid - pre, back, scr[...])
        scr[...] = merged_b
        cp2_out = pltpu.make_async_copy(
            scr.at[pl.dslice(0, _R + 1), :],
            out_ref.at[pl.dslice(0, _R + 1), :], sem_out)
        cp2_out.start()
        cp2_out.wait()


@functools.partial(jax.jit, static_argnames=("n_words", "interpret"),
                   donate_argnums=0)
def _ring_scatter_impl(buf_u8, payload_u8, start_word, *, n_words: int,
                       interpret: bool):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    buf_words = bytes_to_words(buf_u8)
    rows = buf_words.shape[0]
    block = _R * _C
    padded = ((n_words + block - 1) // block) * block
    # zero-pad to whole (8,128) blocks while still bytes (see ops.layout)
    pay_words = bytes_to_words(
        jnp.pad(payload_u8, (0, 4 * (padded - n_words))))
    grid = (padded // block,)
    out = pl.pallas_call(
        functools.partial(_kernel, rows=rows, n_words=n_words),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),       # start word index
            pl.BlockSpec((_R, _C), lambda i: (i, 0)),    # payload block
            pl.BlockSpec(memory_space=pl.ANY),           # ring stays in HBM
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((rows, _C), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((_SCRATCH_ROWS, _C), jnp.uint32),
                        pltpu.SemaphoreType.DMA,
                        pltpu.SemaphoreType.DMA],
        input_output_aliases={2: 0},  # the ring updates in place
        interpret=interpret,
        name="tpurpc_ring_scatter",  # what a device trace calls the kernel
    )(start_word, pay_words, buf_words)
    return words_to_bytes(out)


def ring_scatter(buf, payload, start: int, *, interpret: bool = False):
    """``buf[(start + i) mod capacity] = payload[i]`` as one aliased kernel.

    ``buf``: 1-D device uint8 ring (donated; use the RETURNED array).
    ``payload``: 1-D device uint8 array. ``start``/len(payload) must be
    multiples of 4; capacity ≥ 2·9·512 bytes so the two RMW windows can
    never overlap. Raises ValueError on shapes the kernel can't take —
    callers fall back to the dynamic_update_slice chain.
    """
    import jax.numpy as jnp

    capacity = buf.shape[0]
    n = payload.shape[0]
    if n == 0:
        return buf
    if capacity % 4 or start % 4 or n % 4:
        raise ValueError("ring_scatter needs 4-byte alignment")
    if capacity // 4 < 2 * (_R + 1) * _C:
        raise ValueError("ring smaller than two 9-row RMW windows")
    if n > capacity:
        raise ValueError(f"payload {n} exceeds capacity {capacity}")
    start_word = jnp.asarray([(start // 4) % (capacity // 4)], jnp.int32)
    return _ring_scatter_impl(buf, payload, start_word, n_words=n // 4,
                              interpret=interpret)


def ring_scatter_reference(buf: np.ndarray, payload: np.ndarray,
                           start: int) -> np.ndarray:
    """Numpy oracle for the kernel's contract."""
    out = np.array(buf, copy=True)
    idx = (start + np.arange(payload.shape[0])) % buf.shape[0]
    out[idx] = payload
    return out
