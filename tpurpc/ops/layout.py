"""Flat ``uint8`` ↔ wider dtypes as XLA ops the TPU compiler builds quickly.

The device ring is a flat ``uint8`` array; the kernels work on ``(rows, 128)``
``uint32`` tiles and consumers want ``float32[...]`` views. The obvious
spellings — ``x.reshape(-1, 4)`` then ``bitcast_convert_type``, or the
bitcast's ``(n, 4)`` result collapsed with ``reshape(-1)`` — put a dimension
of 4 minor-most on a flat byte array, and libtpu 0.0.34 takes time in
proportion to the array to compile that: 66–77 s per MiB-sized operand,
measured ahead of time for a v5e (a 4,000,000-byte view: 66 s; the same view
through the functions below: 2 s). The cure is to go by way of ``(rows, 512)``
— one ``(8, 128)``-tileable row per 128 words — with an
``optimization_barrier`` so XLA cannot fold the two reshapes back into the
slow one. Lengths that are not a multiple of 512 bytes are zero-padded first
and cut afterwards.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: bytes per (rows, 512) row: 128 lanes of 32-bit words
_ROW = 512


def bytes_to_words(buf_u8):
    """Flat ``uint8`` whose length is a multiple of 512 → ``(rows, 128)``
    ``uint32``, little-endian."""
    wide = jax.lax.optimization_barrier(buf_u8.reshape(-1, _ROW))
    return jax.lax.bitcast_convert_type(
        wide.reshape(-1, _ROW // 4, 4), jnp.uint32)


def words_to_bytes(words):
    """``(rows, 128) uint32`` → flat ``uint8``: the inverse of
    :func:`bytes_to_words`."""
    wide = jax.lax.bitcast_convert_type(words, jnp.uint8).reshape(-1, _ROW)
    return jax.lax.optimization_barrier(wide).reshape(-1)


def bytes_as(buf_u8, dtype):
    """Flat ``uint8`` of any length that ``dtype`` divides → flat ``dtype``
    (a bit-for-bit reinterpretation, like ``numpy.ndarray.view``)."""
    dt = jnp.dtype(dtype)
    if dt.itemsize == 1:
        return (buf_u8 if dt == jnp.uint8
                else jax.lax.bitcast_convert_type(buf_u8, dt))
    n = buf_u8.shape[0]
    if n % dt.itemsize:
        raise ValueError(f"{n} bytes are not a whole number of {dt} elements")
    pad = -n % _ROW
    if pad:
        buf_u8 = jnp.pad(buf_u8, (0, pad))
    wide = jax.lax.optimization_barrier(buf_u8.reshape(-1, _ROW))
    out = jax.lax.bitcast_convert_type(
        wide.reshape(-1, _ROW // dt.itemsize, dt.itemsize), dt).reshape(-1)
    return out[:n // dt.itemsize] if pad else out
