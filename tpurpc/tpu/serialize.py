"""Device-path serialization: wire bytes ↔ jax.Array with ledger accounting.

The BASELINE north star names two helpers:

* ``SerializeFromDevice`` — tensor payloads leave device memory and enter the
  send ring without host *staging*: exactly one d2h movement (none on a host
  backend, where the array memory is already host-addressable and the wire
  segments alias it), then the ring/endpoint gather-write places the same
  buffer. No intermediate host buffer is ever allocated.
* ``DeserializeToDevice`` — received wire bytes become a ``jax.Array`` with
  exactly one h2d movement (none on a CPU device when the payload is
  64-byte aligned: dlpack import aliases the assembly buffer).

Both report to :mod:`tpurpc.tpu.ledger`; tests assert the copy counts, which
is the honesty mechanism SURVEY.md §7 stage 6 demands of the emulated path.
"""

from __future__ import annotations

from typing import Any, List

import numpy as np

from tpurpc.jaxshim import codec
from tpurpc.tpu import ledger


def _on_host_backend(arr) -> bool:
    try:
        return all(d.platform == "cpu" for d in arr.devices())
    except Exception:
        return False


def serialize_from_device(x) -> List[bytes]:
    """Wire segments for a jax.Array/numpy without host staging.

    Returns the codec's gather list; the payload segment aliases the d2h
    landing buffer (or the array itself on host backends) — downstream gather
    writes (ring slice-send / sendmsg) consume it in place.
    """
    import jax

    if isinstance(x, jax.Array) and not _on_host_backend(x):
        ledger.dma_d2h(x.nbytes)       # the one unavoidable device→host DMA
        host = np.asarray(x)
        ledger.zero_copy(host.nbytes)  # segments alias the DMA landing buffer
        return codec.encode_tensor(host)
    host = np.asarray(x)
    ledger.zero_copy(host.nbytes)
    return codec.encode_tensor(host)


def deserialize_to_device(buf, offset: int = 0):
    """Wire record → jax.Array on JAX's default device; returns (array,
    end). The one movement (or the alias) is billed by ``codec.to_jax``,
    once."""
    arr, end = codec.decode_tensor(buf, offset)  # zero-copy view of buf
    return codec.to_jax(arr), end


def tree_from_device(tree: Any) -> List[bytes]:
    """Pytree variant of :func:`serialize_from_device` (gather segments)."""
    import jax

    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array) and not _on_host_backend(leaf):
            ledger.dma_d2h(leaf.nbytes)
        else:
            ledger.zero_copy(getattr(leaf, "nbytes", 0))
    return codec.encode_tree(tree)


# ---------------------------------------------------------------------------
# SerializeFromDevice → rendezvous region / send ring (tpurpc-express, ISSUE 9)
# ---------------------------------------------------------------------------

def serialize_into(x, write, offset: int = 0) -> int:
    """``SerializeFromDevice`` finished end-to-end: gather-serialize one
    array STRAIGHT into a rendezvous landing window (or any one-sided
    write target) with zero host staging — each codec segment (header,
    payload view aliasing the d2h landing buffer or the array itself)
    lands via ``write(offset, segment)``; no intermediate host buffer is
    ever allocated or joined. ``write`` must be a one-sided placement
    (a :class:`~tpurpc.core.pair.Window` write / rendezvous region); the
    movement is billed as ``rdma_write``, and the copy ledger proves the
    zero-staging claim: exactly one ``dma_d2h`` on device backends (zero on
    host backends, where the segments alias the array) and zero
    ``host_copy``. Returns bytes written past ``offset``."""
    segs = serialize_from_device(x)
    return _write_segments(segs, write, offset)


def serialize_tree_into(tree: Any, write, offset: int = 0) -> int:
    """Pytree variant of :func:`serialize_into` — the outbound half the
    multi-host activation transport (ROADMAP item 5) consumes: device
    activations leave HBM and land in the peer's advertised region with
    no host staging buffer in between."""
    segs = tree_from_device(tree)
    return _write_segments(segs, write, offset)


def _write_segments(segs: List[bytes], write, offset: int) -> int:
    total = 0
    for seg in segs:
        view = memoryview(seg).cast("B")
        write(offset + total, view)
        total += len(view)
    ledger.rdma_write(total)
    return total
