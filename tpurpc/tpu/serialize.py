"""Device-path serialization: wire bytes ↔ jax.Array with ledger accounting.

The BASELINE north star names two helpers:

* ``SerializeFromDevice`` — tensor payloads leave device memory and enter the
  send ring without host *staging*: exactly one d2h movement (none on a host
  backend, where the array memory is already host-addressable and the wire
  segments alias it), then the ring/endpoint gather-write places the same
  buffer. No intermediate host buffer is ever allocated.
  :func:`tree_from_device` is that leg and the ONLY place a reply's device
  leaves are read back: every ``add_tensor_method(device=True)`` behavior
  serializes its responses through it (``jaxshim/service.py``), and the
  frame writer places the gather list it returns, one-sided into the peer's
  landing region above the rendezvous bar (``core/rendezvous.py``
  ``_rdv_write``, the ledger's ``rdma_write``).
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
from tpurpc.obs import lens as _lens
from tpurpc.tpu import ledger


def _on_device(x) -> bool:
    """A ``jax.Array`` whose bytes the host cannot address: reading it is a
    device-to-host transfer. An array of a host backend (and anything that
    is no ``jax.Array``) is aliased where it lies."""
    devices = getattr(x, "devices", None)
    if devices is None or not hasattr(x, "copy_to_host_async"):
        return False
    try:
        return any(d.platform != "cpu" for d in devices())
    except Exception:
        return False


def _read_back(leaves: list) -> None:
    """THE device-to-host site: replace, in place, every leaf of ``leaves``
    that lives on a device with the host landing buffer of its transfer.

    Every transfer is STARTED (``copy_to_host_async``) before any is
    awaited, so they overlap each other and whatever the device still has
    to finish; each is billed ``dma_d2h`` once. Starting and awaiting them
    is one ``d2h`` stage (span ``tpurpc.d2h``), an op only where there was a
    device leaf. jax keeps the landing buffer on the array, so a leaf sent
    twice is read back once. Leaves of a host backend and numpy leaves stay
    as they are, aliased where they lie and billed ``zero_copy``."""
    away = []
    for i, leaf in enumerate(leaves):
        if _on_device(leaf):
            away.append(i)
        else:
            ledger.zero_copy(getattr(leaf, "nbytes", 0))
    if not away:
        return
    total = sum(leaves[i].nbytes for i in away)
    with _lens.stage("d2h", total):
        for i in away:
            leaves[i].copy_to_host_async()
        for i in away:
            ledger.dma_d2h(leaves[i].nbytes)
            leaves[i] = np.asarray(leaves[i])


def tree_from_device(tree: Any) -> List[bytes]:
    """Wire segments of a pytree whose leaves may live on a device: the
    outbound leg of every ``device=True`` reply. The tree is flattened
    once, its device leaves are read back together (:func:`_read_back`),
    and the gather list the codec's host-leaf path (``codec.encode_flat``)
    returns aliases the transfers' landing buffers: no ``tobytes``, no
    join."""
    skeleton, leaves = codec.flatten_tree(tree)
    _read_back(leaves)
    return codec.encode_flat(skeleton, leaves)


def serialize_from_device(x) -> List[bytes]:
    """:func:`tree_from_device` for ONE array as a bare tensor record (no
    tree framing)."""
    leaf = [x]
    _read_back(leaf)
    return codec.encode_tensor(leaf[0])


def deserialize_to_device(buf, offset: int = 0):
    """Wire record → jax.Array on JAX's default device; returns (array,
    end). The one movement (or the alias) is billed by ``codec.to_jax``,
    once."""
    arr, end = codec.decode_tensor(buf, offset)  # zero-copy view of buf
    return codec.to_jax(arr), end
