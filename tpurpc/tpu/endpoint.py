"""Platform.TPU endpoint: the ring byte-pipe whose received tensor payloads
land in a device(HBM)-resident ring and surface as lease-backed jax.Arrays.

This is the file ``create_endpoint`` dispatches to for
``GRPC_PLATFORM_TYPE=TPU`` / ``RDMA_TPU`` (``tpurpc/core/endpoint.py:452-456``)
— the framework's namesake transport, and round 1's headline gap.

Architecture (BASELINE.json north star: "receive ring in HBM, recv yields
device handles, host-memcpy = 0 after frame assembly"):

* The byte pipe itself is the same pooled shm Pair as the other ring
  platforms (creation path mirrors ``rdma_bp_posix.cc:706-796``: pool take →
  init → bootstrap over the connected socket → hybrid-discipline wakeups).
  Control structures — frame headers, metadata, trailers — are parsed
  host-side, exactly as the real-hardware design keeps head/footer words
  host-visible while payloads go to HBM.
* Each connection owns an :class:`~tpurpc.tpu.hbm_ring.HbmRing`
  (``device_ring``), created lazily on first tensor decode so pure-bytes
  RPCs never pay jax initialization.
* :func:`decode_tensor_to_ring` / :func:`decode_tree_to_ring` are the
  ``DeserializeToDevice`` of this platform (SURVEY §7 stage 6): they parse
  the codec's host-visible tensor header, land the payload spans through
  the device ring (``HbmRing.land_many``) straight from the wire-assembly
  buffer (zero host memcpy — the ledger proves it), and hand back device
  arrays whose leases gate the ring's credit return. The landing is ONE
  ``device_put`` per message to the final arrays, and the ring is the
  credit window over them.

The RPC layer reaches the device ring through ``ServerContext.device_ring``
(server) and ``Channel.device_ring()`` (client); the jaxshim tensor service
uses them when registered with ``device=True``.
"""

from __future__ import annotations

import socket
import struct
from typing import Any, List, Optional, Tuple

import numpy as np

from tpurpc.core.endpoint import RingEndpoint
from tpurpc.jaxshim import codec
from tpurpc.obs import lens as _lens
from tpurpc.obs import profiler as _profiler
from tpurpc.tpu.hbm_ring import HbmLease, HbmRing
from tpurpc.utils.config import Platform, get_config
from tpurpc.utils.trace import trace_endpoint

# tpurpc-lens (ISSUE 8, 26): the device-plane decode (wire record → placed
# device view) is one `decode` stage per message here, the parent of the
# ring's `hbm_credit`, `hbm` and `hbm_view` stages (hops nest — see
# obs/lens.py): decode less those three is parse, unflatten and the ring
# call's own bookkeeping.

_LENS_STAGES = {
    "decode_tensor_to_ring": "codec",
    "decode_tree_to_ring": "codec",
    "_parse_tensor_record": "codec",
}
_profiler.register_stages(__file__, _LENS_STAGES)

#: Default wait for device-ring space before failing a decode: long enough to
#: ride out a burst of unreleased leases, short enough to surface a genuine
#: leak as an error instead of a hang.
PLACE_TIMEOUT_S = 30.0


class TpuRingEndpoint(RingEndpoint):
    """Ring endpoint + device-resident receive ring for tensor payloads.

    The byte-pipe contract is inherited unchanged — anything that speaks
    frames over a :class:`RingEndpoint` works here too. What's new is
    :attr:`device_ring`: the per-connection HBM ring that the tensor decode
    path places payloads into.
    """

    def __init__(self, sock: socket.socket, *, pool_key: str,
                 is_server: bool = False, preread: bytes = b""):
        super().__init__(sock, discipline=Platform.TPU.discipline,
                         pool_key=pool_key, preread=preread)
        self.is_server = is_server
        self._hbm: Optional[HbmRing] = None
        import threading

        self._hbm_lock = threading.Lock()

    @property
    def device_ring(self) -> HbmRing:
        """The connection's HBM receive ring; created on first use (jax
        backend init is expensive — pure-bytes traffic never pays it)."""
        if self._hbm is None:
            with self._hbm_lock:
                if self._hbm is None:
                    cap = get_config().hbm_ring_size
                    self._hbm = HbmRing(cap)
                    trace_endpoint.log(
                        "TPU endpoint %s: HBM ring up (%d bytes)",
                        self.peer, cap)
        return self._hbm

    def close(self) -> None:
        # The HbmRing needs no explicit teardown: leases pin spans, and the
        # device buffer dies with the last reference. Dropping the ring here
        # (not at pool putback) matches per-connection device resources.
        self._hbm = None
        super().close()


# ---------------------------------------------------------------------------
# DeserializeToDevice over the device ring.
# ---------------------------------------------------------------------------

def _parse_tensor_record(view: memoryview, offset: int):
    """Host-side parse of one codec tensor record: ``(dtype, shape,
    payload_view, next_offset)`` with the payload as a zero-copy numpy view
    over ``view`` — shared by the single and batched placement paths."""
    if len(view) - offset < codec._HDR.size:
        raise codec.CodecError("short tensor header")
    magic, code, ndim, _, nbytes = codec._HDR.unpack_from(view, offset)
    if magic != codec.MAGIC:
        raise codec.CodecError(f"bad tensor magic {magic!r}")
    try:
        dt = codec._CODE_TO_DTYPE[code]
    except KeyError:
        raise codec.CodecError(f"unknown dtype code {code}") from None
    pos = offset + codec._HDR.size
    if len(view) - pos < 8 * ndim:
        raise codec.CodecError("short tensor dims")
    shape = struct.unpack_from(f"<{ndim}q", view, pos) if ndim else ()
    pos += 8 * ndim
    pos += (-(pos - offset)) % codec._ALIGN
    if len(view) - pos < nbytes:
        raise codec.CodecError(
            f"short tensor payload: want {nbytes}, have {len(view) - pos}")
    payload = np.frombuffer(view, dtype=np.uint8, count=nbytes, offset=pos)
    return dt, shape, payload, pos + nbytes


def decode_tensor_to_ring(ring: HbmRing, buf, offset: int = 0,
                          timeout: Optional[float] = PLACE_TIMEOUT_S
                          ) -> Tuple[HbmLease, int]:
    """One wire tensor record → device landing + lease-backed array.

    Parses the codec header host-side (control words), lands ONLY the
    payload span through ``ring`` directly from ``buf`` (no intermediate
    host buffer — the ledger's host_copy stays 0 for this step), and returns
    ``(lease, next_offset)``. ``lease.array`` is the shaped/dtyped device
    array; releasing the lease returns the span's credit.
    """
    with _lens.stage("decode") as st:
        dt, shape, payload, next_pos = _parse_tensor_record(memoryview(buf),
                                                            offset)
        st.nbytes = payload.nbytes
        lease = ring.land(payload, dt, shape, timeout=timeout)
    return lease, next_pos


def decode_tree_to_ring(ring: HbmRing, buf,
                        timeout: Optional[float] = PLACE_TIMEOUT_S
                        ) -> Tuple[Any, List[HbmLease]]:
    """Pytree wire message → device-resident tree + the leases pinning it.

    Mirrors :func:`tpurpc.jaxshim.codec.decode_tree`, but every leaf's
    payload lands on the ring's device instead of being aliased host-side.
    Returns ``(tree, leases)``; release every lease (or use
    :class:`DeviceMessage`) to return the ring credit.
    """
    import json

    with _lens.stage("decode") as st:
        view = memoryview(buf)
        magic, n_leaves, trailer_len = codec._TREE.unpack_from(view, 0)
        if magic != codec.TREE_MAGIC:
            raise codec.CodecError(f"bad tree magic {magic!r}")
        pos = codec._TREE.size + ((-codec._TREE.size) % codec._ALIGN)
        # Batched landing: parse EVERY leaf header first (host control
        # words), then land all payloads with ONE ring.land_many — one
        # transfer per tree instead of per leaf (a transformer pytree has
        # hundreds of leaves), and a tree that can never fit the ring fails
        # there at once instead of waiting on its own earlier leaves.
        leaves = []  # (payload, dtype, shape)
        for _ in range(n_leaves):
            dt, shape, payload, pos = _parse_tensor_record(view, pos)
            pos += (-pos) % codec._ALIGN
            leaves.append((payload, dt, shape))
            st.nbytes += payload.nbytes
        if len(view) - pos < trailer_len:
            raise codec.CodecError("short tree trailer")
        leases = ring.land_many(leaves, timeout=timeout)
        try:
            trailer = bytes(view[pos:pos + trailer_len])
            tree = codec.unflatten(json.loads(trailer.decode()),
                                   [lease.array for lease in leases])
        except Exception:
            # Corrupt trailer or treedef: every lease must go back, or a
            # poison message permanently pins ring credit.
            for lease in leases:
                lease.release()
            raise
    return tree, leases


class DeviceMessage:
    """A decoded device-resident message: the tree + its ring leases.

    Use as a context manager (or call :meth:`release`) — the ring spans under
    the arrays stay pinned until then, which IS the flow control: a slow
    consumer holding messages back-pressures the placement path.
    """

    __slots__ = ("tree", "_leases", "_released")

    def __init__(self, tree: Any, leases: List[HbmLease]):
        self.tree = tree
        self._leases = leases
        self._released = False

    def release(self) -> None:
        if not self._released:
            self._released = True
            for lease in self._leases:
                lease.release()

    def __enter__(self) -> Any:
        return self.tree

    def __exit__(self, *exc) -> bool:
        self.release()
        return False
