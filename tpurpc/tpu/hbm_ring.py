"""Device receive window: tensor payloads land in device memory, consumers get
lease-backed device arrays, and the leases are the flow control.

An :class:`HbmRing` is a **credit window over directly landed buffers**, the
same on every platform. ``land_many`` hands the typed host views of one
message's leaves to ONE ``jax.device_put`` and leases the arrays it returns:
1 byte moved per payload byte (ledger ``dma_h2d`` alone), no device program,
no host scalar handed to a jit. No byte ring lives on the device: nothing
could read a payload back out of one but one more copy, so the ring owns
offsets and credit, never bytes.

* one way in — :meth:`HbmRing.land` / :meth:`HbmRing.land_many`: the batch
  waits up to ``timeout`` for its TOTAL to fit (:class:`BufferError` where it
  cannot; at once where it exceeds the capacity), each non-empty leaf claims
  its own span ``[tail, tail + nbytes)`` and the tail advances;
* one way out — :meth:`HbmLease.release`: the span's credit returns, and the
  head advances over every released prefix, in order. A landed array is a
  snapshot and survives its release; its lease is the back-pressure: at most
  ``capacity`` bytes of unreleased leases (credit-based flow control, the
  ``pair.cc:276-284`` analog).

The north star's landing, a NIC that DMAs into a dmabuf-exported HBM ring
whose head and footer words stay host-visible, needs an export this
environment cannot reach. If hardware ever offers it, that landing is written
against the hardware; what is real here is the protocol, the lease discipline
and the copy ledger.

Thread model: claims, releases and the wait for credit run under
``self._lock``; the lock spans the landing's one asynchronous dispatch, which
is what keeps spans in tail order: one ring has one producer (the
connection's receive path) and its consumers.

Capacity is a power of two; offsets are monotonic 64-bit counters — the same
invariants as the host ring (tpurpc/core/ring.py), so the flow-control math
is shared by inspection.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional

import numpy as np

from tpurpc.obs import lens as _lens
from tpurpc.obs import metrics as _metrics
from tpurpc.obs import profiler as _profiler
from tpurpc.tpu import ledger

# tpurpc-scope (ISSUE 4): landing totals + scrape-time occupancy over live
# rings (one counter bump per landed BATCH; the per-byte movement accounting
# stays the copy ledger's job)
_HBM_PLACE_MSGS = _metrics.counter("hbm_place_msgs")
_HBM_PLACE_BYTES = _metrics.counter("hbm_place_bytes")
_HBM_RINGS = _metrics.fleet("hbm_ring_occupancy_bytes",
                            lambda r: r.tail - r.head)

# tpurpc-lens (ISSUE 8, 26): three hops, one `lens.stage` each per message.
# `hbm_credit` the wait for credit (no op where a landing never blocked),
# `hbm` the host time to ENQUEUE the message's one transfer and claim its
# spans (dispatch is asynchronous: not device time), `hbm_view` the lease
# hand-off. The landing moves host→device (dma_h2d), so every landed byte is
# also a copy byte of `hbm`.

_LENS_STAGES = {
    "land_many": "hbm-place",
}
_profiler.register_stages(__file__, _LENS_STAGES)


def _u8(payload) -> np.ndarray:
    """``payload`` (a buffer, or an array of any dtype) as flat bytes: a
    view, never a copy."""
    if isinstance(payload, np.ndarray):
        return payload.reshape(-1).view(np.uint8)
    return np.frombuffer(payload, np.uint8)


class HbmRing:
    """Credit window over device-resident landings: host-tracked head/tail
    + leases."""

    def __init__(self, capacity: int, device=None):
        if capacity < 64 or capacity & (capacity - 1):
            raise ValueError("capacity must be a power of two >= 64")
        self.capacity = capacity
        if device is None:
            from tpurpc.utils.jaxenv import default_device

            device = default_device()
        self.device = device
        self.tail = 0   # absolute bytes ever landed
        self.head = 0   # absolute bytes ever freed
        self._lock = threading.Lock()
        #: signaled whenever the head advances (space became writable)
        self._space = threading.Condition(self._lock)
        #: offset -> [nbytes, released] of every span the head has not
        #: passed, in tail order (claims are made under the lock): a span
        #: released out of order waits here for the ones before it
        self._live: Dict[int, list] = {}
        _HBM_RINGS.track(self)

    # -- producer ------------------------------------------------------------

    def writable(self) -> int:
        return self.capacity - (self.tail - self.head)

    def _wait_credit(self, n: int, timeout: Optional[float]) -> None:
        """Block (caller holds ``self._lock``) until ``n`` bytes are
        writable or ``timeout`` seconds pass; with ``timeout=None`` never
        waits. Raises :class:`BufferError` where the ring is still full.
        The wait, where there is one, is one op of the ``hbm_credit`` hop."""
        if n > self.writable() and timeout is not None:
            with _lens.stage("hbm_credit", n):
                deadline = time.monotonic() + timeout
                while n > self.writable():
                    remain = deadline - time.monotonic()
                    if remain <= 0 or not self._space.wait(timeout=remain):
                        break
        if n > self.writable():
            raise BufferError(f"HBM ring full: {n} > {self.writable()}")

    def land(self, payload, dtype, shape,
             timeout: Optional[float] = None) -> "HbmLease":
        """:meth:`land_many` of one leaf."""
        return self.land_many([(payload, dtype, shape)], timeout)[0]

    def land_many(self, leaves,
                  timeout: Optional[float] = None) -> "list[HbmLease]":
        """The landing: one message's ``(payload, dtype, shape)`` leaves in,
        one lease-backed device array per leaf out.

        The typed host views go to the device in ONE ``jax.device_put`` and
        ARE the arrays handed out (ledger ``dma_h2d`` and nothing else). The
        batch waits up to ``timeout`` for its TOTAL to fit
        (:class:`BufferError` where it cannot, at once over capacity), each
        non-empty leaf holds its own span, and the head advances in order as
        leases are released.

        A leaf whose dtype or shape does not fit its bytes raises, with
        every byte of the batch's credit returned."""
        import jax

        srcs = [_u8(p) for p, _, _ in leaves]
        total = sum(src.nbytes for src in srcs)
        if total > self.capacity:
            raise BufferError(
                f"message payloads total {total} bytes > ring capacity "
                f"{self.capacity}; raise TPURPC_HBM_RING_SIZE_KB")
        # the host decode's own views (codec.decode_tensor): free, and a
        # leaf that does not fit its bytes raises here, before any credit.
        # The transfer reads them AFTER device_put returns (chip probe,
        # PERF.md 6, PR 27); what keeps the wire buffer from being recycled
        # meanwhile is the reference jax holds on the view, which chains to
        # the buffer's owner, until the transfer completes.
        host = [src.view(dt).reshape(shape)
                for src, (_, dt, shape) in zip(srcs, leaves)]
        if self.device.platform == "cpu":
            # a CPU device adopts an aligned host buffer in place: the array
            # would alias the wire buffer, and pin it for life, where a
            # TPU's is a snapshot. The one movement the ledger bills is
            # made here
            host = [h.copy() for h in host]
        with self._lock:
            self._wait_credit(total, timeout)
            with _lens.stage("hbm", total) as st:
                st.copy = total
                arrays = jax.device_put(host, self.device)
                if total:
                    ledger.dma_h2d(total)
                # claimed only once the transfer is enqueued: one that
                # raises leaves nothing to undo
                spans, off = [], self.tail
                for h in host:
                    if h.nbytes:
                        # zero-size spans hold no credit: they would all
                        # share one offset and free each other's
                        self._live[off] = [h.nbytes, False]
                    spans.append((off, h.nbytes))
                    off += h.nbytes
                self.tail = off
        with _lens.stage("hbm_view", total):
            leases = [HbmLease(self, off, n, arr)
                      for (off, n), arr in zip(spans, arrays)]
        _HBM_PLACE_MSGS.inc(len(leases))
        _HBM_PLACE_BYTES.inc(total)
        return leases

    # -- consumer ------------------------------------------------------------

    def _release(self, off: int, n: int) -> None:
        """Return one lease's credit."""
        if n == 0:
            return  # zero-size spans hold no credit (never entered _live)
        with self._lock:
            self._live[off][1] = True
            self._advance_locked()

    def _advance_locked(self) -> None:
        """Advance head over every released prefix. Caller holds
        ``self._lock``."""
        advanced = False
        while self._live:
            off = next(iter(self._live))
            n, released = self._live[off]
            if not released:
                break
            del self._live[off]
            self.head = off + n
            advanced = True
        if advanced:
            self._space.notify_all()

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"capacity": self.capacity, "head": self.head,
                    "tail": self.tail, "live_spans": len(self._live),
                    "writable": self.writable()}


class HbmLease:
    """A landed device array holding its span of the window; release returns
    the credit. The array is a snapshot and survives the release.

    ``release()`` is idempotent; dropping the lease without releasing leaks
    the span until process exit (deliberate: silent auto-free under GC
    pressure would make flow control nondeterministic — the reference's
    credits are explicit too, ``pair.cc:276-284``)."""

    __slots__ = ("_ring", "_off", "_n", "array", "_released")

    def __init__(self, ring: HbmRing, off: int, n: int, array):
        self._ring = ring
        self._off = off
        self._n = n
        self.array = array
        self._released = False

    def release(self) -> None:
        if not self._released:
            self._released = True
            self._ring._release(self._off, self._n)

    def __enter__(self):
        return self.array

    def __exit__(self, *exc):
        self.release()
        return False
