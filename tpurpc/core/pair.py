"""Pair: one high-performance connection — two receive rings + a status word, glued by
one-sided writes.

Reference: ``src/core/lib/ibverbs/pair.{h,cc}`` (``PairPollable``).  A pair owns

* a **receive ring** the peer writes messages into (data moves by one-sided writes into
  the peer's ring at the mirrored tail — ``pair.cc:587-622`` ``postWrite``),
* a 16-byte **status buffer** ``{remote_head, peer_exit}`` the peer writes credits and
  the graceful-close flag into (``pair.h:100-103``),
* the six-state lifecycle ``kUninitialized → kInitialized → kConnected →
  kHalfClosed/kDisconnected/kError`` (``pair.h:44-51``), with ``init()`` explicitly
  reviving error/disconnected pairs for pool reuse (``pair.cc:85-141``).

Where the reference's one-sided write is an ``IBV_WR_RDMA_WRITE`` on an RC queue pair,
tpurpc abstracts it as a :class:`MemoryDomain` — in-process buffers for loopback,
POSIX shared memory for cross-process on one host, and a device-staged domain for the
TPU HBM ring (``tpurpc.tpu``).  The *protocol* (framing, credits, close, liveness) is
identical across domains, which is the property the reference proves by running three
different NIC disciplines over one ring format.

Bootstrap mirrors the reference exactly: a boring already-connected socket carries the
address exchange (``exchange_data``, ``rdma_bp_posix.cc:640-692``), after which the
socket is *kept* as the event/liveness channel — the reference keeps its TCP fd for
liveness too (``rdma_conn.h:90-99`` ``IsPeerAlive``) and delivers completion interrupts
via completion-channel fds (``rdma_conn.cc:24-26``); our notify socket plays both roles.
"""

from __future__ import annotations

import contextlib
import ctypes
import enum
import json
import os
import socket
import ssl
import struct
import threading
import time
import uuid
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from tpurpc.analysis.locks import make_lock
from tpurpc.core import _native
from tpurpc.core import transport as _transport
from tpurpc.obs import flight as _flight
from tpurpc.obs import lens as _lens
from tpurpc.obs import metrics as _metrics
from tpurpc.obs import profiler as _profiler
from tpurpc.obs import tracing as _tracing
from tpurpc.tpu import ledger as ring_ledger
from tpurpc.core.ring import (RingCorruption, RingReader, RingWriter,
                              _BYTES_OUT, _MSGS_OUT,
                              truncate_after_read as ring_truncate)
from tpurpc.utils import stats as _stats
from tpurpc.utils.config import get_config

# tpurpc-scope fleet gauges (ISSUE 4): evaluated at scrape time over the
# weakly-referenced live pairs — send-credit stalls and connection counts
# become visible on a live process with zero hot-path cost.
_PAIRS_CONNECTED = _metrics.fleet(
    "pairs_connected", lambda p: 1.0 if p.state.name == "CONNECTED" else 0.0)
_PAIRS_WRITE_STALLED = _metrics.fleet(
    "pairs_write_stalled",
    # CONNECTED only: a pair that died MID-STALL keeps want_write set while
    # anything still references it, and a dead pair's stall is not evidence
    # — the watchdog would keep attributing live calls to credit-starvation
    # long after the wedged peer was torn down (tpurpc-fleet, ISSUE 6)
    lambda p: 1.0 if (p.want_write and p.state.name == "CONNECTED")
    else 0.0)
# tpurpc-blackbox (ISSUE 5): a CONNECTED pair with a complete message
# sitting undrained — the watchdog's poller-wake-latency evidence. Scrape/
# sweep-time only; has_message is a header peek (native scan when built).
_PAIRS_MSG_WAITING = _metrics.fleet(
    "pairs_msg_waiting",
    lambda p: 1.0 if (p.state.name == "CONNECTED" and p.has_message())
    else 0.0)
# tpurpc-hive (ISSUE 16): the connection-scale plane. A parked pair holds
# no ring regions and no poller slot — just the notify socket and a stub —
# and the per-connection resident estimate is what the C100K bench curves
# report per ramp stage.
_PAIRS_PARKED = _metrics.fleet(
    "pairs_parked", lambda p: 1.0 if p._parked else 0.0)
_PAIR_RESIDENT = _metrics.fleet(
    "pair_resident_bytes_est", lambda p: float(p.resident_bytes_est()))
from tpurpc.utils.trace import trace_ring

# tpurpc-lens (ISSUE 8): the `wire` waterfall hop is the transport
# boundary — on this plane, Pair.send's one-sided placement (credit fold,
# chunking and ring encode included). The fused native send bypasses
# RingWriter, so its bytes land in the send_ring hop here too.
_LENS_WIRE_BYTES, _LENS_WIRE_NS, _LENS_WIRE_COPY = _lens.hop_counters("wire")
_LENS_SR_BYTES, _LENS_SR_NS, _LENS_SR_COPY = _lens.hop_counters("send_ring")

_LENS_STAGES = {
    "send": "pair-send",
    "_send_inner": "pair-send",
    "_send_fast": "pair-send",
    "recv_into": "ring-read",
    "recv": "ring-read",
    "spin": "poller-wait",
}
_profiler.register_stages(__file__, _LENS_STAGES)

_U64 = struct.Struct("<Q")

#: Status region layout. Two cache lines: the first holds the PEER-written
#: words (credit head, peer_exit — one-sided writes from the other side), the
#: second holds the LOCALLY-written waiter-advertisement words the peer only
#: reads. Separate lines so peer credit writes and local waiting-flag stores
#: never false-share (cross-process cache-line ping-pong on the hot path).
STATUS_BYTES = 128
_STATUS_HEAD_OFF = 0
_STATUS_EXIT_OFF = 8
#: "a read-waiter is blocked on the notify fd" — senders skip the notify
#: syscall when 0 (receiver is spinning or mid-drain). Futex-style protocol;
#: fences + proof in native/src/ring.cc tpr_store_u64_seqcst.
_STATUS_RXWAIT_OFF = 64
#: same, for a credit-stalled writer blocked on the notify fd
_STATUS_WXWAIT_OFF = 72
_WAIT_OFF = {"read": _STATUS_RXWAIT_OFF, "write": _STATUS_WXWAIT_OFF}


class PairState(enum.Enum):
    """Mirrors ``PairStatus`` (``pair.h:44-51``)."""

    UNINITIALIZED = "uninitialized"
    INITIALIZED = "initialized"
    CONNECTED = "connected"
    HALF_CLOSED = "half_closed"      # peer wrote peer_exit and stopped sending
    DISCONNECTED = "disconnected"
    ERROR = "error"


# ---------------------------------------------------------------------------
# Memory domains: who implements the one-sided write.
# ---------------------------------------------------------------------------

def retry_buffer_op(fn: Callable[[], None], timeout_s: float = 2.0) -> None:
    """Run a release/unmap that may transiently hit BufferError while a
    GIL-free native spin holds an exported view (≤ one bounded slice)."""
    deadline = time.monotonic() + timeout_s
    while True:
        try:
            fn()
            return
        except BufferError:
            if time.monotonic() >= deadline:
                raise
            time.sleep(0.001)


class Region:
    """A chunk of registerable memory owned by this side (ref: ``Buffer``,
    ``buffer.h:12-35`` — pinned + ibv_reg_mr there; here just addressable bytes)."""

    __slots__ = ("handle", "buf", "_close", "on_write")

    def __init__(self, handle: str, buf, close: Callable[[], None] = lambda: None):
        self.handle = handle
        self.buf = memoryview(buf)
        self._close = close
        #: Optional post-apply hook for ASYNCHRONOUS domains (tcp_window):
        #: called by the domain's applier after landing peer bytes in this
        #: region. Synchronous domains (local/shm) never call it — their
        #: writes are visible before the peer's notify token can arrive, so
        #: the token alone is a sufficient wakeup. With an async domain the
        #: token (notify socket) can BEAT the data (record socket); the
        #: applier's kick is what closes that lost-wakeup window.
        self.on_write: Optional[Callable[[], None]] = None

    def close(self) -> None:
        # A GIL-free native spin (Pair.spin) may still pin this memory through
        # an exported buffer view for ≤ one bounded spin slice; BOTH the
        # memoryview release and the shm unmap refuse while exports exist.
        # Retry briefly instead of leaking (the spinner unpins within one
        # bounded slice).
        retry_buffer_op(self.buf.release)
        retry_buffer_op(self._close)


class Window:
    """A write handle onto the *peer's* region (ref: ``MemoryRegion`` envelope shipping
    an ``ibv_mr`` descriptor, ``memory_region.h:14-47``)."""

    __slots__ = ("write", "view", "_close", "touched")

    def __init__(self, write: Callable[[int, bytes], None],
                 close: Callable[[], None] = lambda: None,
                 view: "Optional[memoryview]" = None):
        self.write = write  # write(offset, data) — one-sided, no peer CPU involved
        self.view = view    # mapped memory when host-addressable (native path)
        self._close = close
        #: bytes from the start of ``view`` this process has written through
        #: it, so their pages are mapped here (rendezvous._place_spans)
        self.touched = 0

    def close(self) -> None:
        self._close()


class MemoryDomain:
    """Allocates local regions and opens windows onto peer regions by handle."""

    kind = "abstract"

    def alloc(self, nbytes: int) -> Region:
        raise NotImplementedError

    def open_window(self, handle: str, nbytes: int) -> Window:
        raise NotImplementedError


class LocalDomain(MemoryDomain):
    """In-process domain: regions live in a process-wide registry; windows write
    directly.  This is the "loopback PairPollable" the reference never wrote
    (SURVEY.md §4 calls it the missing fake) — it lets the full pair/poller/endpoint
    stack run in CI with zero hardware."""

    kind = "local"
    _registry: Dict[str, bytearray] = {}
    _lock = make_lock("LocalDomain._lock")

    def alloc(self, nbytes: int) -> Region:
        handle = f"local:{uuid.uuid4().hex}"
        buf = bytearray(nbytes)
        with self._lock:
            self._registry[handle] = buf

        def _close():
            with self._lock:
                self._registry.pop(handle, None)

        return Region(handle, buf, _close)

    def open_window(self, handle: str, nbytes: int) -> Window:
        with self._lock:
            buf = self._registry[handle]
        mv = memoryview(buf)

        def write(off: int, data) -> None:
            mv[off:off + len(data)] = data

        return Window(write, mv.release, view=mv)


class ShmDomain(MemoryDomain):
    """Cross-process domain over POSIX shared memory: a server and its local clients
    exchange ring writes through ``/dev/shm`` with zero kernel involvement per
    message — the closest host-only analog of the reference's NIC-placed writes."""

    kind = "shm"

    # The allocator owns unlink explicitly (Region.close); Python's
    # resource_tracker would otherwise unlink from every process that ever
    # mapped the segment. Unregistering after the fact still races (processes
    # sharing one inherited tracker each send UNREGISTER → KeyError spam in the
    # tracker daemon), so suppress the registration itself. Python 3.13 has
    # SharedMemory(track=False); this is the 3.12 equivalent.
    _track_mu = make_lock("ShmDomain._track_mu")

    @staticmethod
    @contextlib.contextmanager
    def _untracked():
        from multiprocessing import resource_tracker

        with ShmDomain._track_mu:
            orig_reg = resource_tracker.register
            orig_unreg = resource_tracker.unregister

            def _skip_reg(name, rtype):
                if rtype != "shared_memory":
                    orig_reg(name, rtype)

            def _skip_unreg(name, rtype):
                if rtype != "shared_memory":
                    orig_unreg(name, rtype)

            resource_tracker.register = _skip_reg
            resource_tracker.unregister = _skip_unreg
            try:
                yield
            finally:
                resource_tracker.register = orig_reg
                resource_tracker.unregister = orig_unreg

    def alloc(self, nbytes: int) -> Region:
        from multiprocessing import shared_memory

        with self._untracked():
            shm = shared_memory.SharedMemory(create=True, size=nbytes)

        def _close():
            shm.close()
            with self._untracked():  # unlink() also talks to the tracker
                try:
                    shm.unlink()
                except FileNotFoundError:
                    pass

        return Region(f"shm:{shm.name}", shm.buf, _close)

    def open_window(self, handle: str, nbytes: int) -> Window:
        from multiprocessing import shared_memory

        assert handle.startswith("shm:")
        with self._untracked():
            shm = shared_memory.SharedMemory(name=handle[4:])
        mv = shm.buf

        def write(off: int, data) -> None:
            mv[off:off + len(data)] = data

        def _close():
            mv.release()
            shm.close()

        return Window(write, _close, view=mv)


_DOMAINS: Dict[str, Callable[[], MemoryDomain]] = {
    "local": LocalDomain,
    "shm": ShmDomain,
}

#: domains that register themselves on first import — tcp_window because
#: its import starts background machinery (a record server), verbs (the
#: RDMA-NIC skeleton) because construction raises a clear RuntimeError
#: where libibverbs is unavailable
_LAZY_DOMAINS = {"tcp_window": "tpurpc.core.tcpw",
                 "verbs": "tpurpc.core.verbs"}


def register_domain(kind: str, factory: Callable[[], MemoryDomain]) -> None:
    """Extension point the TPU domain uses (``tpurpc.tpu``)."""
    _DOMAINS[kind] = factory


def make_domain(kind: str) -> MemoryDomain:
    """Instantiate a registered domain by name (the ``TPURPC_RING_DOMAIN``
    dispatch). ``tcp_window`` registers lazily on first use — it is the only
    domain whose import starts background machinery (a record server)."""
    if kind not in _DOMAINS and kind in _LAZY_DOMAINS:
        import importlib

        importlib.import_module(_LAZY_DOMAINS[kind])  # registers itself
    factory = _DOMAINS.get(kind)
    if factory is None:
        raise ValueError(f"unknown ring domain {kind!r} "
                         f"(have {sorted(_DOMAINS)})")
    # call OUTSIDE the lookup guard: a KeyError raised inside a registered
    # factory must surface as itself, not as "unknown ring domain"
    return factory()


# ---------------------------------------------------------------------------
# Shared ring-region pool (tpurpc-hive, ISSUE 16).
# ---------------------------------------------------------------------------

_POOL_LEASED_BYTES = _metrics.gauge("ring_pool_leased_bytes")
_POOL_FREE_BYTES = _metrics.gauge("ring_pool_free_bytes")


class RingPool:
    """Process-wide free list of ring/status regions keyed by
    ``(domain kind, byte size)`` — the RDMAvisor-style shared resource pool
    that lets 50k mostly-idle pairs multiplex O(size-classes) ring
    allocations instead of pinning one ring each.

    Safety invariant: a region may enter the free list ONLY once no peer
    window onto it can still write.  ``Pair.init`` forbids region reuse
    within a connection exactly because a stale one-sided writer could land
    bytes in the next tenant's ring; the park handshake's ACK (the peer
    confirming it closed its windows) is the proof that makes cross-pair
    reuse safe here.  Free regions are zeroed before shelving so a fresh
    :class:`~tpurpc.core.ring.RingReader` can never misparse a previous
    tenant's frame headers as live messages.

    Only plain host-memory domains are pooled; device/NIC-bound regions
    (verbs QPs, tcp_window applier bindings) pass through to alloc/close so
    their peer-specific state is never handed to a different pair.
    """

    _instance: "Optional[RingPool]" = None
    _instance_lock = make_lock("RingPool._instance_lock")

    #: lock map, checked by `python -m tpurpc.analysis` (lint rule `lock`)
    _GUARDED_BY = {"_free": "_lock", "_free_bytes": "_lock",
                   "_out": "_lock", "_instance": "_instance_lock"}

    _POOLABLE = frozenset({"local", "shm"})
    _MAX_FREE_BYTES = 256 << 20
    _MAX_FREE_PER_CLASS = 4096

    @classmethod
    def get(cls) -> "RingPool":
        with cls._instance_lock:
            if cls._instance is None:
                cls._instance = RingPool()
            return cls._instance

    @classmethod
    def reset(cls) -> None:
        with cls._instance_lock:
            inst, cls._instance = cls._instance, None
        if inst is not None:
            inst.drain()

    def __init__(self):
        self._free: Dict[Tuple[str, int], List[Region]] = {}
        self._free_bytes = 0
        #: id(region) -> nbytes for regions handed out by lease() — release
        #: of a region the pool never leased (a pair's original init()
        #: allocation entering the pool at first park) must not drive the
        #: leased gauge negative
        self._out: Dict[int, int] = {}
        self._lock = make_lock("RingPool._lock")

    def lease(self, domain: MemoryDomain, nbytes: int) -> Region:
        """Hand out a writer-free region of exactly ``nbytes`` — recycled
        from the free list when the size class has one, freshly allocated
        otherwise.  Callers MUST pair every lease with a :meth:`release` on
        their failure paths (lint rule ``ringpool``)."""
        key = (domain.kind, nbytes)
        region = None
        with self._lock:
            bucket = self._free.get(key)
            if bucket:
                region = bucket.pop()
                self._free_bytes -= nbytes
        if region is None:
            region = domain.alloc(nbytes)
            _stats.counter_inc("ring_pool_alloc")
        else:
            _stats.counter_inc("ring_pool_hit")
        with self._lock:
            self._out[id(region)] = nbytes
            _POOL_LEASED_BYTES.set(float(sum(self._out.values())))
            _POOL_FREE_BYTES.set(float(self._free_bytes))
        return region

    def release(self, region: Optional[Region]) -> None:
        """Return a region to the free list (or close it when the domain
        isn't poolable / the list is full).  The caller asserts the pool
        invariant: no peer window onto this region can still write."""
        if region is None:
            return
        region.on_write = None
        try:
            nbytes = len(region.buf)
        except ValueError:
            nbytes = 0  # already released; nothing to pool
        kind = region.handle.split(":", 1)[0]
        with self._lock:
            self._out.pop(id(region), None)
            poolable = (nbytes > 0 and kind in self._POOLABLE
                        and self._free_bytes + nbytes <= self._MAX_FREE_BYTES
                        and len(self._free.get((kind, nbytes), ()))
                        < self._MAX_FREE_PER_CLASS)
        if poolable:
            try:
                # zero before shelving: the next tenant's reader starts at
                # head 0 and must never see this tenant's frame headers
                np.frombuffer(region.buf, dtype=np.uint8).fill(0)
            except (ValueError, TypeError):
                poolable = False
        if not poolable:
            try:
                region.close()
            except Exception:
                pass
            with self._lock:
                _POOL_LEASED_BYTES.set(float(sum(self._out.values())))
            return
        with self._lock:
            self._free.setdefault((kind, nbytes), []).append(region)
            self._free_bytes += nbytes
            _POOL_LEASED_BYTES.set(float(sum(self._out.values())))
            _POOL_FREE_BYTES.set(float(self._free_bytes))

    def forget(self, region: Optional[Region]) -> None:
        """Drop lease accounting for a region its owner is closing directly
        — teardown paths where the region must NOT re-enter the free list
        (no peer window-close ack exists, so the pool invariant is unproven)."""
        if region is None:
            return
        with self._lock:
            if self._out.pop(id(region), None) is not None:
                _POOL_LEASED_BYTES.set(float(sum(self._out.values())))

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"free_bytes": self._free_bytes,
                    "free_regions": sum(len(b) for b in self._free.values()),
                    "leased_bytes": sum(self._out.values()),
                    "leased_regions": len(self._out)}

    def drain(self) -> None:
        with self._lock:
            regions = [r for b in self._free.values() for r in b]
            self._free.clear()
            self._free_bytes = 0
            _POOL_FREE_BYTES.set(0.0)
        for r in regions:
            try:
                r.close()
            except Exception:
                pass


# ---------------------------------------------------------------------------
# Address: what gets exchanged at bootstrap.
# ---------------------------------------------------------------------------

class Address:
    """Serializable rendezvous blob (ref: ``Address`` with lid/qpn/psn/gid/tag/
    ring_buffer_size, ``address.h:24-31``; peers assert tag+size match,
    ``pair.cc:148-149``)."""

    def __init__(self, tag: str, domain_kind: str, ring_size: int,
                 ring_handle: str, status_handle: str,
                 caps: "Optional[Sequence[str]]" = None):
        self.tag = tag
        self.domain_kind = domain_kind
        self.ring_size = ring_size
        self.ring_handle = ring_handle
        self.status_handle = status_handle
        #: capability strings, negotiated at bootstrap. "waitflag" = this side
        #: publishes the waiter-advertisement words (native fences present),
        #: so its peer may skip notify bytes when no waiter is advertised.
        #: A peer that doesn't advertise it (TPURPC_NATIVE=0, older version)
        #: gets unconditional notifies — asymmetric processes never lose
        #: wakeups (reviewer finding: the skip must be opt-in per peer).
        self.caps = frozenset(caps or ())

    def to_bytes(self) -> bytes:
        return json.dumps({
            "tag": self.tag,
            "domain": self.domain_kind,
            "ring_size": self.ring_size,
            "ring": self.ring_handle,
            "status": self.status_handle,
            "caps": sorted(self.caps),
        }).encode()

    @classmethod
    def from_bytes(cls, raw: bytes) -> "Address":
        d = json.loads(raw.decode())
        return cls(d["tag"], d["domain"], d["ring_size"], d["ring"],
                   d["status"], d.get("caps", ()))


#: Bootstrap frame magic.  A peer whose GRPC_PLATFORM_TYPE disagrees (e.g. a TCP
#: client hitting a ring server) sends arbitrary bytes here; the magic check turns
#: that misconfiguration into an immediate clear error instead of a hang.  (The
#: reference has no such guard — mismatched env vars are undefined behavior there.)
_BOOTSTRAP_MAGIC = b"TRB1"
_MAX_BLOB = 1 << 16
#: Bound on the address-exchange handshake (ref exchange_data poll loop is also
#: bounded, rdma_bp_posix.cc:640-692).
BOOTSTRAP_TIMEOUT_S = 20.0


def _send_blob(sock: socket.socket, blob: bytes) -> None:
    sock.sendall(_BOOTSTRAP_MAGIC + struct.pack("<I", len(blob)) + blob)


def _recv_blob(sock: socket.socket, preread: bytes = b"") -> bytes:
    magic = preread + _recv_exact(sock, 4 - len(preread))
    if magic != _BOOTSTRAP_MAGIC:
        raise ConnectionError(
            f"bad bootstrap magic {magic!r}: peer is not speaking the ring "
            f"bootstrap protocol (GRPC_PLATFORM_TYPE mismatch between peers?)")
    need = struct.unpack("<I", _recv_exact(sock, 4))[0]
    if need > _MAX_BLOB:
        raise ConnectionError(f"bootstrap blob implausibly large ({need} bytes)")
    return _recv_exact(sock, need)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    out = b""
    while len(out) < n:
        chunk = sock.recv(n - len(out))
        if not chunk:
            raise ConnectionError("peer closed during address exchange")
        out += chunk
    return out


def peek_protocol(sock: socket.socket, timeout: float = BOOTSTRAP_TIMEOUT_S
                  ) -> bytes:
    """Server-side protocol dispatch: consume and return the first 4 bytes.

    A ring-platform listener uses this to route each accepted connection —
    ring clients open with the TRB1 bootstrap magic; stock gRPC (h2 preface)
    and native-TCP-framing clients get a TCP endpoint carrying the preread
    bytes instead of a bootstrap error. Works identically on TLS sockets
    (the bytes are post-decryption), which MSG_PEEK cannot."""
    old = sock.gettimeout()
    sock.settimeout(timeout)
    try:
        return _recv_exact(sock, 4)
    finally:
        try:
            sock.settimeout(old)
        except OSError:
            pass


# ---------------------------------------------------------------------------
# The Pair.
# ---------------------------------------------------------------------------

#: notify tokens carried on the notify socket (≈ completion events / WRITE_WITH_IMM)
NOTIFY_DATA = b"d"
NOTIFY_CREDIT = b"c"
NOTIFY_EXIT = b"x"
#: tpurpc-hive park-protocol tokens (same stream; see Pair.maybe_park).
#: PARK asks the peer to close its one-sided windows into our regions and
#: answer ACK — only that ack proves no stale writer remains, which is THE
#: invariant letting the regions enter the shared RingPool despite init()'s
#: always-fresh rule. NACK aborts (peer mid-send). WAKE asks a parked peer
#: to re-arm because we have bytes for it; REARM prefixes a framed Address
#: blob advertising fresh (or, on a park abort, retained) rings.
NOTIFY_PARK = b"p"
NOTIFY_PARK_ACK = b"q"
NOTIFY_PARK_NACK = b"n"
NOTIFY_WAKE = b"w"
#: "r" = re-arm onto FRESHLY LEASED rings (unpark): the peer builds a writer
#: at position zero. "R" = re-arm onto RETAINED rings (park abort / repair):
#: the peer restores its snapshotted writer position. The distinction must
#: ride the frame itself — the RingPool can hand the SAME region straight
#: back to the same pair, so handle identity cannot tell a fresh lease from
#: retained rings (observed: a recycled handle made the peer restore a stale
#: tail against a zeroed ring, black-holing the first post-unpark send).
NOTIFY_REARM = b"r"
NOTIFY_REARM_KEEP = b"R"
_CLASSIC_TOKENS = b"dcx"


class _ParkBusy(Exception):
    """Raised inside the send guard when a park episode owns the write side.
    Internal control flow only: ``Pair.send`` catches it, resolves the episode
    OUTSIDE the guard (strict lock order: _park_lock before _send_guard), and
    retries — callers never see it."""


class ContentAssertion:
    """Single-entrant tripwire on send/recv, like the reference's reentrancy guard
    (``pair.h:64-81``): two threads inside Send (or Recv) concurrently is a caller bug
    we want to explode loudly, not corrupt a ring.

    The park protocol's handlers (window close, re-arm, park initiation) also
    need the guard — they mutate the same side — but they run on the DRAIN or
    poller thread, not the caller's: a legitimate send/recv racing one of them
    is NOT a caller bug.  ``maintenance()`` entry marks the occupancy so the
    regular entry raises the retryable :class:`_ParkBusy` instead of the
    tripwire (found by schedule exploration: a sender crashed with the
    concurrent-entry AssertionError while the peer's park request was being
    handled)."""

    def __init__(self, name: str):
        self._name = name
        self._flag = False
        self._maint = False
        self._lock = make_lock(f"ContentAssertion[{name}]._lock")

    def __enter__(self):
        with self._lock:
            if self._flag:
                if self._maint:
                    raise _ParkBusy
                raise AssertionError(f"concurrent entry into {self._name}")
            self._flag = True

    def __exit__(self, *exc):
        with self._lock:
            self._flag = False
            self._maint = False
        return False

    @contextlib.contextmanager
    def maintenance(self):
        """Guard entry for a park-protocol handler: excludes an in-flight
        send/recv exactly like regular entry (AssertionError on conflict —
        the handler aborts or NACKs), but marks the hold so a racing
        REGULAR entrant gets the retryable :class:`_ParkBusy`."""
        with self._lock:
            if self._flag:
                raise AssertionError(f"concurrent entry into {self._name}")
            self._flag = True
            self._maint = True
        try:
            yield self
        finally:
            self.__exit__()


class Pair:
    """One connection's data plane.  Thread model: one sender thread + one receiver
    thread at a time (enforced by :class:`ContentAssertion`), any thread may poll."""

    def __init__(self, domain: Optional[MemoryDomain] = None,
                 ring_size: Optional[int] = None, tag: Optional[str] = None):
        cfg = get_config()
        self.domain = domain or LocalDomain()
        self.ring_size = ring_size or cfg.ring_buffer_size
        self.tag = tag or uuid.uuid4().hex[:12]
        self.state = PairState.UNINITIALIZED
        self.error: Optional[str] = None

        self.recv_region: Optional[Region] = None
        self.status_region: Optional[Region] = None
        self.reader: Optional[RingReader] = None
        self.writer: Optional[RingWriter] = None
        self._peer_ring: Optional[Window] = None
        self._peer_status: Optional[Window] = None

        #: peer-driven event channel (completion interrupts + liveness); set at connect
        self.notify_sock: Optional[socket.socket] = None
        #: local wakeup pipes (BPEV's grpc_wakeup_fd, pair.h:187) — ONE PER
        #: WAITER ROLE. The notify socket is shared and its tokens are
        #: consumed by whichever waiter drains first; a per-role pipe that
        #: only its own waiter consumes is what makes the kick-after-drain
        #: broadcast lossless (a reader eating a credit token re-kicks both
        #: pipes; the writer's pipe byte can only be consumed by the writer).
        self._wake_r: Dict[str, int] = {"read": -1, "write": -1}
        self._wake_w: Dict[str, int] = {"read": -1, "write": -1}
        #: persistent per-role selectors (epoll fd reused across waits — a
        #: fresh DefaultSelector per wait is 5 syscalls of pure overhead on
        #: the small-RPC path)
        self._selectors: Dict[str, object] = {}
        #: cached (np array, address) pins of the status pages for the
        #: waiter-advertisement words; nulled by teardown before any close
        self._status_np = None
        self._peer_status_np = None
        #: peer capability strings from the bootstrap Address (see Address.caps)
        self.peer_caps: frozenset = frozenset()

        self._send_guard = ContentAssertion("Pair.send")
        self._recv_guard = ContentAssertion("Pair.recv")
        self._credit_lock = make_lock("Pair._credit_lock")
        self._published_head_mirror = 0  # last head value we published to the peer
        self.want_write = False  # a sender is stalled waiting for credits
        #: adaptive-BPEV activity score (see tpurpc/core/poller.py EWMA
        #: constants): 1.0 = hot (waiters busy-poll), decays toward 0 on
        #: spin misses so idle pairs park on fds without spinning first
        self.activity_ewma = 1.0
        # monotonic counters (ref: per-pair live counters, pair.h:235-270)
        self.total_sent = 0
        self.total_recv = 0

        # serializes notify-socket writes (single-byte tokens AND the
        # multi-byte re-arm frame — an interleaved token inside a frame
        # would corrupt the peer's stream parser)
        self._notify_lock = make_lock("Pair._notify_lock")
        # tpurpc-hive (ISSUE 16): idle-pair parking. Lock order where both
        # are held: _park_lock BEFORE _send_guard (the park-request handler
        # takes them in that order; send paths check the park flags inside
        # the guard and RETRY outside it, never acquiring _park_lock under
        # the guard).
        self._park_lock = make_lock("Pair._park_lock")
        #: serializes drain_notifications end to end so the park-protocol
        #: parser sees the token stream in order (two waiters recv'ing
        #: concurrently would otherwise interleave a framed re-arm blob)
        self._drain_mu = make_lock("Pair._drain_mu")
        self._parked = False          # own regions pooled; ~stub remains
        self._park_pending = False    # PARK sent, ack/nack not yet seen
        self._park_sent_at = 0.0
        self._peer_parked = False     # peer's regions gone; writer is None
        #: (peer ring handle, tail, seq, remote_head) snapshot taken when a
        #: peer's PARK closes our writer — restored verbatim if the peer
        #: aborts the park and re-arms with the SAME rings
        self._saved_wstate: Optional[Tuple[str, int, int, int]] = None
        self._peer_ring_handle = ""
        self._notify_buf = b""        # partial re-arm frame reassembly
        self.last_activity = time.monotonic()
        self.parked_epochs = 0        # completed park->unpark round trips
        #: tpurpc-blackbox: interned flight-recorder tag (ints on the hot
        #: path) + open credit-starvation edge + adaptive-poll mode, all
        #: edge-triggered so a healthy pair emits nothing per message
        self._ftag = _flight.tag_for("pair:" + self.tag)
        self._starve_open = False
        self._flight_mode = "bp"
        _PAIRS_CONNECTED.track(self)
        _PAIRS_WRITE_STALLED.track(self)
        _PAIRS_MSG_WAITING.track(self)
        _PAIRS_PARKED.track(self)
        _PAIR_RESIDENT.track(self)

    # -- lifecycle ----------------------------------------------------------

    def init(self) -> None:
        """Allocate fresh rings, reset counters.  Revives ERROR/DISCONNECTED/
        quiesced pairs like the reference (``pair.cc:85-141``, explicitly
        re-initializing recycled pool pairs).

        Regions are always NEW allocations (new shm name), never zero-and-reuse:
        a previous peer that still holds a window onto the old region (its sender
        racing past a state check at disconnect time) must land its stale
        one-sided writes in the orphaned segment, not in the next connection's
        ring.  The reference gets this for free because tearing down the QP kills
        in-flight RDMA; a shm window has no such fence."""
        self._release_channels()
        self._release_regions()
        self.recv_region = self.domain.alloc(self.ring_size)
        self.status_region = self.domain.alloc(STATUS_BYTES)
        # Async-domain wakeup closure (see Region.on_write): data landing in
        # the ring wakes readers; credits/exit landing in the status page
        # wake stalled writers. kick() is idempotent and cheap (pipe byte).
        self.recv_region.on_write = self.kick
        self.status_region.on_write = self.kick
        self.reader = RingReader(self.recv_region.buf, self.ring_size)
        self.writer = None  # created at connect, once peer ring size is known
        self._published_head_mirror = 0
        self.error = None
        self.want_write = False
        self.activity_ewma = 1.0  # recycled pairs start hot like fresh ones
        # hive park state never survives a re-init (fresh connection)
        self._parked = False
        self._park_pending = False
        self._peer_parked = False
        self._saved_wstate = None
        self._notify_buf = b""
        self.last_activity = time.monotonic()
        for role in ("read", "write"):
            r, w = os.pipe()
            os.set_blocking(r, False)
            os.set_blocking(w, False)
            self._wake_r[role] = r
            self._wake_w[role] = w
        self.state = PairState.INITIALIZED

    def local_address(self) -> Address:
        assert self.state in (PairState.INITIALIZED, PairState.CONNECTED)
        caps = ["waitflag"] if _native.load() is not None else []
        # tpurpc-express (ISSUE 9): advertise the rendezvous capability in
        # the bootstrap blob — a ring-plane connection then arms its bulk
        # plane at CONNECT TIME (core/rendezvous.py), with no hello round
        # trip to race the first big payload. Import-cycle-free probe: the
        # env gate lives in the rendezvous module, but pair must not
        # import it (rendezvous imports pair), so read the switch directly.
        if os.environ.get("TPURPC_RENDEZVOUS", "1").lower() not in (
                "0", "off", "false"):
            caps.append("rdv")
        # tpurpc-hive (ISSUE 16): park is a two-sided protocol — the peer
        # must ack the window-close and honor WAKE/REARM. Advertise it so
        # maybe_park never initiates against a peer that cannot answer
        # (the native C loop bootstraps its own Address without this cap;
        # a park request to it would retry forever and never complete).
        caps.append("park")
        return Address(self.tag, self.domain.kind, self.ring_size,
                       self.recv_region.handle, self.status_region.handle,
                       caps=caps)

    def connect_over_socket(self, sock: socket.socket,
                            preread: bytes = b"") -> None:
        """Bootstrap over an already-connected socket: both sides swap Address blobs,
        then open one-sided windows (ref: ``exchange_data`` over the TCP fd,
        ``rdma_bp_posix.cc:640-692``; MR swap ``pair.cc:472-486``).  The socket stays
        alive as the notify/liveness channel.

        The handshake is bounded by ``BOOTSTRAP_TIMEOUT_S``: a peer that connects
        but never speaks (port scanner, platform-mismatched server that handed the
        socket straight to its app) produces a timeout error, not a hang."""
        if self.state is not PairState.INITIALIZED:
            raise RuntimeError(f"connect in state {self.state}")
        sock.settimeout(BOOTSTRAP_TIMEOUT_S)
        try:
            _send_blob(sock, self.local_address().to_bytes())
            peer = Address.from_bytes(_recv_blob(sock, preread))
        except socket.timeout as exc:
            raise ConnectionError(
                f"pair bootstrap timed out after {BOOTSTRAP_TIMEOUT_S}s "
                "(peer not speaking the ring bootstrap protocol?)") from exc
        finally:
            try:
                sock.settimeout(None)
            except OSError:
                pass
        self._attach_peer(peer)
        sock.setblocking(False)
        try:
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        except OSError:
            pass  # not a TCP socket (e.g. unix socketpair)
        self.notify_sock = sock

    def _attach_peer(self, peer: Address) -> None:
        if peer.domain_kind != self.domain.kind:
            raise ValueError(f"domain mismatch: {peer.domain_kind} vs {self.domain.kind}")
        # Reference asserts ring sizes match (pair.cc:148-149); we allow asymmetric
        # rings — the writer just honors the peer's capacity.
        self._peer_ring = self.domain.open_window(peer.ring_handle, peer.ring_size)
        self._peer_status = self.domain.open_window(peer.status_handle, STATUS_BYTES)
        self._peer_ring_handle = peer.ring_handle
        self.peer_caps = peer.caps
        self.writer = RingWriter(peer.ring_size, self._peer_ring.write,
                                 mapped=self._peer_ring.view)
        self.writer.flight_tag = self._ftag
        self.state = PairState.CONNECTED
        _flight.emit(_flight.PAIR_CONNECT, self._ftag, peer.ring_size)
        trace_ring.log("pair %s connected (peer tag %s, ring %d)",
                       self.tag, peer.tag, peer.ring_size)

    # -- notify channel (completion events) ----------------------------------

    # -- waiter advertisement (futex-style sleep handshake) -------------------

    def _status_pin(self):
        """Cached (array, addr) pin of our status region, or None.

        The array reference is what makes the cached address safe: it holds a
        buffer export, so the region cannot unmap under a native call that
        grabbed the pin into a local (teardown nulls the cache FIRST, then
        Region.close retries its release for the in-flight window)."""
        pin = self._status_np
        if pin is None:
            region = self.status_region
            if region is None:
                return None
            try:
                pin = _native.pin(region.buf, writable=True)
            except (ValueError, TypeError):
                return None  # racing teardown
            self._status_np = pin
            if self.status_region is not region:
                # Teardown nulled the attribute between our read and the
                # cache store; a cached export would wedge Region.close's
                # retry forever. Drop it — our local still pins safely for
                # this one call (the retry covers that bounded window).
                self._status_np = None
                return None
        return pin

    def _peer_status_pin(self):
        pin = self._peer_status_np
        if pin is None:
            win = self._peer_status
            if win is None or win.view is None:
                return None
            try:
                pin = _native.pin(win.view, writable=False)
            except (ValueError, TypeError):
                return None
            self._peer_status_np = pin
            if self._peer_status is not win:  # see _status_pin
                self._peer_status_np = None
                return None
        return pin

    def set_waiting(self, role: str, flag: bool) -> None:
        """Publish 'this role is blocked on the notify fd' in our status
        region, where the peer's data/credit producer reads it (one-sided,
        like everything else in the status page). seq_cst store = the full
        fence the sleep protocol's Dekker argument needs (ring.cc).

        No-op without the native lib: then producers notify unconditionally
        (`_peer_waiting` returns True), which is the pre-advertisement
        behavior — correct, just one syscall heavier per send."""
        lib = _native.load()
        if lib is None:
            return
        pin = self._status_pin()
        if pin is None:
            return  # racing teardown; waiters re-check state and exit
        lib.tpr_store_u64_seqcst(pin[1] + _WAIT_OFF[role], 1 if flag else 0)

    def _peer_waiting(self, role: str) -> bool:
        """Is the peer's ``role`` waiter blocked on its notify fd?  True also
        when we can't tell (no native fences / window gone) — then the caller
        sends the notify byte unconditionally, trading a syscall for safety.

        The fenced load after our data/footer/header stores is the producer
        half of the sleep protocol (StoreLoad ordering; ring.cc).

        Negotiated: only a peer that advertised "waitflag" at bootstrap (its
        process has the native fences and DOES publish the words) may have
        its notifies skipped — an asymmetric peer (TPURPC_NATIVE=0, older
        build) leaves the words at 0 forever, which without the capability
        gate would read as "nobody is waiting" and hang it permanently."""
        lib = _native.load()
        if lib is None or "waitflag" not in self.peer_caps:
            return True
        pin = self._peer_status_pin()
        if pin is None:
            return True
        return bool(lib.tpr_load_u64_fenced(pin[1] + _WAIT_OFF[role]))

    def _notify(self, token: bytes) -> None:
        # cross-process message: the transport seam makes the token's
        # send timing an explorable pick under simnet (the raw socket
        # send stays in _notify_raw — the xproc lint rule's allowance)
        _transport.dispatch("frame", self, self._notify_raw, token)

    def _notify_raw(self, token: bytes) -> None:
        sock = self.notify_sock
        if sock is None:
            return
        try:
            # Always locked since tpurpc-hive: the notify stream now also
            # carries multi-byte re-arm frames (_send_frame), and a token
            # landing INSIDE a frame corrupts the peer's parser. (TLS needed
            # the lock anyway — OpenSSL forbids concurrent use of one SSL*,
            # the TcpEndpoint fix.) Single-byte sends can't partially
            # complete, so a dropped token under EAGAIN stays best-effort.
            with self._notify_lock:
                sock.send(token)
        except (ssl.SSLWantWriteError, ssl.SSLWantReadError):
            pass  # TLS record stalled mid-flight; same as a saturated channel
        except (BlockingIOError, InterruptedError):
            pass  # event channel saturated — busy/hybrid pollers don't need it
        except OSError:
            # Best-effort: a send failure here usually means the peer already
            # left (EPIPE after its graceful close) — the authoritative death
            # signals are the peer_exit status word and the RECV-side probe
            # (empty read) in drain_notifications/peek_events. Marking ERROR
            # here turned every graceful close into a poisoned receive path
            # for whatever data was still draining.
            pass

    def _send_frame(self, payload: bytes, timeout_s: float = 5.0) -> bool:
        """Ship a multi-byte park-protocol frame over the notify stream,
        contiguously (the lock excludes token sends) and completely (the
        socket is non-blocking; a PARTIAL frame would corrupt the peer's
        parser, so retry to a bounded deadline instead of dropping)."""
        return bool(_transport.dispatch("frame", self, self._send_frame_raw,
                                        payload, timeout_s))

    def _send_frame_raw(self, payload: bytes, timeout_s: float = 5.0) -> bool:
        import select as _select

        sock = self.notify_sock
        if sock is None:
            return False
        deadline = time.monotonic() + timeout_s
        sent = 0
        with self._notify_lock:
            while sent < len(payload):
                try:
                    sent += sock.send(payload[sent:])
                except (BlockingIOError, InterruptedError,
                        ssl.SSLWantWriteError, ssl.SSLWantReadError):
                    if time.monotonic() >= deadline:
                        return False
                    try:
                        _select.select([], [sock.fileno()], [], 0.05)
                    except (OSError, ValueError):
                        return False
                except OSError:
                    return False
        return True

    def drain_notifications(self) -> bytes:
        """Non-blocking drain of the peer-event channel; returns the tokens seen.
        An empty-read (peer closed) flips the pair to ERROR, the moral equivalent of
        the reference's TCP-fd zero-byte liveness probe (``rdma_conn.h:90-99``).

        Serialized end to end (``_drain_mu``) since tpurpc-hive: the stream
        now carries park-protocol bytes and framed re-arm blobs whose parse
        requires seeing the bytes in order — two waiters recv'ing
        concurrently would interleave a split frame.  Park-protocol bytes
        are acted on here and stripped; callers see only the classic
        data/credit/exit tokens."""
        with self._drain_mu:
            raw = self._drain_raw()
            if not raw and not self._notify_buf:
                return raw
            if not self._notify_buf and not raw.translate(None,
                                                          _CLASSIC_TOKENS):
                return raw  # fast path: classic tokens only
            return self._fold_park_tokens(raw)

    def _drain_raw(self) -> bytes:
        sock = self.notify_sock
        if sock is None:
            return b""
        is_tls = hasattr(sock, "pending")
        out = b""
        while True:
            try:
                if is_tls:
                    # serialize with _notify's sends (see there: concurrent
                    # SSL_read/SSL_write on one SSL* is UB). recv is
                    # non-blocking — the lock hold is microseconds.
                    with self._notify_lock:
                        chunk = sock.recv(65536)
                else:
                    chunk = sock.recv(65536)
            except (BlockingIOError, InterruptedError,
                    ssl.SSLWantReadError, ssl.SSLWantWriteError):
                break  # nothing decryptable yet ≡ EAGAIN on a plain socket
            except OSError:
                # A STALE caller may hold a socket from a previous life of
                # this pooled pair (teardown closed it; init() replaced it):
                # its EBADF must not poison the pair's NEW connection.
                if sock is self.notify_sock and sock.fileno() != -1:
                    self._mark_error("notify channel read failed")
                break
            if chunk == b"":
                if sock is self.notify_sock:  # stale-life guard (see peek)
                    self._on_notify_closed()
                break
            out += chunk
            if len(chunk) < 65536:
                break  # drained; skip the guaranteed-EAGAIN second recv
        return out

    # -- idle-pair parking (tpurpc-hive, ISSUE 16) ----------------------------

    def _fold_park_tokens(self, raw: bytes) -> bytes:
        """Act on and strip park-protocol bytes; return the classic tokens.
        Caller holds ``_drain_mu`` (stream order).  A re-arm frame split
        across recv chunks is stashed in ``_notify_buf`` until complete —
        the sender shipped it atomically, so the rest is already in flight."""
        data = self._notify_buf + raw
        self._notify_buf = b""
        out = bytearray()
        i = 0
        n = len(data)
        while i < n:
            tok = data[i:i + 1]
            if tok == NOTIFY_PARK:
                i += 1
                self._handle_park_request()
            elif tok == NOTIFY_PARK_ACK:
                i += 1
                self._complete_park()
            elif tok == NOTIFY_PARK_NACK:
                i += 1
                with self._park_lock:
                    self._park_pending = False
            elif tok == NOTIFY_WAKE:
                i += 1
                self._handle_wake_request()
            elif tok in (NOTIFY_REARM, NOTIFY_REARM_KEEP):
                frame = data[i + 1:]
                if len(frame) < 8:
                    self._notify_buf = data[i:]
                    break
                if frame[:4] != _BOOTSTRAP_MAGIC:
                    self._mark_error("corrupt re-arm frame on notify stream")
                    break
                blen = struct.unpack("<I", frame[4:8])[0]
                if blen > _MAX_BLOB:
                    self._mark_error("re-arm frame implausibly large")
                    break
                if len(frame) < 8 + blen:
                    self._notify_buf = data[i:]
                    break
                self._handle_rearm(frame[8:8 + blen],
                                   retained=(tok == NOTIFY_REARM_KEEP))
                i += 1 + 8 + blen
            else:
                out += tok
                i += 1
        return bytes(out)

    def _handle_park_request(self) -> None:
        """Peer announced it will park: close our one-sided windows into its
        regions (after this no stale write of ours can land there — the pool
        invariant), snapshot the writer position for an abort-restore, ack."""
        with self._park_lock:
            if self.state is not PairState.CONNECTED or self.want_write:
                self._notify(NOTIFY_PARK_NACK)
                return
            try:
                # excludes an in-flight send; a send ENTERING after us gets
                # the retryable _ParkBusy, not the caller-bug tripwire
                with self._send_guard.maintenance():
                    if self.want_write:
                        self._notify(NOTIFY_PARK_NACK)
                        return
                    w = self.writer
                    if w is not None:
                        self._saved_wstate = (self._peer_ring_handle, w.tail,
                                              w.seq, w.remote_head)
                    self.writer = None
                    for attr in ("_peer_ring", "_peer_status"):
                        win = getattr(self, attr)
                        if win is not None:
                            setattr(self, attr, None)
                            self._peer_status_np = None
                            retry_buffer_op(win.close)
                    self._peer_parked = True
            except AssertionError:
                # a sender is inside send() right now — the pair is not idle
                self._notify(NOTIFY_PARK_NACK)
                return
        self._notify(NOTIFY_PARK_ACK)

    def _complete_park(self) -> None:
        """Peer acked our park request: its windows into our regions are
        closed, so they are writer-free — the one condition under which they
        may enter the shared :class:`RingPool`.  Re-check the ring FIRST:
        bytes that landed between our park decision and the peer's window
        close (the park-decide vs incoming-byte race) abort the park."""
        released = 0
        aborted = False
        with self._park_lock:
            if not self._park_pending:
                return
            self._park_pending = False
            if self.state is not PairState.CONNECTED:
                return
            try:
                # _recv_guard RAISES on concurrent entry: a receiver mid-
                # drain means the pair is not idle — abort, don't block.
                # maintenance entry: a receiver racing US retries as empty
                with self._recv_guard.maintenance():
                    if self.readable() or self.has_message():
                        aborted = True
                    else:
                        # The wake pipes and waiter selectors SURVIVE the
                        # park: a waiter asleep on them stays reachable by
                        # kick() across the whole episode, so unpark can
                        # never lose its wakeup. Only the rings (the actual
                        # memory) and the reader go; ~fd-sized stub remains.
                        pool = RingPool.get()
                        if self.reader is not None:
                            self.reader.release()
                            self.reader = None
                        self._status_np = None
                        for attr in ("recv_region", "status_region"):
                            region = getattr(self, attr)
                            if region is not None:
                                setattr(self, attr, None)
                                try:
                                    released += len(region.buf)
                                except ValueError:
                                    pass
                                pool.release(region)
                        self._published_head_mirror = 0
                        self._parked = True
                        self.parked_epochs += 1
            except AssertionError:
                aborted = True
        if aborted:
            # our rings survive untouched — re-arm the peer's write side
            # against the SAME handles (its saved writer state restores)
            self._send_rearm(retained=True)
            self.kick()
            return
        _flight.emit(_flight.PAIR_PARK, self._ftag, released)
        _stats.counter_inc("pair_park")
        from tpurpc.core.poller import Poller

        Poller.note_parked(self)
        trace_ring.log("pair %s parked (%d ring bytes pooled)",
                       self.tag, released)

    def unpark(self, *, remote: bool = False) -> None:
        """Re-arm a parked pair: lease fresh rings from the pool, rebuild the
        receive plumbing, and ship the new Address to the peer.  Invisible to
        the RPC layers — callers' sends/recvs resume on the fresh rings."""
        leased = 0
        with self._park_lock:
            if not self._parked:
                return
            if self.state is not PairState.CONNECTED:
                return  # dying while parked; teardown forgets the stub
            pool = RingPool.get()
            ring = pool.lease(self.domain, self.ring_size)
            try:
                status = pool.lease(self.domain, STATUS_BYTES)
            except BaseException:
                pool.release(ring)
                raise
            try:
                self.recv_region = ring
                self.status_region = status
                self.recv_region.on_write = self.kick
                self.status_region.on_write = self.kick
                self.reader = RingReader(self.recv_region.buf, self.ring_size)
                self._published_head_mirror = 0
                self._parked = False
            except BaseException:
                # lease-pairing discipline (lint rule `ringpool`): a failed
                # re-arm returns both rings to the pool
                self.recv_region = None
                self.status_region = None
                self.reader = None
                pool.release(ring)
                pool.release(status)
                raise
            leased = self.ring_size + STATUS_BYTES
            self._send_rearm()
        _flight.emit(_flight.PAIR_UNPARK, self._ftag, leased,
                     1 if remote else 0)
        _stats.counter_inc("pair_unpark")
        from tpurpc.core.poller import Poller

        Poller.note_unparked(self)
        self.kick()
        trace_ring.log("pair %s unparked (%s)", self.tag,
                       "remote wake" if remote else "local demand")

    def _send_rearm(self, *, retained: bool = False) -> None:
        """Frame our current Address over the notify stream — the peer
        reopens windows onto these rings and rebuilds its writer."""
        if (self.recv_region is None or self.status_region is None
                or self.state not in (PairState.INITIALIZED,
                                      PairState.CONNECTED)):
            return
        blob = self.local_address().to_bytes()
        tok = NOTIFY_REARM_KEEP if retained else NOTIFY_REARM
        frame = tok + _BOOTSTRAP_MAGIC + struct.pack("<I", len(blob)) + blob
        if not self._send_frame(frame):
            self._mark_error("re-arm frame could not be delivered")

    def _handle_wake_request(self) -> None:
        """Peer has bytes for us but believes our rings are parked — re-arm.
        When we are NOT parked (the WAKE crossed our re-arm in flight, or an
        ack-overdue sender gave up on a park the peer did honor), re-send the
        current Address: the peer's duplicate-re-arm dedup makes this
        idempotent, and it repairs a peer stuck with its windows closed."""
        if self._parked:
            try:
                self.unpark(remote=True)
            except Exception as exc:  # pool exhaustion / racing teardown
                trace_ring.log("pair %s: remote unpark failed: %r",
                               self.tag, exc)
        elif self.state is PairState.CONNECTED:
            self._send_rearm(retained=True)
            self.kick()

    def _handle_rearm(self, blob: bytes, *, retained: bool = False) -> None:
        """Peer advertised (fresh or retained) rings: rebuild our write side.
        Duplicate re-arms for rings we already write are ignored — rebuilding
        a live writer would reset its position mid-stream."""
        try:
            peer = Address.from_bytes(blob)
        except Exception:
            self._mark_error("undecodable re-arm frame")
            return
        with self._park_lock:
            saved, self._saved_wstate = self._saved_wstate, None
            if self.writer is not None:
                if self._peer_ring_handle == peer.ring_handle:
                    return  # duplicate
                # stale windows onto rings the peer replaced: close first
                try:
                    with self._send_guard.maintenance():
                        self.writer = None
                        for attr in ("_peer_ring", "_peer_status"):
                            win = getattr(self, attr)
                            if win is not None:
                                setattr(self, attr, None)
                                self._peer_status_np = None
                                retry_buffer_op(win.close)
                except AssertionError:
                    self._mark_error("re-arm raced an in-flight send")
                    return
            try:
                self._peer_ring = self.domain.open_window(peer.ring_handle,
                                                          peer.ring_size)
                self._peer_status = self.domain.open_window(peer.status_handle,
                                                            STATUS_BYTES)
            except Exception as exc:
                self._mark_error(f"re-arm window open failed: {exc!r}")
                return
            self._peer_ring_handle = peer.ring_handle
            self.writer = RingWriter(peer.ring_size, self._peer_ring.write,
                                     mapped=self._peer_ring.view)
            self.writer.flight_tag = self._ftag
            if retained:
                # park ABORT / repair: the peer kept its rings and its reader
                # position — restore our exact write position (a fresh
                # writer's zero tail would corrupt mid-ring)
                if saved is not None and saved[0] == peer.ring_handle:
                    _, self.writer.tail, self.writer.seq, rh = saved
                    self.writer.remote_head = rh
                else:
                    # rings retained but our snapshot is gone/mismatched: any
                    # guess at the write position corrupts the stream — fail
                    # loudly instead (never observed; belt and braces)
                    self._mark_error("retained re-arm without writer state")
                    return
            elif self.status_region is not None:
                # fresh peer rings: its reader restarts at head 0, so the
                # stale published-head word in OUR status region must never
                # fold into the fresh writer. The peer cannot be publishing
                # concurrently — it publishes only after reading data, and
                # no data can flow until this writer exists.
                try:
                    self.status_region.buf[
                        _STATUS_HEAD_OFF:_STATUS_HEAD_OFF + 8] = bytes(8)
                except (ValueError, TypeError):
                    pass  # racing teardown; state checks surface it
            self._peer_parked = False
        if self.want_write:
            self.process_credits()
        self.kick()

    def maybe_park(self, now: float, park_s: float) -> bool:
        """Poller-sweep hook: initiate (or progress) a park episode for an
        idle pair.  Returns True when park budget was consumed."""
        if (self._parked or self.notify_sock is None
                or "park" not in self.peer_caps):
            return False
        if self._park_pending:
            if now - self._park_sent_at > 2.0:
                with self._park_lock:
                    self._park_pending = False  # ack lost/peer gone; retry
            # an ownerless idle pair has no waiter to consume the ack —
            # drain here (kick after: token theft is safe only with a kick)
            if self.drain_notifications():
                self.kick()
            return False
        if (self.state is not PairState.CONNECTED or self.want_write
                or self.has_message() or self.readable()
                or now - self.last_activity < park_s):
            return False
        with self._park_lock:
            if self._park_pending or self._parked:
                return False
            try:
                with self._send_guard.maintenance():
                    if self.want_write or self.has_message():
                        return False
                    # the flag is visible to any sender that enters the
                    # guard after us — no write can race the peer's
                    # window-close (senders divert to the park-aware path)
                    self._park_pending = True
                    self._park_sent_at = now
            except AssertionError:
                return False  # a sender is mid-flight: not idle
        self._notify(NOTIFY_PARK)
        _stats.counter_inc("pair_park_requested")
        return True

    def resident_bytes_est(self) -> int:
        """Estimated per-connection resident bytes this pair pins: ring
        allocations while live, a ~stub while parked (scrape-time gauge and
        the hive bench's bytes/connection curve)."""
        n = 256  # object + bookkeeping stub
        region = self.recv_region
        if region is not None:
            n += self.ring_size
        if self.status_region is not None:
            n += STATUS_BYTES
        return n

    def _on_notify_closed(self) -> None:
        """Peer's end of the notify socket closed. Graceful close writes
        peer_exit BEFORE closing (``Disconnect`` pair.cc:325-347), so fold the
        status words first; only an unexplained closure is an ERROR (the
        crash-detection analog of the zero-byte TCP probe, rdma_conn.h:90-99).

        ASYNC domains (tcp_window) add a wrinkle: the exit word travels the
        record stream while the EOF travels the notify socket — the EOF can
        win the race even on a graceful close. Give the exit word a short
        grace window before declaring the peer crashed (the record stream
        delivers in milliseconds when the peer is alive enough to have
        closed gracefully; a genuinely crashed peer never sets it and we
        error after the window exactly as before)."""
        if self.state is PairState.CONNECTED:
            self.process_credits()  # may observe peer_exit -> HALF_CLOSED
        if self.state is PairState.CONNECTED and self.domain.kind not in (
                "local", "shm"):
            deadline = time.monotonic() + 2.0
            while (self.state is PairState.CONNECTED
                   and time.monotonic() < deadline):
                time.sleep(0.005)
                self.process_credits()
        if self.state is PairState.CONNECTED:
            self._mark_error("peer vanished (notify socket closed)")

    def peek_events(self) -> bool:
        """Non-consuming probe of the notify channel (``MSG_PEEK``): True if events
        are pending or the peer died.  The background :class:`~tpurpc.core.poller.
        Poller` uses this so it never steals tokens an event-discipline waiter is
        blocked on — only the pair's owner consumes via
        :meth:`drain_notifications`."""
        sock = self.notify_sock
        if sock is None:
            return False
        if hasattr(sock, "pending"):
            # SSLSocket: MSG_PEEK is unsupported (ValueError on flags) and
            # meaningless on a record stream. A non-consuming HINT suffices
            # for the poller's purpose: decrypted bytes pending, or raw
            # ciphertext readable on the fd (a spurious True just makes the
            # owner drain and find nothing). pending() reads SSL state —
            # serialized with sends/recvs like every other SSL op.
            with self._notify_lock:
                if sock.pending():
                    return True
            import select

            try:
                r, _, _ = select.select([sock.fileno()], [], [], 0)
            except (OSError, ValueError):
                return True  # racing close; owner's drain will resolve it
            return bool(r)
        try:
            chunk = sock.recv(1, socket.MSG_PEEK)
        except (BlockingIOError, InterruptedError):
            return False
        except OSError:
            # Poller scans race pool recycling: a captured socket from the
            # pair's PREVIOUS life (closed at quiesce, replaced by init)
            # raises EBADF here — benign staleness, not a liveness failure;
            # marking would poison whatever connection holds the pair NOW.
            if sock is self.notify_sock and sock.fileno() != -1:
                self._mark_error("notify channel read failed")
                return True
            return False
        if chunk == b"":
            if sock is self.notify_sock:
                self._on_notify_closed()
            return True
        return True

    # -- wakeup fds (local poller -> blocked selector) ------------------------

    @property
    def wakeup_fd(self) -> int:
        """The read-waiter wakeup fd (``grpc_endpoint_get_fd`` analog)."""
        return self._wake_r["read"]

    def wakeup_fd_for(self, role: str) -> int:
        return self._wake_r[role]

    def kick(self, exclude: Optional[str] = None) -> None:
        """Wake every blocked waiter on this pair (``poller.cc:92-101`` writing
        the pair's ``grpc_wakeup_fd``).

        Unconditional non-blocking writes: round 1 guarded this with an
        "armed" flag cleared by the consumer, and the window between a
        consumer draining the byte and clearing the flag suppressed
        concurrent kicks — a lost wakeup the old 50 ms select cap papered
        over. A redundant byte in a pipe is free; a suppressed kick is a
        stall. EAGAIN on a full pipe means a byte is already pending, which
        is exactly the required post-condition.

        ``exclude`` skips one role's pipe: a waiter that just drained shared
        notify tokens re-checks its own predicate immediately, so kicking
        itself only buys a guaranteed spurious wake (an extra select+consume
        round per RPC, measured on the 64B path)."""
        for role in ("read", "write"):
            if role == exclude:
                continue
            fd = self._wake_w[role]
            if fd >= 0:
                try:
                    os.write(fd, b"\x01")
                except (BlockingIOError, OSError):
                    pass

    def consume_wakeup(self, role: str = "read") -> None:
        fd = self._wake_r[role]
        if fd < 0:
            return
        try:
            while os.read(fd, 64):
                pass
        except (BlockingIOError, OSError):
            pass

    def waiter_selector(self, role: str):
        """The role's persistent selector over (notify socket, role pipe);
        created lazily, lives until the connection's channels are released.
        Only the role's single waiter thread touches it (ContentAssertion
        enforces one reader + one writer)."""
        import selectors

        sel = self._selectors.get(role)
        if sel is None:
            sel = selectors.DefaultSelector()
            try:
                if self.notify_sock is not None:
                    sel.register(self.notify_sock, selectors.EVENT_READ)
                fd = self._wake_r[role]
                if fd >= 0:
                    sel.register(fd, selectors.EVENT_READ)
            except (OSError, ValueError, KeyError):
                pass  # racing close; the waiter's predicate re-check handles it
            self._selectors[role] = sel
        return sel

    # -- status / credits -----------------------------------------------------

    def _poll_status_words(self) -> Tuple[int, int]:
        buf = self.status_region.buf
        return (_U64.unpack_from(buf, _STATUS_HEAD_OFF)[0],
                _U64.unpack_from(buf, _STATUS_EXIT_OFF)[0])

    def process_credits(self) -> None:
        """Fold the peer-written status buffer into local writer state
        (``pair.cc:294-301`` reading mirrored remote_head; peer_exit check
        ``pair.cc:349-375``).  Serialized: sender thread and poller thread both call
        this, and check-then-act on ``remote_head`` must be atomic."""
        w = self.writer
        if w is None or self.status_region is None:
            return  # no write side / our status inbox is parked in the pool
        with self._credit_lock:
            try:
                head, peer_exit = self._poll_status_words()
            except ValueError:
                return  # region released under us (park/teardown race)
            if head > w.remote_head:
                w.update_remote_head(head)
        if peer_exit and self.state is PairState.CONNECTED:
            self.state = PairState.HALF_CLOSED
            trace_ring.log("pair %s: peer_exit observed -> HALF_CLOSED", self.tag)

    def _publish_credits_if_due(self, force: bool = False) -> None:
        """One-sided-write our head into the peer's status buffer after consuming
        ≥ half ring (``pair.cc:276-284``, ``updateStatus`` ``:624-641``)."""
        reader = self.reader
        win = self._peer_status
        if win is None or reader is None:
            return  # reader parked: head 0 re-publishes on the fresh ring
        if force or reader.should_publish_head():
            head = reader.take_publish()
            if head != self._published_head_mirror:
                self._published_head_mirror = head
                try:
                    win.write(_STATUS_HEAD_OFF, _U64.pack(head))
                except ValueError:
                    # window closed under us (peer parking): the publish is
                    # lost but heads are cumulative — the next publish after
                    # re-arm carries it
                    return
                # Wake the peer's credit-stalled writer only if one is
                # actually asleep; a spinning writer watches the head word
                # natively (tpr_spin_u64_change) and needs no byte.
                if force or self._peer_waiting("write"):
                    self._notify(NOTIFY_CREDIT)

    # -- data plane -----------------------------------------------------------

    def send(self, slices: Sequence, byte_idx: int = 0) -> int:
        """Send as much of ``slices[byte_idx:]`` as flow control allows; returns bytes
        accepted.  Partial sends are normal — the caller re-arms on write-ready
        (``rdma_flush`` loop + ``notify_on_write``, ``rdma_bp_posix.cc:470-586``).
        Large payloads are chunked to ``send_chunk_size`` per ring message
        (old-gen chunked flush, ``rdma_utils.h:87-92``)."""
        # HALF_CLOSED is not sendable either: the peer has left and will never drain
        # its ring or return credits — accepting bytes would black-hole them.
        if self.state is not PairState.CONNECTED:
            raise BrokenPipeError(f"pair {self.tag} not sendable: {self.state}"
                                  + (f" ({self.error})" if self.error else ""))
        t0 = time.monotonic_ns()
        while True:
            try:
                if _tracing.LIVE and _tracing.current() is not None:
                    # traced call on this thread: the ring-encode interval is
                    # the "send-lease" span of the timeline (SURVEY §7 #4)
                    with _tracing.span("send-lease"):
                        n = self._send_traced(slices, byte_idx)
                else:
                    n = self._send_traced(slices, byte_idx)
                break
            except _ParkBusy:
                n = self._resolve_park_for_send()
                if n is not None:
                    break  # peer parked: 0 accepted, wake in flight
        # tpurpc-lens `wire` hop: bytes accepted across the transport
        # boundary and the nanoseconds the placement (credits + chunking +
        # ring encode) took — one pair of bumps per send call
        dt = time.monotonic_ns() - t0
        _LENS_WIRE_NS.inc(dt)
        _LENS_WIRE_BYTES.inc(n)
        _LENS_WIRE_COPY.inc(n)
        return n

    def _resolve_park_for_send(self) -> Optional[int]:
        """Resolve the park episode that made ``_send_inner`` raise
        :class:`_ParkBusy` — called OUTSIDE the send guard (lock order).
        Returns a byte count for ``send`` to report (peer parked: 0 accepted,
        partial-send semantics — the endpoint re-arms on write-ready and the
        WAKE token is already in flight), or None to retry the send."""
        with self._park_lock:
            peer_parked = self._peer_parked
            parked = self._parked
            pending = self._park_pending
        if peer_parked:
            # each retry re-sends the wake: idempotent, and it makes a lost
            # token survivable (the endpoint's wait_writable has a timeout)
            self._notify(NOTIFY_WAKE)
            self.want_write = True
            return 0
        if parked:
            self.unpark()
            return None
        if pending:
            # our own park request is in flight; drain for the ack/nack so
            # the episode resolves, bounded so a dead peer can't wedge senders
            deadline = time.monotonic() + 2.5
            while time.monotonic() < deadline:
                if self.drain_notifications():
                    self.kick()  # stolen tokens: waiters re-check predicates
                with self._park_lock:
                    if not (self._park_pending or self._parked
                            or self._peer_parked):
                        return None
                    if self._parked or self._peer_parked:
                        return None  # resolved; next retry takes that branch
                if self.state is not PairState.CONNECTED:
                    return None  # retry surfaces the state error
                time.sleep(0.001)
            with self._park_lock:
                self._park_pending = False  # ack overdue; peer likely gone
        return None

    def _send_traced(self, slices: Sequence, byte_idx: int = 0) -> int:
        if _stats.profiling_on():
            with _stats.profile("pair_send"):
                return self._send_profiled(slices, byte_idx)
        return self._send_profiled(slices, byte_idx)

    def _send_profiled(self, slices: Sequence, byte_idx: int = 0) -> int:
        # tpurpc-blackbox: emit want_write EDGES only (stall begin/end) —
        # the bool compare in the finally is the whole per-send cost
        was_stalled = self.want_write
        try:
            return self._send_inner(slices, byte_idx)
        finally:
            now_stalled = self.want_write
            if now_stalled != was_stalled:
                if now_stalled:
                    _flight.emit(_flight.WRITE_STALL_BEGIN, self._ftag)
                    # distinguish "partial send re-armed" from "writer is
                    # OUT of credits" — every fast/slow path that stalls
                    # with zero writable payload is a starvation edge
                    w = self.writer
                    if (w is not None and not self._starve_open
                            and w.writable_payload() == 0):
                        self._starve_open = True
                        inflight = w.tail - w.remote_head
                        _flight.emit(_flight.CREDIT_STARVE_BEGIN,
                                     self._ftag, inflight)
                else:
                    _flight.emit(_flight.WRITE_STALL_END, self._ftag)
                    if self._starve_open:
                        self._starve_open = False
                        _flight.emit(_flight.CREDIT_STARVE_END, self._ftag)

    def _send_inner(self, slices: Sequence, byte_idx: int = 0) -> int:
        cfg = get_config()
        with self._send_guard:
            if self._parked or self._park_pending or self._peer_parked:
                # checked INSIDE the guard: park initiation/ack also hold it,
                # so a sender entering after a park decision always observes
                # the flag — no write can race the peer's window close
                raise _ParkBusy
            views: List[memoryview] = []
            skip = byte_idx
            for s in slices:
                v = memoryview(s).cast("B")
                if skip >= len(v):
                    skip -= len(v)
                    continue
                views.append(v[skip:] if skip else v)
                skip = 0
            fast = self._send_fast(views, cfg)
            if fast is not None:
                return fast
            self.process_credits()
            total = 0
            while views:
                # Batch EVERY chunk the current credits admit into one
                # writer.write_many call (one bulk ring placement + one
                # header store per chunk) instead of a writev per chunk —
                # the gather-side half of the batched pipeline. Chunks stay
                # ≤ send_chunk_size so the peer's drain granularity (and
                # the old-gen chunked-flush semantics) are unchanged.
                budget = self.writer.writable_payload()
                if budget == 0:
                    self.want_write = True
                    if not self._starve_open:
                        self._starve_open = True
                        _flight.emit(_flight.CREDIT_STARVE_BEGIN, self._ftag,
                                     self.writer.tail
                                     - self.writer.remote_head)
                    break
                chunks: List[List[memoryview]] = []
                n = 0
                while views and n < budget:
                    chunk: List[memoryview] = []
                    c = 0
                    room = min(cfg.send_chunk_size, budget - n)
                    while views and c < room:
                        v = views[0]
                        take = min(len(v), room - c)
                        chunk.append(v[:take])
                        if take == len(v):
                            views.pop(0)
                        else:
                            views[0] = v[take:]
                        c += take
                    chunks.append(chunk)
                    n += c
                    # every chunk's framing overhead eats writable payload;
                    # leave the precise accept/stop decision to write_many
                    budget = max(0, budget - (c + 24))
                wrote_msgs, wrote_bytes = self.writer.write_many(chunks)
                if wrote_msgs:
                    _stats.batch_hist("ring_write").record(wrote_msgs)
                if wrote_msgs < len(chunks):
                    # credits moved under us: re-queue the unwritten chunks'
                    # segments (identity-preserving) and stall for credits
                    views[0:0] = [seg for ch in chunks[wrote_msgs:]
                                  for seg in ch]
                    total += wrote_bytes
                    self.want_write = True
                    break
                total += wrote_bytes
            if not views:
                self.want_write = False
            self.total_sent += total
            # ONE completion event per send call, not per chunk (round 1's
            # per-chunk token was a measured throughput killer) — and only
            # when a receiver is actually ASLEEP on its notify fd. A spinning
            # receiver sees the ring header the instant it lands; skipping
            # the byte makes the BP/BPEV fast path a zero-syscall send, the
            # reference's defining property (its RDMA WRITE needs no
            # completion on the passive side; only the event path wakes via
            # the completion channel, poller.cc:92-101). The waiting flag +
            # fences make the skip lossless (ring.cc sleep-protocol proof).
            if total:
                self.last_activity = time.monotonic()
                if self._peer_waiting("read"):
                    self._notify(NOTIFY_DATA)
            return total

    def _send_fast(self, views: "List[memoryview]", cfg) -> "Optional[int]":
        """Fused native send (``tpr_send_fast``): credit fold + chunked
        gather-encode + the sleep-protocol notify decision collapse into one
        GIL-held C call — the ~10 Python-level steps of the slow path are
        the measured per-RPC overhead in the multi-core spin regime.
        Returns bytes accepted, or None when the fast path doesn't apply
        (no native lib, unmapped ring, teardown racing)."""
        lib = _native.load()
        writer = self.writer
        if (lib is None or writer is None or writer._nat is None
                or not views):
            return None
        status_pin = self._status_pin()
        if status_pin is None:
            return None
        peer_rxwait = 0
        if "waitflag" in self.peer_caps:
            peer_pin = self._peer_status_pin()
            if peer_pin is not None:
                peer_rxwait = peer_pin[1] + _STATUS_RXWAIT_OFF
        # Small gather lists coalesce into ONE buffer first: address
        # extraction costs a numpy construction per segment (~1µs), which
        # exceeds the memcpy of a few hundred bytes — one staging copy + one
        # pin beats N pins on the small-RPC path. Large payloads keep true
        # scatter-gather. (Preallocated fill, not b"".join: the hot-path
        # no-copy lint bans the join idiom outright.)
        small_total = sum(len(v) for v in views)
        if len(views) > 1 and small_total <= 4096:
            staged = bytearray(small_total)
            pos = 0
            for v in views:
                staged[pos:pos + len(v)] = v
                pos += len(v)
            views = [memoryview(staged)]
        n = len(views)
        # locals pin every view for the call's duration
        seg_ptrs = (ctypes.c_void_p * n)(
            *[_native.addr_of(v, writable=False) for v in views])
        seg_lens = (ctypes.c_uint64 * n)(*[len(v) for v in views])
        tail = ctypes.c_uint64(writer.tail)
        seq = ctypes.c_uint64(writer.seq)
        rh = ctypes.c_uint64(writer.remote_head)
        notify = ctypes.c_int(0)
        # The credit lock spans the CALL and the writeback: the peer can
        # consume freshly written bytes and publish a head beyond our stale
        # writer.tail the instant the C call's stores land, and a concurrent
        # process_credits() folding that head against the not-yet-written-
        # back tail would raise a spurious RingCorruption. The call is
        # GIL-held and bounded, so the hold is short.
        seq_before = writer.seq
        t0 = time.monotonic_ns()
        with self._credit_lock:
            got = lib.tpr_send_fast(
                writer._nat_addr, writer.layout.capacity,
                ctypes.byref(tail), ctypes.byref(seq),
                status_pin[1] + _STATUS_HEAD_OFF, ctypes.byref(rh),
                peer_rxwait or None, seg_ptrs, seg_lens, n,
                cfg.send_chunk_size, ctypes.byref(notify))
            writer.tail = tail.value
            writer.seq = seq.value
            if rh.value > writer.remote_head:
                writer.remote_head = rh.value
        dt = time.monotonic_ns() - t0
        if writer.seq > seq_before:  # ring messages this one C call encoded
            _stats.batch_hist("ring_write").record(writer.seq - seq_before)
            # the fused C path bypasses RingWriter.writev, so the registry
            # totals are bumped here (same counters, same meaning) — and so
            # are the lens send_ring hop counters
            _MSGS_OUT.inc(writer.seq - seq_before)
            _BYTES_OUT.inc(got)
            _LENS_SR_BYTES.inc(got)
            _LENS_SR_NS.inc(dt)
            _LENS_SR_COPY.inc(got)
        ring_ledger.host_copy(got)
        self.total_sent += got
        if got:
            self.last_activity = time.monotonic()
        total_len = sum(len(v) for v in views)
        self.want_write = got < total_len
        # the fast path folds only the credit word; peer_exit still must
        # flip state (cheap single unpack — Disconnect, pair.cc:325-347)
        if self.state is PairState.CONNECTED and self.status_region is not None:
            try:
                if _U64.unpack_from(self.status_region.buf,
                                    _STATUS_EXIT_OFF)[0]:
                    self.state = PairState.HALF_CLOSED
            except ValueError:
                pass  # racing teardown; caller's state checks surface it
        if notify.value:
            self._notify(NOTIFY_DATA)
        return got

    def recv_into(self, dst) -> int:
        """Drain the receive ring into ``dst``; publishes credits as a side effect
        (``PairPollable::Recv`` → ``RingBufferPollable::Read``,
        ``ring_buffer.cc:122-191``).

        Rides the BATCHED drain (``RingReader.drain_into``): every complete
        message queued in the ring moves in one pass with one head publish,
        and the batch size feeds the ``ring_drain`` histogram the bench
        reports as ``batch_msgs_per_wakeup``."""
        try:
            return self._recv_into_guarded(dst)
        except _ParkBusy:
            # park completion owns the read side this instant; it either
            # aborts (rings intact, kick re-wakes us) or parks (recv on a
            # parked pair reads 0 anyway) — transient empty, not an error
            return 0

    def _recv_into_guarded(self, dst) -> int:
        with self._recv_guard:
            reader = self.reader
            if reader is None:  # quiesced/destroyed under a racing reader thread
                if self._parked:
                    return 0  # parked, not closed: the first peer byte
                    # arrives as a WAKE on the notify fd and re-arms us —
                    # callers just keep wait_readable-ing, RPC-invisible
                raise ConnectionError("pair is closed")
            try:
                n, nmsgs = reader.drain_into(dst)
            except (RingCorruption, ValueError) as exc:
                # ring memory released by a concurrent teardown — surface as a
                # connection error, not data corruption
                if "released" in str(exc):
                    raise ConnectionError("pair is closed") from None
                raise
            if nmsgs:
                _stats.batch_hist("ring_drain").record(nmsgs)
            self.total_recv += n
            if n:
                self.last_activity = time.monotonic()
            self._publish_credits_if_due()
            return n

    def recv(self, max_bytes: int = 1 << 20) -> bytes:
        cap = self.reader.layout.capacity if self.reader is not None else 0
        buf = bytearray(min(max_bytes, cap))
        n = self.recv_into(buf)
        ring_truncate(buf, n)  # in place: bytes(buf[:n]) would copy twice
        return bytes(buf)

    def has_message(self) -> bool:
        return self.reader is not None and self.reader.has_message()

    def readable(self) -> int:
        return self.reader.readable() if self.reader is not None else 0

    def has_pending_writes(self) -> bool:
        """True when a sender stalled for credits and space has since appeared — the
        poller uses this to wake writers (``poller.cc:77-88`` checking
        ``HasPendingWrites``)."""
        if not self.want_write or self.writer is None:
            return False
        self.process_credits()
        return self.writer.writable_payload() > 0

    # -- native busy-poll (GIL-free) -------------------------------------------

    def spin(self, role: str, timeout_us: int) -> bool:
        """Bounded native spin on the role's watched words, GIL released.

        ``read`` watches the local receive ring for a complete message
        (header+footer words, like ``pollable_epoll``'s ``HasMessage`` scan,
        ``ev_epollex_rdma_bp_linux.cc:1020-1110``); ``write`` watches the
        status buffer's remote-head word the peer one-sided-writes credits
        into (``pair.cc:294-301``). Returns True when the watched condition
        fired OR the spin is impossible (no native lib, memory released) —
        the caller always re-checks the full predicate in Python either way;
        False means the slice timed out quietly.

        The buffer is pinned by an exported view for the call's duration;
        Region.close retries its unmap until spinners unpin (≤ one slice).
        """
        spin = _native.load_spin()
        if spin is None:
            # Pure-Python fallback: no bounded native spin exists, so the
            # caller's loop would become a GIL-held hot poll. Yield the core
            # each lap (the round-1 polling_yield behavior) and let the
            # caller's ready() do the checking.
            time.sleep(0)
            return True
        if role == "read":
            reader = self.reader
            if reader is None or reader._msg_len:
                return True
            pin = reader._nat_pin  # local ref pins the ring across the call
            if pin is None:
                try:
                    pin, addr = _native.pin(reader.buf, writable=True)
                except (ValueError, TypeError):
                    return True  # ring released; predicate will surface it
            else:
                addr = reader._nat_addr
            r = spin.tpr_ring_wait_message(
                addr, reader.layout.capacity, reader.head,
                reader.seq, timeout_us)
            return r != 0
        writer = self.writer
        if writer is None:
            return True
        pin = self._status_pin()  # local ref pins across the GIL-free call
        if pin is None:
            return True
        # Watch for divergence from the last FOLDED credit value, not from the
        # word's current value: a credit that landed between the caller's
        # predicate check and this call returns immediately instead of
        # spinning a whole slice past it.
        r = spin.tpr_spin_u64_change(
            pin[1] + _STATUS_HEAD_OFF, writer.remote_head, timeout_us)
        return r != 0

    # -- close / liveness ------------------------------------------------------

    def get_status(self) -> PairState:
        """Cheap liveness probe: fold in peer_exit + notify-channel health
        (``get_status`` ``pair.cc:349-375``)."""
        if self.state is PairState.CONNECTED:
            self.process_credits()
        return self.state

    def disconnect(self) -> None:
        """Graceful close: one-sided-write ``peer_exit=1`` into the peer's status
        buffer, notify, then stop sending (``Disconnect`` ``pair.cc:325-347``)."""
        if self.state in (PairState.CONNECTED, PairState.HALF_CLOSED):
            self._publish_credits_if_due(force=True)
            try:
                self._peer_status.write(_STATUS_EXIT_OFF, _U64.pack(1))
                self._notify(NOTIFY_EXIT)
            except Exception:
                pass
            _flight.emit(_flight.PAIR_DISCONNECT, self._ftag)
        self.state = PairState.DISCONNECTED
        if self.want_write:
            # balance the open stall edge: a dead pair's stall is over (the
            # sender fails, the RPC surfaces an error) — an unclosed begin
            # would keep the watchdog attributing to credit-starvation for
            # the whole flight-evidence window after the peer is gone
            _flight.emit(_flight.WRITE_STALL_END, self._ftag)
        self.want_write = False  # no sender can stall on a closed pair

    def _mark_error(self, why: str) -> None:
        if self.state not in (PairState.DISCONNECTED,):
            self.state = PairState.ERROR
            _flight.emit(_flight.PEER_DEATH, self._ftag)
            if self.want_write:
                # same balancing as disconnect(): peer death mid-stall ends
                # the stall — the evidence must say so
                _flight.emit(_flight.WRITE_STALL_END, self._ftag)
        if self.error is None:
            self.error = why
        # Waiters may be blocked in an uncapped select; the state change IS
        # their wake condition, so deliver it.
        self.kick()
        trace_ring.log("pair %s -> ERROR: %s", self.tag, why)

    def _release_channels(self) -> None:
        """Per-connection state: peer windows, notify socket, wakeup pipe, reader
        view.  (Views into regions must drop before regions can close — shm unmap
        refuses while exported pointers exist.)

        Kick FIRST: with the uncapped select (poller.py), a waiter blocked on
        these very fds would otherwise hang forever — closing a registered fd
        silently deregisters it from epoll, delivering nothing. The kick bytes
        are level-readable, so even a waiter mid-gap (between its predicate
        check and the select) wakes and observes the state change; a waiter
        that races the close itself gets EBADF from select, which _wait treats
        as a state-change wakeup."""
        # Detach the async-domain applier hook BEFORE the wake fds close:
        # a record landing mid-teardown must not kick() into a just-closed
        # (and possibly OS-reused) fd number.
        for region in (self.recv_region, self.status_region):
            if region is not None:
                region.on_write = None
        if self._parked or self._park_pending:
            # a parked pair dying mid-park: drop its parked-watcher slot so
            # the poller's map can't accumulate dead stubs (gauge hygiene)
            self._parked = False
            self._park_pending = False
            try:
                from tpurpc.core.poller import Poller

                Poller.forget_parked(self)
            except Exception:
                pass
        self.kick()
        sels, self._selectors = self._selectors, {}
        for sel in sels.values():
            try:
                sel.close()
            except OSError:
                pass
        if self.reader is not None:
            self.reader.release()
            self.reader = None
        self.writer = None
        # Order against _peer_status_pin's re-cache race: null the ATTRIBUTE
        # first (new pins become impossible), then the cache, then close —
        # an in-flight _peer_waiting still pinning through a local is covered
        # by the retry.
        for attr in ("_peer_ring", "_peer_status"):
            w = getattr(self, attr)
            if w is not None:
                setattr(self, attr, None)
                self._peer_status_np = None
                retry_buffer_op(w.close)
        if self.notify_sock is not None:
            try:
                self.notify_sock.close()
            except OSError:
                pass
            self.notify_sock = None
        for pipes in (self._wake_r, self._wake_w):
            for role, fd in pipes.items():
                if fd >= 0:
                    try:
                        os.close(fd)
                    except OSError:
                        pass
                    pipes[role] = -1

    def _release_regions(self) -> None:
        # Attribute first, then cache, then close (see _peer-status comment in
        # _release_channels; _status_pin re-checks the attribute after caching).
        for attr in ("recv_region", "status_region"):
            r = getattr(self, attr)
            if r is not None:
                setattr(self, attr, None)
                self._status_np = None
                RingPool.get().forget(r)  # pool-leased (unparked) regions
                r.close()

    def _release_resources(self) -> None:
        self._release_channels()
        self._release_regions()

    def quiesce(self) -> None:
        """Release everything per-connection — channels, peer refs, AND ring
        regions (init() always allocates fresh regions, see its docstring, so an
        idle pooled pair pinning /dev/shm would buy nothing)."""
        if self.state in (PairState.CONNECTED, PairState.HALF_CLOSED):
            self.disconnect()
        self._release_resources()
        self.state = PairState.UNINITIALIZED

    def destroy(self) -> None:
        if self.state in (PairState.CONNECTED, PairState.HALF_CLOSED):
            self.disconnect()
        self._release_resources()
        self.state = PairState.UNINITIALIZED


def create_loopback_pair(ring_size: int = 1 << 16,
                         domain: Optional[MemoryDomain] = None) -> Tuple[Pair, Pair]:
    """Two connected in-process pairs over a unix socketpair — the CI-testable fake
    the reference never wrote (SURVEY.md §4's 'missing fake')."""
    domain = domain or LocalDomain()
    a = Pair(domain, ring_size)
    b = Pair(domain, ring_size)
    a.init()
    b.init()
    sa, sb = socket.socketpair()
    done: List[Optional[BaseException]] = [None]

    def _bside():
        try:
            b.connect_over_socket(sb)
        except BaseException as exc:  # surfaced below
            done[0] = exc

    t = threading.Thread(target=_bside, daemon=True)
    t.start()
    a.connect_over_socket(sa)
    t.join(timeout=10)
    if done[0] is not None:
        raise done[0]
    return a, b
