"""tpurpc-express: one-sided rendezvous transfers for bulk tensor payloads.

The paper's real thesis ("RPC Considered Harmful", arXiv:1805.08430) is that
large DL tensors should not ride the framed request/response path at all:
chunked ring framing pays per-chunk credit handshakes, per-fragment headers,
and a receive-side landing copy for every payload byte. This module moves
any payload over a size bar the way the reference moves every payload —
as ONE one-sided write into a peer-advertised registered landing region
(RDMAbox, arXiv:2104.12197: merged writes into pre-registered regions) —
while the framed RPC carries only a small offer/claim/complete control
exchange:

    sender                                  receiver
    ------                                  --------
    OFFER(req, nbytes, kinds)  ──────────►  lease landing region from pool
                               ◄──────────  CLAIM(req, lease, region descr)
    one-sided write of every
    gather segment into the
    region (RDMA WRITE on the
    verbs domain; ONE memoryview
    copy on shm/local/tcp_window)
    COMPLETE(lease, nbytes, flags) ───────► deliver region view zero-copy
                                            (decode aliases it in place)

Every control message rides the existing framed connection, so ordering
with interleaved small MESSAGEs is free (frame arrival order), and a peer
that never negotiated the capability never sees an unknown frame.

Protocol invariants (modeled exhaustively in ``analysis/ringcheck.py
check_rendezvous``; mutants ``write_before_claim`` and
``complete_before_write`` are both killed):

* the sender writes a region only between CLAIM and COMPLETE/RELEASE;
* a region is reused only after COMPLETE (and, in this emulation, after
  every consumer alias died — the pool's weakref-finalize recycling) or
  after an explicit RELEASE;
* peer death with a claimed region releases it (``RdvLink.close``).

Steady-state fast path: after each completed transfer the receiver
PRE-GRANTS standing regions (req id 0) up to ``_PREGRANT_DEPTH``, so a
stream pays zero claim round trips — the RDMAbox pre-registered-buffer
discipline. A link's standing regions are ONE window of ONE size class,
the largest the link has streamed: a message rides any free
standing region big enough, whatever its own class; a completion of a
class above the window (a one-shot claim: no standing region held it)
grows the window to that class, one below it grants nothing of its own;
and the sender hands back (``OP_RELEASE``) each free standing region
smaller than its window, and every free one before it offers a message
larger than all it holds, so the regions a window grew out of return to
the pool, which closes free regions of other classes to make room for a
class it lacks. A stream of one size keeps its four regions of that class,
as before. Pre-granted transfers emit no flight events (edges, not
traffic); solicited offers/claims/releases do, which is exactly the
evidence the stall watchdog's ``rendezvous`` stage reads.

Credit flow control (ISSUE 31): a link's standing regions ARE its credit
window. A sender that holds a whole window (``_PREGRANT_DEPTH`` standing
regions big enough for the message) and finds them all still with the
consumer waits for one of its own doorbells, as the framed ring's
writer waits for ring credit, and places the message one-sided when it
rings; it asks the receiver for a
one-shot region beyond its window only where it has nothing to wait for
or the wait has run out (ISSUE 33: under a fan-in every region asked for
beyond a window is one more message parked in the receiver's queue, and
the pool's spare regions are what a late link's window and an overdue
wait's claim are made of). A link short of its window asks first, and
waits when the receiver refuses ("no more memory: what you hold is your
window").
How long is learnt, not configured: the link measures its window class's
RESIDENCE (a region's COMPLETE to the doorbell read that finds it free,
taken only while the sender is watching; kept by the capacity of the
standing regions, not by the class of the message that rode one) and
waits for the oldest region until it has been out for the smoothed residence plus four deviations
(``_Residence``). A refusal is believed for that same bound, so a link
short of its window asks the receiver again once a window's turn, not
once a message.
Degradation, never a hang: every wait is finite and ends in a path that
existed before it. A link with no measured residence, or no standing
region big enough, does not wait (the first estimate is seeded from
below by the longest a region was seen out, ``_saw_free``); a region that
outlives its estimate (a late consumer, one that retains more than a
window, or a stalled one) sends this message by another path, a one-shot
claim or framed, and the next message waits again: one message beyond the
window a bound; the link's close ends the wait into the framed path (where
the dead transport raises); the call's deadline or the stream's end
raises ``SendAbandoned`` and starts no copy for a call that is over. So a
refused claim, a claim timeout, a write failure, an un-negotiated peer or
a compressed payload still falls back to the framed path at once, and a
full window falls back after at most one learnt residence.

Lifetime/recycling: a delivered payload is a numpy wrapper over the landing
region. Every downstream alias — codec decode views, 64B-aligned dlpack
imports into jax.Arrays — transitively references the wrapper, so a
``weakref.finalize`` on it is a sound "no consumer can observe this memory"
signal; only then does the region return to the pool's free list. Consumers
that copy simply never pin.

Env knobs: ``TPURPC_RENDEZVOUS`` (default on), ``TPURPC_RENDEZVOUS_MIN_KB``
(size bar, default 256 — bench ``stream_by_size`` measures the crossover),
``TPURPC_RENDEZVOUS_POOL_MB`` (landing pool budget per domain, default 256),
``TPURPC_RENDEZVOUS_CLAIM_TIMEOUT_S`` (claim wait before falling back to
the framed path, default 5).
"""

from __future__ import annotations

import ctypes
import itertools
import mmap
import os
import struct
import threading
import time
import weakref
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from tpurpc.analysis.locks import make_condition, make_lock
from tpurpc.core import _native
from tpurpc.core import pair as _pair
from tpurpc.core import transport as _transport
from tpurpc.obs import flight as _flight
from tpurpc.obs import lens as _lens
from tpurpc.obs import metrics as _metrics
from tpurpc.obs import profiler as _profiler
from tpurpc.tpu import ledger as _ledger
from tpurpc.utils import stats as _stats

__all__ = [
    "LandingPool", "RegionLease", "RdvLink", "landing_pool",
    "link_for_endpoint", "enabled", "min_bytes", "size_class",
    "OP_OFFER", "OP_CLAIM", "OP_COMPLETE", "OP_RELEASE", "HELLO_PAYLOAD",
    "BlockGrant", "GrantWriter", "SendAbandoned",
]

# tpurpc-lens: the one-sided bulk write is its own waterfall hop — the
# bytes that no longer flow through wire/send_ring show up here
_LENS_RDV_BYTES, _LENS_RDV_NS, _LENS_RDV_COPY = _lens.hop_counters(
    "rendezvous")

_LENS_STAGES = {
    "send_message": "rendezvous",
    "_await_credit": "rdv_credit",
    "_rdv_write": "rendezvous",
    "rdv_claim": "rendezvous",
    "on_offer": "rendezvous",
    "on_complete": "rendezvous",
}
_profiler.register_stages(__file__, _LENS_STAGES)

#: transfers negotiated / completed / fallen back — the ops-facing truth of
#: whether the bulk plane is actually carrying traffic
_RDV_SENT = _metrics.counter("rdv_transfers_sent")
_RDV_RECV = _metrics.counter("rdv_transfers_received")
#: message bytes those sent transfers placed one-sided (codec header
#: included): over the payload a sender put out in all, the share that left
#: on this path; what fell back to the framed path is the rest (a server's
#: replies are what it sends, so on a server this is the reply side)
_RDV_SENT_BYTES = _metrics.counter("rdv_bytes_sent")
#: placements whose copy into the peer's window ran with the interpreter
#: released (``place_released``), and their bytes: a sender whose every
#: message left one-sided through a view-backed window reads
#: rdv_place_released_bytes == rdv_bytes_sent
_RDV_PLACE_RELEASED = _metrics.counter("rdv_place_released")
_RDV_PLACE_RELEASED_BYTES = _metrics.counter("rdv_place_released_bytes")
#: payload bytes those received transfers delivered (the Python plane's
#: twin of the C table's native_rdv_recv_bytes): over the payload a
#: receiver took in all, the share of traffic that stayed on this path
_RDV_RECV_BYTES = _metrics.counter("rdv_bytes_received")
_RDV_FALLBACK = _metrics.counter("rdv_fallbacks")
_RDV_REFUSED = _metrics.counter("rdv_claims_refused")
#: the sender's credit wait (ISSUE 31): sends that waited for one of their
#: own doorbells after a failed claim, the time they waited (the ``rdv_credit``
#: lens hop's busy time under the name beside its siblings), and the waits
#: that outlived the link's residence estimate and ended in the framed path
#: (each also one of rdv_fallbacks)
_RDV_CREDIT_WAITS = _metrics.counter("rdv_credit_waits")
_RDV_CREDIT_WAIT_NS = _metrics.counter("rdv_credit_wait_ns")
_RDV_CREDIT_EXPIRED = _metrics.counter("rdv_credit_expired")
#: control ops that rode the FRAMED path (tpurpc-pulse: a descriptor-ring
#: link in steady state holds this flat — the ctrlring smoke and bench's
#: ctrl_wakeups_per_msg both read it as the zero-control-frames proof)
_RDV_CTRL_FRAMES = _metrics.counter("rdv_ctrl_frames")
#: the per-link window, on the receiver: completions that landed
#: in a standing region, those of them whose message was of a smaller class
#: than its region (a ride), the standing leases senders handed back, and
#: free regions the pool closed to make budget for another class
_RDV_WINDOW_RECV = _metrics.counter("rdv_window_received")
_RDV_WINDOW_RIDES = _metrics.counter("rdv_window_rides")
_RDV_WINDOW_RETURNS = _metrics.counter("rdv_window_returns")
_RDV_POOL_RECLAIMS = _metrics.counter("rdv_pool_reclaims")

# tpurpc-pulse: framed control sends are control-plane busy time — same
# hop as the descriptor-ring posts/drains in core/ctrlring.py, so the
# waterfall shows the whole control plane's busy share in one row
_LENS_CTRL_BYTES, _LENS_CTRL_NS, _LENS_CTRL_COPY = _lens.hop_counters(
    "ctrl")

# -- control ops (canonical small ints; each wire plane maps them onto its
#    own frame vocabulary — frame.py types 8..11, h2 extension-frame flags)
OP_OFFER = 1
OP_CLAIM = 2
OP_COMPLETE = 3
OP_RELEASE = 4

#: capability hello for the native framing plane: a PING with this payload.
#: Any compliant peer (including the C plane and older builds) just echoes
#: it in a PONG; only a rendezvous-capable peer ALSO recognizes it and
#: arms its link — so the negotiation is safe against every deployed peer.
HELLO_PAYLOAD = b"\x00tpurpc-rdv1"

_OFFER = struct.Struct("<QQ")       # req_id, nbytes (+ kinds utf8 tail)
_CLAIM_HDR = struct.Struct("<QQB")  # req_id, lease_id, ok
_CLAIM_REG = struct.Struct("<QQ16sB")  # offset, capacity, nonce, standing
_COMPLETE = struct.Struct("<QQB")   # lease_id, nbytes, flags
_RELEASE = struct.Struct("<QQ")     # lease_id (0 = none), req_id
_DOORBELL = struct.Struct("<Q")     # consumer-freed count (see below)

_MIN_CLASS = 64 * 1024
_ALIGN = 64
_PAGE = mmap.PAGESIZE
_NONCE_BYTES = 16
_MAX_TRANSFER = 1 << 30  # sanity bound on one offer
_WINDOW_CACHE = 64       # open peer-region windows kept per link
#: standing claims per (link, size class). Sized so a pipelined sender
#: (bounded stream-credit window) never waits a claim round trip in steady
#: state — misses re-pay ~0.8 ms on the 1-core rig (measured; 18/64
#: messages missed at depth 2, zero at 4).
_PREGRANT_DEPTH = 4

_SENTINEL_PENDING = object()
_SENTINEL_REFUSED = object()

#: test seams (tests/test_chaos.py, tools/rendezvous_smoke.py): a receiver
#: with drop_offers set ignores OFFERs entirely (claim-starved sender); a
#: sender with wedge_after_claim set blocks there until the event fires or
#: the link dies (peer-death-mid-rendezvous chaos scenario); place_pinned
#: is called by a placement that holds its window's pin and has copied
#: nothing yet (a close that meets a placement in flight)
TEST_HOOKS: Dict[str, object] = {}


def place_released(view: memoryview,
                   placed: Sequence[Tuple[int, memoryview]]) -> int:
    """The sender's one payload-sized copy: each ``(offset, bytes)`` of
    ``placed`` goes into ``view``, a peer region's mapped window, WITHOUT
    the interpreter. ``view[a:b] = src`` is a memcpy made holding it from
    first byte to last: 4 MiB is half a millisecond in which no other
    thread of the process runs, eight times a batch on a server that
    answers a fan-in (PERF.md 6, PR 38). The spans go in one native call on
    the handle that releases it (``tpr_place``: one give-up a placement,
    header and leaves together); without the native library numpy copies
    span by span, which releases it for all but the smallest (numpy keeps
    it under 500 elements, so a header is copied as it always was).

    What the interpreter used to guarantee is pinned instead: an exported
    array over ``view`` for the length of the copy, so a close of the
    window from another thread meets ``BufferError`` and retries
    (``_close_window``) and nothing is unmapped under a copy in flight;
    the sources are pinned the same way. A window already closed raises
    ``ValueError`` here, before any byte moves, as the slice assignment
    did. Returns the bytes placed.

    The native call stamps the end of its copy on the monotonic clock and
    the first thing done on return is to read that clock again: the
    difference is one op of hop ``place_return``, what this thread paid to
    have the interpreter back (numpy's copy has no such stamp and makes no
    op)."""
    dst, base = _native.pin(view, writable=True)
    hook = TEST_HOOKS.get("place_pinned")
    if hook is not None:
        hook()
    offs, lens, pins = [], [], []
    for off, src in placed:
        n = len(src)
        if n == 0:
            continue
        if off < 0 or off + n > len(dst):
            raise ValueError(f"placement of {n} bytes at {off} leaves the "
                             f"{len(dst)}-byte window")
        offs.append(off)
        lens.append(n)
        pins.append(_native.pin(src, writable=False))
    if not pins:
        return 0
    spin = _native.load_spin()
    total = sum(lens)
    if spin is not None:
        k = len(pins)
        u64s = ctypes.c_uint64 * k
        copied_ns = spin.tpr_place(
            base, u64s(*offs),
            (ctypes.c_void_p * k)(*[addr for _, addr in pins]),
            u64s(*lens), k)
        # the price of the give-up, taken where it is paid: the copy ended
        # at C's stamp, and this thread runs again only now
        _lens.account("place_return", time.monotonic_ns() - copied_ns, total)
    else:
        for off, n, (src, _) in zip(offs, lens, pins):
            np.copyto(dst[off:off + n], src)
    _RDV_PLACE_RELEASED.inc()
    _RDV_PLACE_RELEASED_BYTES.inc(total)
    return total


def _place_spans(win: _pair.Window,
                 placed: Sequence[Tuple[int, memoryview]]) -> None:
    """One placement, whatever the window's domain: the released copy
    where the peer's region is mapped here, the domain's own one-sided
    write a span where it is not (verbs WRs, tcp_window records).

    Pages this mapping has not written yet are touched first, a byte a
    page, WITH the interpreter: a first-touch fault taken while the other
    threads of the process run is ten times one taken while they stand
    still, on the host the cells run on (a 4 MiB placement into a fresh
    region: 12 ms under the slice assignment, 139 to 152 ms released, 0.5 ms
    into pages already mapped; eight clients' warm-up messages added 1.2
    to 2.8 s to ``setup_s``; PERF.md 6, PR 38). The touch costs what the
    faults always cost, once a region a process."""
    view = win.view
    if view is None:
        for off, src in placed:
            win.write(off, src)
        return
    end = max((off + len(src) for off, src in placed), default=0)
    if end > win.touched:
        for off, src in placed:
            n = len(src)
            if n:
                view[off:off + n:_PAGE] = bytes(-(-n // _PAGE))
        win.touched = end
    place_released(view, placed)


def _close_window(win: _pair.Window) -> None:
    """Close a window that no cache holds any more. A placement still in
    flight through it (another sender thread's: ``place_released`` runs
    without the interpreter) pins the mapping, so the release meets
    ``BufferError`` until that copy returns; a window whose pin outlives
    the retry stays mapped, which is a leak and not a write into unmapped
    memory."""
    try:
        _pair.retry_buffer_op(win.close)
    except Exception:
        pass


def _env(name: str, default: str) -> str:
    return os.environ.get(name, default)


def enabled() -> bool:
    return _env("TPURPC_RENDEZVOUS", "1").lower() not in ("0", "off",
                                                          "false")


def min_bytes() -> int:
    """The size bar: payloads at or above it rendezvous, below it they keep
    today's framed path untouched. Read live (the bench A/B toggles it)."""
    try:
        return max(1, int(_env("TPURPC_RENDEZVOUS_MIN_KB", "256"))) * 1024
    except ValueError:
        return 256 * 1024


def _pool_budget() -> int:
    try:
        return max(1, int(_env("TPURPC_RENDEZVOUS_POOL_MB", "256"))) << 20
    except ValueError:
        return 256 << 20


def _claim_timeout() -> float:
    try:
        return float(_env("TPURPC_RENDEZVOUS_CLAIM_TIMEOUT_S", "5"))
    except ValueError:
        return 5.0


def size_class(nbytes: int) -> int:
    """Round a transfer size up to its pool size class — the granularity
    at which regions pool and pre-grants match. Four classes an octave
    (4, 5, 6, 7 x a power of two, floor 64 KiB), so a region is at most a
    quarter larger than its transfer: a tensor is a power of two of
    payload plus a header, which whole octaves round up to twice its size
    — half the pool's budget, and with it the standing window of every
    second link of a fan-in (8 links x ``_PREGRANT_DEPTH`` of a 4 MiB
    message wanted 32 regions of the 31 the default budget held at 8 MiB
    each; it holds 51 at 5 MiB). A class is its own class."""
    if nbytes > _MAX_TRANSFER:
        raise ValueError(f"transfer of {nbytes} bytes exceeds the "
                         f"{_MAX_TRANSFER} rendezvous bound")
    c = _MIN_CLASS
    while c < nbytes:
        c <<= 1
    if c == _MIN_CLASS:
        return c
    step = c >> 3  # a quarter of the octave below c
    return c - step * ((c - nbytes) // step)


def _region_bytes(cls: int) -> int:
    """What a region of class ``cls`` charges the pool's budget: its span,
    the alignment pad, the nonce and the doorbell word."""
    return cls + _ALIGN + _NONCE_BYTES + _DOORBELL.size


# ---------------------------------------------------------------------------
# Landing pool: registered regions the receiver advertises.
# ---------------------------------------------------------------------------

class _PoolRegion:
    """One registered landing region: domain Region + the 64B alignment
    offset of its payload span + the anti-mixup nonce and the consumer-done
    DOORBELL word behind it (layout: ``[pad][payload cap][nonce 16]
    [doorbell 8]``)."""

    __slots__ = ("region", "offset", "capacity", "nonce")

    def __init__(self, region: _pair.Region, offset: int, capacity: int,
                 nonce: bytes):
        self.region = region
        self.offset = offset
        self.capacity = capacity
        self.nonce = nonce

    def doorbell_store(self, value: int) -> None:
        """Publish the consumer-freed count INTO the region, where the
        sender reads it through its already-open window — the zero-frame
        "this region is reusable" signal (RDMAbox's pre-registered-buffer
        discipline without a control message per transfer). Plain stores
        suffice on TSO hardware: the count is monotonic and the sender
        orders its payload write after the matching read by program order;
        non-view domains (verbs/tcp_window) never read it and stay on
        explicit grant frames."""
        _DOORBELL.pack_into(self.region.buf,
                            self.offset + self.capacity + _NONCE_BYTES,
                            value)


class RegionLease:
    """A pool region claimed for transfers on one link.

    Two lifetimes: a one-shot lease (solicited claim) delivers once and
    recycles when the delivered wrapper's last alias dies; a STANDING
    lease (``standing=True``, the steady-state grant) stays claimed across
    many transfers — after each delivery the wrapper's death rings the
    region's doorbell instead of recycling, and the sender reuses the
    region with no further control traffic."""

    __slots__ = ("pool", "pr", "lease_id", "cls", "kind", "pregrant",
                 "standing", "delivered", "_freed", "_retired", "_recycled",
                 "_discard", "_lock")

    #: lint rule `lock`: settlement state shared between the delivering
    #: reader thread, wrapper finalizers (whichever thread drops the last
    #: alias) and the link's death path
    _GUARDED_BY = {"delivered": "_lock", "_freed": "_lock",
                   "_retired": "_lock", "_recycled": "_lock",
                   "_discard": "_lock"}

    def __init__(self, pool: "LandingPool", pr: _PoolRegion, lease_id: int,
                 cls: int):
        self.pool = pool
        self.pr = pr
        self.lease_id = lease_id
        self.cls = cls
        self.kind = pool.kind
        self.pregrant = False
        self.standing = False
        self.delivered = 0
        self._freed = 0
        self._retired = False
        self._recycled = False
        self._discard = False
        self._lock = make_lock("RegionLease._lock")

    def _maybe_recycle_locked(self) -> bool:
        """The ONE recycle rule: a region returns to the pool exactly once,
        when no further delivery can happen (retired, or a one-shot lease
        already delivered) AND no delivered wrapper is still aliased."""
        if self._recycled:
            return False
        done = self._retired or (self.delivered > 0 and not self.standing)
        if done and self._freed == self.delivered:
            # contract: caller holds _lock (the _locked suffix)
            self._recycled = True  # tpr: allow(lock)
            return True
        return False

    def claim_fields(self) -> Tuple[str, str, int, int, bytes, bool]:
        pr = self.pr
        return (self.kind, pr.region.handle, pr.offset, pr.capacity,
                pr.nonce, self.standing)

    def deliver(self, nbytes: int):
        """The received payload as a writable buffer aliasing the region.
        Region reuse is gated on the wrapper's death: every consumer alias
        (decode views, aligned dlpack imports) transitively references it,
        so the finalize fires only when no consumer can observe the memory
        anymore — then a one-shot lease recycles to the pool and a
        standing lease rings the doorbell for the sender."""
        with self._lock:
            if self._retired or (self.delivered and not self.standing):
                raise RuntimeError("lease already settled")
            if nbytes > self.pr.capacity:
                raise ValueError(f"complete of {nbytes} exceeds leased "
                                 f"capacity {self.pr.capacity}")
            if self.standing and self.delivered != self._freed:
                # the sender reused a standing region before its previous
                # wrapper died — a protocol violation the doorbell exists
                # to prevent; refuse the delivery rather than hand out a
                # second alias over live memory
                raise RuntimeError("standing region completed while its "
                                   "previous delivery is still aliased")
            self.delivered += 1
            gen = self.delivered
        wrapper = np.frombuffer(self.pr.region.buf, np.uint8, count=nbytes,
                                offset=self.pr.offset)
        weakref.finalize(wrapper, self._on_wrapper_dead, gen)
        # hand out a memoryview OVER the wrapper (not the ndarray itself):
        # the stream layer treats message bodies as buffers (`body in
        # (sentinels)` must stay a scalar check), and every consumer alias
        # still chains to the wrapper, so the finalize stays sound
        return memoryview(wrapper)

    def _on_wrapper_dead(self, gen: int) -> None:
        with self._lock:
            self._freed = max(self._freed, gen)
            recycle = self._maybe_recycle_locked()
            discard = self._discard
            ring = self.standing and not self._retired
        if recycle:
            self.pool._recycle(self.pr, self.cls, discard=discard)
        elif ring:
            self.pr.doorbell_store(gen)

    def release(self, discard: bool = False) -> None:
        """Return the region without (further) delivery: refused/aborted
        transfer, or link teardown with the region claimed/standing. If a
        delivered wrapper is still aliased, the actual recycle defers to
        its finalize.

        ``discard=True`` (the PEER-DEATH path): the region is destroyed
        instead of pooled — a straggling sender on the dead connection may
        still hold a window and land a late one-sided write, which must hit
        orphaned memory, never a region re-leased to a new transfer (the
        same stale-write rule Pair.init enforces by never reusing ring
        regions across connections)."""
        with self._lock:
            self._retired = True
            if discard:
                self._discard = True
            recycle = self._maybe_recycle_locked()
            discard = self._discard
        if recycle:
            self.pool._recycle(self.pr, self.cls, discard=discard)


class LandingPool:
    """Per-domain pool of registered, 64B-aligned landing regions.

    Regions are allocated from the :class:`~tpurpc.core.pair.MemoryDomain`
    named by ``kind`` (shm for cross-process on one host, the pair's own
    domain on ring planes, verbs on RDMA hardware), pooled by size class
    (:func:`size_class`) under a byte budget, and recycled only when
    provably unobservable (see :meth:`RegionLease.deliver`)."""

    #: lint rule `lock`: the free lists, zombie quarantine and byte budget
    #: are shared between reader threads, finalizers and lease callers
    _GUARDED_BY = {"_free": "_lock", "_zombies": "_lock",
                   "_allocated": "_lock"}

    def __init__(self, kind: str, budget: Optional[int] = None):
        self.kind = kind
        self._domain = _pair.make_domain(kind)
        self._lock = make_lock("LandingPool._lock")
        self._free: Dict[int, List[_PoolRegion]] = {}
        #: discarded (death-quarantined) regions still pinned by consumer
        #: aliases; close retried on later pool activity, never re-leased
        self._zombies: List[_PoolRegion] = []
        self._allocated = 0
        self._budget = budget if budget is not None else _pool_budget()

    @staticmethod
    def _try_close(pr: _PoolRegion) -> bool:
        """Non-blocking best-effort region destruction (the GC-callback
        discard path must never sit in Region.close's bounded retry)."""
        try:
            pr.region.buf.release()
        except BufferError:
            return False
        try:
            pr.region._close()
        except Exception:
            pass  # the mapping is gone either way at process exit
        return True

    def lease(self, nbytes: int, lease_id: int) -> Optional[RegionLease]:
        """A region of capacity ≥ ``nbytes``: a free one of its class, else
        a new one, for which free regions of other classes are closed where
        the budget is spent (``rdv_pool_reclaims``). None when even that
        does not make room: the claim is then refused, which tells the
        sender that the standing regions it holds are its whole window: it
        waits for one of their doorbells for as long as the link's measured
        residence says one is due, and otherwise (none held, none ever seen
        to free, or the wait outlived the estimate) falls back to the framed
        path — degradation, never a deadlock."""
        cls = size_class(nbytes)
        with self._lock:
            zombies, self._zombies = self._zombies, []
        if zombies:  # retry quarantined closes off the hot path
            still = [pr for pr in zombies if not self._try_close(pr)]
            if still:
                with self._lock:
                    self._zombies.extend(still)
        victims: List[_PoolRegion] = []
        with self._lock:
            bucket = self._free.get(cls)
            if bucket:
                pr = bucket.pop()
                pr.doorbell_store(0)  # fresh lease: no consumer history
                return RegionLease(self, pr, lease_id, cls)
            alloc_bytes = _region_bytes(cls)
            need = self._allocated + alloc_bytes - self._budget
            if need > 0:
                # the regions a link's window grew out of come back to the
                # free lists (``RdvLink._hand_back``), where no other class
                # could use them: close them, largest first. Leased,
                # standing and aliased regions are on no free list
                if sum(_region_bytes(c) * len(b)
                       for c, b in self._free.items()) < need:
                    return None
                for c in sorted(self._free, reverse=True):
                    b = self._free[c]
                    while b and need > 0:
                        victims.append(b.pop())
                        need -= _region_bytes(c)
                        self._allocated -= _region_bytes(c)
            self._allocated += alloc_bytes
        for pr in victims:
            _RDV_POOL_RECLAIMS.inc()
            if not self._try_close(pr):
                with self._lock:
                    self._zombies.append(pr)
        try:
            region = self._domain.alloc(alloc_bytes)
        except Exception:
            with self._lock:
                self._allocated -= alloc_bytes
            return None
        base = np.frombuffer(region.buf, np.uint8)
        offset = int((-base.ctypes.data) % _ALIGN)
        del base
        nonce = os.urandom(_NONCE_BYTES)
        region.buf[offset + cls:offset + cls + _NONCE_BYTES] = nonce
        return RegionLease(self, _PoolRegion(region, offset, cls, nonce),
                           lease_id, cls)

    def _recycle(self, pr: _PoolRegion, cls: int,
                 discard: bool = False) -> None:
        if discard:
            # death-path quarantine: never re-lease a region a straggling
            # peer window might still write; destroy it (deferred to the
            # zombie sweep while consumer aliases pin the mapping)
            with self._lock:
                self._allocated -= _region_bytes(pr.capacity)
            if not self._try_close(pr):
                with self._lock:
                    self._zombies.append(pr)
            return
        with self._lock:
            self._free.setdefault(cls, []).append(pr)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "allocated_bytes": self._allocated,
                "free_regions": sum(len(v) for v in self._free.values()),
            }

    def trim(self) -> None:
        """Release every pooled free region back to the OS (atexit / test
        isolation). In-flight and alias-pinned regions are untouched."""
        with self._lock:
            buckets, self._free = self._free, {}
            for bucket in buckets.values():
                for pr in bucket:
                    self._allocated -= _region_bytes(pr.capacity)
        for bucket in buckets.values():
            for pr in bucket:
                try:
                    pr.region.close()
                except Exception:
                    pass  # an alias raced the trim; the region stays mapped


_pools: Dict[str, LandingPool] = {}
_pools_lock = make_lock("rendezvous._pools_lock")


def landing_pool(kind: str) -> LandingPool:
    """The process-wide landing pool for one domain kind (regions are
    shared across connections; per-link lease registries keep the death-
    release story per connection)."""
    pool = _pools.get(kind)
    if pool is None:
        with _pools_lock:
            pool = _pools.get(kind)
            if pool is None:
                pool = _pools[kind] = LandingPool(kind)
    return pool


def _trim_pools_atexit() -> None:
    for pool in list(_pools.values()):
        pool.trim()
    # Regions still pinned by live consumer aliases (an app holding a
    # decoded tensor at exit) cannot close; at interpreter teardown their
    # SharedMemory destructors would each print an unraisable BufferError
    # ("Exception ignored in __del__") for a condition that is expected and
    # harmless — the OS reclaims the mappings with the process. Neutralize
    # the destructor AFTER the orderly trim; explicit close paths all ran
    # (or can no longer run) by now.
    try:
        from multiprocessing import shared_memory

        shared_memory.SharedMemory.__del__ = lambda self: None
    except Exception:
        pass


import atexit  # noqa: E402  (registration belongs next to what it cleans)

atexit.register(_trim_pools_atexit)


# ---------------------------------------------------------------------------
# Wire payload codecs (control messages are tiny; clarity over cleverness).
# ---------------------------------------------------------------------------

def _pack_offer(req_id: int, nbytes: int, kinds: Sequence[str]) -> bytes:
    return _OFFER.pack(req_id, nbytes) + ",".join(kinds).encode()


def _unpack_offer(payload) -> Tuple[int, int, List[str]]:
    buf = bytes(payload)
    req_id, nbytes = _OFFER.unpack_from(buf)
    kinds = buf[_OFFER.size:].decode() or ""
    return req_id, nbytes, [k for k in kinds.split(",") if k]


def _pack_claim(req_id: int, lease: Optional[RegionLease]) -> bytes:
    if lease is None:
        return _CLAIM_HDR.pack(req_id, 0, 0)
    kind, handle, offset, capacity, nonce, standing = lease.claim_fields()
    kb = kind.encode()
    return (_CLAIM_HDR.pack(req_id, lease.lease_id, 1)
            + _CLAIM_REG.pack(offset, capacity, nonce, 1 if standing else 0)
            + bytes([len(kb)]) + kb + handle.encode())


class _Claim:
    """Sender-side view of a claimed region. A STANDING claim is reusable:
    after each COMPLETE the sender bumps ``used`` and may write again only
    once the region's doorbell word (consumer-freed count, stored by the
    receiver's wrapper finalize) catches up — zero control frames per
    steady-state transfer."""

    __slots__ = ("lease_id", "kind", "handle", "offset", "capacity",
                 "nonce", "standing", "used", "inflight", "done_ns",
                 "busy_ns")

    def __init__(self, lease_id, kind, handle, offset, capacity, nonce,
                 standing=False):
        self.lease_id = lease_id
        self.kind = kind
        self.handle = handle
        self.offset = offset
        self.capacity = capacity
        self.nonce = nonce
        self.standing = standing
        self.used = 0
        self.inflight = False  # a sender thread owns this claim right now
        #: monotonic_ns of the COMPLETE that handed the region's current
        #: use to the consumer (0: not out, or not stamped): where its
        #: residence is measured from
        self.done_ns = 0
        #: monotonic_ns of the last look that found this use still with
        #: the consumer (0: none yet)
        self.busy_ns = 0


def _unpack_claim(payload) -> Tuple[int, Optional[_Claim]]:
    buf = bytes(payload)
    req_id, lease_id, ok = _CLAIM_HDR.unpack_from(buf)
    if not ok:
        return req_id, None
    pos = _CLAIM_HDR.size
    offset, capacity, nonce, standing = _CLAIM_REG.unpack_from(buf, pos)
    pos += _CLAIM_REG.size
    klen = buf[pos]
    pos += 1
    kind = buf[pos:pos + klen].decode()
    handle = buf[pos + klen:].decode()
    return req_id, _Claim(lease_id, kind, handle, offset, capacity, nonce,
                          standing=bool(standing))


class SendAbandoned(Exception):
    """``RdvLink.send_message`` gave a message up inside its credit wait
    because the call that owns it ended (its deadline passed, or the
    caller's stop condition held): nothing was sent, and the framed path
    must not send it either. The caller's own error path takes over."""


class _Residence:
    """How long one link's standing regions of one size class stay with
    the consumer, as the sender measures it: a smoothed mean and mean
    deviation in ns, the retransmission timer's estimator (Jacobson and
    Karels; RFC 6298 §2 with its gains 1/8 and 1/4, the first measurement
    R seeding mean R and deviation R/2, and the doorbell read slice as the
    clock granularity G). ``refused_ns`` is when a claim of the class last
    failed, ``expired_ns`` when a wait for it last ran out (0: never)."""

    __slots__ = ("mean_ns", "dev_ns", "refused_ns", "expired_ns")

    def __init__(self, sample_ns: int):
        self.mean_ns = sample_ns
        self.dev_ns = sample_ns // 2
        self.refused_ns = 0
        self.expired_ns = 0

    def feed(self, sample_ns: int) -> None:
        self.dev_ns += (abs(sample_ns - self.mean_ns) - self.dev_ns) // 4
        self.mean_ns += (sample_ns - self.mean_ns) // 8

    def slice_ns(self) -> int:
        """Sleep between two doorbell reads of a credit wait."""
        return max(1, self.mean_ns // 8)

    def bound_ns(self) -> int:
        """A region out for longer than this is overdue."""
        return self.mean_ns + max(self.slice_ns(), 4 * self.dev_ns)


# ---------------------------------------------------------------------------
# The link: one per framed connection, both roles.
# ---------------------------------------------------------------------------

class _CtrlFrameCoalescer:
    """Self-clocking writev combiner for FRAMED control ops — PR 3's
    FrameWriter discipline applied to the rendezvous control plane's cold
    path: the first sender flushes directly; ops arriving while a flush is
    in flight queue and drain in ONE multi-frame send (``send_ops``), so a
    burst of COMPLETEs from N streams costs one transport write instead of
    N.  An idle link pays zero added latency (no timer).  Transports
    without a multi-op send (``send_ops=None`` — the h2 planes) send
    per-op; FIFO order is preserved either way."""

    _GUARDED_BY = {"_pending": "_mu", "_flushing": "_mu"}

    def __init__(self, send_op: Callable[[int, int, bytes], None],
                 send_ops: Optional[Callable] = None):
        self._send_op = send_op
        self._send_ops = send_ops
        self._mu = make_lock("_CtrlFrameCoalescer._mu")
        self._pending: List[Tuple[int, int, bytes]] = []
        self._flushing = False

    def send(self, op: int, stream_id: int, payload: bytes) -> None:
        if self._send_ops is None:
            self._send_op(op, stream_id, payload)
            return
        with self._mu:
            self._pending.append((op, stream_id, payload))
            if self._flushing:
                return  # the in-flight flusher writes it
            self._flushing = True
        while True:
            with self._mu:
                batch, self._pending = self._pending, []
                if not batch:
                    self._flushing = False
                    return
            try:
                if len(batch) == 1:
                    self._send_op(*batch[0])
                else:
                    self._send_ops(batch)
                    _stats.batch_hist("ctrl_coalesce").record(len(batch))
            except BaseException:
                # connection dying: drop the queue (every control path
                # treats sends as best-effort; link close releases leases)
                with self._mu:
                    self._pending = []
                    self._flushing = False
                raise


#: cross-link window reuse — every hit is a writer QP + bounce
#: registration NOT created (verbs) or a mmap/attach NOT repeated (shm)
_WINDOW_SHARE_HITS = _metrics.counter("rdv_window_share_hits")


class _WindowShare:
    """Process-wide refcounted cache of open peer-region windows keyed
    ``(kind, handle)`` — the rendezvous half of the ISSUE 16 shared-MR
    plane. Ten links (or ten thousand pairs' links) writing into the same
    peer arena share ONE open window — on verbs that is one writer QP and
    one bounce registration instead of one per link, which is how the
    registration count stays O(distinct regions × size-classes) rather
    than O(pairs).

    ``acquire`` bumps a refcount (opening on a miss); ``release`` drops
    it, parking a zero-ref window on a bounded idle LRU so the next
    acquirer of the same region skips the open entirely. Windows are
    opened on the share's OWN domains, never a link's, so a shared window
    cannot die with whichever link happened to open it first.

    Write safety across holders: a claim/grant leases a region to exactly
    one transfer at a time, and the verbs bounce staging is offset-mapped
    (window offset == bounce offset), so concurrent holders writing
    disjoint claimed spans never collide — the argument that makes
    per-link window reuse sound extends unchanged across links.
    """

    _GUARDED_BY = {"_entries": "_lock", "_idle": "_lock",
                   "_domains": "_lock"}

    _MAX_IDLE = 64

    def __init__(self):
        self._lock = make_lock("WindowShare._lock")
        #: key -> [window, refcount, window_bytes]
        self._entries: Dict[Tuple[str, str], list] = {}
        self._idle: List[Tuple[str, str]] = []  # refcount-0 keys, LRU
        self._domains: Dict[str, _pair.MemoryDomain] = {}

    def _domain(self, kind: str) -> _pair.MemoryDomain:
        with self._lock:
            d = self._domains.get(kind)
            if d is None:
                d = self._domains[kind] = _pair.make_domain(kind)
            return d

    def acquire(self, kind: str, handle: str, nbytes: int) -> _pair.Window:
        key = (kind, handle)
        stale = None
        with self._lock:
            e = self._entries.get(key)
            if e is not None:
                if e[2] >= nbytes:
                    if e[1] == 0:
                        try:
                            self._idle.remove(key)
                        except ValueError:
                            pass
                    e[1] += 1
                    _WINDOW_SHARE_HITS.inc()
                    return e[0]
                if e[1] == 0:
                    # undersized and idle: retire it, reopen bigger below
                    stale = self._entries.pop(key)
                    try:
                        self._idle.remove(key)
                    except ValueError:
                        pass
        if stale is not None:
            _close_window(stale[0])
        win = self._domain(kind).open_window(handle, nbytes)
        with self._lock:
            if key not in self._entries:
                self._entries[key] = [win, 1, nbytes]
                return win
        # raced another opener, or an undersized entry is still
        # referenced: hand out a PRIVATE window — release()'s identity
        # check routes it straight to close instead of the refcount
        return win

    def release(self, kind: str, handle: str, win: _pair.Window) -> None:
        key = (kind, handle)
        close_now = []
        with self._lock:
            e = self._entries.get(key)
            if e is not None and e[0] is win:
                if e[1] > 0:
                    e[1] -= 1
                    if e[1] == 0:
                        self._idle.append(key)
                        while len(self._idle) > self._MAX_IDLE:
                            k = self._idle.pop(0)
                            dead = self._entries.pop(k, None)
                            if dead is not None:
                                close_now.append(dead[0])
            else:
                close_now.append(win)  # private window (see acquire)
        for w in close_now:
            _close_window(w)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"entries": len(self._entries),
                    "idle": len(self._idle),
                    "referenced": sum(1 for e in self._entries.values()
                                      if e[1] > 0)}

    def drain(self) -> None:
        """Close every cached window and domain (test isolation; callers
        must have released their refs — a drained-under window fails its
        next write, same as a closed link's would)."""
        with self._lock:
            wins = [e[0] for e in self._entries.values()]
            self._entries.clear()
            self._idle = []
            domains = list(self._domains.values())
            self._domains.clear()
        for w in wins:
            _close_window(w)
        for d in domains:
            try:
                d.close()
            except Exception:
                pass


_WINDOW_SHARE: Optional[_WindowShare] = None
_WINDOW_SHARE_LOCK = make_lock("rendezvous._WINDOW_SHARE")


def window_share() -> _WindowShare:
    global _WINDOW_SHARE
    with _WINDOW_SHARE_LOCK:
        if _WINDOW_SHARE is None:
            _WINDOW_SHARE = _WindowShare()
        return _WINDOW_SHARE


class RdvLink:
    """Rendezvous state for ONE framed connection: the sender role (offer,
    one-sided write, complete) and the receiver role (pool leases, claims,
    zero-copy delivery) — every connection carries both directions.

    Transport-agnostic: the owning connection supplies ``send_op(op,
    stream_id, payload)`` (frame the control message), ``deliver(stream_id,
    flags, wrapper)`` (hand a completed payload to the stream layer), and
    optionally ``pump(pred, deadline)`` for inline-pump transports where
    the waiting sender must drive the reader itself."""

    #: lint rule `lock`: every registry below is shared between the
    #: connection reader/pump thread, sender threads and the death path
    _GUARDED_BY = {"_reqs": "_lock", "_grants": "_lock",
                   "_leases": "_lock", "_req_lease": "_lock",
                   "_pregrants_out": "_lock", "_windows": "_lock",
                   "_window_order": "_lock", "_residence": "_lock"}

    def __init__(self, name: str,
                 send_op: Callable[[int, int, bytes], None],
                 deliver: Callable[[int, int, object], None],
                 pool_kinds: Sequence[str] = ("shm",),
                 open_kinds: Sequence[str] = ("shm", "local"),
                 pump: Optional[Callable] = None,
                 send_ops: Optional[Callable] = None):
        self._send_op = send_op
        self._coalescer = _CtrlFrameCoalescer(send_op, send_ops)
        #: tpurpc-pulse seams, bound by the owning connection when its
        #: descriptor-ring plane arms: ``ctrl_post(op, sid, payload) ->
        #: bool`` places a control op in the peer's ring (True = the
        #: framed path must NOT also send it); ``ctrl_drain()`` consumes
        #: this side's ring from a sender thread (pregrant pickup)
        self.ctrl_post: Optional[Callable[[int, int, bytes], bool]] = None
        self.ctrl_drain: Optional[Callable[[], int]] = None
        self._deliver = deliver
        self._pool_kinds = tuple(pool_kinds)
        self._open_kinds = tuple(open_kinds)
        self._pump = pump
        self._lock = make_lock("RdvLink._lock")
        self._cond = make_condition("RdvLink._cond", self._lock)
        self.negotiated = False
        self.closed = False
        #: reader-thread ident the sender must never block on (a claim wait
        #: there would deadlock against the claim's own delivery)
        self.disallowed_thread: Optional[int] = None
        #: the connection's max_receive_message_length (None/negative =
        #: unlimited): offers past it are REFUSED, pushing the transfer to
        #: the framed path whose oversize machinery rejects it with the
        #: proper RESOURCE_EXHAUSTED — the bulk plane must not become a
        #: receive-limit bypass
        self.recv_limit: Optional[int] = None
        self._req_ids = itertools.count(1)
        self._lease_ids = itertools.count(1)
        self._reqs: Dict[int, dict] = {}            # sender: req -> state
        self._grants: Dict[int, List[_Claim]] = {}  # sender: cls -> claims
        self._residence: Dict[int, _Residence] = {}  # sender: cls -> est.
        self._leases: Dict[int, RegionLease] = {}   # receiver: id -> lease
        self._req_lease: Dict[int, int] = {}        # receiver: req -> lease
        self._pregrants_out: Dict[int, int] = {}    # receiver: cls -> count
        self._windows: Dict[Tuple[str, str], _pair.Window] = {}
        self._window_order: List[Tuple[str, str]] = []
        self._domains: Dict[str, _pair.MemoryDomain] = {}
        self._ftag = _flight.tag_for("rdv:" + name)

    # -- negotiation ---------------------------------------------------------

    def on_peer_hello(self, payload: bytes = b"") -> None:
        """The peer demonstrated it speaks the rendezvous control frames
        (hello PING on the native framing, the custom SETTINGS id on h2)."""
        self.negotiated = True

    # -- control send seam (tpurpc-pulse) -------------------------------------

    def _ctrl_send(self, op: int, stream_id: int, payload: bytes,
                   ring_ok: bool = True) -> None:
        """Send one control op: descriptor ring when the link adopted one
        (zero frames, zero wakeups), else the framed path through the
        self-clocking coalescer.  Ring failures (full, closed, oversized)
        degrade to framed — never a lost op, never an exception for the
        degradation itself; framed-path transport errors propagate exactly
        as ``send_op``'s always did.

        ``ring_ok=False`` pins the op to the framed path: a COMPLETE whose
        payload rode an ASYNCHRONOUS landing domain (tcp_window records,
        verbs WRs — anything without a host-addressable view) is ordered
        after the payload only by the shared record/QP stream the framed
        connection rides; a ring-posted COMPLETE would overtake the bytes
        and deliver a torn region (caught live by the tcpw cross-process
        test)."""
        post = self.ctrl_post
        if post is not None and ring_ok:
            try:
                if _transport.dispatch("post", self, post, op, stream_id,
                                       payload):
                    return
            except Exception:
                pass  # ring tearing down: the framed path still works
        t0 = time.monotonic_ns()
        _transport.dispatch("frame", self, self._coalescer.send, op,
                            stream_id, payload)
        _RDV_CTRL_FRAMES.inc()
        n = len(payload)
        dt = time.monotonic_ns() - t0
        _LENS_CTRL_BYTES.inc(n)
        _LENS_CTRL_NS.inc(dt)

    # -- sender role ---------------------------------------------------------

    def eligible(self, total: int, flags_compressed: bool = False) -> bool:
        return (self.negotiated and not self.closed and enabled()
                and not flags_compressed
                and total >= min_bytes() and total <= _MAX_TRANSFER
                and threading.get_ident() != self.disallowed_thread)

    def send_message(self, stream_id: int, flags: int,
                     segs: Sequence, total: int,
                     deadline: Optional[float] = None,
                     should_stop: Optional[Callable[[], bool]] = None
                     ) -> bool:
        """Move one whole MESSAGE payload via rendezvous. True when the
        payload was placed and COMPLETE sent (the framed path must NOT also
        send it); False to fall back to the framed path — refused claim
        with no credit worth waiting for, timeout, write failure — never an
        exception for fallback cases.

        ``deadline`` (a ``time.monotonic()`` instant) and ``should_stop``
        are the owning call's end, where the caller has one; they bound
        only the credit wait (``_await_credit``), which raises
        :class:`SendAbandoned` when either cuts it."""
        cls = size_class(total)
        # the link's window class where the window holds the message, else
        # the class that will be the window once the message is claimed:
        # what the residence estimate and the refusal memory are kept under
        key = self._hand_back(total) or cls
        claim = self._take_grant(key, total)
        if claim is None and self._has_standing(total):
            # tpurpc-pulse: every standing region's doorbell is behind —
            # the consumer is mid-batch.  A solicited claim here costs a
            # full control round trip (~0.8 ms on this rig); a bounded
            # yield-poll of the doorbells (draining our ctrl ring for
            # pregrant top-ups as we go) hands the core to the consumer
            # and almost always turns up a freed region in a few slices.
            poll_until = time.monotonic() + 0.002
            drain = self.ctrl_drain
            while claim is None and time.monotonic() < poll_until:
                if drain is not None:
                    try:
                        drain()
                    except Exception:
                        drain = None
                time.sleep(0)
                claim = self._take_grant(key, total, watching=True)
        waited = False
        if claim is None and self._window_full(total):
            # a full standing window IS this link's credit: it waits for
            # one of its own doorbells before it asks the receiver for
            # memory beyond it (a one-shot region more is a message more
            # parked in the receiver's queue, and the pool's spare regions
            # are what a late link's window and an overdue wait's claim
            # are made of)
            claim = self._await_credit(key, total, deadline, should_stop)
            waited = True
        if claim is None:
            if not self._refusal_stands(key):
                claim = self.rdv_claim(stream_id, total, cls)
                if claim is None:
                    self._claim_failed(key)
            if claim is None and not waited:
                # no more memory: what this link holds is its window, and
                # a window waits for credit (once a message)
                claim = self._await_credit(key, total, deadline,
                                           should_stop)
        if claim is None:
            _RDV_FALLBACK.inc()
            return False
        wedge = TEST_HOOKS.get("wedge_after_claim")
        if wedge is not None:
            while not wedge.wait(timeout=0.05):  # pragma: no cover - chaos
                if self.closed:
                    break
        try:
            self._rdv_write(claim, segs, total)
        except BaseException:
            self._drop_grant(claim)
            self.rdv_release(claim)
            _RDV_FALLBACK.inc()
            return False
        self.rdv_complete(claim, stream_id, flags, total)
        _RDV_SENT.inc()
        _RDV_SENT_BYTES.inc(total)
        return True

    def _take_grant(self, key: int, total: int,
                    watching: bool = False) -> Optional[_Claim]:
        """A usable cached grant of capacity ≥ ``total``, whatever its
        class, the largest first (the window before regions it grew out
        of): a one-shot claim is consumed; a STANDING claim is acquired
        (inflight flag) and reused only when its doorbell shows every
        previous delivery's aliases died — the zero-frame steady-state
        path. ``watching``: this look follows, by no more than a poll
        slice, one that found every region busy, so a region it finds free
        has just come free and its residence is a measurement of window
        class ``key`` (``_saw_free``)."""
        with self._lock:
            if self.closed:
                return None
            fits = sorted((c for b in self._grants.values() for c in b
                           if c.capacity >= total),
                          key=lambda c: -c.capacity)
        for claim in fits:
            if not claim.standing:
                with self._lock:
                    b = self._grants.get(claim.capacity)
                    if b is not None and claim in b:
                        b.remove(claim)
                        return claim
                continue
            with self._lock:
                if claim.inflight:
                    continue
                claim.inflight = True
            if self._standing_free(claim):
                if claim.used:
                    self._saw_free(key, claim, watching)
                return claim
            with self._lock:
                claim.inflight = False
                claim.busy_ns = time.monotonic_ns()
        return None

    def _hand_back(self, total: int) -> int:
        """Give the receiver back every FREE standing grant smaller than
        the link's window class (the regions its window grew out of), or
        than the message where it is larger than every grant held (the
        receiver grows the window to its class when it completes): one
        ``OP_RELEASE`` each, the grant dropped first so that no send takes
        it after. A grant in flight, or whose doorbell is behind, stays
        until a later send finds it free. Returns the window class where
        the window holds ``total``, else 0. A link whose grants are all of
        one class that holds the message reads no doorbell here."""
        with self._lock:
            standing = self._standing_for_locked(0)
            window = max((c.capacity for c in standing), default=0)
            bar = window if window >= total else total
            spare = [c for c in standing
                     if c.capacity < bar and not c.inflight]
            for c in spare:
                c.inflight = True
        for claim in spare:
            if claim.used and not self._standing_free(claim):
                with self._lock:
                    claim.inflight = False
                continue
            with self._lock:
                b = self._grants.get(claim.capacity)
                if b is not None and claim in b:
                    b.remove(claim)
            self.rdv_release(claim)
        return window if window >= total else 0

    def _refusal_stands(self, key: int) -> bool:
        """Was the last claim of a message for window class ``key`` refused
        so lately that asking again would get the same answer? The pool
        gains room when a region comes back, which takes a residence, so a
        refusal is believed for one residence bound: a full window asks the
        receiver once a window's turn, not once a message (each refused
        OFFER is the receiver's interpreter, the fan-in's limit, taken from
        the very handlers the sender waits for). Only where the link can
        wait instead: a window class with a measured residence."""
        with self._lock:
            est = self._residence.get(key)
            return (est is not None and est.refused_ns != 0
                    and time.monotonic_ns() - est.refused_ns
                    < est.bound_ns())

    def _claim_failed(self, key: int) -> None:
        with self._lock:
            est = self._residence.get(key)
            if est is not None:
                est.refused_ns = time.monotonic_ns()

    def _saw_free(self, key: int, claim: _Claim, watching: bool) -> None:
        """A region that was out has come back: where the sender was
        watching, the time since the region's COMPLETE is one measurement
        of the residence of window class ``key`` (the capacity of the
        link's standing regions, whatever the message's own class). A free
        found at a send's first look says only that the residence was at
        most that long (an idle sender would read its own idle time), so it
        is no measurement; where the window class has no estimate yet, the
        last look that found the region still out seeds one, from below."""
        with self._lock:
            done, claim.done_ns = claim.done_ns, 0
            busy, claim.busy_ns = claim.busy_ns, 0
            est = self._residence.get(key)
            if watching and done:
                sample = time.monotonic_ns() - done
                if est is None:
                    self._residence[key] = _Residence(sample)
                else:
                    est.feed(sample)
            elif est is None and busy > done > 0:
                # no estimate yet, and nobody was watching: the region was
                # last SEEN out ``busy - done`` after its COMPLETE, which
                # its residence is at least. That seeds the estimate (the
                # waits it arms are watched and correct it). Without it a
                # link whose window filled before any doorbell rang inside
                # a yield-poll sends framed, so it polls for 2 ms of every
                # long framed send, sees no ring, and stays without an
                # estimate: 150 to 280 framed messages on two to four
                # links of eight in a fan-in (PERF.md 6, PR 33)
                self._residence[key] = _Residence(busy - done)

    def _await_credit(self, key: int, total: int,
                      deadline: Optional[float],
                      should_stop: Optional[Callable[[], bool]]
                      ) -> Optional[_Claim]:
        """The window is full (or short, and the receiver has no more
        memory): wait for one of this link's own doorbells, for as long as
        the link's history says one is due. The OLDEST region out is waited
        for until it has been out for window class ``key``'s residence
        bound, measured from its own COMPLETE or from the last expired wait,
        if that is later; any region big enough that frees meanwhile is
        taken.

        None (the caller falls back to the framed path, as before this
        wait existed) at once where there is nothing to expect: no
        measured residence of the window class, no stamped standing region
        out;
        when the link closes; and when the oldest region outlives the
        bound (``_credit_expired``). Raises
        :class:`SendAbandoned` when the caller's ``should_stop`` holds or
        its ``deadline`` passes inside the wait."""
        with self._lock:
            est = self._residence.get(key)
            out = [c.done_ns for c in self._standing_for_locked(total)
                   if c.done_ns and not c.inflight]
            if est is None or not out or self.closed:
                return None
            # after an expired wait, a bound from THAT: a window sends one
            # message beyond itself a bound, not one a look
            limit_ns = max(min(out), est.expired_ns) + est.bound_ns()
            slice_ns = est.slice_ns()
        _RDV_CREDIT_WAITS.inc()
        deadline_ns = None if deadline is None else deadline * 1e9
        drain = self.ctrl_drain

        def over() -> bool:
            return self.closed or (should_stop is not None
                                   and should_stop())

        t0_ns = time.monotonic_ns()
        st = _lens.stage("rdv_credit", total).begin()
        try:
            while True:
                if drain is not None:
                    try:
                        drain()
                    except Exception:
                        drain = None
                claim = self._take_grant(key, total, watching=True)
                if claim is not None or self.closed:
                    return claim
                if should_stop is not None and should_stop():
                    raise SendAbandoned("stream ended in the credit wait")
                now_ns = time.monotonic_ns()
                wake_ns = min(now_ns + slice_ns, limit_ns)
                if deadline_ns is not None:
                    if now_ns >= deadline_ns:
                        raise SendAbandoned("deadline passed in the "
                                            "credit wait")
                    wake_ns = min(wake_ns, deadline_ns)
                if now_ns >= limit_ns:
                    self._credit_expired(key, now_ns - t0_ns)
                    return None
                self._idle(over, wake_ns / 1e9)
        finally:
            _RDV_CREDIT_WAIT_NS.inc(st.end())

    def _credit_expired(self, key: int, waited_ns: int) -> None:
        """The oldest region outlived its estimate: the consumer is late
        (a tail of its residence), retains more than a window, or has
        stalled. THIS message goes by another path, which is what a
        consumer that waits for it needs; the next one waits again, so a
        full window sends one message beyond itself a bound and no more
        (messages sent unwaited until a doorbell is next seen to free are,
        under a fan-in, 10 to 20 framed ones an expired wait, each 15 MB
        of host copies under the receiver's interpreter, which makes the
        other links' regions late in their turn). The time this sender
        just spent waiting is taken out of the residence of every region
        still out: the consumer may have been waiting for this very
        message, a residence that contained the sender's own stall would
        raise the next bound by it, batch after batch."""
        _RDV_CREDIT_EXPIRED.inc()
        with self._lock:
            est = self._residence.get(key)
            if est is not None:
                est.expired_ns = time.monotonic_ns()
            for c in self._standing_for_locked(0):
                if c.done_ns:
                    c.done_ns += waited_ns

    def _idle(self, pred: Callable[[], bool], deadline: float) -> None:
        """Block until ``pred()`` holds or ``deadline`` (a
        ``time.monotonic()`` instant) passes: pumping the transport where
        the waiting sender must drive the reader itself, else on the
        link's condition (``close`` and every CLAIM notify it)."""
        if self._pump is not None:
            self._pump(pred, deadline)
            return
        with self._cond:
            while not pred():
                remain = deadline - time.monotonic()
                if remain <= 0:
                    break
                self._cond.wait(remain)

    def _standing_for_locked(self, total: int) -> List[_Claim]:
        """The link's STANDING grants of capacity ≥ ``total``, of every
        class (the caller holds ``_lock``)."""
        return [c for b in self._grants.values() for c in b
                if c.standing and c.capacity >= total]

    def _window_full(self, total: int) -> bool:
        """Does this link hold, among the STANDING regions big enough for
        the message, as many as a receiver grants one link
        (``_PREGRANT_DEPTH``)? Then they are its credit window, and a send
        that finds them busy waits for one (``_await_credit``) before it
        asks for a one-shot region beyond them; a link still short of its
        window asks at once, which is how it is topped up."""
        with self._lock:
            return len(self._standing_for_locked(total)) >= _PREGRANT_DEPTH

    def _has_standing(self, total: int) -> bool:
        """Any STANDING cached grant big enough (busy or not) — the signal
        that a freed doorbell, not a new claim, is what's worth waiting
        a moment for."""
        with self._lock:
            return bool(self._standing_for_locked(total))

    def _standing_free(self, claim: _Claim) -> bool:
        """Has the receiver's consumer freed every previous use? Reads the
        region-resident doorbell word through the sender's mapped window —
        no control frame. Non-view domains can't read it and answer False
        (they stay on explicit offer/claim rounds)."""
        try:
            win = self._window_for(claim)
        except Exception:
            return False
        view = win.view
        if view is None:
            return False
        db = claim.offset + claim.capacity + _NONCE_BYTES
        try:
            (freed,) = _DOORBELL.unpack_from(view, db)
        except (ValueError, struct.error):
            return False
        return freed == claim.used

    def _drop_grant(self, claim: _Claim) -> None:
        """Forget a cached grant after a failed write (its region is being
        released): it must not be reused."""
        with self._lock:
            claim.inflight = False
            b = self._grants.get(claim.capacity)
            if b is not None and claim in b:
                b.remove(claim)

    def rdv_claim(self, stream_id: int, total: int,
                  cls: int) -> Optional[_Claim]:
        """OFFER the transfer and wait (pumping where the transport needs
        it) for the peer's CLAIM. None = refused or timed out (the offer is
        then explicitly abandoned with a RELEASE so a crossing claim frees
        its region)."""
        req = next(self._req_ids)
        st = {"claim": _SENTINEL_PENDING}
        with self._lock:
            if self.closed:
                return None
            self._reqs[req] = st
        _flight.emit(_flight.RDV_OFFER, self._ftag, req, total)
        try:
            self._ctrl_send(OP_OFFER, stream_id,
                          _pack_offer(req, total, self._open_kinds))
        except Exception:
            with self._lock:
                self._reqs.pop(req, None)
            return None
        deadline = time.monotonic() + _claim_timeout()

        def pred() -> bool:
            return st["claim"] is not _SENTINEL_PENDING or self.closed

        self._idle(pred, deadline)
        with self._lock:
            self._reqs.pop(req, None)
            claim = st["claim"]
        if claim is _SENTINEL_PENDING:
            # timed out: abandon the offer — a claim crossing this release
            # on the wire finds no pending request and is released by
            # on_claim's unknown-request path
            _flight.emit(_flight.RDV_RELEASE, self._ftag, 0, req)
            try:
                self._ctrl_send(OP_RELEASE, 0, _RELEASE.pack(0, req))
            except Exception:
                pass
            return None
        if claim is _SENTINEL_REFUSED or claim is None:
            return None
        _flight.emit(_flight.RDV_CLAIM, self._ftag, req, claim.lease_id)
        return claim

    def _window_for(self, claim: _Claim) -> _pair.Window:
        key = (claim.kind, claim.handle)
        win = self._windows.get(key)
        if win is not None:
            return win
        # the per-link map holds a REF on the process-wide share — the
        # open (QP connect + bounce registration on verbs) happens at most
        # once per region across every link in the process
        win = window_share().acquire(
            claim.kind, claim.handle,
            claim.offset + claim.capacity + _NONCE_BYTES + _DOORBELL.size)
        extra = None
        evict_key = None
        evict_win = None
        with self._lock:
            prev = self._windows.get(key)
            if prev is not None:
                extra, win = win, prev  # raced a sibling sender thread
            else:
                self._windows[key] = win
                self._window_order.append(key)
                if len(self._window_order) > _WINDOW_CACHE:
                    evict_key = self._window_order.pop(0)
                    evict_win = self._windows.pop(evict_key, None)
        if extra is not None:
            window_share().release(claim.kind, claim.handle, extra)
        if evict_win is not None:
            window_share().release(evict_key[0], evict_key[1], evict_win)
        return win

    def _rdv_write(self, claim: _Claim, segs: Sequence, total: int) -> None:
        """The one-sided placement: every gather segment lands directly in
        the claimed region — no staging join, no landing copy on the other
        side. One RDMA WRITE per segment on the verbs domain; on the
        software domains one copy of the whole gather list, made with the
        interpreter released (``place_released``)."""
        t0 = time.monotonic_ns()
        win = self._window_for(claim)
        view = win.view
        if view is not None:
            if claim.nonce and bytes(
                    view[claim.offset + claim.capacity:
                         claim.offset + claim.capacity + _NONCE_BYTES]
                    ) != claim.nonce:
                raise OSError("rendezvous region nonce mismatch: the "
                              "claimed handle resolves to different memory "
                              "on this host")

        off = claim.offset
        placed = []
        for seg in segs:
            sv = memoryview(seg).cast("B")
            placed.append((off, sv))
            off += len(sv)
        # the one-sided landing is a cross-process message: under simnet
        # the store itself becomes a deliverable, reorderable event (a
        # straggler's write must land only in quarantined memory)
        _transport.dispatch("write", self, _place_spans, win, placed)
        _ledger.rdma_write(total)
        dt = time.monotonic_ns() - t0
        _LENS_RDV_NS.inc(dt)
        _LENS_RDV_BYTES.inc(total)
        _LENS_RDV_COPY.inc(total)

    def rdv_complete(self, claim: _Claim, stream_id: int, flags: int,
                     total: int) -> None:
        if not claim.standing:
            # solicited transfers are edges worth recording; standing-
            # region reuse is steady-state traffic and stays silent (the
            # flight recorder's edges-not-traffic contract)
            _flight.emit(_flight.RDV_WRITE, self._ftag, claim.lease_id,
                         total)
            _flight.emit(_flight.RDV_COMPLETE, self._ftag, claim.lease_id,
                         total)
        with self._lock:
            claim.used += 1
            claim.inflight = False
            if claim.standing:
                claim.done_ns = time.monotonic_ns()
            # a view-backed (synchronous shm/local) landing write is
            # visible the moment it returns, so its COMPLETE may ride the
            # ring; an async domain's bytes are still in flight on the
            # record/QP stream — only the framed path (same stream)
            # sequences the COMPLETE after them
            win = self._windows.get((claim.kind, claim.handle))
        sync_write = win is not None and win.view is not None
        self._ctrl_send(OP_COMPLETE, stream_id,
                        _COMPLETE.pack(claim.lease_id, total, flags & 0xFF),
                        ring_ok=sync_write)

    def rdv_release(self, claim: _Claim) -> None:
        """Abandon a claimed region without completing (write failure,
        cancelled transfer): the peer frees it for reuse."""
        _flight.emit(_flight.RDV_RELEASE, self._ftag, claim.lease_id, 0)
        try:
            self._ctrl_send(OP_RELEASE, 0, _RELEASE.pack(claim.lease_id, 0))
        except Exception:
            pass

    # -- receiver role -------------------------------------------------------

    def on_op(self, op: int, stream_id: int, payload) -> None:
        """Dispatch one control frame (called from the connection's reader/
        pump). Never raises — a malformed control message degrades to a
        refused/ignored transfer, not a dead connection."""
        try:
            if op == OP_OFFER:
                self.on_offer(stream_id, payload)
            elif op == OP_CLAIM:
                self.on_claim(payload)
            elif op == OP_COMPLETE:
                self.on_complete(stream_id, payload)
            elif op == OP_RELEASE:
                self.on_release(payload)
        except Exception:
            from tpurpc.utils.trace import trace_endpoint

            trace_endpoint.log("rendezvous control op %d failed", op)

    def on_offer(self, stream_id: int, payload) -> None:
        req, nbytes, kinds = _unpack_offer(payload)
        _flight.emit(_flight.RDV_OFFER, self._ftag, req, nbytes)
        if TEST_HOOKS.get("drop_offers"):
            return  # chaos seam: starve the sender's claim wait
        lease = self._lease_for(nbytes, kinds)
        if lease is None:
            _RDV_REFUSED.inc()
            self._ctrl_send(OP_CLAIM, stream_id, _pack_claim(req, None))
            return
        with self._lock:
            if self.closed:
                lease.release()
                return
            self._leases[lease.lease_id] = lease
            self._req_lease[req] = lease.lease_id
        _flight.emit(_flight.RDV_CLAIM, self._ftag, req, lease.lease_id)
        self._ctrl_send(OP_CLAIM, stream_id, _pack_claim(req, lease))

    def _lease_for(self, nbytes: int, kinds: Sequence[str]
                   ) -> Optional[RegionLease]:
        if not enabled() or nbytes > _MAX_TRANSFER:
            return None
        limit = self.recv_limit
        if limit is not None and limit >= 0 and nbytes > limit:
            return None  # refusal → framed path → RESOURCE_EXHAUSTED there
        for kind in self._pool_kinds:
            if kind not in kinds:
                continue
            try:
                # ownership transfers by return: the caller registers the
                # lease in _leases and every death path releases it there
                lease = landing_pool(kind).lease(  # tpr: allow(ringpool)
                    nbytes, next(self._lease_ids))
            except Exception:
                continue
            if lease is not None:
                return lease
        return None

    def on_claim(self, payload) -> None:
        req, claim = _unpack_claim(payload)
        if req == 0:
            # unsolicited pre-grant: cache it for the next same-class send
            if claim is not None:
                with self._lock:
                    if self.closed:
                        pass  # receiver's close releases everything anyway
                    else:
                        self._grants.setdefault(claim.capacity,
                                                []).append(claim)
            return
        with self._lock:
            st = self._reqs.get(req)
            if st is not None:
                st["claim"] = claim if claim is not None \
                    else _SENTINEL_REFUSED
                self._cond.notify_all()
                return
        # the sender already gave up on this request (timeout raced the
        # claim): hand the region straight back
        if claim is not None:
            try:
                self._ctrl_send(OP_RELEASE, 0,
                              _RELEASE.pack(claim.lease_id, 0))
            except Exception:
                pass

    def on_complete(self, stream_id: int, payload) -> None:
        lease_id, nbytes, flags = _COMPLETE.unpack(bytes(payload))
        with self._lock:
            lease = self._leases.get(lease_id)
            if lease is not None and not lease.standing:
                # one-shot lease: consumed by this completion. STANDING
                # leases stay claimed — the sender reuses the region on
                # the doorbell with no further grants.
                del self._leases[lease_id]
                for r, lid in list(self._req_lease.items()):
                    if lid == lease_id:
                        del self._req_lease[r]
        if lease is None:
            return  # already released (crossed a release) — drop
        if not lease.pregrant:
            _flight.emit(_flight.RDV_COMPLETE, self._ftag, lease_id, nbytes)
        try:
            wrapper = lease.deliver(nbytes)
        except Exception:
            # protocol violation (oversized complete / reuse while the
            # previous delivery is aliased): drop the region entirely —
            # its pool recycle re-zeroes the doorbell, so a confused
            # sender can never land bytes in it again
            with self._lock:
                self._leases.pop(lease_id, None)
                if lease.pregrant:
                    self._pregrants_out[lease.cls] = max(
                        0, self._pregrants_out.get(lease.cls, 1) - 1)
            lease.release(discard=True)  # a confused sender may write again
            return
        _RDV_RECV.inc()
        _RDV_RECV_BYTES.inc(nbytes)
        if lease.standing:
            _RDV_WINDOW_RECV.inc()
            if size_class(nbytes) < lease.cls:
                _RDV_WINDOW_RIDES.inc()
        cls, kind = lease.cls, lease.kind
        self._deliver(stream_id, flags, wrapper)
        self._maybe_pregrant(cls, kind)

    def _maybe_pregrant(self, cls: int, kind: str) -> None:
        """RDMAbox discipline: keep the link's window of STANDING regions
        granted, topped up to ``_PREGRANT_DEPTH``, after a completion of
        class ``cls`` (its region's). The window is of ONE class, the
        largest standing class of the link, ``W``: a completion of a class
        below it tops up ``W`` and grants nothing of its own (the sender
        rides ``W``'s regions with smaller messages), and one above it (a
        one-shot claim: no standing region held it) grants
        ``_PREGRANT_DEPTH`` of its class, the new ``W``; the sender hands
        back the regions it grew out of (``_hand_back``). A standing grant
        costs one claim frame EVER: after each use the consumer-done signal
        rides the region's own doorbell word, so steady-state transfers
        carry exactly one control frame (the COMPLETE) and zero claim round
        trips."""
        with self._lock:
            cls = max([cls] + [c for c, n in self._pregrants_out.items()
                               if n > 0])
        while True:
            with self._lock:
                if (self.closed or self._pregrants_out.get(
                        cls, 0) >= _PREGRANT_DEPTH):
                    return
            try:
                lease = landing_pool(kind).lease(cls, next(self._lease_ids))
            except Exception:
                return
            if lease is None:
                return
            lease.pregrant = True
            lease.standing = True
            with self._lock:
                if self.closed:
                    lease.release()
                    return
                self._leases[lease.lease_id] = lease
                self._pregrants_out[cls] = self._pregrants_out.get(cls,
                                                                   0) + 1
            try:
                self._ctrl_send(OP_CLAIM, 0, _pack_claim(0, lease))
            except Exception:
                with self._lock:
                    self._leases.pop(lease.lease_id, None)
                    self._pregrants_out[cls] = max(
                        0, self._pregrants_out.get(cls, 1) - 1)
                lease.release()
                return

    def on_release(self, payload) -> None:
        lease_id, req = _RELEASE.unpack(bytes(payload))
        with self._lock:
            if not lease_id and req:
                lease_id = self._req_lease.pop(req, 0)
            lease = self._leases.pop(lease_id, None)
            if lease is not None and lease.pregrant:
                self._pregrants_out[lease.cls] = max(
                    0, self._pregrants_out.get(lease.cls, 1) - 1)
        if lease is not None:
            if lease.standing:
                _RDV_WINDOW_RETURNS.inc()
            _flight.emit(_flight.RDV_RELEASE, self._ftag, lease_id, req)
            lease.release()

    # -- lifecycle -----------------------------------------------------------

    def close(self) -> None:
        """Connection teardown / peer death: every claimed region is
        released back to its pool (the modeled peer-death invariant), every
        waiting sender is woken to fall back or fail with the transport."""
        with self._lock:
            if self.closed:
                return
            self.closed = True
            leases = list(self._leases.values())
            self._leases.clear()
            self._req_lease.clear()
            self._pregrants_out.clear()
            self._grants.clear()
            windows = list(self._windows.items())
            self._windows.clear()
            self._window_order = []
            self._cond.notify_all()
        for lease in leases:
            # teardown is an EDGE (once per connection death), so every
            # claimed region's release is recorded — standing grants
            # included; the postmortem's claim→death→release story needs it
            _flight.emit(_flight.RDV_RELEASE, self._ftag,
                         lease.lease_id, 0)
            # DISCARD, don't pool: the peer (or a straggling sender thread
            # on this dying connection) may still hold a window and land a
            # late one-sided write — it must hit orphaned memory, never a
            # region re-leased to a new transfer
            lease.release(discard=True)
        for (kind, handle), win in windows:
            # drop this link's refs; the share parks or closes as the
            # cross-link refcount dictates
            window_share().release(kind, handle, win)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "negotiated": int(self.negotiated),
                "claimed_leases": len(self._leases),
                "cached_grants": sum(len(v) for v in
                                     self._grants.values()),
            }


# ---------------------------------------------------------------------------
# Block-granular standing grants (tpurpc-keystone, ISSUE 11).
#
# The LandingPool leases CONTIGUOUS size-classed spans; the KV plane's unit
# is the BLOCK — a grant names a scatter of block offsets inside one
# registered arena region (the decode server's KvBlockManager), and the
# sender one-sided-writes each block straight into place: KV lands in the
# decode arena with zero host landing copies and zero staging joins. A
# grant is STANDING in the RDMAbox sense at the window level: the sender's
# GrantWriter keeps one open window per (kind, handle), so a stream of
# handoffs into the same arena pays the window-open exactly once.
# ---------------------------------------------------------------------------

_GRANT_HDR = struct.Struct("<QIIQQ16s")  # grant_id, block_bytes, n_offsets,
#                                          window_bytes, nonce_off, nonce


class BlockGrant:
    """A peer-advertised landing descriptor at block granularity: which
    blocks of which registered region the sender may write, plus the
    anti-mixup nonce (stored at ``nonce_off`` inside the region — the
    writer verifies it through its window before placing a byte, the same
    stale-handle defense as RegionLease's trailer nonce)."""

    __slots__ = ("grant_id", "kind", "handle", "block_bytes", "offsets",
                 "window_bytes", "nonce", "nonce_off")

    def __init__(self, grant_id: int, kind: str, handle: str,
                 block_bytes: int, offsets: Sequence[int],
                 window_bytes: int, nonce: bytes, nonce_off: int):
        self.grant_id = int(grant_id)
        self.kind = kind
        self.handle = handle
        self.block_bytes = int(block_bytes)
        self.offsets = tuple(int(o) for o in offsets)
        self.window_bytes = int(window_bytes)
        self.nonce = bytes(nonce)
        self.nonce_off = int(nonce_off)

    @property
    def capacity(self) -> int:
        return self.block_bytes * len(self.offsets)

    def to_wire(self) -> bytes:
        kb = self.kind.encode()
        return (_GRANT_HDR.pack(self.grant_id, self.block_bytes,
                                len(self.offsets), self.window_bytes,
                                self.nonce_off, self.nonce)
                + bytes([len(kb)]) + kb + self.handle.encode()
                + b"\x00" + b"".join(struct.pack("<Q", o)
                                     for o in self.offsets))

    @classmethod
    def from_wire(cls, payload) -> "BlockGrant":
        buf = bytes(payload)
        (grant_id, block_bytes, n, window_bytes, nonce_off,
         nonce) = _GRANT_HDR.unpack_from(buf)
        pos = _GRANT_HDR.size
        klen = buf[pos]
        pos += 1
        kind = buf[pos:pos + klen].decode()
        pos += klen
        end = buf.index(b"\x00", pos)
        handle = buf[pos:end].decode()
        pos = end + 1
        offsets = struct.unpack_from(f"<{n}Q", buf, pos)
        return cls(grant_id, kind, handle, block_bytes, offsets,
                   window_bytes, nonce, nonce_off)


class GrantWriter:
    """The sender half of block-granular grants: opens (and CACHES — the
    standing discipline) one window per (kind, handle), verifies the
    grant's nonce, then places each chunk with a one-sided write. All
    placement bytes ride the ``rendezvous`` lens hop and the ledger's
    ``rdma_write`` — the same accounting as RdvLink's bulk path, so the
    copy-ledger proof ("KV landed with zero host landing copies") is one
    ``ledger.track()`` window away."""

    _GUARDED_BY = {"_windows": "_lock"}

    def __init__(self):
        self._domains: Dict[str, _pair.MemoryDomain] = {}
        self._windows: Dict[Tuple[str, str], _pair.Window] = {}
        self._lock = make_lock("GrantWriter._lock")

    def _window(self, grant: BlockGrant) -> _pair.Window:
        key = (grant.kind, grant.handle)
        win = self._windows.get(key)
        if win is not None:
            return win
        win = window_share().acquire(grant.kind, grant.handle,
                                     grant.window_bytes)
        extra = None
        with self._lock:
            prev = self._windows.get(key)
            if prev is not None:
                extra, win = win, prev
            else:
                self._windows[key] = win
        if extra is not None:
            window_share().release(grant.kind, grant.handle, extra)
        return win

    def write_blocks(self, grant: BlockGrant, chunks: Sequence) -> int:
        """Place ``chunks[i]`` (bytes-like, ≤ block_bytes) at
        ``grant.offsets[i]``. Returns bytes written. Raises on nonce
        mismatch or oversized chunks — the caller releases/abandons the
        grant (the `rdv` pairing discipline applies to grants too)."""
        if len(chunks) > len(grant.offsets):
            raise ValueError(f"{len(chunks)} chunks for a "
                             f"{len(grant.offsets)}-block grant")
        win = self._window(grant)
        view = win.view
        if grant.nonce:
            if view is not None:
                seen = bytes(view[grant.nonce_off:
                                  grant.nonce_off + len(grant.nonce)])
                if seen != grant.nonce:
                    raise OSError(
                        "block-grant nonce mismatch: the granted handle "
                        "resolves to different memory on this host")
        t0 = time.monotonic_ns()
        total = 0
        placed = []
        for off, chunk in zip(grant.offsets, chunks):
            sv = memoryview(chunk).cast("B")
            if len(sv) > grant.block_bytes:
                raise ValueError(f"chunk of {len(sv)} exceeds the "
                                 f"{grant.block_bytes}-byte block")
            placed.append((off, sv))
            total += len(sv)

        # the block placement is a cross-process one-sided write: simnet
        # reorders/crashes it against the COMPLETE that must follow it
        _transport.dispatch("write", self, _place_spans, win, placed)
        _ledger.rdma_write(total)
        dt = time.monotonic_ns() - t0
        _LENS_RDV_NS.inc(dt)
        _LENS_RDV_BYTES.inc(total)
        _LENS_RDV_COPY.inc(total)
        return total

    def close(self) -> None:
        with self._lock:
            windows = list(self._windows.items())
            self._windows.clear()
        for (kind, handle), win in windows:
            window_share().release(kind, handle, win)


def domains_for_endpoint(endpoint) -> Tuple[Tuple[str, ...],
                                            Tuple[str, ...]]:
    """(pool_kinds, open_kinds) for a connection over ``endpoint``.

    Ring endpoints prefer the pair's own domain (the registered memory the
    connection already trusts — verbs MRs on hardware, shm segments on one
    host, tcp_window regions cross-host, whose shared ordered record
    connection also sequences the COMPLETE after the payload); everything
    else (plain TCP, h2) uses the shm pool, the one-host emulation of a
    registered region. ``open_kinds`` is what OUR sender can open windows
    into — a claim naming anything else is impossible to honor and the
    receiver never issues one (it picks from the offer's kinds)."""
    pair = getattr(endpoint, "pair", None)
    pool: List[str] = []
    if pair is not None:
        kind = pair.domain.kind
        if kind in ("shm", "local", "tcp_window", "verbs"):
            pool.append(kind)
    if "shm" not in pool:
        pool.append("shm")
    open_kinds = list(dict.fromkeys(pool + ["shm", "local"]))
    return tuple(pool), tuple(open_kinds)


def link_for_endpoint(endpoint, name: str,
                      send_op: Callable[[int, int, bytes], None],
                      deliver: Callable[[int, int, object], None],
                      pump: Optional[Callable] = None,
                      send_ops: Optional[Callable] = None
                      ) -> Optional[RdvLink]:
    """An armed-but-unnegotiated link for a new framed connection, or None
    when rendezvous is disabled process-wide.  ``send_ops(list_of_(op,
    sid, payload))`` is the multi-frame control send the cold-path
    coalescer flushes bursts through (native framing only)."""
    if not enabled():
        return None
    pool_kinds, open_kinds = domains_for_endpoint(endpoint)
    return RdvLink(name, send_op, deliver, pool_kinds=pool_kinds,
                   open_kinds=open_kinds, pump=pump, send_ops=send_ops)
