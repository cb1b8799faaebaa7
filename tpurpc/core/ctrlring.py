"""tpurpc-pulse: shared-memory descriptor rings for the rendezvous control
plane.

PR 9 moved bulk payloads onto the one-sided rendezvous plane — and moved the
waterfall's bottleneck with them: ARCHITECTURE §18 measures ~0.6 ms/message
of control-plane wakeups (COMPLETE frames, notify syscalls, cross-thread
queue handoffs across both processes) against ~0.4 ms for the payload memcpy
itself.  The copy is no longer the cost; the round trips are.  This module
makes the control plane itself ride polled descriptor rings (RDMAbox's
merged-doorbell/batched-I/O discipline, arXiv:2104.12197; the DMA Streaming
Framework's descriptor-ring orchestration, arXiv:2603.10030): per-link
submission/completion rings carved from the same shared-memory domain as the
landing pool, so a steady-state bulk transfer crosses ZERO thread boundaries
— one one-sided payload write plus one 128-byte ring-slot store per message,
no frame encode/decode, no fd kicks, no parked-thread handoffs.

Layout — one ring per direction, each owned by its CONSUMER (the side that
reads it allocates it and advertises the handle in the PING-hello capability
blob; the producer opens a window onto it):

    header (64 B): magic, version, nslots, slot_bytes,
                   cons_head (u64 — the ring's DOORBELL word: consumed
                   count, published by the consumer once per drained BATCH,
                   exactly PR 9's consumer-done gate),
                   parked (u32 — consumer-is-blocked flag, the futex-style
                   handshake), nonce (16 B anti-mixup, as for landing
                   regions)
    slots  (nslots × slot_bytes): seq-stamped records
        [stamp u64][frame_seq u64][stream_id u32][len u16][op u8][flags u8]
        [payload ≤ slot_bytes-24]

Protocol (modeled exhaustively in ``analysis/ringcheck.py check_ctrlring``;
mutants ``ctrl_publish_before_write``, ``ctrl_reuse_before_doorbell`` and
``ctrl_park_no_redrain`` are all killed):

* the producer writes a slot's payload and fields FIRST and the ``stamp``
  (seq+1) LAST — a reader that observes the stamp observes a whole record;
* a slot is reused only after the consumer's published ``cons_head`` covers
  its previous lap (``seq - cons_head < nslots`` before any store) — the
  ring-full case falls back to the framed control path, never overwrites;
* lost-wakeup close: the producer stores the stamp, THEN reads ``parked``
  and sends one framed kick when set; the consumer sets ``parked``, THEN
  re-drains once before blocking.  Either order of the race delivers.

The consumer's wait (``read_frame_polled``): a reader that is not looking
at its ring is either in a bounded GIL-free spin on the NEXT stamp word or
has ``parked`` up behind the re-drain — no third state.  A record that
came within ``_DENSE_WINDOWS`` busy windows (``busy_polling_timeout_us``)
of the one before is a stream's: the reader stays unparked that long
again, spin and framed probe in turn; when that runs out empty, and after
any sparser record, it parks on the framed read until a kick or a frame.
A steady stream never costs a kick, a sparse caller one ``CTRL_KICK`` a
record and no spinning at all.

Ordering with the framed path: every record carries ``frame_seq`` — the
count of frames its sender had written when posting — and the consumer
processes a record only once it has dispatched that many frames.  A control
op posted after a framed MESSAGE on the same stream therefore lands after
it, and vice versa (the consumer drains the ring before dispatching each
frame), so per-stream delivery order survives the split control plane.

Negotiation rides the existing PING-hello: each side appends its receive
ring's descriptor to the rendezvous hello payload.  Un-negotiated peers
(the native C plane, h2 planes, older builds), non-host-addressable domains
and cross-host handles (nonce mismatch) keep the framed control path — the
PR 9 fallback ladder is untouched, and every ring failure (full, closed,
oversized payload) degrades to a framed send, never a lost op.

Env knobs: ``TPURPC_CTRL_RING`` (default on), ``TPURPC_CTRL_RING_SLOTS``
(default 64).
"""

from __future__ import annotations

import os
import struct
import time
from typing import Callable, Dict, Optional, Tuple

from tpurpc.analysis.locks import make_lock
from tpurpc.core import _native
from tpurpc.core import pair as _pair
from tpurpc.core import transport as _transport
from tpurpc.core.endpoint import ReadTimeout
from tpurpc.core.poller import _effective_cpus
from tpurpc.obs import flight as _flight
from tpurpc.obs import lens as _lens
from tpurpc.obs import metrics as _metrics
from tpurpc.obs import profiler as _profiler
from tpurpc.utils import stats as _stats
from tpurpc.utils.config import get_config

# tpurpc-lens frame markers: a thread polling/draining/posting descriptor
# rings is doing control-plane work — the waterfall's `ctrl` hop carries
# the bytes, these carry the CPU attribution
_LENS_STAGES = {
    "read_frame_polled": "ctrl-ring",
    "drain": "ctrl-ring",
    "post": "ctrl-ring",
    "spin": "ctrl-ring",
}
_profiler.register_stages(__file__, _LENS_STAGES)

__all__ = [
    "CtrlRing", "CtrlPeer", "CtrlPlane", "enabled", "read_frame_polled",
    "TEST_HOOKS", "SLOT_BYTES", "MAX_CTRL_PAYLOAD",
]

# tpurpc-lens: control-plane work (ring posts/drains AND framed control
# sends) is its own waterfall hop — carrying a few hundred bytes per bulk
# message it can never trip the slowest-hop argmin (the <1%-of-bulk-bytes
# rule), but its busy share is exactly the collapse this PR must make
# visible per hop instead of inferring from wall clock
_LENS_CTRL_BYTES, _LENS_CTRL_NS, _LENS_CTRL_COPY = _lens.hop_counters("ctrl")

_POSTS = _metrics.counter("ctrl_ring_posts")
_RECORDS = _metrics.counter("ctrl_ring_records")
_KICKS = _metrics.counter("ctrl_ring_kicks")
_FULL = _metrics.counter("ctrl_ring_full_fallbacks")
#: the reader's two legs: records it found while watching (their producer
#: sent no kick), and times it raised ``parked`` to block on the framed path
#: (the next record costs its producer a kick)
_SPIN_HITS = _metrics.counter("ctrl_ring_spin_hits")
_PARKS = _metrics.counter("ctrl_ring_parks")

#: scrape-time truth for the watchdog's `ctrl-ring` stage: records posted
#: into peers' rings that their consumers have not yet drained
_BACKLOG = _metrics.fleet("ctrl_ring_backlog", lambda p: p.backlog())

#: test seams (tests/test_ctrlring.py, tools/ctrlring_smoke.py):
#: ``freeze_drain`` makes every consumer's drain a no-op — posted records
#: age in the ring, the induced stuck-ring stall the watchdog must name
TEST_HOOKS: Dict[str, object] = {}

_MAGIC = 0x54504352  # 'TPCR'
_VERSION = 1
SLOT_BYTES = 128
_NONCE_BYTES = 16

#: header: magic, version, nslots, slot_bytes, cons_head, parked, pad, nonce
_HDR = struct.Struct("<IIIIQII16s")
_HDR_BYTES = 64
_CONS_HEAD = struct.Struct("<Q")
_CONS_HEAD_OFF = 16
_PARKED = struct.Struct("<I")
_PARKED_OFF = 24
_NONCE_OFF = 32

#: slot record header; the stamp (first u64) is stored SEPARATELY, last
_SLOT_HDR = struct.Struct("<QQIHBB")
_SLOT_HDR_BYTES = _SLOT_HDR.size  # 24
_STAMP = struct.Struct("<Q")
MAX_CTRL_PAYLOAD = SLOT_BYTES - _SLOT_HDR_BYTES

#: hello-blob framing: u16 length prefix + descriptor
_BLOB_LEN = struct.Struct("<H")
_DESC = struct.Struct("<IIQ16sB")  # nslots, slot_bytes, nbytes, nonce, klen


def enabled() -> bool:
    return os.environ.get("TPURPC_CTRL_RING", "1").lower() not in (
        "0", "off", "false")


def _default_slots() -> int:
    try:
        return max(8, int(os.environ.get("TPURPC_CTRL_RING_SLOTS", "64")))
    except ValueError:
        return 64


class CtrlRing:
    """The consumer-owned half: allocates the shm region, drains records,
    publishes ``cons_head`` once per batch, owns the ``parked`` word."""

    #: lint rule `lock`: the drain cursor and closed flag are shared
    #: between whichever thread holds the drain lock and the close path
    _GUARDED_BY = {"head": "_lock", "closed": "_lock"}

    def __init__(self, kind: str = "shm", nslots: Optional[int] = None):
        self.kind = kind
        self.nslots = nslots or _default_slots()
        self.slot_bytes = SLOT_BYTES
        self.nonce = os.urandom(_NONCE_BYTES)
        self._domain = _pair.make_domain(kind)
        self.nbytes = _HDR_BYTES + self.nslots * self.slot_bytes
        self.region = self._domain.alloc(self.nbytes)
        self.head = 0          # consumed count (local truth)
        self._published = 0    # last cons_head stored into the header
        self.closed = False
        self._pin = None       # (array, address) export spin() watches through
        self._lock = make_lock("CtrlRing._lock")
        _HDR.pack_into(self.region.buf, 0, _MAGIC, _VERSION, self.nslots,
                       self.slot_bytes, 0,
                       1,  # parked: nobody polls until a reader adopts us
                       0, self.nonce)

    def descriptor(self) -> bytes:
        """The hello-blob descriptor the producer opens a window with."""
        kb = self.kind.encode()
        return (_DESC.pack(self.nslots, self.slot_bytes, self.nbytes,
                           self.nonce, len(kb))
                + kb + self.region.handle.encode())

    # -- consumer side --------------------------------------------------------

    def set_parked(self, parked: bool) -> None:
        with self._lock:
            if self.closed:
                return
            _PARKED.pack_into(self.region.buf, _PARKED_OFF,
                              1 if parked else 0)

    def drain(self, on_op: Callable[[int, int, object], None],
              frames_dispatched: Callable[[], int]) -> int:
        """Consume every ready record in ONE pass (the batched-completion
        fast path: the Python consumer observes completed batches, one
        ``cons_head`` publish per batch).  A record whose ``frame_seq``
        outruns the dispatched-frame count is left in place — the frames it
        must order after are still in flight.  Concurrent drainers skip
        (try-lock): records dispatch in slot order, exactly once."""
        if TEST_HOOKS.get("freeze_drain"):
            return 0
        if not self._lock.acquire(blocking=False):
            return 0
        try:
            if self.closed:
                return 0
            buf = self.region.buf
            n = 0
            t0 = time.monotonic_ns()
            nbytes = 0
            while True:
                slot = _HDR_BYTES + (self.head % self.nslots) \
                    * self.slot_bytes
                (stamp,) = _STAMP.unpack_from(buf, slot)
                if stamp != self.head + 1:
                    break
                (_stamp, frame_seq, stream_id, ln, op,
                 _flags) = _SLOT_HDR.unpack_from(buf, slot)
                if frame_seq > frames_dispatched():
                    break  # ordered after frames still in flight
                payload = bytes(buf[slot + _SLOT_HDR_BYTES:
                                    slot + _SLOT_HDR_BYTES + ln])
                # _lock IS held — acquired nonblocking above (the lint's
                # with-statement pattern can't see a try-acquire/finally)
                self.head += 1  # tpr: allow(lock)
                n += 1
                nbytes += ln
                on_op(op, stream_id, payload)
            if n:
                # one doorbell store per drained batch — the consumer-done
                # gate the producer's full-check reads through its window
                _CONS_HEAD.pack_into(buf, _CONS_HEAD_OFF, self.head)
                self._published = self.head
                _RECORDS.inc(n)
                dt = time.monotonic_ns() - t0
                _LENS_CTRL_BYTES.inc(nbytes)
                _LENS_CTRL_NS.inc(dt)
                _stats.batch_hist("ctrl_ring_batch").record(n)
            return n
        finally:
            self._lock.release()

    def spin(self, timeout_us: int) -> bool:
        """Bounded GIL-free wait on the NEXT record's stamp word (the u64
        at slot ``head % nslots``, which the producer turns into
        ``head + 1`` last).  True when the word moved or cannot be watched
        (closed, no native library): the caller drains either way.  False
        when the slice ran out quietly.  The region is pinned across the
        call; ``close`` retries its release meanwhile, as for ``Pair.spin``."""
        spin = _native.load_spin()
        with self._lock:
            if self.closed or spin is None:
                return True
            pin = self._pin
            if pin is None:
                pin = self._pin = _native.pin(self.region.buf, writable=False)
            slot = _HDR_BYTES + (self.head % self.nslots) * self.slot_bytes
            (seen,) = _STAMP.unpack_from(self.region.buf, slot)
            if seen == self.head + 1:
                return True
        # watch for divergence from the value READ, not for the value
        # wanted: a stamp published between the read and the call returns
        # at once.  On a later lap the Python producer zeroes the old stamp
        # with the record's fields before it publishes: wait that out too
        addr = pin[1] + slot
        moved = spin.tpr_spin_u64_change(addr, seen, timeout_us)
        if moved and seen:
            moved = spin.tpr_spin_u64_change(addr, 0, timeout_us)
        return moved != 0

    def close(self) -> None:
        """Link death/teardown.  The region is released on OUR side only —
        a straggling producer still holds its window and may land a late
        slot store, which hits the orphaned mapping (dead memory), never a
        ring re-advertised to a new link: rings are per-connection and
        never pooled (Pair.init's stale-write rule)."""
        with self._lock:
            if self.closed:
                return
            self.closed = True
            self._pin = None  # drop our own export before releasing
        try:
            _pair.retry_buffer_op(self.region.buf.release, timeout_s=0.5)
            self.region._close()
        except Exception:
            pass  # the OS reclaims the mapping with the process


class CtrlPeer:
    """The producer half: a window onto the peer's receive ring.  ``post``
    returns 0 (not posted — framed fallback), 1 (posted) or 2 (posted AND
    the consumer is parked — send one framed kick)."""

    _GUARDED_BY = {"seq": "_lock", "closed": "_lock", "_stalled": "_lock"}

    def __init__(self, kind: str, handle: str, nslots: int, slot_bytes: int,
                 nbytes: int, nonce: bytes, ftag: int = 0):
        if slot_bytes != SLOT_BYTES:
            raise ValueError(f"peer ring slot_bytes {slot_bytes} != "
                             f"{SLOT_BYTES}")
        domain = _pair.make_domain(kind)
        self._win = domain.open_window(handle, nbytes)
        view = self._win.view
        if view is None:
            self._win.close()
            raise OSError("ctrl ring needs a host-addressable window "
                          f"(domain {kind!r} has none)")
        (magic, version, r_nslots, r_slot_bytes, _head, _parked, _pad,
         r_nonce) = _HDR.unpack_from(view, 0)
        if (magic != _MAGIC or version != _VERSION or r_nslots != nslots
                or r_slot_bytes != slot_bytes or r_nonce != nonce):
            self._win.close()
            raise OSError("ctrl ring descriptor mismatch: the advertised "
                          "handle resolves to different memory on this "
                          "host")
        self.view = view
        self.nslots = nslots
        self.slot_bytes = slot_bytes
        self.seq = 0        # next record index (stamp = seq+1)
        self.closed = False
        self._stalled = False  # ring-full edge (flight stall bracket)
        self._ftag = ftag
        self._lock = make_lock("CtrlPeer._lock")
        _BACKLOG.track(self)

    def backlog(self) -> int:
        """Records posted but not yet consumed by the peer (the fleet
        gauge the watchdog's `ctrl-ring` stage reads)."""
        if self.closed:
            return 0
        try:
            (head,) = _CONS_HEAD.unpack_from(self.view, _CONS_HEAD_OFF)
        except (ValueError, struct.error):
            return 0
        return max(0, self.seq - head)

    def post(self, op: int, stream_id: int, payload: bytes,
             frame_seq: int) -> int:
        if len(payload) > MAX_CTRL_PAYLOAD:
            return 0
        with self._lock:
            if self.closed:
                return 0
            view = self.view
            try:
                (head,) = _CONS_HEAD.unpack_from(view, _CONS_HEAD_OFF)
            except (ValueError, struct.error):
                return 0
            if self.seq - head >= self.nslots:
                # ring full: degrade to the framed path (never overwrite an
                # unconsumed slot).  The full→not-full transition is a
                # flight-bracketed stall edge — aged open, it is the
                # watchdog's evidence the consumer stopped draining.
                if not self._stalled:
                    self._stalled = True
                    _flight.emit(_flight.CTRL_STALL_BEGIN, self._ftag,
                                 self.seq - head)
                _FULL.inc()
                return 0
            if self._stalled:
                self._stalled = False
                _flight.emit(_flight.CTRL_STALL_END, self._ftag, 0)
            slot = _HDR_BYTES + (self.seq % self.nslots) * self.slot_bytes
            # payload and fields FIRST ...
            view[slot + _SLOT_HDR_BYTES:
                 slot + _SLOT_HDR_BYTES + len(payload)] = payload
            _SLOT_HDR.pack_into(view, slot, 0, frame_seq, stream_id,
                                len(payload), op, 0)
            # ... the stamp LAST: a consumer that observes it observes a
            # whole record (the publish-after-write discipline the
            # ctrl_publish_before_write mutant inverts)
            _STAMP.pack_into(view, slot, self.seq + 1)
            self.seq += 1
            _POSTS.inc()
            # parked is read strictly AFTER the stamp store: either the
            # consumer's park-then-redrain sees our record, or we see its
            # parked flag and kick — the lost-wakeup race has no third leg
            try:
                (parked,) = _PARKED.unpack_from(view, _PARKED_OFF)
            except (ValueError, struct.error):
                parked = 1
            return 2 if parked else 1

    def close(self) -> None:
        with self._lock:
            if self.closed:
                return
            self.closed = True
        try:
            self._win.close()
        except Exception:
            pass


# ---------------------------------------------------------------------------
# The per-connection plane: rx + tx + the reader's watch.
# ---------------------------------------------------------------------------

#: records closer together than this many busy windows (4 x 500 us) are a
#: stream, and its reader watches that long for the next instead of parking.
#: On the chip (PERF.md §6, PR 29) a 4 MiB stream's records come 0.5 to 1 ms
#: apart: a 500 us watch parks between them and costs the sender a kick
#: frame a message (4.63 GB/s); 1.5 and 2 ms never park (4.86, 4.72).
_DENSE_WINDOWS = 4
#: one GIL-free slice on the stamp word between two framed probes: how long
#: the FRAMED ring goes unwatched while the reader watches.  Each probe
#: holds the interpreter lock ~13 us that a handler thread wanted: at 64 us
#: one reader's probes cost that stream a tenth of its rate (same runs).
_SPIN_SLICE_US = 250


class CtrlPlane:
    """One connection's descriptor-ring control plane: the locally owned
    receive ring (advertised in the hello), the window onto the peer's
    (opened from the peer's hello), and the READER's state: watching behind
    a stream's record (until ``_hot_until``), or parked.
    ``armed`` flips exactly once, when the peer's descriptor verifies —
    until then (and forever, for un-negotiated peers) every control op
    stays framed."""

    def __init__(self, name: str, kind: str = "shm"):
        self._ftag = _flight.tag_for("ctrl:" + name)
        self.rx: Optional[CtrlRing] = None
        self.tx: Optional[CtrlPeer] = None
        self.armed = False
        # no watch — park at once — where it cannot be GIL-free (no native
        # library) or would steal the producer's only core
        self._watch_s = (
            0.0 if _native.load_spin() is None or _effective_cpus() < 2
            else _DENSE_WINDOWS * get_config().busy_polling_timeout_us / 1e6)
        self._last_record = 0.0  # when the reader last found one
        self._hot_until = 0.0    # the watch's end; 0: none since a park
        self._closed = False
        try:
            self.rx = CtrlRing(kind=kind)
        except Exception:
            self.rx = None  # no shm on this host: framed control forever

    # -- negotiation ----------------------------------------------------------

    def hello_blob(self) -> bytes:
        """Appended to the rendezvous HELLO_PAYLOAD: this side's receive
        ring descriptor (empty when ring control is off/unavailable)."""
        if self.rx is None or not enabled():
            return b""
        desc = self.rx.descriptor()
        return _BLOB_LEN.pack(len(desc)) + desc

    def on_hello(self, blob: bytes) -> bool:
        """Parse the peer's descriptor and open the submission window.
        Any failure — empty blob (peer predates rings / disabled), a
        handle this host cannot open (cross-host TCP), a nonce mismatch —
        leaves the link framed.  Returns True on adoption."""
        if self.armed or self._closed or not blob or not enabled():
            return False
        try:
            (nslots, slot_bytes, nbytes, nonce,
             klen) = _DESC.unpack_from(blob, _BLOB_LEN.size)
            pos = _BLOB_LEN.size + _DESC.size
            kind = blob[pos:pos + klen].decode()
            handle = blob[pos + klen:].decode()
            self.tx = CtrlPeer(kind, handle, nslots, slot_bytes, nbytes,
                               nonce, ftag=self._ftag)
        except Exception:
            return False
        self.armed = True
        _flight.emit(_flight.CTRL_ADOPT, self._ftag, nslots, slot_bytes)
        return True

    # -- producer face --------------------------------------------------------

    def post(self, op: int, stream_id: int, payload: bytes, frame_seq: int,
             kick: Callable[[], None]) -> bool:
        """Post one control op to the peer's ring; True when placed (the
        framed path must NOT also send it).  A parked consumer gets one
        framed kick — the only frame a cold→hot transition costs."""
        tx = self.tx
        if tx is None or not self.armed:
            return False
        t0 = time.monotonic_ns()
        r = _transport.dispatch("post", self, tx.post, op, stream_id,
                                payload, frame_seq)
        if not r:
            return False
        n = len(payload)
        dt = time.monotonic_ns() - t0
        _LENS_CTRL_BYTES.inc(n)
        _LENS_CTRL_NS.inc(dt)
        if r == 2:
            _KICKS.inc()
            try:
                _transport.dispatch("kick", self, kick)
            except Exception:
                pass  # connection dying; the framed paths surface it
        return True

    # -- consumer face --------------------------------------------------------

    def drain(self, on_op: Callable[[int, int, object], None],
              frames_dispatched: Callable[[], int]) -> int:
        """Dispatch what is ready, from any thread (a sender waiting for a
        grant drains too); the reader's state is not this call's to move."""
        rx = self.rx
        return 0 if rx is None else rx.drain(on_op, frames_dispatched)

    def saw(self, n: int) -> None:
        """The reader's own drain found ``n`` records (reader thread only).
        Close behind the one before, they are a stream's: watch as long
        again for the next.  A sparser record opens nothing — watching for
        it would only burn a core and the handlers' lock; the next finds
        the reader parked and costs its producer a kick."""
        now = time.monotonic()
        if now < self._hot_until:
            _SPIN_HITS.inc(n)  # found by watching: nobody was kicked
        dense = now - self._last_record < self._watch_s
        self._last_record = now
        if dense:
            if not self._hot_until:
                _flight.emit(_flight.CTRL_SPIN, self._ftag,
                             self.rx.head if self.rx is not None else 0)
            self._hot_until = now + self._watch_s

    def watching(self) -> bool:
        """Behind a stream's record: the next one is worth watching for."""
        return time.monotonic() < self._hot_until

    def spin(self) -> bool:
        """One bounded GIL-free slice on the receive ring's next stamp."""
        rx = self.rx
        return rx is None or rx.spin(_SPIN_SLICE_US)

    def park(self) -> None:
        """About to block on the framed path: raise the parked flag so the
        producer's next post kicks us.  The caller MUST re-drain once
        after this (the lost-wakeup close the ctrl_park_no_redrain mutant
        removes)."""
        rx = self.rx
        if rx is not None:
            rx.set_parked(True)
        _PARKS.inc()
        if self._hot_until:
            self._hot_until = 0.0
            _flight.emit(_flight.CTRL_PARK, self._ftag,
                         rx.head if rx is not None else 0)

    def unpark(self) -> None:
        rx = self.rx
        if rx is not None:
            rx.set_parked(False)

    def backlog(self) -> int:
        tx = self.tx
        return tx.backlog() if tx is not None else 0

    def close(self) -> None:
        self._closed = True
        self.armed = False
        tx, self.tx = self.tx, None
        if tx is not None:
            tx.close()
        rx, self.rx = self.rx, None
        if rx is not None:
            rx.close()


# ---------------------------------------------------------------------------
# The polled read loop shared by every connection reader/pump.
# ---------------------------------------------------------------------------

def read_frame_polled(read_frame, drain: Callable[[], int],
                      plane: CtrlPlane, timeout: Optional[float] = None,
                      should_stop: Optional[Callable[[], bool]] = None):
    """``read_frame`` with the descriptor-ring watch/park discipline.

    The invariant: from the moment this thread stops looking at its ring
    until it looks again, either (1) it is in a bounded GIL-free wait on
    the ring's next stamp word, or (2) ``parked`` is up and was followed by
    the mandatory re-drain, so the producer's post kicks.  No third state.

    WATCHING (behind a stream's record, ``CtrlPlane.saw``): unparked,
    alternate a non-blocking framed probe with one native spin on the stamp
    word — neither ring is blind for more than a slice.  PARKED (the watch
    ran out, or none was opened): raise the flag, re-drain once, and block
    on the framed read for as long as the caller allows — the producer's
    kick (or any frame) wakes us.  ``should_stop`` (inline-pump callers:
    "my predicate is satisfied") raises ReadTimeout so the pump re-checks.

    Returns whatever ``read_frame`` returns (Frame/CONSUMED/None); raises
    ReadTimeout past ``timeout``.
    """
    deadline = None if timeout is None else time.monotonic() + timeout

    def look() -> int:
        n = drain()
        if n:
            plane.saw(n)
        return n

    def check_stop() -> None:
        if should_stop is not None and should_stop():
            raise ReadTimeout()

    plane.unpark()  # a ring is born parked: its reader's look adopts it
    while True:
        look()
        check_stop()
        while plane.watching():
            if deadline is not None and time.monotonic() >= deadline:
                raise ReadTimeout()
            try:
                f = read_frame(timeout=0)
            except TimeoutError:
                pass
            else:
                # a record posted BEFORE this frame was sent is visible in
                # shm by store order — deliver it first, so per-stream
                # order holds across the ring/framed split
                look()
                return f
            moved = plane.spin()
            found = look()
            check_stop()
            if moved and not found:
                # published but not ours to take yet: ordered after frames
                # still in flight (or another thread is mid-drain) — the
                # framed read below is what delivers them
                break
        # the mandatory re-drain closes the park/post race — a record
        # posted before our flag store is found here, one posted after
        # sees the flag and kicks
        plane.park()
        try:
            if look():
                continue
            check_stop()
            remain = (None if deadline is None
                      else max(0.0, deadline - time.monotonic()))
            f = read_frame(timeout=remain)
            look()  # ring records posted before this frame deliver first
            return f
        finally:
            plane.unpark()
