"""tpurpc-lens byte-flow waterfall: per-hop byte/nanosecond attribution.

ROADMAP item 2's question is "streaming runs at 1.72 GB/s against an
8.5 GB/s memcpy ceiling — WHICH hop eats the gap?", and nothing in the
telemetry stack could answer it: the registry counts bytes per subsystem
and the copy ledger counts bytes per mechanism, but neither says how much
*time* each hop of the streaming path spent moving those bytes. The
waterfall is that instrument: every hop of the data path carries a pair of
always-on registry counters — bytes moved and busy nanoseconds — and the
scrape-time division ``bytes / busy_ns`` is that hop's effective GB/s
(B/ns ≡ GB/s, no unit conversion). The hop with the lowest effective rate
under load is, by construction, the one to attack.

The hop chain, in data-flow order (the ISSUE 8 vocabulary; the server's
per-message hops ``srv_*``, ``hbm_credit`` and ``hbm_view`` of ISSUE 26 are
listed with their sites in :data:`HOPS`, where ``d2h`` of ISSUE 28, the
fan-in batcher's four ``batch_*`` of ISSUE 33, ``srv_reply_wait`` of
ISSUE 36 and ``batch_ready`` and ``place_return`` of ISSUE 39 are
appended, and the paged KV cache's ``kv_admit``, ``kv_write``, ``kv_ack`` and
``kv_state_write`` after them: the registry is append-only)::

    d2h        a reply's device leaves read back into host landing buffers
               (tpu/serialize.py: start every transfer, await each)
    device     serialize: header + gather list over tensor bytes the host
               can address (jaxshim/codec.py encode)
    send_ring  RingWriter placement into the peer's receive ring
               (core/ring.py writev/write_many + the fused native send)
    wire       bytes crossing the transport boundary: the pair-plane
               one-sided send (core/pair.py Pair.send, credit machinery
               included) and TCP socket writes (core/endpoint.py)
    peer_ring  RingReader drain out of the local receive ring
               (core/ring.py read_into/drain_into/read_many)
    decode     codec parse of wire bytes back into tensors
               (jaxshim/codec.py decode_tree_at, tpu/endpoint.py
               decode_tree_to_ring)
    hbm        host time to ENQUEUE a message's one h2d transfer
               (tpu/hbm_ring.py land_many; dispatch is asynchronous, so
               this is not device time)
    jax_array  materialization as jax.Array — dlpack alias or the
               device_put staging copy (jaxshim/codec.py to_jax)

Cost model — why this is ALWAYS on, like the rest of the obs stack:

* accounting sites run once per **batched operation** (a drain, a gathered
  writev, a tree decode), never per byte: two ``time.monotonic_ns`` reads
  and three or four GIL-atomic Counter bumps per op, and for the second
  clock (below) a thread-local look, or one step of a counter where the
  stage opens a message. ``thread_time_ns`` is a system call: 0.27 us a
  read in the sandbox, 5.6 to 6.2 on the chip's host in a tight loop (13
  as the clocked stages of a loaded server time it, 46 cold), where
  ``monotonic_ns`` is 0.07 | 0.09 and none; and that host's clock moves in
  steps of 10 ms. Read at both ends of every stage it took 18% off
  ``stream4m_c1`` (PERF.md 6, PR 39); hence one message in N. What a whole
  ``with`` of an empty stage costs, clocked and not, on both hosts:
  PERF.md 6, PR 39. Five to nine stages a 4 MiB message;
* a site is one :class:`stage` (``with lens.stage("hbm", n): ...``): it
  bumps the hop's ``bytes``, ``busy_ns``, ``cpu_ns``, ``ops`` (and
  ``copy_bytes``) on exit, and in a process that has imported jax it is
  also a
  ``jax.profiler.TraceAnnotation`` named ``tpurpc.<hop>``, so the stages
  lie on the device trace's own clock whenever a profiler session runs
  (no session, or no jax: no annotation is made). Older
  sites in ``core/`` still bump counters bound by :func:`hop_counters` by
  hand. The ``stage`` lint rule enforces a literal declared hop and
  pure-int arguments at both kinds of site;
* hops may NEST (``wire`` wraps ``send_ring`` on the pair plane;
  ``decode`` wraps ``jax_array``): the table is a waterfall of per-hop
  effective rates, not a disjoint partition of wall time. The invariant
  that matters holds regardless: every hop's effective GB/s is an upper
  bound on the end-to-end rate through it, so the MINIMUM names the
  bottleneck.

Two clocks a stage (ISSUE 39). ``busy_ns`` is the wall clock between
``begin`` and ``end``; ``cpu_ns`` is the CALLING THREAD's CPU clock over the
same interval (``CLOCK_THREAD_CPUTIME_ID``: time the thread was on a core,
in Python or under it, whoever held the interpreter). ``busy_ns - cpu_ns``
is the time the thread spent OFF a core, and what that is depends on the
kind of stage:

* a stage with no blocking call of its own (``decode`` less its children,
  ``hbm_view``, the fan-in cells' hand-over ``srv_handler - decode``): the
  line for the interpreter, and the OS's run queue;
* a stage around a dispatch (``hbm``: ``jax.device_put``; ``batch_run``:
  ``fn``, the cut, the asks; ``batch_stack`` less ``batch_ready``: the stack
  program's dispatch): the line, plus whatever the runtime blocks on inside
  the call;
* a stage that IS a wait (``srv_recv``, ``hbm_credit``, ``rdv_credit``,
  ``d2h``'s awaits, ``batch_d2h``, ``batch_ready``): the wait; its ``cpu_ns``
  is what the wait itself costs (a spin, a poll, the wake).

CPU spent with the interpreter held and CPU spent beside it (a released
``memcpy``, the runtime's own work on the caller's thread) are one number:
the thread's clock cannot tell them apart. Hops billed by :func:`account`
or by hand (:func:`hop_counters`) are no one thread's interval and keep
``cpu_ns`` 0. ``cpu_ns`` is an ESTIMATE, because the read of the clock is
dear ("the second clock" at :class:`stage`): one message in N is clocked,
whole, and billed times N (``_CPU_EVERY``, a constant).
``lens_cpu_clock_reads`` counts the reads made, and a hop's ``cpu_ns`` over its ``busy_ns`` is a
ratio of a sample's CPU to everybody's wall, to be read over thousands of
ops, not tens.

``proc_cpu_ns`` / ``proc_wall_ns`` (``obs/metrics.py``, set at every export)
are the process's CPU and the monotonic clock: a window's delta of the two
is the cores the process kept busy, and the denominator of any share "of
the window"; ``obs_bg_cpu_ns`` / ``obs_bg_ticks`` are the observers' own
threads (the sampler, the tsdb, the SLO loop, the watchdog, a collector),
bumped by each once a tick; ``GET /debug/waterfall`` shows both, and their
quotient, a tick's CPU, under ``observers``.

The copy ledger is folded in: each hop row carries ``copy_bytes`` (bytes
that hop moved via a host memcpy / staging copy) so the table shows copies
alongside throughput — a hop running fast *because* it aliases reads
differently from one running fast while copying.

Served at ``GET /debug/waterfall`` (``?text=1`` for the table rendering,
``?local=1`` per-shard), merged across shard workers by the PR 7 fan-out,
rendered live by ``python -m tpurpc.tools.top``, and recorded into the
bench artifact (``waterfall_gbps_by_hop`` + ``waterfall_slowest_hop``).

``TPURPC_LENS=0`` switches the lens plane off (the sampling profiler stops
and the scrape routes answer 404-style disabled docs); the hop counters
themselves are branch-free and stay live — they are the same class of
always-on accounting as ``ring_bytes_read``.
"""

from __future__ import annotations

import itertools
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

from tpurpc.obs import metrics as _metrics

__all__ = [
    "HOPS", "HOP_NAMES", "hop_counters", "stage", "account", "CallStages",
    "enabled", "waterfall", "render_text", "slowest_hop",
]

#: the declared hop registry, in data-flow order: (name, accounting site /
#: what the hop means). Append-only — names land in scrape output and
#: bench artifacts.
HOPS: Tuple[Tuple[str, str], ...] = (
    ("device", "serialize: header and gather list over tensor bytes the "
               "host can address (codec encode; a reply's device leaves "
               "were read back under d2h first)"),
    ("send_ring", "RingWriter placement into the peer's receive ring"),
    ("wire", "transport boundary: pair one-sided send / TCP socket write"),
    ("rendezvous", "one-sided bulk payload write into the peer-advertised "
                   "landing region (tpurpc-express)"),
    ("ctrl", "control-plane work: descriptor-ring posts/drains and framed "
             "rendezvous control sends (tpurpc-pulse)"),
    ("native_send", "native-plane rdv placement: the one-sided memcpy "
                    "into the peer-advertised landing region (tpr_rdv.cc)"),
    ("native_recv", "native-plane delivery: completed landing regions "
                    "handed to the stream layer (tpr_rdv.cc deliver)"),
    ("native_rdv", "native-plane claim wait: solicited offer -> claim "
                   "grant round trip (tpr_rdv.cc rdv_claim)"),
    ("peer_ring", "RingReader drain out of the local receive ring"),
    ("decode", "codec parse of wire bytes back into tensors"),
    ("hbm", "host time to enqueue a message's one h2d transfer, not "
            "device time (HbmRing.land_many)"),
    ("jax_array", "materialization as jax.Array (dlpack alias or "
                  "device_put staging)"),
    # ISSUE 26: the server's per-message path, one stage per message on
    # the call's handler thread (both planes: rpc/server.py and
    # rpc/native_server.py); srv_queue and srv_call are counters only
    ("srv_recv", "handler thread waiting for the call's next message "
                 "(the wire, and getting the interpreter back)"),
    ("srv_queue", "a delivered message waiting on the call's queue for "
                  "the handler thread (Python plane; the native plane "
                  "counts native_srv_queue_*)"),
    ("srv_handler", "the registered behavior with one message: from its "
                    "hand-over until the behavior asks for the next"),
    ("srv_send", "serialize and write one response message"),
    ("srv_call", "whole server calls, start of the handler to its end "
                 "(the denominator of the stages' coverage)"),
    ("hbm_credit", "a landing blocked waiting for ring credit "
                   "(HbmRing._space; no op where it never blocked)"),
    ("hbm_view", "the lease hand-off of a landed message "
                 "(HbmRing.land_many)"),
    # ISSUE 28: the outbound leg, one stage per response that had a leaf
    # on a device (tpu/serialize.py _read_back, inside srv_handler)
    ("d2h", "a reply's device leaves read back: every leaf's "
            "device-to-host transfer started, then each awaited (the wait "
            "covers what the device still had to finish for them)"),
    # ISSUE 31: the sender role's credit wait, one op a send that waited
    ("rdv_credit", "a refused sender waiting for one of its own standing "
                   "regions' doorbells (RdvLink._await_credit; no op "
                   "where a send never waited)"),
    # ISSUE 33: the fan-in batcher (jaxshim/service.py FanInBatcher), on
    # the batcher's own threads. A handler that submits a row and goes on
    # does not contain them in its srv_handler; one that parks in
    # `batcher(tree)` does. The three per-batch spans carry the batch's
    # ordinal as `call` and its occupancy (request rows) as `seq`
    ("batch_wait", "a row queued in the batcher, from submit to the "
                   "dispatch of its batch (counters only: one op a row)"),
    ("batch_stack", "one batch gathered: device leaves by one dispatch of "
                    "the stack program, host leaves by numpy and one h2d; "
                    "where rows hold credit, until the batch is ready on "
                    "the device and the leases are back (fn's dispatch, "
                    "made in between, taken out); bytes and copy are the "
                    "rows' payload, pad rows not counted (batcher thread)"),
    ("batch_run", "the consumer's dispatch, fn(batch), until it returns; a "
                  "result with a device leaf has its read-back started here "
                  "and is awaited under batch_d2h (batcher thread; "
                  "asynchronous, so not device time)"),
    ("batch_d2h", "a batch's result with a device leaf awaited on the host, "
                  "leaf by leaf (a large one piece by piece): what the "
                  "device still had to finish for it, and the read-back "
                  "(completion thread; no op where the result has no "
                  "device leaf)"),
    # ISSUE 36: a stream handler may answer with a future
    # (rpc/server.py _DeferredReplies): a reply's wait for its turn
    ("srv_reply_wait", "a reply yielded as a future, from its resolution "
                       "until its srv_send starts: the wait behind an "
                       "earlier reply of its stream and for the thread "
                       "that writes (counters only: one op a deferred "
                       "reply; no one thread's time)"),
    # ISSUE 39: the two places where a thread of the server stands still
    # for something that is not the interpreter, each by itself
    ("batch_ready", "the batcher's one wait for the device: until the "
                    "stacked batch of rows that hold credit is ready "
                    "(block_until_ready), inside batch_stack; one op a "
                    "leased batch (batcher thread)"),
    ("place_return", "a released placement's way back: from the end of "
                     "tpr_place's copy, stamped in C with the interpreter "
                     "given up, until its thread has the interpreter again "
                     "(counters only: one op a native placement; the "
                     "interval starts on no Python thread)"),
    # the paged KV cache in HBM (serving/pages.py)
    ("kv_admit", "a sequence's admission waiting for pages: for the "
                 "sequences offered before it and for acknowledgements that "
                 "make room (one op an admission that waited; handler "
                 "thread)"),
    ("kv_write", "one dispatch of page messages: the scatter program "
                 "jit_tpurpc_kv_pages of one message or of a run of a "
                 "sequence's next ones, on the cache's writing thread, its "
                 "span carrying the first's call and message and layer, a "
                 "message's page count and the messages, msgs "
                 "(asynchronous, so not device time)"),
    ("kv_ack", "a sequence from its last write's dispatch until its "
               "acknowledgement is resolved: the device finishing it and "
               "the acknowledging thread's turn (counters only: one op a "
               "sequence)"),
    ("kv_state_write", "one dispatch of state messages: the program "
                       "jit_tpurpc_kv_state of one message or of a run at "
                       "consecutive state layers, on the cache's writing "
                       "thread, its span carrying the first's call, "
                       "message and state layer, the slot and the "
                       "messages, msgs (asynchronous, so not device time)"),
)

HOP_NAMES: Tuple[str, ...] = tuple(name for name, _ in HOPS)

_BYTES: Dict[str, _metrics.Counter] = {}
_NS: Dict[str, _metrics.Counter] = {}
_COPY: Dict[str, _metrics.Counter] = {}
_OPS: Dict[str, _metrics.Counter] = {}
_CPU: Dict[str, _metrics.Counter] = {}
_SPAN: Dict[str, str] = {}
for _name, _desc in HOPS:
    _BYTES[_name] = _metrics.counter(f"lens_{_name}_bytes")
    _NS[_name] = _metrics.counter(f"lens_{_name}_busy_ns")
    _COPY[_name] = _metrics.counter(f"lens_{_name}_copy_bytes")
    _OPS[_name] = _metrics.counter(f"lens_{_name}_ops")
    _CPU[_name] = _metrics.counter(f"lens_{_name}_cpu_ns")
    _SPAN[_name] = f"tpurpc.{_name}"


def hop_counters(name: str) -> Tuple[_metrics.Counter, _metrics.Counter,
                                     _metrics.Counter]:
    """The ``(bytes, busy_ns, copy_bytes)`` counter triple for one declared
    hop. Instrumented modules call this ONCE at import (module-level, a
    string-constant hop name — the ``stage`` lint rule checks both) and
    cache the counters as globals; the per-op cost is then the bumps alone.
    """
    if name not in _BYTES:
        raise ValueError(f"unknown waterfall hop {name!r}; "
                         f"declared hops: {HOP_NAMES}")
    return _BYTES[name], _NS[name], _COPY[name]


# -- the stage primitive (ISSUE 26) ---------------------------------------------

class _Thread(threading.local):
    """What a thread's stages share. Defaults on the class: a thread that
    was never given a message reads them at the price of a hit (a miss on a
    ``threading.local`` costs eight times one)."""

    #: the thread's current ``(call, seq)``: set by the call path's
    #: top-level stage of each message, read by the stages nested under it
    #: so that every span of one message carries the same pair
    ids: Optional[Tuple[int, int]] = None
    #: whether that message's stages read the CPU clock (below)
    clocked: Optional[bool] = None


_tls = _Thread()
_CALL_IDS = itertools.count(1)
_annotation = None  # jax.profiler.TraceAnnotation, once this process has it

# -- the second clock, and what reading it costs (ISSUE 39) ---------------------
#
# ``time.thread_time_ns()`` is a system call (CLOCK_THREAD_CPUTIME_ID has no
# vDSO path) made holding the interpreter: 0.27 us on a plain Linux host; on
# the sandboxed kernel of the chip's host 6 us in a tight loop and 13 to 19
# on a loaded server, where read at both ends of EVERY stage it took 18%
# off ``stream4m_c1`` (PERF.md 6, PR 39). So the clock is read for one message
# (or batch, or reply) in N, whole: the decision is made by the stage that
# opens it on its thread (the one given ``call=`` with a pair the thread
# does not have yet; on a thread that was never given one, every stage for
# itself) and the stages nested under it inherit it, so that a parent less
# its children, and ``exclude``, stay exact within every clocked message. A
# clocked stage bills its CPU times N: every N-th message a hop opens is
# clocked, whatever it holds (a systematic sample: fair to all of them
# unless a hop's costs repeat with a period that divides N: a prime, so
# that no rotation of two, four or eight connections does). N is a
# constant: at 31 the reads, ten a clocked message, cost ``stream4m_c1``
# 1.5% on that host (370 reads a second: a sparse read costs the pace
# about 40 us, three times what it is timed at) and nothing that pairs of
# runs resolve in the other cells, and a hop that opens a hundred messages
# a second still has fifty clocked in a 15 s window; the cost goes as 1/N
# and the error of a twin as its root (PERF.md 6, PR 39). (Timed when the
# module is loaded, the read gave 6 us or 45, process by process, on that
# host, so nothing is timed. On a host with a fine clock and a cheap read
# one message in 31 is sample enough.)
_CPU_EVERY = 31
#: per deciding hop: the messages it has opened
_OPENED = {name: itertools.count() for name in HOP_NAMES}
_CPU_READS = _metrics.counter("lens_cpu_clock_reads")
_OBS_BG_CPU = _metrics.counter("obs_bg_cpu_ns")
_OBS_BG_TICKS = _metrics.counter("obs_bg_ticks")


def _clocked(hop: str) -> bool:
    """Whether the message that a stage of ``hop`` opens now reads the CPU
    clock: the hop's every ``_CPU_EVERY``-th does, its first among them
    (``next`` of a count is one step of the interpreter: threads share it
    without a lock and none is counted twice)."""
    return next(_OPENED[hop]) % _CPU_EVERY == 0


def _annotation_cls():
    """``jax.profiler.TraceAnnotation`` where this process has ALREADY
    imported jax, else None. Never imports it: clients and parents stay off
    jax (one process per chip), and ``obs/`` must not be what loads it."""
    global _annotation
    prof = sys.modules.get("jax.profiler")
    _annotation = getattr(prof, "TraceAnnotation", None)
    return _annotation


class stage:
    """One accounted operation of a declared hop, as a context manager
    (or ``begin()`` / ``end()`` where a generator's ``yield`` stands
    between them).

    On exit, also when the body raised: ``lens_<hop>_busy_ns`` += elapsed,
    ``lens_<hop>_cpu_ns`` += the calling thread's CPU over the same
    interval where this message reads the CPU clock, times the N it is
    one of (see "the second clock" above; what was read is left in
    ``cpu_ns``, 0 where it was not, for a caller that takes this stage out
    of another), ``lens_<hop>_bytes`` += ``nbytes``, ``lens_<hop>_ops`` += 1,
    ``lens_<hop>_copy_bytes`` += ``copy``. ``nbytes`` / ``copy`` may be
    set on the object inside the body, for sites that only know the size
    once the work is done. In a process that has imported jax the stage is
    also a ``jax.profiler.TraceAnnotation`` ``tpurpc.<hop>`` on the calling
    thread, carrying the thread's current ``call`` and ``seq``, while a
    profiler session is on (``TraceAnnotation.is_enabled()``): the session
    is the only switch. ``call=`` sets that pair for
    the thread (the call path does, once per message); nested stages
    inherit it. ``marks`` (names to ints) are the span's too.
    ``begin`` and ``end`` run on one thread."""

    __slots__ = ("hop", "nbytes", "copy", "cpu_ns", "marks", "_t0", "_c0",
                 "_span")

    def __init__(self, hop: str, nbytes: int = 0, *,
                 call: Optional[int] = None, seq: int = 0,
                 marks: Optional[Dict[str, int]] = None):
        if call is not None:
            ids = (call, seq)
            if _tls.ids != ids:
                # a pair the thread does not have yet: a new message on it
                _tls.ids = ids
                _tls.clocked = _clocked(hop)
        self.hop = hop
        self.nbytes = nbytes
        self.copy = 0
        self.marks = marks

    def begin(self) -> "stage":
        ann = _annotation or _annotation_cls()
        # no session: a 50 ns look, not a 0.5 us annotation that records
        # nothing
        if ann is not None and ann.is_enabled():
            call, seq = _tls.ids or (0, 0)
            self._span = ann(_SPAN[self.hop], call=call, seq=seq,
                             **(self.marks or {}))
            self._span.__enter__()
        else:
            self._span = None
        clocked = _tls.clocked
        if clocked is None:  # a thread that was never given a message
            clocked = _clocked(self.hop)
        # the wall interval encloses the CPU interval, so a clocked stage's
        # cpu_ns <= its busy_ns whatever the reads themselves cost
        self._t0 = time.monotonic_ns()
        self._c0 = time.thread_time_ns() if clocked else None
        return self

    def end(self) -> int:
        hop = self.hop
        c0 = self._c0
        if c0 is None:
            dt = time.monotonic_ns() - self._t0
            self.cpu_ns = 0
        else:
            self.cpu_ns = cpu = time.thread_time_ns() - c0
            dt = time.monotonic_ns() - self._t0
            _CPU[hop].inc(cpu * _CPU_EVERY)
            _CPU_READS.inc(2)
        if self._span is not None:
            self._span.__exit__(None, None, None)
        _NS[hop].inc(dt)
        _BYTES[hop].inc(self.nbytes)
        _OPS[hop].inc()
        if self.copy:
            _COPY[hop].inc(self.copy)
        return dt

    def exclude(self, ns: int, cpu_ns: int) -> None:
        """Take a sibling stage that ran inside this one's interval out of
        this one, ``ns`` of its busy time and ``cpu_ns`` of its CPU (the
        sibling's ``cpu_ns`` after its ``end``: as read, before it is
        billed times N; a response sent while the handler's stage is
        open), so that the call path's top-level stages stay additive on
        both clocks. (To a read: a clocked sibling's own two reads of the
        thread's clock lie inside its wall and half outside its CPU, so
        this stage keeps about one read of CPU that its wall gave away.)"""
        self._t0 += ns
        if self._c0 is not None:
            self._c0 += cpu_ns

    __enter__ = begin

    def __exit__(self, *exc) -> bool:
        self.end()
        return False


def current_ids() -> Optional[Tuple[int, int]]:
    """The calling thread's current ``(call, seq)``, None on a thread that
    was never given a message: for work handed to another thread, whose
    stage takes them as ``call=`` and ``seq=`` so that its span names the
    message it serves."""
    return _tls.ids


def account(hop: str, busy_ns: int, nbytes: int = 0, ops: int = 1) -> None:
    """One operation of ``hop`` timed by the caller: counters only, no span
    and no ``cpu_ns`` (``srv_call``: the duration the call path already
    computes; ``srv_queue``: a wait that is no thread's time). ``ops``:
    that many, their times added up by the caller (``batch_wait``: a
    batch's rows)."""
    _NS[hop].inc(busy_ns)
    _BYTES[hop].inc(nbytes)
    _OPS[hop].inc(ops)


class CallStages:
    """The call path's stages of ONE server call, driven from its handler
    thread by either plane (``rpc/server.py``, ``rpc/native_server.py``)::

        with stages.recv() as rx:     # srv_recv: wait for message `seq`
            ...; rx.nbytes = n
        stages.handle(n)              # srv_handler opens: the behavior has it
        ...                           #   yield / behavior(...)
        stages.handled()              # ...until it asks for the next one
        tx = stages.send_begin()      # srv_send: one response out, and its
        ...; stages.send_end(tx)      #   time out of an open srv_handler

    so that ``srv_recv`` + ``srv_handler`` + ``srv_send`` add up to the call
    (``srv_call``, :meth:`finish`) less what no stage covers. ``touch`` is
    called for every message in or out: the stall watchdog's progress."""

    __slots__ = ("call", "seq", "handling", "_touch", "_t0")

    def __init__(self, touch):
        self.call = next(_CALL_IDS)  # process-wide ordinal: the spans' `call`
        self.seq = 0
        self.handling: Optional[stage] = None
        self._touch = touch
        self._t0 = time.monotonic_ns()

    def recv(self) -> stage:
        return stage("srv_recv", call=self.call, seq=self.seq)

    def handle(self, nbytes: int) -> None:
        self._touch()
        self.handling = stage("srv_handler", nbytes).begin()

    def handled(self) -> None:
        if self.handling is not None:
            self.handling.end()
            self.handling = None
            self.seq += 1

    def send_begin(self, seq: Optional[int] = None) -> stage:
        """``seq``: the response's ordinal in its stream, for a response
        written by another thread than the call's own (a reply that was
        yielded as a future): that thread's span then carries this call."""
        if seq is None:
            return stage("srv_send").begin()
        return stage("srv_send", call=self.call, seq=seq).begin()

    def send_end(self, tx: stage, inside: bool = True) -> None:
        """``inside=False``: the send ran on another thread, beside the
        handler's stage and not within it, so nothing is taken out."""
        dt = tx.end()
        if inside and self.handling is not None:
            self.handling.exclude(dt, tx.cpu_ns)
        self._touch()

    def finish(self) -> None:
        """The call is over: one op of ``srv_call``, once. A plane whose
        caller sees the end of a stream before the handler thread is done
        with it calls this BEFORE it writes the trailers, so that a
        snapshot taken when the caller has its reply holds the call."""
        if self._t0:
            self.handled()
            account("srv_call", time.monotonic_ns() - self._t0)
            self._t0 = 0


def enabled() -> bool:
    """The lens master switch (``TPURPC_LENS=0`` off). Gates the sampling
    profiler and the scrape routes; the hop counters are branch-free
    always-on accounting and ignore it."""
    from tpurpc.utils.config import _env

    return (_env("TPURPC_LENS") or "1").lower() not in ("0", "off", "false")


# -- scrape-time export -------------------------------------------------------

def waterfall() -> dict:
    """The per-hop effective-throughput table, sampled from the counters at
    call time. ``gbps`` is ``bytes / busy_ns`` (identical units); a hop
    that has seen no traffic reports zeros and is excluded from the
    bottleneck argmin."""
    # the registry's collectors first (tpurpc-xray's pulls the C core's
    # byte/busy_ns table into the native hops), so slowest_hop judges the
    # PRODUCTION plane too
    _metrics.registry().collect()
    rows: List[dict] = []
    for name, desc in HOPS:
        b = _BYTES[name].snapshot()
        ns = _NS[name].snapshot()
        cp = _COPY[name].snapshot()
        rows.append({
            "hop": name,
            "bytes": b,
            "busy_ms": round(ns / 1e6, 3),
            "cpu_ms": round(_CPU[name].snapshot() / 1e6, 3),
            "gbps": round(b / ns, 3) if ns else 0.0,
            "copy_bytes": cp,
            "ops": _OPS[name].snapshot(),
            "what": desc,
        })
    bg_cpu, bg_ticks = _OBS_BG_CPU.snapshot(), _OBS_BG_TICKS.snapshot()
    out = {"hops": rows, "slowest_hop": slowest_hop(rows),
           # which messages read the second clock, and how often it was read
           "cpu_clock": {"every": _CPU_EVERY,
                         "reads": _CPU_READS.snapshot()},
           # what the measurement takes: the obs/ loops' own threads
           "observers": {"cpu_ms": round(bg_cpu / 1e6, 3), "ticks": bg_ticks,
                         "us_a_tick": (round(bg_cpu / bg_ticks / 1e3, 1)
                                       if bg_ticks else 0.0)}}
    try:
        from tpurpc.obs import interp

        # who held the interpreter since the sampler started (ISSUE 40)
        held = interp.shares()
        if held is not None:
            out["interpreter"] = held
    except Exception:
        pass
    try:
        from tpurpc.tpu import ledger

        out["ledger"] = ledger.snapshot()
    except Exception:
        pass
    return out


def slowest_hop(rows: Optional[List[dict]] = None) -> Optional[str]:
    """The bottleneck hop: lowest effective GB/s among hops that actually
    moved bytes (and spent time doing it). None before any traffic.

    Hops that carried under 1% of the busiest hop's bytes are excluded:
    once the rendezvous plane carries the bulk payloads, the framed ``wire``
    hop sees only control frames — a few KB at small-message rates — and a
    control-only hop's low GB/s is not an upper bound on the BULK flow, so
    naming it the bottleneck would be the instrument lying."""
    if rows is None:
        rows = waterfall()["hops"]
    live = [r for r in rows if r["bytes"] > 0 and r["busy_ms"] > 0]
    if not live:
        return None
    bar = max(r["bytes"] for r in live) * 0.01
    bulk = [r for r in live if r["bytes"] >= bar]
    return min(bulk or live, key=lambda r: r["gbps"])["hop"]


def interpreter_line(held: dict) -> str:
    """One line of the interpreter's lock and the cores: free %, hand-overs
    a second, the three roles that held the lock most, the three roles that
    kept the most cores busy, and the sampler's own share of a core."""
    top = ", ".join(f"{role} {pct:.1f}%" for role, pct in held["top"])
    cores = sorted(held.get("cores", {}).items(), key=lambda rc: -rc[1])[:3]
    busy = ", ".join(f"{role} {c:.2f}" for role, c in cores)
    return (f"interpreter: free {held['free_pct']:.1f}%, "
            f"{held['handovers_s']:.0f} hand-overs/s; held by "
            f"{top or 'nobody'} ({held['samples']} samples); cores "
            f"{busy or 'none'}; sampler {held.get('sampler_pct', 0.0):.2f}% "
            f"of a core")


def render_text(doc: Optional[dict] = None) -> str:
    """Human rendering of the waterfall (``?text=1`` / tools.top pane)."""
    doc = doc if doc is not None else waterfall()
    rows = doc["hops"]
    lines = [f"{'hop':<10} {'GB/s':>8} {'MiB':>10} {'busy_ms':>10} "
             f"{'cpu_ms':>10} {'copy_MiB':>9}  what"]
    lines.append("-" * len(lines[0]))
    for r in rows:
        mark = " <-- slowest" if r["hop"] == doc.get("slowest_hop") else ""
        lines.append(
            f"{r['hop']:<10} {r['gbps']:>8.3f} "
            f"{r['bytes'] / (1 << 20):>10.1f} {r['busy_ms']:>10.1f} "
            f"{r.get('cpu_ms', 0.0):>10.1f} "
            f"{r['copy_bytes'] / (1 << 20):>9.1f}  {r['what'][:46]}{mark}")
    if doc.get("slowest_hop") is None:
        lines.append("(no traffic yet: every hop idle)")
    held = doc.get("interpreter")
    if held:
        lines.append(interpreter_line(held))
    obs, clock = doc.get("observers"), doc.get("cpu_clock")
    if obs and clock:  # a member that predates them sends neither
        lines.append(
            f"observers: {obs['cpu_ms']:.1f} ms of CPU in {obs['ticks']} "
            f"ticks ({obs['us_a_tick']:.1f} us a tick); cpu_ms: every "
            f"{clock['every']}. message clocked ({clock['reads']} reads)")
    return "\n".join(lines) + "\n"
