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
fan-in batcher's four ``batch_*`` of ISSUE 33 and ``srv_reply_wait`` of
ISSUE 36 are appended: the registry is append-only)::

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
  and three or four GIL-atomic Counter bumps per op;
* a site is one :class:`stage` (``with lens.stage("hbm", n): ...``): it
  bumps the hop's ``bytes``, ``busy_ns``, ``ops`` (and ``copy_bytes``) on
  exit, and in a process that has imported jax it is also a
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
)

HOP_NAMES: Tuple[str, ...] = tuple(name for name, _ in HOPS)

_BYTES: Dict[str, _metrics.Counter] = {}
_NS: Dict[str, _metrics.Counter] = {}
_COPY: Dict[str, _metrics.Counter] = {}
_OPS: Dict[str, _metrics.Counter] = {}
_SPAN: Dict[str, str] = {}
for _name, _desc in HOPS:
    _BYTES[_name] = _metrics.counter(f"lens_{_name}_bytes")
    _NS[_name] = _metrics.counter(f"lens_{_name}_busy_ns")
    _COPY[_name] = _metrics.counter(f"lens_{_name}_copy_bytes")
    _OPS[_name] = _metrics.counter(f"lens_{_name}_ops")
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

#: the thread's current ``(call, seq)``: set by the call path's top-level
#: stage of each message, read by the stages nested under it so that every
#: span of one message carries the same pair
_tls = threading.local()
_CALL_IDS = itertools.count(1)
_annotation = None  # jax.profiler.TraceAnnotation, once this process has it


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
    ``lens_<hop>_bytes`` += ``nbytes``, ``lens_<hop>_ops`` += 1,
    ``lens_<hop>_copy_bytes`` += ``copy``. ``nbytes`` / ``copy`` may be
    set on the object inside the body, for sites that only know the size
    once the work is done. In a process that has imported jax the stage is
    also a ``jax.profiler.TraceAnnotation`` ``tpurpc.<hop>`` on the calling
    thread, carrying the thread's current ``call`` and ``seq``, while a
    profiler session is on (``TraceAnnotation.is_enabled()``): the session
    is the only switch. ``call=`` sets that pair for
    the thread (the call path does, once per message); nested stages
    inherit it. ``begin`` and ``end`` run on one thread."""

    __slots__ = ("hop", "nbytes", "copy", "_t0", "_span")

    def __init__(self, hop: str, nbytes: int = 0, *,
                 call: Optional[int] = None, seq: int = 0):
        if call is not None:
            _tls.ids = (call, seq)
        self.hop = hop
        self.nbytes = nbytes
        self.copy = 0

    def begin(self) -> "stage":
        ann = _annotation or _annotation_cls()
        # no session: a 50 ns look, not a 0.5 us annotation that records
        # nothing
        if ann is not None and ann.is_enabled():
            call, seq = getattr(_tls, "ids", (0, 0))
            self._span = ann(_SPAN[self.hop], call=call, seq=seq)
            self._span.__enter__()
        else:
            self._span = None
        self._t0 = time.monotonic_ns()
        return self

    def end(self) -> int:
        dt = time.monotonic_ns() - self._t0
        if self._span is not None:
            self._span.__exit__(None, None, None)
        hop = self.hop
        _NS[hop].inc(dt)
        _BYTES[hop].inc(self.nbytes)
        _OPS[hop].inc()
        if self.copy:
            _COPY[hop].inc(self.copy)
        return dt

    def exclude(self, ns: int) -> None:
        """Take ``ns`` of a sibling stage that ran inside this one's
        interval out of this one's busy time (a response sent while the
        handler's stage is open), so that the call path's top-level stages
        stay additive."""
        self._t0 += ns

    __enter__ = begin

    def __exit__(self, *exc) -> bool:
        self.end()
        return False


def account(hop: str, busy_ns: int, nbytes: int = 0, ops: int = 1) -> None:
    """One operation of ``hop`` timed by the caller: counters only, no span
    (``srv_call``: the duration the call path already computes;
    ``srv_queue``: a wait that is no thread's time). ``ops``: that many,
    their times added up by the caller (``batch_wait``: a batch's rows)."""
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
            self.handling.exclude(dt)
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
            "gbps": round(b / ns, 3) if ns else 0.0,
            "copy_bytes": cp,
            "ops": _OPS[name].snapshot(),
            "what": desc,
        })
    out = {"hops": rows, "slowest_hop": slowest_hop(rows)}
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


def render_text(doc: Optional[dict] = None) -> str:
    """Human rendering of the waterfall (``?text=1`` / tools.top pane)."""
    doc = doc if doc is not None else waterfall()
    rows = doc["hops"]
    lines = [f"{'hop':<10} {'GB/s':>8} {'MiB':>10} {'busy_ms':>10} "
             f"{'copy_MiB':>9}  what"]
    lines.append("-" * len(lines[0]))
    for r in rows:
        mark = " <-- slowest" if r["hop"] == doc.get("slowest_hop") else ""
        lines.append(
            f"{r['hop']:<10} {r['gbps']:>8.3f} "
            f"{r['bytes'] / (1 << 20):>10.1f} {r['busy_ms']:>10.1f} "
            f"{r['copy_bytes'] / (1 << 20):>9.1f}  {r['what'][:46]}{mark}")
    if doc.get("slowest_hop") is None:
        lines.append("(no traffic yet: every hop idle)")
    return "\n".join(lines) + "\n"
