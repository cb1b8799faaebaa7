"""tpurpc-blackbox stall watchdog: find the wedged RPC and name the stage.

A serving fleet's worst page is "one call is stuck and nothing says where".
The watchdog is a background sweeper over an in-process registry of
in-flight RPCs (both server handlers and the pipelined client's windows
register): any call that has made no PROGRESS for a multiple of its
method's ROLLING p99 — or for a static floor when the method has no
history yet — produces a structured diagnosis. Progress is the call's
start and, for a streaming call, every message it receives or sends
(:func:`call_progress`, one touch from the server's ``srv_recv`` and
``srv_send`` sites): a stream is one call however long it lives, and a
healthy one must not be barred on its age. The diagnosis names the blocked
*stage*, derived from the flight recorder's tail plus the scrape plane's
fleet gauges:

* ``credit-starvation`` — an open (unmatched) send-lease reserve, an
  unresolved ring credit-starvation edge, or a freshly write-stalled pair;
* ``peer-not-reading`` — a write stall/starvation that has persisted well
  past the stall bar (the peer is alive but not draining its ring);
* ``h2-flow-control`` — an h2 send window exhausted within the stall
  window (the peer stopped granting WINDOW_UPDATE credit);
* ``batcher-wait`` — requests parked in the fan-in batcher's queue;
* ``poller-wake`` — a pair with a complete message waiting that no waiter
  has drained (wake-latency / lost-kick territory);
* ``device-infer`` — the transport is quiet and the handler is simply
  still executing (the model/device is the long pole).

Diagnoses are served at ``GET /debug/stalls``, mirrored into the
``watchdog_trips`` / ``watchdog_stalls{stage}`` anomaly counters, flip
``/healthz`` to degraded (503) while active, flag the call's trace for
tail capture (:func:`tpurpc.obs.tracing.tail_flag` — so the postmortem has
the span tree), and log one flight-recorder replay per trip.

Cost: registration is a dict store + one monotonic stamp per RPC;
completion feeds a fixed-size rolling duration window per method (p99
computed lazily, cached 0.5 s). The sweeper is one daemon thread at
``TPURPC_WATCHDOG_SWEEP_S`` (default 0.25 s) that does nothing while no
call is over its bar. ``TPURPC_WATCHDOG=0`` disables everything.
"""

from __future__ import annotations

import itertools
import logging
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from tpurpc.obs import flight as _flight
from tpurpc.obs import metrics as _metrics
from tpurpc.obs import profiler as _obs_profiler

__all__ = ["StallWatchdog", "get", "call_started", "call_progress",
           "call_finished", "STAGES"]

#: tpurpc-lens: the sweeper thread parked between sweeps is infrastructure
#: idle time, not unattributed serving work
_LENS_STAGES = {"_loop": "idle", "sweep_once": "idle"}
_obs_profiler.register_stages(__file__, _LENS_STAGES)

_log = logging.getLogger("tpurpc.watchdog")

STAGES = ("credit-starvation", "peer-not-reading", "h2-flow-control",
          "ctrl-ring", "rendezvous", "kv-swap", "migration", "decode-step",
          "batcher-wait", "poller-wake", "device-infer", "slo", "unknown",
          # tpurpc-xray (ISSUE 19): stages diagnosed from the C core's
          # shm flight ring + metrics table — evidence the Python plane
          # cannot see (append-only, like the event codes)
          "native-ctrl-frozen", "native-pin-wait", "native-rdv-fallback",
          "native-delivery")

# tpurpc-argus (ISSUE 14): trip hooks — automatic evidence capture
# (obs/bundle.py) registers here so every sweeper trip and every external
# trip (a firing SLO page routes through external_trip) can snapshot a
# postmortem bundle. Hooks run on the tripping thread (the sweeper or the
# SLO evaluator — never an RPC hot path) and must never raise outward.

_trip_hooks: List = []


def add_trip_hook(fn) -> None:
    """Register ``fn(diag_dict)`` to run once per NEW trip (sweeper or
    external). Duplicate registrations are ignored."""
    if fn not in _trip_hooks:
        _trip_hooks.append(fn)


def remove_trip_hook(fn) -> None:
    try:
        _trip_hooks.remove(fn)
    except ValueError:
        pass


def _run_trip_hooks(diag: dict) -> None:
    for fn in list(_trip_hooks):
        try:
            fn(diag)
        except Exception:
            _log.exception("watchdog trip hook failed")

#: anomaly counters (always-on registry): total trips + per-stage breakdown
_TRIPS = _metrics.counter("watchdog_trips")
_STALLS = _metrics.labeled_counter("watchdog_stalls", ("stage",))

_BEGIN_END = {
    _flight.WRITE_STALL_BEGIN: _flight.WRITE_STALL_END,
    _flight.CREDIT_STARVE_BEGIN: _flight.CREDIT_STARVE_END,
}


class _Roll:
    """Fixed-size rolling duration window per method; p99 cached 0.5 s."""

    __slots__ = ("buf", "n", "_p99", "_stamp")
    SIZE = 128

    def __init__(self):
        self.buf = [0] * self.SIZE
        self.n = 0
        self._p99 = None
        self._stamp = 0.0

    def record(self, dur_ns: int) -> None:
        self.buf[self.n % self.SIZE] = dur_ns
        self.n += 1

    def p99_ns(self) -> Optional[int]:
        if self.n < 8:
            return None  # too little history to call anything an outlier
        now = time.monotonic()
        if self._p99 is None or now - self._stamp > 0.5:
            window = sorted(self.buf[:min(self.n, self.SIZE)])
            self._p99 = window[max(0, int(len(window) * 0.99) - 1)]
            self._stamp = now
        return self._p99


class StallWatchdog:
    def __init__(self, sweep_s: Optional[float] = None,
                 mult: Optional[float] = None,
                 min_stall_s: Optional[float] = None):
        import os

        self.enabled = os.environ.get("TPURPC_WATCHDOG", "1").lower() not in (
            "0", "off", "false")
        self.sweep_s = sweep_s if sweep_s is not None else float(
            os.environ.get("TPURPC_WATCHDOG_SWEEP_S", "0.25"))
        self.mult = mult if mult is not None else float(
            os.environ.get("TPURPC_WATCHDOG_MULT", "8"))
        self.min_stall_s = min_stall_s if min_stall_s is not None else float(
            os.environ.get("TPURPC_WATCHDOG_MIN_S", "1.0"))
        #: token -> [method, t0_ns, trace_id, kind, tripped, progress_ns]
        self._inflight: Dict[int, list] = {}
        self._tokens = itertools.count(1)
        self._rolls: Dict[str, _Roll] = {}
        self._active: List[dict] = []
        self._history: deque = deque(maxlen=64)
        self._thread: Optional[threading.Thread] = None
        self._thread_lock = threading.Lock()
        self._wake = threading.Event()

    # -- per-RPC face (hot-ish: one dict store / delete) ----------------------

    def call_started(self, method: str, trace_id: int = 0,
                     kind: str = "server") -> Optional[int]:
        if not self.enabled:
            return None
        tok = next(self._tokens)
        # [method, t0, trace_id, kind, tripped-stages] — the last slot
        # records which stages already paged for THIS call (tpurpc-oracle:
        # a diagnosis that sharpens, e.g. rendezvous -> native-ctrl-frozen
        # once the C evidence lands, re-trips under the sharper stage so
        # the trip hooks capture the better story; each stage at most once)
        t0 = time.monotonic_ns()
        self._inflight[tok] = [method, t0, trace_id, kind, set(), t0]
        if self._thread is None:
            self._ensure_thread()
        return tok

    def call_progress(self, token: Optional[int]) -> None:
        """The call moved a message: the stall bar counts from here."""
        entry = self._inflight.get(token)
        if entry is not None:
            entry[5] = time.monotonic_ns()

    def call_finished(self, token: Optional[int],
                      error: bool = False) -> None:
        if token is None:
            return
        entry = self._inflight.pop(token, None)
        if entry is None or error:
            return  # failures don't tighten the p99 bar
        dur = time.monotonic_ns() - entry[1]
        method = entry[0]
        roll = self._rolls.get(method)
        if roll is None:
            if len(self._rolls) >= 256:
                return  # bounded method cardinality
            roll = self._rolls.setdefault(method, _Roll())
        roll.record(dur)

    def slow_threshold_ns(self, method: str) -> Optional[int]:
        """``mult × rolling-p99`` for tail capture's slow bar, or None
        without enough history."""
        roll = self._rolls.get(method)
        if roll is None:
            return None
        p99 = roll.p99_ns()
        return None if p99 is None else int(p99 * self.mult)

    def rolling_p99_ns(self) -> Optional[int]:
        """The WORST rolling p99 across methods with history, or None —
        tpurpc-fleet's admission gate and load reports read this as the
        server's latency signal (one method in trouble is the fleet
        signal; averaging would hide it)."""
        worst = None
        for roll in list(self._rolls.values()):
            p99 = roll.p99_ns()
            if p99 is not None and (worst is None or p99 > worst):
                worst = p99
        return worst

    def method_p99s(self) -> Dict[str, int]:
        """Per-method rolling p99s (ns) for methods with enough history —
        tpurpc-argus's tsdb samples these into ``watchdog_p99_us{method}``
        series: unlike the cumulative ``srv_call_us`` histogram, a rolling
        window RECOVERS after a degradation ends, which is what a burn-
        rate alert must see to resolve."""
        out: Dict[str, int] = {}
        for method, roll in list(self._rolls.items()):
            p99 = roll.p99_ns()
            if p99 is not None:
                out[method] = p99
        return out

    # -- the sweeper ----------------------------------------------------------

    def _ensure_thread(self) -> None:
        with self._thread_lock:
            if self._thread is not None:
                return
            t = threading.Thread(target=self._loop, daemon=True,
                                 name="tpurpc-watchdog")
            self._thread = t
            t.start()

    def _loop(self) -> None:
        while True:
            self._wake.wait(timeout=self.sweep_s)
            self._wake.clear()
            try:
                self.sweep_once()
            except Exception:  # the watchdog must never take anything down
                _log.exception("watchdog sweep failed")
            _metrics.observer_tick()

    def _stall_bar_ns(self, method: str) -> int:
        bar = int(self.min_stall_s * 1e9)
        p99m = self.slow_threshold_ns(method)
        if p99m is not None:
            bar = max(bar, p99m)  # never page on a method's normal tail
        return bar

    def sweep_once(self, now_ns: Optional[int] = None) -> List[dict]:
        """One sweep: rebuild the active diagnosis list; fire trip actions
        for newly detected stalls. Exposed for tests (deterministic
        sweeps) — the daemon loop calls it on the configured cadence."""
        now = now_ns if now_ns is not None else time.monotonic_ns()
        active: List[dict] = []
        to_trip: List[tuple] = []
        evidence = None
        for tok, entry in list(self._inflight.items()):
            method, t0, trace_id, kind, tripped_stages, progress = entry
            if now - progress < self._stall_bar_ns(method):
                continue
            age = now - t0
            if evidence is None:
                evidence = self._gather_evidence(now)
            stage, detail = self._attribute(evidence, kind, age)
            diag = {
                "method": method,
                "kind": kind,
                "stage": stage,
                "detail": detail,
                # tpurpc-oracle: the same diagnosis as a structured
                # object (stage + entity + evidence refs) — the prose
                # above stays byte-identical for the text face
                "cause": self._cause_struct(evidence, stage),
                "age_s": round(age / 1e9, 3),
                "trace_id": f"{trace_id:016x}" if trace_id else None,
                "since_ns": t0,
            }
            active.append(diag)
            if stage not in tripped_stages:
                tripped_stages.add(stage)
                to_trip.append((diag, trace_id, age))
        self._active = active
        if active:
            for d in active:
                done = {"t": time.time()}  # tpr: allow(wallclock)
                done.update(d)
                if not self._history or self._history[-1].get(
                        "since_ns") != d["since_ns"] or \
                        self._history[-1].get("stage") != d["stage"]:
                    self._history.append(done)
        # trips fire AFTER the snapshot state is updated: a trip hook
        # (the bundle writer, tpurpc-oracle's diagnosis) that reads
        # ``snapshot()`` must see the diagnosis that tripped it
        for diag, trace_id, age in to_trip:
            self._trip(diag, trace_id, age)
        return active

    def _trip(self, diag: dict, trace_id: int, age_ns: int) -> None:
        _TRIPS.inc()
        _STALLS.labels(diag["stage"]).inc()
        _flight.emit(_flight.WATCHDOG_TRIP,
                     _flight.tag_for(diag["method"]), age_ns // 1_000_000)
        if trace_id:
            # postmortem spans: promote the wedged call's provisional trace
            # NOW, while it is still in flight — /traces has the tree even
            # if the call never completes
            from tpurpc.obs import tracing as _tracing

            _tracing.tail_flag(trace_id)
        # module-level dump_text, not the recorder's: the trip log must
        # replay the MERGED timeline — native-plane stages cite C evidence
        _log.warning(
            "stall: %s %s in flight %.2fs — stage %s (%s)\n%s",
            diag["kind"], diag["method"], diag["age_s"], diag["stage"],
            diag["detail"],
            _flight.dump_text(
                since_ns=diag["since_ns"] - 1_000_000_000))
        _run_trip_hooks(diag)

    def external_trip(self, stage: str, method: str, detail: str) -> None:
        """A trip raised by another verification subsystem rather than the
        sweeper — tpurpc-proof's live protocol verifier
        (``TPURPC_VERIFY_PROTOCOL=1``) calls this when a declared flight
        machine sees an illegal transition. Counts like a sweeper trip
        (``watchdog_trips`` / ``watchdog_stalls{stage}``), lands in the
        history served at ``/debug/stalls``, and logs one flight replay —
        but registers no in-flight call (there is nothing to age out)."""
        if not self.enabled:
            return
        _TRIPS.inc()
        _STALLS.labels(stage).inc()
        diag = {
            "method": method,
            "kind": "external",
            "stage": stage,
            "detail": detail,
            # an external verifier supplies no flight-edge evidence
            "cause": {"stage": stage, "entity": None, "evidence": []},
            "age_s": 0.0,
            "trace_id": None,
            "since_ns": time.monotonic_ns(),
        }
        done = {"t": time.time()}  # tpr: allow(wallclock)
        done.update(diag)
        self._history.append(done)
        _log.warning(
            "external trip: %s — stage %s (%s)\n%s",
            method, stage, detail,
            _flight.dump_text(
                since_ns=time.monotonic_ns() - 2_000_000_000))
        _run_trip_hooks(diag)

    # -- stage attribution ----------------------------------------------------

    def _gather_evidence(self, now_ns: int) -> dict:
        """One pass over the flight tail + fleet gauges, shared by every
        diagnosis in a sweep."""
        # the MERGED timeline (tpurpc-xray): the module-level snapshot
        # folds the C core's shm flight ring in, so native rdv/ctrl edges
        # and the native-only codes below are first-class evidence
        events = _flight.snapshot(
            since_ns=now_ns - 60_000_000_000, limit=512)
        open_lease = 0
        open_edges: Dict[tuple, int] = {}  # (begin_code, tag) -> t_ns
        # tpurpc-express: unmatched rendezvous edges — an OFFER the peer
        # never claimed ((tag, 'o', req)) or a claimed region never
        # completed/released ((tag, 'l', lease)) — are the evidence a call
        # is wedged INSIDE a bulk-tensor handoff, not in the ring/h2 path
        open_rdv: Dict[tuple, int] = {}
        # tpurpc-pulse: an open (unmatched) ring-full stall edge — the
        # producer sees the peer's descriptor ring full and the consumer
        # is not draining it; paired with a nonzero ctrl_ring_backlog
        # gauge this outranks the generic rendezvous story (the wedge is
        # the CONTROL plane, not the transfer)
        open_ctrl: Dict[int, int] = {}
        # tpurpc-cadence: per-scheduler step bracket — an open
        # GEN_STEP_BEGIN (no matching END) is a decode step IN the model
        # right now; its age says whether that is traffic or a wedge. The
        # last END stamp catches the other failure shape: sequences
        # waiting while the loop has stopped stepping entirely.
        open_step: Dict[int, int] = {}
        # tpurpc-keystone: open swap/migration brackets — a KV_SWAP_BEGIN
        # or MIG_BEGIN with no matching END is a sequence mid-move; aged
        # past the stall floor it is the wedge, and it outranks the
        # generic decode-step story (more specific evidence wins)
        open_swap: Dict[tuple, int] = {}
        open_mig: Dict[tuple, int] = {}
        # tpurpc-xray: native-plane evidence. A C-side tx-ring-full stall
        # (CTRL_STALL_BEGIN on an "nctrl:*" entity) is a FROZEN C CONSUMER
        # — the peer's native drain loop stopped; a pin-wait bracket is a
        # link close() wedged behind window pins; delivery-stall brackets
        # and recent fallbacks come straight off the C ring.
        open_nctrl: Dict[int, int] = {}
        open_pin: Dict[int, int] = {}
        open_dlv: Dict[int, int] = {}
        native_fallbacks: List[int] = []
        last_step_end = 0
        last_step_batch = 0
        last_h2 = 0
        for e in events:
            code = e["code"]
            if code == _flight.LEASE_RESERVE:
                open_lease += 1
            elif code in (_flight.LEASE_COMMIT, _flight.LEASE_ABORT):
                open_lease = max(0, open_lease - 1)
            elif code in _BEGIN_END:
                open_edges[(code, e["tag"])] = e["t_ns"]
            elif code in _BEGIN_END.values():
                for b, en in _BEGIN_END.items():
                    if en == code:
                        open_edges.pop((b, e["tag"]), None)
            elif code == _flight.H2_WINDOW_EXHAUSTED:
                last_h2 = e["t_ns"]
            elif code == _flight.CTRL_STALL_BEGIN:
                if e.get("lane") == "native":
                    open_nctrl[e["tag"]] = e["t_ns"]
                else:
                    open_ctrl[e["tag"]] = e["t_ns"]
            elif code == _flight.CTRL_STALL_END:
                if e.get("lane") == "native":
                    open_nctrl.pop(e["tag"], None)
                else:
                    open_ctrl.pop(e["tag"], None)
            elif code == _flight.NATIVE_PIN_WAIT_BEGIN:
                open_pin[e["tag"]] = e["t_ns"]
            elif code == _flight.NATIVE_PIN_WAIT_END:
                open_pin.pop(e["tag"], None)
            elif code == _flight.NATIVE_DLV_STALL_BEGIN:
                open_dlv[e["tag"]] = e["t_ns"]
            elif code == _flight.NATIVE_DLV_STALL_END:
                open_dlv.pop(e["tag"], None)
            elif code == _flight.NATIVE_RDV_FALLBACK:
                native_fallbacks.append(e["t_ns"])
            elif code == _flight.RDV_OFFER:
                open_rdv[(e["tag"], "o", e["a1"])] = e["t_ns"]
            elif code == _flight.RDV_CLAIM:
                open_rdv.pop((e["tag"], "o", e["a1"]), None)
                open_rdv[(e["tag"], "l", e["a2"])] = e["t_ns"]
            elif code == _flight.RDV_COMPLETE:
                open_rdv.pop((e["tag"], "l", e["a1"]), None)
            elif code == _flight.RDV_RELEASE:
                open_rdv.pop((e["tag"], "l", e["a1"]), None)
                open_rdv.pop((e["tag"], "o", e["a2"]), None)
            elif code == _flight.GEN_STEP_BEGIN:
                open_step[e["tag"]] = e["t_ns"]
                last_step_batch = e["a1"]
            elif code == _flight.GEN_STEP_END:
                open_step.pop(e["tag"], None)
                last_step_end = e["t_ns"]
            elif code == _flight.KV_SWAP_BEGIN:
                open_swap[(e["tag"], e["a1"])] = e["t_ns"]
            elif code == _flight.KV_SWAP_END:
                open_swap.pop((e["tag"], e["a1"]), None)
            elif code == _flight.MIG_BEGIN:
                open_mig[(e["tag"], e["a1"])] = e["t_ns"]
            elif code == _flight.MIG_END:
                open_mig.pop((e["tag"], e["a1"]), None)

        # tpurpc-xray: the C metrics table backs the flight-tail evidence
        # (depth gauge for the delivery story, fallback total for storms)
        try:
            from tpurpc.obs import native_obs as _nobs

            ntab = _nobs.counters()
        except Exception:
            ntab = {}

        def fleet_sum(name: str) -> float:
            m = _metrics.registry().metrics().get(name)
            if m is None or not isinstance(m, _metrics.FleetGauge):
                return 0.0
            return m.collect()[0]

        return {
            "now_ns": now_ns,
            "open_lease": open_lease,
            "open_edges": open_edges,
            "open_rdv": open_rdv,
            "open_ctrl": open_ctrl,
            "ctrl_ring_backlog": fleet_sum("ctrl_ring_backlog"),
            "open_nctrl": open_nctrl,
            "open_pin": open_pin,
            "open_dlv": open_dlv,
            "native_fallbacks": native_fallbacks,
            "native_dlv_depth": ntab.get("dlv_depth", 0),
            "native_fallback_total": ntab.get("rdv_fallbacks", 0),
            "open_swap": open_swap,
            "open_mig": open_mig,
            "open_step": open_step,
            "last_step_end_ns": last_step_end,
            "last_step_batch": last_step_batch,
            "last_h2_ns": last_h2,
            "pairs_write_stalled": fleet_sum("pairs_write_stalled"),
            "batcher_queue_depth": fleet_sum("batcher_queue_depth"),
            "pairs_msg_waiting": fleet_sum("pairs_msg_waiting"),
            "decode_waiting": fleet_sum("decode_waiting"),
            "decode_running": fleet_sum("decode_running"),
        }

    def _attribute(self, ev: dict, kind: str, age_ns: int) -> tuple:
        now = ev["now_ns"]
        starve_age = 0
        for (code, tag), t in ev["open_edges"].items():
            starve_age = max(starve_age, now - t)
        if ev["open_lease"] > 0:
            return ("credit-starvation",
                    "send-lease held: reserve without commit/abort in the "
                    "flight tail — the ring write lock is wedged")
        # tpurpc-xray: a C-side tx-ring-full stall bracket is the most
        # specific control-plane story there is — the peer's NATIVE drain
        # loop (poller/pump thread) froze, diagnosed purely from C
        # evidence (the Python plane never sees these posts at all)
        open_nctrl = ev.get("open_nctrl") or {}
        if open_nctrl:
            oldest = max(now - t for t in open_nctrl.values())
            if oldest >= self.min_stall_s * 1e9 / 2:
                return ("native-ctrl-frozen",
                        f"native ctrl ring full {oldest / 1e9:.2f}s on "
                        f"{len(open_nctrl)} link(s): the peer's C consumer "
                        "stopped draining its descriptor ring")
        # tpurpc-pulse: a stuck descriptor ring is MORE specific than the
        # rendezvous story it wedges — the control op (offer/claim/
        # complete) is sitting in a ring nobody drains.  Evidence: an aged
        # open ring-full stall bracket, or posted-but-unconsumed records
        # (the backlog gauge) behind an aged rendezvous edge.
        open_ctrl = ev.get("open_ctrl") or {}
        backlog = ev.get("ctrl_ring_backlog", 0)
        open_rdv = ev.get("open_rdv") or {}
        ctrl_age = 0
        if open_ctrl:
            ctrl_age = max(now - t for t in open_ctrl.values())
        elif backlog > 0 and open_rdv:
            ctrl_age = max(now - t for t in open_rdv.values())
        if ctrl_age >= self.min_stall_s * 1e9 / 2:
            return ("ctrl-ring",
                    f"descriptor-ring control plane stalled "
                    f"{ctrl_age / 1e9:.2f}s: {int(backlog)} posted "
                    f"record(s) undrained"
                    + (f", {len(open_ctrl)} link(s) ring-full"
                       if open_ctrl else "")
                    + " — the peer's ring consumer stopped draining")
        if open_rdv:
            oldest = max(now - t for t in open_rdv.values())
            # a fresh edge is a transfer IN PROGRESS (claim round trips are
            # µs-scale); only an edge aged past half the stall floor is
            # evidence of a wedge rather than of traffic
            if oldest >= self.min_stall_s * 1e9 / 2:
                offers = sum(1 for k in open_rdv if k[1] == "o")
                claims = len(open_rdv) - offers
                return ("rendezvous",
                        f"bulk-tensor rendezvous wedged {oldest / 1e9:.2f}s:"
                        f" {offers} offer(s) unanswered, {claims} claimed "
                        "region(s) without complete/release in the flight "
                        "tail")
        # tpurpc-xray: the remaining native-plane stories, all from C
        # evidence alone. A pin-wait bracket is a link close() wedged
        # behind window pins (a claim waiter or in-flight placement holds
        # the mapping); a delivery-stall bracket backed by the depth
        # gauge is the server's delivery shard not draining; a burst of
        # fallback edges is the rendezvous plane silently degrading every
        # bulk send to the framed path.
        open_pin = ev.get("open_pin") or {}
        if open_pin:
            oldest = max(now - t for t in open_pin.values())
            if oldest >= self.min_stall_s * 1e9 / 2:
                return ("native-pin-wait",
                        f"native link close() waiting {oldest / 1e9:.2f}s "
                        "on pinned landing windows — a claim waiter or "
                        "in-flight placement still holds the mapping")
        open_dlv = ev.get("open_dlv") or {}
        if open_dlv:
            oldest = max(now - t for t in open_dlv.values())
            if oldest >= self.min_stall_s * 1e9 / 2:
                return ("native-delivery",
                        f"native delivery shard backlogged "
                        f"{oldest / 1e9:.2f}s "
                        f"({int(ev.get('native_dlv_depth', 0))} item(s) "
                        "queued): decode/materialization is not keeping "
                        "up with the pollers")
        fallbacks = ev.get("native_fallbacks") or []
        recent_fb = [t for t in fallbacks if now - t < 10e9]
        if len(recent_fb) >= 3:
            return ("native-rdv-fallback",
                    f"{len(recent_fb)} native rendezvous fallback(s) in "
                    "10s (total "
                    f"{int(ev.get('native_fallback_total', 0))}): bulk "
                    "sends are degrading to the framed path — claims "
                    "refused, timing out, or placement failing")
        # tpurpc-keystone: an aged open swap/migration bracket is MORE
        # specific than the decode-step story — the loop (or a migration
        # thread) is inside a KV move, and every stream behind the
        # boundary waits on it
        open_swap = ev.get("open_swap") or {}
        if open_swap:
            oldest = max(now - t for t in open_swap.values())
            if oldest >= self.min_stall_s * 1e9 / 2:
                return ("kv-swap",
                        f"KV swap wedged {oldest / 1e9:.2f}s: a "
                        f"swap begin without its end in the flight tail "
                        f"({len(open_swap)} open) — the host copy or "
                        "arena re-admission is stuck")
        open_mig = ev.get("open_mig") or {}
        if open_mig:
            oldest = max(now - t for t in open_mig.values())
            if oldest >= self.min_stall_s * 1e9 / 2:
                return ("migration",
                        f"live migration wedged {oldest / 1e9:.2f}s: "
                        f"{len(open_mig)} sequence(s) detached with no "
                        "migration-end — the peer handoff "
                        "(offer/ship/complete) is stuck")
        open_step = ev.get("open_step") or {}
        if open_step:
            oldest = max(now - t for t in open_step.values())
            # a fresh step edge is a decode step in flight (ms-scale);
            # only one aged past half the stall floor is a wedge — the
            # model call itself is the long pole, and every stream in the
            # batch is stalled behind it
            if oldest >= self.min_stall_s * 1e9 / 2:
                return ("decode-step",
                        f"decode step wedged {oldest / 1e9:.2f}s in the "
                        f"model (batch of {int(ev.get('last_step_batch', 0))}"
                        "): every running stream waits on this step")
        if (ev.get("decode_waiting", 0) > 0
                and not open_step
                and (not ev.get("last_step_end_ns")
                     or now - ev["last_step_end_ns"]
                     >= self.min_stall_s * 1e9)):
            return ("decode-step",
                    f"{int(ev['decode_waiting'])} sequence(s) waiting but "
                    "the decode loop has not completed a step inside the "
                    "stall window — the scheduler thread is wedged or "
                    "starved")
        if starve_age or ev["pairs_write_stalled"] > 0:
            if starve_age > 2 * age_ns or (
                    starve_age > 3 * self.min_stall_s * 1e9):
                return ("peer-not-reading",
                        "write stall/credit starvation persisted "
                        f"{starve_age / 1e9:.2f}s: the peer is connected "
                        "but not draining its receive ring")
            return ("credit-starvation",
                    "ring writer out of credits "
                    f"({int(ev['pairs_write_stalled'])} pair(s) "
                    "write-stalled)")
        if ev["last_h2_ns"] and now - ev["last_h2_ns"] < age_ns + int(1e9):
            # an exhaustion event within the stalled call's lifetime
            # (plus a second of slack for sweep-phase skew)
            return ("h2-flow-control",
                    "h2 send window exhausted: the peer stopped granting "
                    "WINDOW_UPDATE credit")
        if ev["batcher_queue_depth"] > 0:
            return ("batcher-wait",
                    f"{int(ev['batcher_queue_depth'])} request(s) parked "
                    "in the fan-in batcher queue")
        if ev["pairs_msg_waiting"] > 0:
            return ("poller-wake",
                    "a complete message is sitting undrained in a pair's "
                    "receive ring — wake latency or a lost kick")
        if kind == "server":
            return ("device-infer",
                    "transport quiet, handler still executing: the "
                    "model/device call is the long pole")
        return ("device-infer",
                "no local transport anomaly: the call is in flight at the "
                "peer (its handler/device is the long pole)")

    def _cause_struct(self, ev: dict, stage: str) -> dict:
        """tpurpc-oracle: the machine-readable twin of ``_attribute`` —
        the stage, the entity (connection/link) the oldest witness names,
        and ``[plane, ref, value]`` evidence rows citing the exact flight
        edges / gauges the prose describes. ``diagnose.py`` consumes this
        directly; the prose face stays untouched. ``device-infer`` (and
        external trips) legitimately carry no local evidence."""
        now = ev["now_ns"]
        evidence: List[list] = []
        entity: Optional[str] = None

        def add_table(table, slug, tag_index=None):
            nonlocal entity
            for key, t in sorted(table.items(), key=lambda kv: kv[1])[:4]:
                tag = key[tag_index] if tag_index is not None else key
                name = _flight.tag_name(tag)
                if entity is None:
                    entity = name
                evidence.append(
                    ["flight", f"{slug}:{name}@{t}",
                     round((now - t) / 1e9, 3)])

        def add_gauge(name):
            v = ev.get(name, 0)
            if v:
                evidence.append(["metrics", name, v])

        if stage == "credit-starvation":
            if ev.get("open_lease"):
                evidence.append(
                    ["flight", "lease-reserve-open", ev["open_lease"]])
            add_table(ev.get("open_edges") or {}, "stall-edge", 1)
            add_gauge("pairs_write_stalled")
        elif stage == "peer-not-reading":
            add_table(ev.get("open_edges") or {}, "stall-edge", 1)
            add_gauge("pairs_write_stalled")
        elif stage == "native-ctrl-frozen":
            add_table(ev.get("open_nctrl") or {}, "nctrl-ring-full")
        elif stage == "ctrl-ring":
            add_table(ev.get("open_ctrl") or {}, "ctrl-ring-full")
            add_gauge("ctrl_ring_backlog")
            if not ev.get("open_ctrl"):
                add_table(ev.get("open_rdv") or {}, "rdv-open", 0)
        elif stage == "rendezvous":
            add_table(ev.get("open_rdv") or {}, "rdv-open", 0)
        elif stage == "native-pin-wait":
            add_table(ev.get("open_pin") or {}, "pin-wait")
        elif stage == "native-delivery":
            add_table(ev.get("open_dlv") or {}, "dlv-stall")
            add_gauge("native_dlv_depth")
        elif stage == "native-rdv-fallback":
            for t in (ev.get("native_fallbacks") or [])[-4:]:
                evidence.append(
                    ["flight", f"rdv-fallback@{t}",
                     round((now - t) / 1e9, 3)])
            add_gauge("native_fallback_total")
        elif stage == "kv-swap":
            add_table(ev.get("open_swap") or {}, "kv-swap-open", 0)
        elif stage == "migration":
            add_table(ev.get("open_mig") or {}, "mig-open", 0)
        elif stage == "decode-step":
            add_table(ev.get("open_step") or {}, "step-open")
            add_gauge("decode_waiting")
            if ev.get("last_step_end_ns"):
                evidence.append(
                    ["flight", f"last-step-end@{ev['last_step_end_ns']}",
                     round((now - ev["last_step_end_ns"]) / 1e9, 3)])
        elif stage == "h2-flow-control":
            if ev.get("last_h2_ns"):
                evidence.append(
                    ["flight", f"h2-exhausted@{ev['last_h2_ns']}",
                     round((now - ev["last_h2_ns"]) / 1e9, 3)])
        elif stage == "batcher-wait":
            add_gauge("batcher_queue_depth")
        elif stage == "poller-wake":
            add_gauge("pairs_msg_waiting")
        return {"stage": stage, "entity": entity, "evidence": evidence}

    # -- export ---------------------------------------------------------------

    def active(self) -> List[dict]:
        return list(self._active)

    def snapshot(self) -> dict:
        out = {
            "active": list(self._active),
            "history": list(self._history),
            "inflight": len(self._inflight),
            "sweep_s": self.sweep_s,
            "mult": self.mult,
            "min_stall_s": self.min_stall_s,
            "enabled": self.enabled,
        }
        # tpurpc-manycore: a shard worker's registry names its shard so the
        # aggregated /debug/stalls view attributes each diagnosis
        from tpurpc.obs import shard as _shard

        if _shard.shard_id() >= 0:
            out["shard"] = _shard.shard_id()
        return out

    def reset(self) -> None:
        """Test isolation: forget in-flight calls and diagnoses (the
        sweeper thread, if started, keeps running harmlessly)."""
        self._inflight.clear()
        self._rolls.clear()
        self._active = []
        self._history.clear()


_instance: Optional[StallWatchdog] = None
_instance_lock = threading.Lock()


def get() -> StallWatchdog:
    global _instance
    if _instance is None:
        with _instance_lock:
            if _instance is None:
                _instance = StallWatchdog()
    return _instance


def call_started(method: str, trace_id: int = 0,
                 kind: str = "server") -> Optional[int]:
    return get().call_started(method, trace_id, kind)


def call_progress(token: Optional[int]) -> None:
    if token is not None:
        get().call_progress(token)


def call_finished(token: Optional[int], error: bool = False) -> None:
    if token is not None:
        get().call_finished(token, error=error)


def postfork_reset() -> None:
    """Fresh watchdog in a forked shard worker: the inherited instance's
    sweeper thread did not survive the fork (and ``call_started`` would
    never restart it — ``_thread`` is non-None but dead), and its in-flight
    registry describes the supervisor's calls, not this worker's."""
    global _instance, _instance_lock
    _instance_lock = threading.Lock()
    _instance = None
