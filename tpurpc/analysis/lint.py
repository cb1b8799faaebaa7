"""tpurpc-specific AST lint passes.

Four rules, each guarding an invariant the round-5 review found violated by
hand (ISSUE 2) and that no general-purpose linter knows about:

* ``lease``    — lease pairing: a function that calls a ``*send_reserve*``
  entry point must reach ``*send_commit*`` on success and ``*send_abort*`` on
  every exception path (the abort must sit in an ``except``/``finally``), and
  the fill code between reserve and commit must be covered by that handler.
  An unaborted lease wedges the peer's ring write lock forever (the exact
  round-5 native-plane bug).
* ``copy``     — hot-path no-copy: in the data-plane modules
  (``core/ring.py``, ``core/pair.py``, ``wire/grpc_h2.py``,
  ``jaxshim/codec.py``) the patterns ``b"".join(...)``,
  ``*.from_buffer_copy(...)`` and ``bytes(x[a:b])`` / ``bytearray(x[a:b])``
  are banned: the first two hide whole-payload copies, the last double-copies
  (slicing ``bytes``/``bytearray`` copies once, materializing again copies
  twice). The sanctioned escape hatch is slicing a ``memoryview`` (zero-copy)
  and calling ``.tobytes()`` — one visible, greppable copy.
* ``lock``     — lock map: a class that declares ``_GUARDED_BY =
  {"attr": "_lock"}`` promises that ``self.attr`` is only MUTATED inside
  ``with self._lock:`` (``__init__`` is exempt: construction happens-before
  sharing). This is the bug class of the round-5 ``xds.py`` finding — an
  unlocked ``subscribed[:]`` mutation racing a locked snapshot.
  ``{"attr": None}`` declares the opposite promise (``FanInBatcher``'s
  queue and flags): NO lock, so ``self.attr`` is only mutated by a step the
  interpreter cannot split: a mutator call on the container
  (``append`` / ``popleft`` / ``remove`` ...) or a plain store of a
  constant or a local. ``+=``, a subscript or slice store, and a store of
  anything computed from ``self`` are read-modify-write and are flagged.
* ``wallclock``— monotonic clocks: ``time.time()`` is banned for anything
  that could feed duration/interval math; genuinely absolute timestamps
  (channelz report fields, human-facing log stamps) carry an explicit
  ``# tpr: allow(wallclock)`` annotation.
* ``block``    — no unbounded blocking on the inline dispatch path
  (``rpc/server.py``, the functions the reactor invocation from
  ``_ServerSink.commit`` runs on the connection READER thread —
  ``INLINE_DISPATCH_PATH``): ``time.sleep`` and timeout-less
  ``.acquire()`` / ``.get()`` / ``.wait()`` / ``.join()`` stall every
  stream on the connection. Bounded-slice waits (an explicit timeout)
  pass; deliberate exceptions carry ``# tpr: allow(block)``.
* ``log``      — hot-path modules (``core/ring.py``, ``core/pair.py``,
  ``core/poller.py``, ``wire/grpc_h2.py``) may only call ``log_debug`` /
  ``log_info`` behind a ``TraceFlag`` guard — ``flag.log(...)`` (which
  tests ``enabled`` first) or ``if flag:`` / ``if flag.enabled:`` — so
  %-formatting and string building never run on the fast path when
  tracing is off. ``log_error`` is exempt (error paths are cold by
  definition). Deliberate exceptions carry ``# tpr: allow(log)``.
* ``shard``    — shard confinement (tpurpc-manycore, ISSUE 7): in modules
  where a class declares ``_MERGE_BOUNDARY = ("fn", ...)``, any attribute
  named in any class's ``_GUARDED_BY`` is shard-local state — mutating it
  through a non-``self`` base (another shard's queue, a sub-batch's result
  slot) is a cross-shard write, allowed ONLY inside the declared merge-
  boundary functions. Per-core shards meet at exactly one place; the rule
  keeps it that way. Deliberate exceptions carry ``# tpr: allow(shard)``.
* ``flight``   — flight-recorder emission sites in the same hot modules
  must use the preallocated event encoder as designed: arguments to
  ``*flight*.emit(...)`` may be names, attributes, numeric constants and
  arithmetic over them — never dict/list/set/tuple displays, f-strings,
  string/bytes constants, comprehensions, or nested CALLS (a ``str()``,
  ``format()``, ``tag_for()`` or even ``len()`` in the argument list is
  per-event work the always-on recorder must not pay; precompute the int
  on a cold path). Deliberate exceptions carry ``# tpr: allow(flight)``.
* ``stage``    — tpurpc-lens (ISSUE 8) attribution plumbing, two halves.
  (a) Frame-marker / hop registrations are STATIC module-level constants:
  ``profiler.register_stages(...)`` and ``lens.hop_counters(...)`` calls
  must sit at module level (the sampler reads the registry lock-free, so
  it must be fully populated at import and never mutate at runtime), with
  ``register_stages`` taking ``__file__``/a string literal plus a dict of
  string constants (literal or a module-level ``_LENS_STAGES`` constant)
  and ``hop_counters`` a declared-hop string literal — no dynamic
  strings. (b) Waterfall hop accounting sites — ``.inc(...)`` on a
  ``_LENS_*``-bound counter — run per batched op on the data plane and
  must use the same pure-int plumbing the ``flight`` rule enforces: names,
  attributes and arithmetic only, no calls/displays/str constants. The
  newer form of a site, ``lens.stage("<hop>", nbytes)`` (ISSUE 26: one
  call does the timing, the bumps and the profiler span), is held to the
  same: the hop a string literal naming a declared hop (``lens.HOPS``),
  every other argument pure-int plumbing.
  Deliberate exceptions carry ``# tpr: allow(stage)``.

* ``kv``       — KV block-alloc pairing (tpurpc-keystone, ISSUE 11): a
  function that calls ``*alloc_blocks*`` / ``*alloc_for_prompt*`` must
  reach a ``*free_blocks*`` / ``*swap_out*`` / ``*quarantine*`` /
  ``*release_kv*`` on an exception path (except/finally) — a raise
  between alloc and ownership hand-off leaks arena blocks (device
  memory) forever. ``# tpr: allow(kv)`` marks same-statement ownership
  transfers.

* ``rawlock``  — factory-made locks (tpurpc-proof, ISSUE 12): in a module
  that imports ``make_lock``/``make_rlock``/``make_condition`` from
  :mod:`tpurpc.analysis.locks`, constructing ``threading.Lock()`` /
  ``threading.RLock()`` / ``threading.Condition()`` directly is a blind
  spot — the raw primitive escapes both ``TPURPC_DEBUG_LOCKS`` lock-order
  checking and the deterministic schedule explorer's factory seam. Route
  it through the factory with a ``Class._attr`` name, or carry
  ``# tpr: allow(rawlock)`` where the raw primitive is the point (the
  checked-lock implementation itself, post-fork singleton rebuilds).

* ``tpr-obs``  — the C emission macro (tpurpc-xray, ISSUE 19): the
  ``flight`` rule's discipline, extended to the native plane's
  ``TPR_OBS(kEv..., tag, a1, a2)`` sites in ``native/src``. Text-based
  (no C AST here): the event code must be a static ``kEv*`` constant,
  the tag a pre-interned variable (``tag_for(...)`` in the argument
  list interns per event — cold-path work on the hot path), arguments
  carry no string/char literals and no function calls (the same
  precompute-the-int contract), and raw ``tpr_obs::emit(...)`` outside
  the plane's own implementation bypasses the macro's ``enabled()``
  guard. Checked by :func:`lint_native_source` /
  :func:`lint_native_tree` (the CLI's default pass includes it);
  deliberate exceptions carry ``// tpr: allow(tpr-obs)``.

* ``diag``     — read-only diagnosis (tpurpc-oracle, ISSUE 20): the
  evidence-rule functions in ``obs/diagnose.py`` (``_collect_*`` /
  ``_score_*``) may only READ the telemetry planes. A counter bump, a
  flight emit, a trip, a capture, or a tag intern from inside a
  diagnosis mutates the very evidence the next diagnosis reads — the
  observer effect as a bug class. Banned callee names inside those
  functions: ``inc``/``dec``/``set``/``observe``/``record``/``emit``/
  ``capture``/``external_trip``/``tag_for``/``sample_once``/``reset``/
  ``clamp``. Deliberate exceptions carry ``# tpr: allow(diag)``.

Suppression grammar: a line comment ``# tpr: allow(<rule>)`` disables that
rule for its line. The hot-path modules are expected to carry NO ``copy``
suppressions — a copy on the data plane is either fixed or it is a finding.
Suppressions are themselves audited (:func:`audit_suppressions`): an
``allow(rule)`` whose rule would NOT fire on that line with suppressions
disabled is stale and reported as a ``suppress`` violation — dead
annotations accrete into camouflage for real ones.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

#: repo-relative suffixes of the modules under the no-copy rules
HOT_COPY_MODULES = (
    os.path.join("tpurpc", "core", "ring.py"),
    os.path.join("tpurpc", "core", "pair.py"),
    os.path.join("tpurpc", "wire", "grpc_h2.py"),
    os.path.join("tpurpc", "jaxshim", "codec.py"),
)

#: repo-relative suffixes of the modules under the guarded-logging rule:
#: the data plane's per-message/per-scan code, where an unguarded
#: log_debug("%s", x) pays its string formatting even with tracing off
HOT_LOG_MODULES = (
    os.path.join("tpurpc", "core", "ring.py"),
    os.path.join("tpurpc", "core", "pair.py"),
    os.path.join("tpurpc", "core", "poller.py"),
    os.path.join("tpurpc", "wire", "grpc_h2.py"),
)

#: modules whose flight-recorder emission sites must stay on the
#: preallocated-encoder discipline (ISSUE 5 — the recorder is ALWAYS on,
#: so any per-event construction here is a permanent hot-path tax).
#: tpurpc-fleet (ISSUE 6) extends the rule to the fleet plumbing: the
#: hedge / drain / admission / subchannel-ejection emission sites in the
#: channel, server, and resolver run per-RPC or per-pick — same
#: discipline, interned tags, pure-int args.
FLIGHT_HOT_MODULES = HOT_LOG_MODULES + (
    os.path.join("tpurpc", "rpc", "channel.py"),
    os.path.join("tpurpc", "rpc", "server.py"),
    os.path.join("tpurpc", "rpc", "resolver.py"),
    # tpurpc-express (ISSUE 9): rendezvous emission sites run per solicited
    # bulk transfer — interned link tags, pure-int args
    os.path.join("tpurpc", "core", "rendezvous.py"),
    # tpurpc-hive (ISSUE 16): the accept path emits ACCEPT_SHED at storm
    # rate — one interned listener tag, two precomputed ints, per shed
    os.path.join("tpurpc", "core", "endpoint.py"),
    # tpurpc-cadence (ISSUE 10): the decode scheduler emits on the step
    # loop — once per device step and at membership edges, but the step
    # cadence can be kHz, so the same discipline applies: interned
    # scheduler tag, precomputed int locals, nothing allocated per emit
    os.path.join("tpurpc", "serving", "scheduler.py"),
    # tpurpc-keystone (ISSUE 11): the KV plane emits at alloc/free/swap/
    # handoff edges — per-sequence, but a preemption storm makes that a
    # high-rate path; same pure-int discipline
    os.path.join("tpurpc", "serving", "kv.py"),
    os.path.join("tpurpc", "serving", "disagg.py"),
    # tpurpc-pulse (ISSUE 13): descriptor-ring emission sites run at
    # adoption/flip/stall edges on the control hot path — same pure-int
    # discipline, interned plane tag
    os.path.join("tpurpc", "core", "ctrlring.py"),
    # tpurpc-argus (ISSUE 14): the tsdb sample tick and the slo evaluator
    # run forever on background cadences, and the bundle/collector planes
    # emit lifecycle events — every flight emission site stays on the
    # interned-tag pure-int discipline (the tsdb sample path itself is
    # additionally alloc-audited by its preallocated-ring design)
    os.path.join("tpurpc", "obs", "tsdb.py"),
    os.path.join("tpurpc", "obs", "slo.py"),
    os.path.join("tpurpc", "obs", "bundle.py"),
    os.path.join("tpurpc", "obs", "collector.py"),
    # tpurpc-oracle (ISSUE 20): the diagnosis engine is read-only by
    # contract (the `diag` rule) — but keeping it under the flight
    # pure-int discipline means any future emission site added here
    # inherits the interned-tag contract instead of silently regressing
    os.path.join("tpurpc", "obs", "diagnose.py"),
)

#: module suffix -> qualified functions on its INLINE DISPATCH path (the
#: reactor invocation from _ServerSink.commit: these run on the connection
#: reader thread, where an unbounded block stalls every stream on the
#: connection — ISSUE 3's no-block-in-dispatch rule). The `block` rule
#: forbids time.sleep and timeout-less .acquire()/.get()/.wait()/.join()
#: inside them; bounded-slice waits (an explicit timeout) pass, and a
#: deliberate exception carries an allow(block) annotation.
INLINE_DISPATCH_PATH: Dict[str, Tuple[str, ...]] = {
    os.path.join("tpurpc", "rpc", "server.py"): (
        "_ServerSink.commit",
        "_ServerStream.commit_message",
        "_ServerStream.commit_external",
        "_ServerStream._acquire_credit",
        "_ServerStream._release_credit",
        "_ServerStream.next_request",
        "_ServerConnection._claim_inline",
        "_ServerConnection._run_inline",
        "_ServerConnection._run_handler",
        "_ServerConnection._run_handler_inner",
        "_ServerConnection._send_trailers",
        "_ServerConnection._finish_stream",
        "_ServerConnection._rdv_deliver",
    ),
    # tpurpc-cadence (ISSUE 10): the decode STEP LOOP is the serving
    # plane's reader-thread analog — every running stream stalls behind
    # it, so it must never hold a timeout-less lock or park unbounded
    # (its idle wait is a bounded condition slice; submit kicks it early)
    os.path.join("tpurpc", "serving", "scheduler.py"): (
        "DecodeScheduler._step_loop",
        "DecodeScheduler._boundary",
        "DecodeScheduler._admit",
        "DecodeScheduler._prefill_batch",
        "DecodeScheduler._run_step",
    ),
    # tpurpc-oracle (ISSUE 20): the diagnosis engine runs inside scrape
    # dispatch, watchdog trip hooks, and the bundle writer — a diagnosis
    # that parks unbounded wedges the very sweep that called it
    os.path.join("tpurpc", "obs", "diagnose.py"): (
        "detect_onset",
        "series_shifts",
        "find_symptom",
        "diagnose",
        "diagnose_doc",
        "_combine",
    ),
}

#: the CROSS-PROCESS modules (ISSUE 17): every wire effect these emit —
#: a framed send, a peer-ring post, a one-sided landing, a wakeup kick —
#: must leave through ``tpurpc.core.transport.dispatch``, the seam the
#: simnet simulator (and any future fault injector) hooks.  A raw
#: primitive called around the seam is an effect message-level
#: exploration can never reorder, drop, or partition — a hole in the
#: checked protocol surface.
XPROC_MODULES = (
    os.path.join("tpurpc", "core", "pair.py"),
    os.path.join("tpurpc", "core", "rendezvous.py"),
    os.path.join("tpurpc", "core", "ctrlring.py"),
    os.path.join("tpurpc", "serving", "disagg.py"),
)

#: send-side raw-primitive name keywords: a ``*_raw`` callee whose name
#: carries one of these is a wire send (``_drain_raw`` and friends are
#: receive-side — local reads of the process's own ring/socket)
_XPROC_SEND_WORDS = ("notify", "send", "frame", "post", "write", "kick")

#: method names whose call on a guarded attribute counts as a mutation
_MUTATORS = frozenset({
    "append", "appendleft", "extend", "insert", "pop", "popleft", "popitem",
    "remove", "clear", "update", "add", "discard", "setdefault", "sort",
})

_ALLOW_RE = re.compile(r"#\s*tpr:\s*allow\(([a-z_,\s]+)\)")

#: every rule an ``allow(...)`` may name (the suppression audit flags
#: unknown names too — a typo'd rule suppresses nothing forever)
KNOWN_RULES = frozenset({
    "lease", "copy", "lock", "wallclock", "block", "log", "shard",
    "flight", "stage", "rdv", "kv", "rawlock", "ringpool", "xproc",
    "diag",
})

#: suppression-audit mode: when True, ``_allowed_rules`` answers empty —
#: the audit re-lints with suppressions void to learn which would fire
_AUDIT_IGNORE_SUPPRESSIONS = False


class LintViolation:
    __slots__ = ("path", "line", "col", "rule", "message")

    def __init__(self, path: str, line: int, col: int, rule: str,
                 message: str):
        self.path = path
        self.line = line
        self.col = col
        self.rule = rule
        self.message = message

    def __repr__(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: [{self.rule}] {self.message}"

    __str__ = __repr__


def _allowed_rules(source_lines: Sequence[str], line: int) -> Set[str]:
    """Rules suppressed on ``line`` (1-based) via ``# tpr: allow(rule)``."""
    if _AUDIT_IGNORE_SUPPRESSIONS:
        return set()
    if 1 <= line <= len(source_lines):
        m = _ALLOW_RE.search(source_lines[line - 1])
        if m:
            return {tok.strip() for tok in m.group(1).split(",")}
    return set()


def _attach_parents(tree: ast.AST) -> None:
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            child._tpr_parent = node  # type: ignore[attr-defined]


def _ancestors(node: ast.AST) -> Iterable[ast.AST]:
    cur = getattr(node, "_tpr_parent", None)
    while cur is not None:
        yield cur
        cur = getattr(cur, "_tpr_parent", None)


def _call_name(node: ast.Call) -> str:
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return ""


def _is_self_attr(node: ast.AST, attr: Optional[str] = None) -> Optional[str]:
    """``self.X`` / ``cls.X`` → ``X`` (optionally requiring ``X == attr``)."""
    if (isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name)
            and node.value.id in ("self", "cls")):
        if attr is None or node.attr == attr:
            return node.attr
    return None


# -- rule: wallclock ---------------------------------------------------------

def _check_wallclock(tree: ast.AST, path: str,
                     lines: Sequence[str]) -> List[LintViolation]:
    out = []
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
                and node.func.attr == "time"
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == "time"):
            if "wallclock" in _allowed_rules(lines, node.lineno):
                continue
            out.append(LintViolation(
                path, node.lineno, node.col_offset, "wallclock",
                "time.time() is not monotonic: use time.monotonic() for "
                "durations/intervals, or annotate a genuinely absolute "
                "timestamp with '# tpr: allow(wallclock)'"))
    return out


# -- rule: copy --------------------------------------------------------------

def _check_copy(tree: ast.AST, path: str,
                lines: Sequence[str]) -> List[LintViolation]:
    out = []
    for node in ast.walk(tree):
        viol = None
        if isinstance(node, ast.Call):
            f = node.func
            if (isinstance(f, ast.Attribute) and f.attr == "join"
                    and isinstance(f.value, ast.Constant)
                    and isinstance(f.value.value, bytes)):
                viol = ("b\"\".join() gathers with a hidden whole-payload "
                        "copy: encode into a preallocated buffer or pass the "
                        "segment list through (gather writes)")
            elif (isinstance(f, ast.Attribute)
                  and f.attr == "from_buffer_copy"):
                viol = ("from_buffer_copy duplicates the payload: use "
                        "from_buffer / a memoryview over the source")
            elif (isinstance(f, ast.Name) and f.id in ("bytes", "bytearray")
                  and len(node.args) == 1
                  and isinstance(node.args[0], ast.Subscript)
                  and isinstance(node.args[0].slice, ast.Slice)):
                viol = (f"{f.id}(x[a:b]) double-copies when x is "
                        "bytes/bytearray: slice a memoryview (zero-copy) "
                        "and .tobytes() if you truly need to materialize")
        if viol is None:
            continue
        if "copy" in _allowed_rules(lines, node.lineno):
            continue
        out.append(LintViolation(path, node.lineno, node.col_offset,
                                 "copy", viol))
    return out


# -- rule: block -------------------------------------------------------------

def _block_violation(node: ast.Call) -> Optional[str]:
    """Why this call is an unbounded block, or None."""
    f = node.func
    if not isinstance(f, ast.Attribute):
        return None
    kw = {k.arg for k in node.keywords}
    if (f.attr == "sleep" and isinstance(f.value, ast.Name)
            and f.value.id == "time"):
        return "time.sleep() parks the reader thread"
    if f.attr == "acquire" and not node.args and not (
            kw & {"timeout", "blocking"}):
        return ".acquire() with no timeout can park forever"
    if f.attr == "get" and not node.args and "timeout" not in kw:
        return ".get() with no timeout can park forever"
    if f.attr == "wait" and not node.args and "timeout" not in kw:
        return ".wait() with no timeout can park forever"
    if f.attr == "join" and not node.args and "timeout" not in kw:
        return ".join() with no timeout can park forever"
    return None


def _check_block(tree: ast.AST, path: str, lines: Sequence[str],
                 functions: "frozenset[str]") -> List[LintViolation]:
    """Forbid unbounded blocking calls inside the named functions (the
    inline-dispatch path: they run on the connection reader thread)."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        parent = getattr(node, "_tpr_parent", None)
        qual = (f"{parent.name}.{node.name}"
                if isinstance(parent, ast.ClassDef) else node.name)
        if qual not in functions:
            continue
        for call in ast.walk(node):
            if not isinstance(call, ast.Call):
                continue
            why = _block_violation(call)
            if why is None:
                continue
            if "block" in _allowed_rules(lines, call.lineno):
                continue
            out.append(LintViolation(
                path, call.lineno, call.col_offset, "block",
                f"{qual} is on the inline dispatch path (runs on the "
                f"connection reader thread) and {why}: every stream on the "
                "connection stalls behind it — bound the wait with a "
                "timeout or move the work to the pool; a deliberate "
                "exception carries '# tpr: allow(block)'"))
    return out


# -- rule: log ---------------------------------------------------------------

_HOT_LOG_CALLS = frozenset({"log_debug", "log_info"})


def _is_flag_guard(test: ast.AST) -> bool:
    """Does this ``if`` test reference a TraceFlag? Convention-based: a
    name/attribute starting with ``trace_`` (every flag instance in the
    tree), a bare ``flag``/``*_flag`` binding, or an ``.enabled`` read."""
    for node in ast.walk(test):
        if isinstance(node, ast.Name) and (
                node.id.startswith("trace_") or node.id == "flag"
                or node.id.endswith("_flag")):
            return True
        if isinstance(node, ast.Attribute) and (
                node.attr.startswith("trace_") or node.attr == "enabled"
                or node.attr.endswith("_flag")):
            return True
    return False


def _check_log(tree: ast.AST, path: str,
               lines: Sequence[str]) -> List[LintViolation]:
    """Guarded logging on the hot paths: ``log_debug``/``log_info`` must
    sit inside ``if <TraceFlag>:`` (or use ``flag.log(...)``, which never
    matches here — ``.log`` is a method name, not these functions)."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        name = (f.id if isinstance(f, ast.Name)
                else f.attr if isinstance(f, ast.Attribute) else "")
        if name not in _HOT_LOG_CALLS:
            continue
        if any(isinstance(anc, ast.If) and _is_flag_guard(anc.test)
               for anc in _ancestors(node)):
            continue
        if "log" in _allowed_rules(lines, node.lineno):
            continue
        out.append(LintViolation(
            path, node.lineno, node.col_offset, "log",
            f"{name}() on a hot-path module without a TraceFlag guard: "
            "its string formatting runs even with tracing off — use "
            "flag.log(...) or wrap in 'if <trace_flag>:'; a deliberate "
            "exception carries '# tpr: allow(log)'"))
    return out


# -- rule: flight -------------------------------------------------------------

#: node types allowed inside a flight-emit argument: plain value reads and
#: integer arithmetic over them — nothing that allocates or calls
_FLIGHT_BANNED = (ast.Dict, ast.Set, ast.List, ast.Tuple, ast.JoinedStr,
                  ast.FormattedValue, ast.Call, ast.ListComp, ast.SetComp,
                  ast.DictComp, ast.GeneratorExp, ast.Lambda, ast.Starred)


def _is_flight_emit(node: ast.Call) -> bool:
    f = node.func
    if isinstance(f, ast.Attribute) and f.attr == "emit":
        base = f.value
        if isinstance(base, ast.Name) and "flight" in base.id.lower():
            return True
        if isinstance(base, ast.Attribute) and "flight" in base.attr.lower():
            return True  # e.g. flight.RECORDER.emit — RECORDER's owner
        # RECORDER.emit / self._recorder.emit shapes
        if isinstance(base, ast.Name) and "recorder" in base.id.lower():
            return True
        if (isinstance(base, ast.Attribute)
                and "recorder" in base.attr.lower()):
            return True
    if isinstance(f, ast.Name) and "flight_emit" in f.id:
        return True
    return False


def _flight_arg_violation(arg: ast.AST) -> Optional[str]:
    for node in ast.walk(arg):
        if isinstance(node, _FLIGHT_BANNED):
            return (f"builds a {type(node).__name__} per event")
        if isinstance(node, ast.Constant) and isinstance(
                node.value, (str, bytes)):
            return "passes a str/bytes constant (events carry ints; "\
                   "intern strings once with tag_for on a cold path)"
    return None


def _check_flight(tree: ast.AST, path: str,
                  lines: Sequence[str]) -> List[LintViolation]:
    """Flight-recorder emission sites must be pure int plumbing: the
    recorder is ALWAYS on, so allocation/calls in an emit argument are a
    permanent per-event cost the preallocated encoder exists to avoid."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not _is_flight_emit(node):
            continue
        if "flight" in _allowed_rules(lines, node.lineno):
            continue
        args = list(node.args) + [k.value for k in node.keywords]
        for arg in args:
            why = _flight_arg_violation(arg)
            if why is None:
                continue
            out.append(LintViolation(
                path, node.lineno, node.col_offset, "flight",
                f"flight emit argument {why}: the always-on recorder's "
                "hot path must stay on the preallocated encoder — "
                "precompute ints (tag_for at connect time, lengths on the "
                "cold path); a deliberate exception carries "
                "'# tpr: allow(flight)'"))
            break
    return out


# -- rule: stage -------------------------------------------------------------

def _module_consts(tree: ast.AST) -> Dict[str, ast.AST]:
    """Top-level ``NAME = <expr>`` bindings (the constants registrations
    may reference)."""
    out: Dict[str, ast.AST] = {}
    for stmt in getattr(tree, "body", ()):
        if (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                and isinstance(stmt.targets[0], ast.Name)):
            out[stmt.targets[0].id] = stmt.value
    return out


def _static_str_dict(node: Optional[ast.AST],
                     consts: Dict[str, ast.AST]) -> bool:
    """Is ``node`` a dict of string constants — directly or via a
    module-level constant Name?"""
    if isinstance(node, ast.Name):
        node = consts.get(node.id)
    if not isinstance(node, ast.Dict):
        return False
    for k, v in zip(node.keys, node.values):
        if not (isinstance(k, ast.Constant) and isinstance(k.value, str)):
            return False
        if not (isinstance(v, ast.Constant) and isinstance(v.value, str)):
            return False
    return True


def _is_lens_stage(node: ast.Call) -> bool:
    """``lens.stage(...)`` / ``_lens.stage(...)``, or a bare ``stage(...)``
    (inside ``obs/lens.py`` itself)."""
    f = node.func
    if isinstance(f, ast.Name):
        return True
    return (isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name)
            and f.value.id.lstrip("_") == "lens")


def _declared_hops() -> Tuple[str, ...]:
    from tpurpc.obs import lens

    return lens.HOP_NAMES


def _check_stage(tree: ast.AST, path: str,
                 lines: Sequence[str]) -> List[LintViolation]:
    """tpurpc-lens (ISSUE 8): static stage/hop registrations + pure-int
    hop accounting. See the module docstring's ``stage`` entry."""
    out = []
    consts = _module_consts(tree)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node)
        if name == "register_stages":
            if "stage" in _allowed_rules(lines, node.lineno):
                continue
            if _enclosing_fn(node) is not None:
                out.append(LintViolation(
                    path, node.lineno, node.col_offset, "stage",
                    "register_stages inside a function: frame-marker "
                    "registrations must be module-level (the sampler reads "
                    "the registry lock-free — populate it at import, never "
                    "at runtime); a deliberate exception carries "
                    "'# tpr: allow(stage)'"))
                continue
            args = list(node.args)
            a0_ok = len(args) >= 1 and (
                (isinstance(args[0], ast.Name) and args[0].id == "__file__")
                or (isinstance(args[0], ast.Constant)
                    and isinstance(args[0].value, str)))
            a1_ok = len(args) >= 2 and _static_str_dict(args[1], consts)
            if not (a0_ok and a1_ok):
                out.append(LintViolation(
                    path, node.lineno, node.col_offset, "stage",
                    "register_stages arguments must be static: __file__ "
                    "(or a string literal) plus a dict of string constants "
                    "— a module-level _LENS_STAGES constant or a literal; "
                    "dynamic strings make the frame registry unauditable; "
                    "a deliberate exception carries '# tpr: allow(stage)'"))
        elif name == "hop_counters":
            if "stage" in _allowed_rules(lines, node.lineno):
                continue
            bad = _enclosing_fn(node) is not None
            bad = bad or not (node.args
                              and isinstance(node.args[0], ast.Constant)
                              and isinstance(node.args[0].value, str))
            if bad:
                out.append(LintViolation(
                    path, node.lineno, node.col_offset, "stage",
                    "hop_counters must bind a declared hop at module level "
                    "with a string-literal hop name (the cached-counter "
                    "contract: sites pay only the bump); a deliberate "
                    "exception carries '# tpr: allow(stage)'"))
        elif name == "stage" and _is_lens_stage(node):
            if "stage" in _allowed_rules(lines, node.lineno):
                continue
            hop = node.args[0] if node.args else None
            if not (isinstance(hop, ast.Constant)
                    and hop.value in _declared_hops()):
                out.append(LintViolation(
                    path, node.lineno, node.col_offset, "stage",
                    "lens.stage must name a declared hop (lens.HOPS) with "
                    "a string literal: a dynamic or misspelt hop is a "
                    "KeyError on the data plane and a counter nobody "
                    "reads; a deliberate exception carries "
                    "'# tpr: allow(stage)'"))
                continue
            for arg in node.args[1:] + [k.value for k in node.keywords]:
                why = _flight_arg_violation(arg)
                if why is None:
                    continue
                out.append(LintViolation(
                    path, node.lineno, node.col_offset, "stage",
                    f"lens.stage argument {why}: a stage runs per batched "
                    "op on the data plane — precompute the int (the flight "
                    "rule's contract); a deliberate exception carries "
                    "'# tpr: allow(stage)'"))
                break
        elif name == "inc":
            f = node.func
            if not (isinstance(f, ast.Attribute)
                    and isinstance(f.value, ast.Name)
                    and f.value.id.startswith("_LENS_")):
                continue
            if "stage" in _allowed_rules(lines, node.lineno):
                continue
            for arg in list(node.args) + [k.value for k in node.keywords]:
                why = _flight_arg_violation(arg)
                if why is None:
                    continue
                out.append(LintViolation(
                    path, node.lineno, node.col_offset, "stage",
                    f"waterfall hop accounting argument {why}: hop "
                    "counters bump per batched op on the data plane — "
                    "precompute the int (the flight rule's contract); a "
                    "deliberate exception carries '# tpr: allow(stage)'"))
                break
    return out


# -- rule: lock --------------------------------------------------------------

def _guarded_by_decl(cls: ast.ClassDef) -> Dict[str, Tuple[str, ...]]:
    """Parse a class-level ``_GUARDED_BY = {"attr": "_lock" | ("_a","_b") |
    None}``. ``None`` (no lock: atomic steps only) parses to ``()``."""
    for stmt in cls.body:
        if (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                and isinstance(stmt.targets[0], ast.Name)
                and stmt.targets[0].id == "_GUARDED_BY"
                and isinstance(stmt.value, ast.Dict)):
            decl: Dict[str, Tuple[str, ...]] = {}
            for k, v in zip(stmt.value.keys, stmt.value.values):
                if not (isinstance(k, ast.Constant) and isinstance(k.value, str)):
                    continue
                if isinstance(v, ast.Constant) and isinstance(v.value, str):
                    decl[k.value] = (v.value,)
                elif isinstance(v, ast.Constant) and v.value is None:
                    decl[k.value] = ()
                elif isinstance(v, (ast.Tuple, ast.List)):
                    locks = tuple(e.value for e in v.elts
                                  if isinstance(e, ast.Constant)
                                  and isinstance(e.value, str))
                    if locks:
                        decl[k.value] = locks
            return decl
    return {}


def _with_holds(node: ast.AST, locks: Tuple[str, ...]) -> bool:
    """Is ``node`` lexically inside ``with self.<lock>:`` for a lock in
    ``locks``? (``with self._cv`` counts for the condition's own lock.)"""
    for anc in _ancestors(node):
        if isinstance(anc, ast.With):
            for item in anc.items:
                expr = item.context_expr
                # with self._lock: / with self._lock.something(): not counted
                name = _is_self_attr(expr)
                if name is None and isinstance(expr, ast.Call):
                    # e.g. `with self._lock_for(x):` — not a declared guard
                    continue
                if name in locks:
                    return True
    return False


def _mutation_target(node: ast.AST) -> Optional[ast.AST]:
    """The ``self.attr`` expression this statement mutates, if any."""
    if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
        targets = (node.targets if isinstance(node, ast.Assign)
                   else [node.target])
        for t in targets:
            if isinstance(t, ast.Tuple):
                for e in t.elts:
                    got = _mutation_target_expr(e)
                    if got is not None:
                        return got
            got = _mutation_target_expr(t)
            if got is not None:
                return got
    elif isinstance(node, ast.Delete):
        for t in node.targets:
            got = _mutation_target_expr(t)
            if got is not None:
                return got
    elif isinstance(node, ast.Call):
        f = node.func
        if (isinstance(f, ast.Attribute) and f.attr in _MUTATORS
                and _is_self_attr(f.value) is not None):
            return f.value
    return None


def _mutation_target_expr(t: ast.AST) -> Optional[ast.AST]:
    # self.attr = ... / self.attr[...] = ... / self.attr[:] = ...
    if _is_self_attr(t) is not None:
        return t
    if isinstance(t, ast.Subscript) and _is_self_attr(t.value) is not None:
        return t.value
    return None


def _check_locks(tree: ast.AST, path: str,
                 lines: Sequence[str]) -> List[LintViolation]:
    out = []
    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        decl = _guarded_by_decl(cls)
        if not decl:
            continue
        for fn in cls.body:
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if fn.name == "__init__":
                continue  # construction happens-before sharing
            for node in ast.walk(fn):
                tgt = _mutation_target(node)
                if tgt is None:
                    continue
                attr = _is_self_attr(tgt)
                if attr not in decl:
                    continue
                if "lock" in _allowed_rules(lines, node.lineno):
                    continue
                if not decl[attr]:
                    if not _atomic_step(node):
                        out.append(LintViolation(
                            path, node.lineno, node.col_offset, "lock",
                            f"{cls.name}.{attr} is declared lock-free "
                            "(_GUARDED_BY None) but is mutated by a "
                            f"read-modify-write (in {fn.name}): only a "
                            "mutator call or a plain store of a constant "
                            "or a local is one step"))
                    continue
                if _with_holds(node, decl[attr]):
                    continue
                out.append(LintViolation(
                    path, node.lineno, node.col_offset, "lock",
                    f"{cls.name}.{attr} is declared guarded by "
                    f"{'/'.join(decl[attr])} but is mutated outside "
                    f"'with self.{decl[attr][0]}:' (in {fn.name})"))
    return out


def _atomic_step(node: ast.AST) -> bool:
    """Is this mutation of a lock-free attribute one step under the
    interpreter? A mutator call is (``self.q.append(x)``); so is
    ``self.flag = <constant or local name>``. ``+=``, ``self.q[i] = x`` and
    ``self.q = self.q[n:]`` read, compute and write."""
    if isinstance(node, ast.Call):
        return True
    return (isinstance(node, ast.Assign) and len(node.targets) == 1
            and _is_self_attr(node.targets[0]) is not None
            and isinstance(node.value, (ast.Constant, ast.Name)))


# -- rule: shard -------------------------------------------------------------

def _merge_boundary_decl(cls: ast.ClassDef) -> Optional[Tuple[str, ...]]:
    """Parse a class-level ``_MERGE_BOUNDARY = ("fn", ...)`` declaration."""
    for stmt in cls.body:
        if (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                and isinstance(stmt.targets[0], ast.Name)
                and stmt.targets[0].id == "_MERGE_BOUNDARY"
                and isinstance(stmt.value, (ast.Tuple, ast.List))):
            return tuple(e.value for e in stmt.value.elts
                         if isinstance(e, ast.Constant)
                         and isinstance(e.value, str))
    return None


def _attr_mutation_target(node: ast.AST) -> Optional[ast.Attribute]:
    """The ``<expr>.attr`` an Assign/AugAssign/Delete/mutator-call mutates,
    for ANY base expression (the cross-instance analog of
    :func:`_mutation_target`, which only matches ``self``)."""
    def as_attr(t: ast.AST) -> Optional[ast.Attribute]:
        if isinstance(t, ast.Attribute):
            return t
        if isinstance(t, ast.Subscript) and isinstance(t.value, ast.Attribute):
            return t.value
        return None

    if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
        targets = (node.targets if isinstance(node, ast.Assign)
                   else [node.target])
        for t in targets:
            if isinstance(t, ast.Tuple):
                for e in t.elts:
                    got = as_attr(e)
                    if got is not None:
                        return got
            got = as_attr(t)
            if got is not None:
                return got
    elif isinstance(node, ast.Delete):
        for t in node.targets:
            got = as_attr(t)
            if got is not None:
                return got
    elif isinstance(node, ast.Call):
        f = node.func
        if (isinstance(f, ast.Attribute) and f.attr in _MUTATORS
                and isinstance(f.value, ast.Attribute)):
            return f.value
    return None


def _check_shard(tree: ast.AST, path: str,
                 lines: Sequence[str]) -> List[LintViolation]:
    """tpurpc-manycore (ISSUE 7): shard-confinement of guarded state.

    Armed only in modules where some class declares ``_MERGE_BOUNDARY =
    ("fn", ...)`` — a shard/merger module. There, any attribute listed in
    ANY class's ``_GUARDED_BY`` is shard-local state: mutating it through a
    base other than ``self`` (``other_shard._queue.append``,
    ``sub.out = ...``) is a cross-shard mutation, legal ONLY inside a
    function named in a ``_MERGE_BOUNDARY`` — the single place shards are
    allowed to meet. Everything else is the hot path, where cross-shard
    writes are exactly the coupling the per-core design forbids.
    Deliberate exceptions carry ``# tpr: allow(shard)``."""
    boundary: Set[str] = set()
    guarded: Dict[str, str] = {}  # attr -> declaring class (for the message)
    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        mb = _merge_boundary_decl(cls)
        if mb is not None:
            boundary.update(mb)
        for attr in _guarded_by_decl(cls):
            guarded.setdefault(attr, cls.name)
    if not boundary or not guarded:
        return []
    out = []
    for node in ast.walk(tree):
        tgt = _attr_mutation_target(node)
        if tgt is None or tgt.attr not in guarded:
            continue
        if isinstance(tgt.value, ast.Name) and tgt.value.id in ("self", "cls"):
            continue  # shard-local mutation: the lock map's jurisdiction
        fn = _enclosing_fn(node)
        if fn is not None and getattr(fn, "name", None) in boundary:
            continue
        if "shard" in _allowed_rules(lines, node.lineno):
            continue
        out.append(LintViolation(
            path, node.lineno, node.col_offset, "shard",
            f"cross-shard mutation of {guarded[tgt.attr]}.{tgt.attr} "
            f"(guarded shard-local state) outside the merge boundary "
            f"{sorted(boundary)} — shards may only meet at the declared "
            "boundary; a deliberate exception carries '# tpr: allow(shard)'"))
    return out


# -- rule: rawlock -----------------------------------------------------------

_LOCK_FACTORIES = frozenset({"make_lock", "make_rlock", "make_condition"})
_RAW_PRIMITIVES = frozenset({"Lock", "RLock", "Condition"})


def _imports_lock_factory(tree: ast.AST) -> bool:
    """Does this module import any lock factory from analysis.locks?"""
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if not mod.endswith("locks"):
                continue
            if any(alias.name in _LOCK_FACTORIES for alias in node.names):
                return True
    return False


def _check_rawlock(tree: ast.AST, path: str,
                   lines: Sequence[str]) -> List[LintViolation]:
    """tpurpc-proof (ISSUE 12): in a module that already imports the lock
    factory, a raw ``threading.Lock()``/``RLock()``/``Condition()`` is a
    verification blind spot — it dodges TPURPC_DEBUG_LOCKS *and* the
    schedule explorer's factory seam. The decode loop ran unwatched for
    two PRs exactly this way."""
    if not _imports_lock_factory(tree):
        return []
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if not (isinstance(f, ast.Attribute) and f.attr in _RAW_PRIMITIVES
                and isinstance(f.value, ast.Name)
                and f.value.id == "threading"):
            continue
        if "rawlock" in _allowed_rules(lines, node.lineno):
            continue
        factory = {"Lock": "make_lock", "RLock": "make_rlock",
                   "Condition": "make_condition"}[f.attr]
        out.append(LintViolation(
            path, node.lineno, node.col_offset, "rawlock",
            f"raw threading.{f.attr}() in a module that imports the lock "
            f"factory: TPURPC_DEBUG_LOCKS and the schedule explorer never "
            f"see it — use {factory}(\"Class._attr\"); a deliberate "
            "exception carries '# tpr: allow(rawlock)'"))
    return out


# -- the suppression audit ----------------------------------------------------

def find_suppressions(source: str) -> List[Tuple[int, str]]:
    """Every ``(line, rule)`` named by a real ``# tpr: allow(...)``
    COMMENT. Tokenized, not regexed over raw lines: docstrings and error
    messages QUOTE the grammar constantly, and quoting a suppression is
    not writing one."""
    import io
    import tokenize

    out: List[Tuple[int, str]] = []
    try:
        tokens = list(tokenize.generate_tokens(
            io.StringIO(source).readline))
    except (tokenize.TokenError, SyntaxError, IndentationError):
        return out
    for tok in tokens:
        if tok.type != tokenize.COMMENT:
            continue
        m = _ALLOW_RE.search(tok.string)
        if m:
            for name in m.group(1).split(","):
                name = name.strip()
                if name:
                    out.append((tok.start[0], name))
    return out


def audit_suppressions_source(source: str, path: str) -> List[LintViolation]:
    """Report stale suppressions in one module: re-lint with every
    suppression void, then flag any ``allow(rule)`` whose rule did not
    fire on that line (plus unknown rule names — a typo suppresses
    nothing forever). Stale suppressions are gate failures: they read as
    "this line is a known exception" when nothing is excepted."""
    sups = find_suppressions(source)
    if not sups:
        return []
    global _AUDIT_IGNORE_SUPPRESSIONS
    _AUDIT_IGNORE_SUPPRESSIONS = True
    try:
        fired = lint_source(source, path)
    finally:
        _AUDIT_IGNORE_SUPPRESSIONS = False
    fired_at = {(v.line, v.rule) for v in fired}
    out: List[LintViolation] = []
    for line, rule in sups:
        if rule not in KNOWN_RULES:
            out.append(LintViolation(
                path, line, 0, "suppress",
                f"suppression names unknown rule '{rule}' "
                f"(known: {', '.join(sorted(KNOWN_RULES))})"))
        elif (line, rule) not in fired_at:
            out.append(LintViolation(
                path, line, 0, "suppress",
                f"stale suppression: rule '{rule}' would not fire on this "
                "line — delete the annotation (dead allows accrete into "
                "camouflage for live ones)"))
    return out


def audit_suppressions(paths: Iterable[str]) -> List[LintViolation]:
    out: List[LintViolation] = []
    for p in paths:
        with open(p, "r", encoding="utf-8") as f:
            out.extend(audit_suppressions_source(f.read(), p))
    return out


def audit_suppressions_tree(root: Optional[str] = None) -> List[LintViolation]:
    return audit_suppressions(_tree_paths(root))


# -- rule: lease -------------------------------------------------------------

def _calls_matching(node: ast.AST, needle: str) -> List[ast.Call]:
    return [n for n in ast.walk(node)
            if isinstance(n, ast.Call) and needle in _call_name(n)]


def _try_aborts(try_node: ast.Try) -> bool:
    """Does this Try call ``*send_abort*`` in a handler or finally?"""
    for h in try_node.handlers:
        for stmt in h.body:
            if _calls_matching(stmt, "send_abort"):
                return True
    for stmt in try_node.finalbody:
        if _calls_matching(stmt, "send_abort"):
            return True
    return False


def _enclosing_stmt(node: ast.AST, block: List[ast.stmt]) -> Optional[ast.stmt]:
    """The statement of ``block`` that (transitively) contains ``node``."""
    chain = [node] + list(_ancestors(node))
    for stmt in block:
        if stmt in chain:
            return stmt
    return None


def _check_lease(tree: ast.AST, path: str,
                 lines: Sequence[str]) -> List[LintViolation]:
    out = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        reserves = [c for c in _calls_matching(fn, "send_reserve")
                    if _enclosing_fn(c) is fn]
        if not reserves:
            continue
        if any("lease" in _allowed_rules(lines, r.lineno) for r in reserves):
            continue
        commits = [c for c in _calls_matching(fn, "send_commit")
                   if _enclosing_fn(c) is fn]
        aborts = [c for c in _calls_matching(fn, "send_abort")
                  if _enclosing_fn(c) is fn]
        rl = reserves[0].lineno
        if not commits:
            out.append(LintViolation(
                path, rl, reserves[0].col_offset, "lease",
                f"{fn.name} reserves a send lease but never commits it: a "
                "reserved-and-dropped lease wedges the ring write lock"))
            continue
        covered_aborts = [
            a for a in aborts
            if any(isinstance(anc, (ast.ExceptHandler,)) for anc in
                   _ancestors(a))
            or any(isinstance(anc, ast.Try) and a in
                   [d for s in anc.finalbody for d in ast.walk(s)]
                   for anc in _ancestors(a))]
        if not covered_aborts:
            out.append(LintViolation(
                path, rl, reserves[0].col_offset, "lease",
                f"{fn.name} reserves a send lease with no send_abort on any "
                "exception path (except/finally): a raise between reserve "
                "and commit leaks the lease"))
            continue
        out.extend(_check_lease_region(fn, reserves, commits, path))
    return out


def _enclosing_fn(node: ast.AST) -> Optional[ast.AST]:
    for anc in _ancestors(node):
        if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Lambda)):
            return anc
    return None


def _check_lease_region(fn, reserves, commits, path) -> List[LintViolation]:
    """Fill code strictly between reserve and commit (same statement block)
    must sit inside a Try whose handler/finally aborts — an exception raised
    while filling the reserved span must release the lease."""
    out = []
    for res in reserves:
        # locate the common block holding both the reserve and a commit
        for anc in [res] + list(_ancestors(res)):
            parent = getattr(anc, "_tpr_parent", None)
            if parent is None:
                break
            for field in ("body", "orelse", "finalbody"):
                block = getattr(parent, field, None)
                if not (isinstance(block, list) and anc in block):
                    continue
                commit_stmts = [s for c in commits
                                for s in [_enclosing_stmt(c, block)]
                                if s is not None]
                if not commit_stmts:
                    continue
                ri = block.index(anc)
                ci = max(block.index(s) for s in commit_stmts)
                for between in block[ri + 1:ci]:
                    ok = (isinstance(between, ast.Try)
                          and _try_aborts(between))
                    ok = ok or isinstance(between, (ast.Pass, ast.Continue,
                                                    ast.Break))
                    if not ok:
                        out.append(LintViolation(
                            path, between.lineno, between.col_offset,
                            "lease",
                            f"{fn.name}: statement between send_reserve and "
                            "send_commit is not covered by a "
                            "try/except-abort — an exception here leaks the "
                            "lease"))
                return out
    return out


# -- rule: rdv (rendezvous claim pairing, tpurpc-express ISSUE 9) -------------

def _check_rdv(tree: ast.AST, path: str,
               lines: Sequence[str]) -> List[LintViolation]:
    """A function that obtains a rendezvous region claim (``*rdv_claim*``)
    must send ``*rdv_complete*`` on the success path AND cover an exception
    path (except/finally) with ``*rdv_release*`` — a claimed-and-dropped
    region pins the peer's landing pool until the connection dies (the
    lease-pairing rule's shape, lifted to the bulk-transfer plane).
    Suppression: ``# tpr: allow(rdv)`` on the claim line."""
    out = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        claims = [c for c in _calls_matching(fn, "rdv_claim")
                  if _enclosing_fn(c) is fn]
        if not claims:
            continue
        if any("rdv" in _allowed_rules(lines, c.lineno) for c in claims):
            continue
        completes = [c for c in _calls_matching(fn, "rdv_complete")
                     if _enclosing_fn(c) is fn]
        releases = [c for c in _calls_matching(fn, "rdv_release")
                    if _enclosing_fn(c) is fn]
        cl = claims[0].lineno
        if not completes:
            out.append(LintViolation(
                path, cl, claims[0].col_offset, "rdv",
                f"{fn.name} claims a rendezvous region but never "
                "completes it: the peer's landing region stays claimed "
                "until the connection dies"))
            continue
        covered = [
            r for r in releases
            if any(isinstance(anc, ast.ExceptHandler)
                   for anc in _ancestors(r))
            or any(isinstance(anc, ast.Try) and r in
                   [d for s in anc.finalbody for d in ast.walk(s)]
                   for anc in _ancestors(r))]
        if not covered:
            out.append(LintViolation(
                path, cl, claims[0].col_offset, "rdv",
                f"{fn.name} claims a rendezvous region with no "
                "rdv_release on any exception path (except/finally): a "
                "raise between claim and complete leaks the claim"))
    return out


# -- rule: kv (block-alloc pairing, tpurpc-keystone ISSUE 11) -----------------

#: call-name fragments that RELEASE kv blocks for the `kv` rule
_KV_RELEASERS = ("free_blocks", "swap_out", "quarantine", "release_kv")


def _check_kv(tree: ast.AST, path: str,
              lines: Sequence[str]) -> List[LintViolation]:
    """A function that allocates KV blocks (``*alloc_blocks*`` /
    ``*alloc_for_prompt*``) must cover an exception path (except/finally)
    with a release — ``*free_blocks*`` / ``*swap_out*`` /
    ``*quarantine*`` / ``*release_kv*`` — or the blocks leak out of the
    arena's accounting forever (the rdv/lease pairing rule, lifted to the
    KV plane, where the leak is device memory). Ownership-transfer sites
    (the table adopts the blocks in the same statement) carry
    ``# tpr: allow(kv)`` on the alloc line."""
    out = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        allocs = [c for c in (_calls_matching(fn, "alloc_blocks")
                              + _calls_matching(fn, "alloc_for_prompt"))
                  if _enclosing_fn(c) is fn]
        if not allocs:
            continue
        if any("kv" in _allowed_rules(lines, c.lineno) for c in allocs):
            continue
        releases = [c for frag in _KV_RELEASERS
                    for c in _calls_matching(fn, frag)
                    if _enclosing_fn(c) is fn]
        covered = [
            r for r in releases
            if any(isinstance(anc, ast.ExceptHandler)
                   for anc in _ancestors(r))
            or any(isinstance(anc, ast.Try) and r in
                   [d for s in anc.finalbody for d in ast.walk(s)]
                   for anc in _ancestors(r))]
        if not covered:
            al = allocs[0].lineno
            out.append(LintViolation(
                path, al, allocs[0].col_offset, "kv",
                f"{fn.name} allocates KV blocks with no free/swap/"
                "quarantine on any exception path (except/finally): a "
                "raise between alloc and ownership hand-off leaks arena "
                "blocks forever"))
    return out


# -- rule: ringpool (shared ring-pool lease pairing, tpurpc-hive ISSUE 16) ----

def _pool_calls(fn: ast.AST, attr: str) -> List[ast.Call]:
    """Calls ``<something-pool>.<attr>(...)`` — the receiver's source text
    must mention "pool" (``pool.lease``, ``self._pool.release``,
    ``RingPool.get().lease``), which keeps the rule off the unrelated
    ``lease``/``release`` vocabularies (KV leases, RegionLease, reader
    release)."""
    out = []
    for n in ast.walk(fn):
        if (isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
                and n.func.attr == attr):
            try:
                base = ast.unparse(n.func.value)
            except Exception:
                base = ""
            if "pool" in base.lower():
                out.append(n)
    return out


def _check_ringpool(tree: ast.AST, path: str,
                    lines: Sequence[str]) -> List[LintViolation]:
    """A function that leases from a shared ring pool (``pool.lease``)
    must cover an exception path (except/finally) with ``pool.release``
    — a leased-and-dropped region strands bytes in the pool's ``leased``
    accounting forever and, worse, the region itself is gone (the
    kv/rdv pairing rule, lifted to the C100K ring plane where the leak
    is the pool the whole fleet parks into). Ownership-transfer sites
    (the pair adopts the regions in the same lock scope and its
    ``_release_regions`` owns the return path) carry
    ``# tpr: allow(ringpool)`` on the lease line."""
    out = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        leases = [c for c in _pool_calls(fn, "lease")
                  if _enclosing_fn(c) is fn]
        if not leases:
            continue
        if any("ringpool" in _allowed_rules(lines, c.lineno)
               for c in leases):
            continue
        # both return idioms pair a pool lease: RingPool's
        # ``pool.release(region)`` and the landing plane's
        # ``lease.release()`` (RegionLease returns itself to its pool)
        releases = [c for c in _pool_calls(fn, "release")
                    if _enclosing_fn(c) is fn]
        for n in ast.walk(fn):
            if (isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
                    and n.func.attr == "release"
                    and _enclosing_fn(n) is fn):
                try:
                    base = ast.unparse(n.func.value)
                except Exception:
                    base = ""
                if "lease" in base.lower():
                    releases.append(n)
        covered = [
            r for r in releases
            if any(isinstance(anc, ast.ExceptHandler)
                   for anc in _ancestors(r))
            or any(isinstance(anc, ast.Try) and r in
                   [d for s in anc.finalbody for d in ast.walk(s)]
                   for anc in _ancestors(r))]
        if not covered:
            ln = leases[0].lineno
            out.append(LintViolation(
                path, ln, leases[0].col_offset, "ringpool",
                f"{fn.name} leases from a ring pool with no pool.release "
                "on any exception path (except/finally): a raise between "
                "lease and adoption strands the region and its "
                "leased-bytes accounting forever"))
    return out


def _xproc_raw_send(call: ast.Call) -> Optional[str]:
    """The raw-send tag of ``call`` if it is a cross-process wire effect
    invoked directly, else None.  Three shapes count: a send-side
    ``*_raw`` primitive (the designated dispatch target of a seam
    wrapper), the rendezvous ``_place`` landing closure, and a peer-ring
    window post (``tx.post(...)`` — the receiver lives in the OTHER
    process's mapped ring)."""
    func = call.func
    if isinstance(func, ast.Attribute):
        name = func.attr
    elif isinstance(func, ast.Name):
        name = func.id
    else:
        return None
    if name.endswith("_raw") and any(w in name for w in _XPROC_SEND_WORDS):
        return name
    if name == "_place":
        return name
    if name == "post" and isinstance(func, ast.Attribute):
        try:
            base = ast.unparse(func.value)
        except Exception:
            base = ""
        if base == "tx" or base.endswith(".tx"):
            return f"{base}.post"
    return None


def _check_xproc(tree: ast.AST, path: str,
                 lines: Sequence[str]) -> List[LintViolation]:
    """Cross-process modules (XPROC_MODULES) must route wire effects
    through the transport seam (ISSUE 17): a raw send primitive — a
    send-side ``*_raw`` callee, the ``_place`` one-sided landing
    closure, a direct peer-ring ``tx.post`` — may be CALLED only from
    (a) a function that itself routes through ``transport.dispatch``
    (the seam wrapper, whose ``NotImplemented`` fallback is the
    un-hooked production path), or (b) another ``*_raw`` function (raw
    implementations may compose below the seam).  Anything else is a
    wire effect the simnet explorer can never see, reorder, or drop.
    Suppress deliberate pre-seam paths (the bootstrap address-exchange
    handshake) with ``# tpr: allow(xproc)``."""
    out = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        raws = []
        dispatches = False
        for n in ast.walk(fn):
            if not isinstance(n, ast.Call) or _enclosing_fn(n) is not fn:
                continue
            f = n.func
            cname = (f.attr if isinstance(f, ast.Attribute)
                     else f.id if isinstance(f, ast.Name) else None)
            if cname == "dispatch":
                dispatches = True
            tag = _xproc_raw_send(n)
            if tag is not None:
                raws.append((n, tag))
        if not raws or dispatches or fn.name.endswith("_raw"):
            continue
        for n, tag in raws:
            if "xproc" in _allowed_rules(lines, n.lineno):
                continue
            out.append(LintViolation(
                path, n.lineno, n.col_offset, "xproc",
                f"{fn.name} calls raw transport primitive {tag} around "
                "the transport seam: cross-process effects must leave "
                "through transport.dispatch so message-level exploration "
                "(simnet) and fault injection see every send"))
    return out


# -- rule: tpr-obs (C emission discipline, tpurpc-xray ISSUE 19) --------------

#: C-side suppression comment — ``// tpr: allow(tpr-obs)`` (the python
#: grammar's char class has no ``-``, so the C rule carries its own)
_NATIVE_ALLOW_RE = re.compile(r"//\s*tpr:\s*allow\(([a-z_\-,\s]+)\)")
_NATIVE_CODE_RE = re.compile(r"^(?:tpr_obs::)?kEv\w+$")
_NATIVE_CALL_RE = re.compile(r"\b\w+\s*\(")
#: files that ARE the obs plane — raw emit is their implementation detail
_NATIVE_OBS_IMPL = ("tpr_obs.h", "tpr_obs.cc")


def _native_allowed(lines: Sequence[str], line: int) -> bool:
    if _AUDIT_IGNORE_SUPPRESSIONS:
        return False
    if 1 <= line <= len(lines):
        m = _NATIVE_ALLOW_RE.search(lines[line - 1])
        if m:
            return "tpr-obs" in {t.strip() for t in m.group(1).split(",")}
    return False


def _native_split_args(text: str) -> List[str]:
    """Top-level comma split of a balanced C argument list."""
    out, depth, start = [], 0, 0
    for i, ch in enumerate(text):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == "," and depth == 0:
            out.append(text[start:i])
            start = i + 1
    out.append(text[start:])
    return [a.strip() for a in out]


def lint_native_source(source: str, path: str) -> List[LintViolation]:
    """The ``tpr-obs`` rule over one C source: every ``TPR_OBS(...)``
    site must be static-tag pure-int plumbing (see the module docstring's
    ``tpr-obs`` entry), and ``tpr_obs::emit`` may only be called raw
    inside the obs plane's own implementation."""
    out: List[LintViolation] = []
    lines = source.split("\n")
    base = os.path.basename(path)
    if base not in _NATIVE_OBS_IMPL:
        for i, ln in enumerate(lines, 1):
            if "tpr_obs::emit" not in ln or _native_allowed(lines, i):
                continue
            out.append(LintViolation(
                path, i, ln.index("tpr_obs::emit"), "tpr-obs",
                "raw tpr_obs::emit() bypasses the TPR_OBS macro's "
                "enabled() guard: the off-switch must cost one relaxed "
                "load, not an emit — go through TPR_OBS; a deliberate "
                "exception carries '// tpr: allow(tpr-obs)'"))
    for m in re.finditer(r"\bTPR_OBS\s*\(", source):
        line = source.count("\n", 0, m.start()) + 1
        stripped = lines[line - 1].lstrip()
        if stripped.startswith("#define") or stripped.startswith("//"):
            continue
        if _native_allowed(lines, line):
            continue
        # balanced-paren argument extraction (sites span lines)
        depth, i = 1, m.end()
        while i < len(source) and depth:
            if source[i] == "(":
                depth += 1
            elif source[i] == ")":
                depth -= 1
            i += 1
        if depth:
            continue  # unbalanced tail: not a call site we can judge
        args = _native_split_args(source[m.end():i - 1])
        col = m.start() - (source.rfind("\n", 0, m.start()) + 1)

        def flag(msg: str) -> None:
            out.append(LintViolation(path, line, col, "tpr-obs", msg))

        if len(args) != 4:
            flag(f"TPR_OBS takes (code, tag, a1, a2); got {len(args)} "
                 "argument(s)")
            continue
        if not _NATIVE_CODE_RE.match(args[0]):
            flag(f"event code {args[0]!r} is not a static kEv* constant: "
                 "dynamic codes make the shared-ABI event vocabulary "
                 "unauditable (flight.py mirrors these numbers)")
        if "tag_for" in args[1]:
            flag("tag_for() in the tag argument interns per event: "
                 "intern ONCE at link/conn setup (the cold path) and "
                 "pass the cached uint16 — the flight rule's interned-"
                 "tag contract, on the C plane")
        for arg in args:
            if '"' in arg or "'" in arg:
                flag(f"argument {arg!r} carries a string/char literal: "
                     "events carry ints (tags are interned, names live "
                     "in the shm tag table)")
                break
        for arg in args[1:]:
            if "tag_for" in arg:
                continue  # already flagged with the specific story
            if _NATIVE_CALL_RE.search(arg):
                flag(f"argument {arg!r} calls a function per event: the "
                     "always-on C ring's writers pay 4 relaxed stores "
                     "and 2 seq stamps per record — precompute the int; "
                     "a deliberate exception carries "
                     "'// tpr: allow(tpr-obs)'")
                break
    out.sort(key=lambda v: (v.path, v.line, v.col))
    return out


def native_src_root() -> str:
    """The repo's ``native/src`` directory (sibling of the package)."""
    return os.path.join(os.path.dirname(tree_root()), "native", "src")


def lint_native_tree(root: Optional[str] = None) -> List[LintViolation]:
    """The ``tpr-obs`` pass over every C source under ``native/src``."""
    root = root or native_src_root()
    if not os.path.isdir(root):
        return []
    out: List[LintViolation] = []
    for fn in sorted(os.listdir(root)):
        if not fn.endswith((".cc", ".h", ".cpp", ".hpp")):
            continue
        p = os.path.join(root, fn)
        with open(p, "r", encoding="utf-8") as f:
            out.extend(lint_native_source(f.read(), p))
    return out


# -- driver ------------------------------------------------------------------

# -- rule: diag --------------------------------------------------------------

# Callee names that mutate a telemetry plane. Matched by name (Attribute
# attr or bare Name) because the evidence rules reach planes through the
# Planes facade and module handles — a cheap syntactic net that catches
# the real mutators (Counter.inc, flight.emit, watchdog.external_trip,
# tag_for interning, bundle capture) without a type system.
_DIAG_MUTATORS = frozenset({
    "inc", "dec", "set", "observe", "record", "emit", "capture",
    "external_trip", "tag_for", "sample_once", "reset", "clamp",
})
# Bare-name calls that are common builtins share names with mutators
# ("set" the constructor) — only these bare names count as mutation.
_DIAG_BARE_MUTATORS = frozenset({"emit", "tag_for", "external_trip"})


def _check_diag(tree: ast.AST, path: str,
                lines: Sequence[str]) -> List[LintViolation]:
    """Evidence rules (``_collect_*`` / ``_score_*``) must only READ the
    planes: a diagnosis that emits, bumps, trips or interns mutates the
    evidence the next diagnosis reads (the observer effect as a bug)."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not (node.name.startswith("_collect_")
                or node.name.startswith("_score_")):
            continue
        for call in ast.walk(node):
            if not isinstance(call, ast.Call):
                continue
            f = call.func
            if isinstance(f, ast.Attribute):
                bad = f.attr in _DIAG_MUTATORS
            elif isinstance(f, ast.Name):
                bad = f.id in _DIAG_BARE_MUTATORS
            else:
                bad = False
            if not bad:
                continue
            if "diag" in _allowed_rules(lines, call.lineno):
                continue
            name = f.attr if isinstance(f, ast.Attribute) else f.id
            out.append(LintViolation(
                path, call.lineno, call.col_offset, "diag",
                f"{node.name} is an evidence rule and must be read-only, "
                f"but calls {name}(): mutating a telemetry plane from "
                "inside a diagnosis corrupts the evidence the next "
                "diagnosis reads — collect facts, return them; a "
                "deliberate exception carries '# tpr: allow(diag)'"))
    return out


def lint_source(source: str, path: str,
                hot_copy: Optional[bool] = None,
                hot_log: Optional[bool] = None,
                hot_flight: Optional[bool] = None) -> List[LintViolation]:
    """Lint one module's source. ``hot_copy``/``hot_log``/``hot_flight``
    force/suppress the no-copy, guarded-logging and flight-encoder rules
    (default: decided by ``path`` suffix against HOT_COPY_MODULES /
    HOT_LOG_MODULES / FLIGHT_HOT_MODULES)."""
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as exc:
        return [LintViolation(path, exc.lineno or 0, exc.offset or 0,
                              "syntax", str(exc))]
    _attach_parents(tree)
    lines = source.splitlines()
    out = []
    out.extend(_check_wallclock(tree, path, lines))
    if hot_copy is None:
        hot_copy = path.replace("\\", "/").endswith(
            tuple(m.replace(os.sep, "/") for m in HOT_COPY_MODULES))
    if hot_copy:
        out.extend(_check_copy(tree, path, lines))
    if hot_log is None:
        hot_log = path.replace("\\", "/").endswith(
            tuple(m.replace(os.sep, "/") for m in HOT_LOG_MODULES))
    if hot_log:
        out.extend(_check_log(tree, path, lines))
    if hot_flight is None:
        hot_flight = path.replace("\\", "/").endswith(
            tuple(m.replace(os.sep, "/") for m in FLIGHT_HOT_MODULES))
    if hot_flight:
        out.extend(_check_flight(tree, path, lines))
    norm = path.replace("\\", "/")
    for suffix, fns in INLINE_DISPATCH_PATH.items():
        if norm.endswith(suffix.replace(os.sep, "/")):
            out.extend(_check_block(tree, path, lines, frozenset(fns)))
    if norm.endswith("tpurpc/obs/diagnose.py"):
        out.extend(_check_diag(tree, path, lines))
    out.extend(_check_locks(tree, path, lines))
    out.extend(_check_shard(tree, path, lines))
    out.extend(_check_stage(tree, path, lines))
    out.extend(_check_lease(tree, path, lines))
    out.extend(_check_rdv(tree, path, lines))
    out.extend(_check_kv(tree, path, lines))
    out.extend(_check_ringpool(tree, path, lines))
    if norm.endswith(tuple(m.replace(os.sep, "/") for m in XPROC_MODULES)):
        out.extend(_check_xproc(tree, path, lines))
    out.extend(_check_rawlock(tree, path, lines))
    out.sort(key=lambda v: (v.path, v.line, v.col))
    return out


def lint_paths(paths: Iterable[str]) -> List[LintViolation]:
    out = []
    for p in paths:
        with open(p, "r", encoding="utf-8") as f:
            out.extend(lint_source(f.read(), p))
    return out


def tree_root() -> str:
    """The repo's ``tpurpc`` package directory."""
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tree_paths(root: Optional[str] = None) -> List[str]:
    root = root or tree_root()
    paths = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                paths.append(os.path.join(dirpath, fn))
    return paths


def lint_tree(root: Optional[str] = None) -> List[LintViolation]:
    """Lint every ``.py`` under the tpurpc package (the default CLI pass)."""
    return lint_paths(_tree_paths(root))
