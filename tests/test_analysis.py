"""tpurpc.analysis: lint fixtures, lock-order detector, ring model checker.

Three layers (ISSUE 2):
* AST lint — positive/negative fixtures per rule, and the repo-wide gate
  (the tree must be clean, with zero copy-suppressions in hot modules).
* CheckedLock — a seeded lock-order cycle the detector must flag, the
  self-deadlock trap, cv-wait-while-holding, and blocking-call notes.
* ringcheck — the exhaustive suites must pass on the real protocol and
  reject every seeded mutant.

Plus regression tests for the concurrency fixes this subsystem surfaced
(poller start/stop, channelz counter snapshots, xds subscription handoff).
"""

import threading

import pytest

from tpurpc.analysis import lint, locks, ringcheck
from tpurpc.analysis.lint import lint_source


def _rules(violations):
    return sorted({v.rule for v in violations})


# ---------------------------------------------------------------------------
# lint: lease pairing
# ---------------------------------------------------------------------------

LEASE_OK = '''
def write_lease(lib, call, segs):
    if lib.tpr_call_send_reserve2(call) != 0:
        return False
    try:
        fill(segs)
    except BaseException:
        lib.tpr_call_send_abort(call)
        raise
    if lib.tpr_call_send_commit(call) != 0:
        raise RuntimeError("send failed")
    return True
'''

LEASE_NO_COMMIT = '''
def write_lease(lib, call, segs):
    lib.tpr_call_send_reserve2(call)
    try:
        fill(segs)
    except BaseException:
        lib.tpr_call_send_abort(call)
        raise
'''

LEASE_NO_ABORT = '''
def write_lease(lib, call, segs):
    lib.tpr_call_send_reserve2(call)
    fill(segs)
    lib.tpr_call_send_commit(call)
'''

LEASE_ABORT_NOT_EXCEPTIONAL = '''
def write_lease(lib, call, segs):
    lib.tpr_call_send_reserve2(call)
    if not fill(segs):
        lib.tpr_call_send_abort(call)
        return False
    lib.tpr_call_send_commit(call)
    return True
'''

LEASE_UNCOVERED_FILL = '''
def write_lease(lib, call, segs):
    lib.tpr_call_send_reserve2(call)
    fill(segs)  # raises -> lease leaks: not inside the try
    try:
        fill(segs)
    except BaseException:
        lib.tpr_call_send_abort(call)
        raise
    lib.tpr_call_send_commit(call)
'''


def test_lease_pairing_positive():
    assert lint_source(LEASE_OK, "fixture.py") == []


def test_lease_missing_commit_flagged():
    vs = lint_source(LEASE_NO_COMMIT, "fixture.py")
    assert _rules(vs) == ["lease"] and "never commits" in vs[0].message


def test_lease_missing_abort_flagged():
    vs = lint_source(LEASE_NO_ABORT, "fixture.py")
    assert _rules(vs) == ["lease"] and "exception path" in vs[0].message


def test_lease_abort_outside_handler_flagged():
    vs = lint_source(LEASE_ABORT_NOT_EXCEPTIONAL, "fixture.py")
    assert _rules(vs) == ["lease"]


def test_lease_uncovered_fill_flagged():
    vs = lint_source(LEASE_UNCOVERED_FILL, "fixture.py")
    assert any("not covered" in v.message for v in vs)


def test_lease_suppression():
    src = LEASE_NO_COMMIT.replace(
        "lib.tpr_call_send_reserve2(call)",
        "lib.tpr_call_send_reserve2(call)  # tpr: allow(lease)")
    assert lint_source(src, "fixture.py") == []


# ---------------------------------------------------------------------------
# lint: hot-path no-copy
# ---------------------------------------------------------------------------

def test_copy_join_flagged_in_hot_module():
    src = 'def f(parts):\n    return b"".join(parts)\n'
    vs = lint_source(src, "fixture.py", hot_copy=True)
    assert _rules(vs) == ["copy"]
    # the same source outside a hot module passes
    assert lint_source(src, "fixture.py", hot_copy=False) == []


def test_copy_from_buffer_copy_flagged():
    src = "def f(ctypes, v):\n    return (ctypes.c_uint8 * 4).from_buffer_copy(v)\n"
    assert _rules(lint_source(src, "fixture.py", hot_copy=True)) == ["copy"]


def test_copy_slice_to_bytes_flagged():
    src = "def f(buf, n):\n    return bytes(buf[:n])\n"
    assert _rules(lint_source(src, "fixture.py", hot_copy=True)) == ["copy"]


def test_copy_tobytes_escape_hatch_allowed():
    src = ("def f(buf, n):\n"
           "    mv = memoryview(buf)\n"
           "    return mv[:n].tobytes()\n")
    assert lint_source(src, "fixture.py", hot_copy=True) == []


def test_copy_suppression_comment():
    src = 'def f(parts):\n    return b"".join(parts)  # tpr: allow(copy)\n'
    assert lint_source(src, "fixture.py", hot_copy=True) == []


def test_hot_modules_carry_no_copy_suppressions():
    """Acceptance: the data-plane modules are clean WITHOUT suppressions."""
    import os

    root = os.path.dirname(lint.tree_root())
    for suffix in lint.HOT_COPY_MODULES:
        path = os.path.join(root, suffix)
        with open(path, "r", encoding="utf-8") as f:
            src = f.read()
        assert "allow(copy" not in src, f"{suffix} suppresses the copy rule"
        assert lint_source(src, path) == []


# ---------------------------------------------------------------------------
# lint: lock map
# ---------------------------------------------------------------------------

LOCKMAP_OK = '''
class Pool:
    _GUARDED_BY = {"items": "_lock", "count": "_lock"}

    def __init__(self):
        self.items = []   # __init__ exempt: construction happens-before
        self.count = 0

    def add(self, x):
        with self._lock:
            self.items.append(x)
            self.count += 1
'''

LOCKMAP_BAD = '''
class Pool:
    _GUARDED_BY = {"items": "_lock"}

    def add(self, x):
        self.items.append(x)

    def reset(self):
        self.items[:] = []
'''


def test_lockmap_positive():
    assert lint_source(LOCKMAP_OK, "fixture.py") == []


def test_lockmap_unlocked_mutations_flagged():
    vs = lint_source(LOCKMAP_BAD, "fixture.py")
    assert _rules(vs) == ["lock"] and len(vs) == 2  # append + slice-assign


def test_lockmap_wrong_lock_flagged():
    src = LOCKMAP_OK.replace('with self._lock:', 'with self._other:')
    vs = lint_source(src, "fixture.py")
    assert _rules(vs) == ["lock"]


LOCKFREE = '''
class Batcher:
    _GUARDED_BY = {"_queue": None, "_parked": None, "_closed": None}
    _MERGE_BOUNDARY = ("_merge_loop",)

    def __init__(self):
        self._queue = []      # __init__ exempt, as for a locked attribute
        self._parked = 0

    def submit(self, p):
        self._queue.append(p)         # one step: a mutator call
        self._parked = 0              # one step: a constant stored

    def park(self, want):
        self._parked = want           # one step: a local stored

    def close(self):
        self._closed = True

    def step(self):
        STEP

def elsewhere(other):
    other._queue.append(1)
'''


@pytest.mark.parametrize("step,flagged", [
    ("self._queue.popleft()", False),
    ("self._queue.remove(self.p)", False),
    ("self._parked += 1", True),                      # read-modify-write
    ("self._queue[0] = 1", True),
    ("self._queue = self._queue[8:]", True),          # the old cut
    ("self._parked = len(self._queue)", True),
    ("self._parked += 1  # tpr: allow(lock)", False),
])
def test_lockmap_lock_free_attributes_take_atomic_steps_only(step, flagged):
    """``_GUARDED_BY = {"attr": None}`` (ISSUE 35, ``FanInBatcher``): no lock
    by design, so every mutation must be one step under the interpreter."""
    vs = [v for v in lint_source(LOCKFREE.replace("STEP", step), "x.py")
          if v.rule == "lock"]
    assert len(vs) == int(flagged)
    if flagged:
        assert "lock-free" in vs[0].message and "(in step)" in vs[0].message


def test_lockmap_lock_free_attributes_stay_shard_local():
    v = [x for x in lint_source(LOCKFREE.replace("STEP", "pass"), "x.py")
         if x.rule == "shard"]
    assert len(v) == 1 and "Batcher._queue" in v[0].message


def test_the_batcher_declares_its_hand_over_lock_free():
    """The declaration follows the code: the queue and both flags lock-free,
    the two tallies under a lock no producer takes."""
    from tpurpc.jaxshim.service import FanInBatcher

    assert FanInBatcher._GUARDED_BY == {
        "_queue": None, "_parked": None, "_closed": None,
        "batches_run": "_tally", "rows_run": "_tally"}
    path = lint.tree_root() + "/jaxshim/service.py"
    with open(path) as f:
        assert lint_source(f.read(), path) == []


# ---------------------------------------------------------------------------
# lint: monotonic clocks
# ---------------------------------------------------------------------------

def test_wallclock_flagged_and_suppressable():
    src = "import time\n\ndef f():\n    return time.time()\n"
    assert _rules(lint_source(src, "fixture.py")) == ["wallclock"]
    ok = src.replace("time.time()", "time.time()  # tpr: allow(wallclock)")
    assert lint_source(ok, "fixture.py") == []
    mono = src.replace("time.time()", "time.monotonic()")
    assert lint_source(mono, "fixture.py") == []


# ---------------------------------------------------------------------------
# lint: guarded logging on hot-path modules (ISSUE 4)
# ---------------------------------------------------------------------------

LOG_SRC = '''
from tpurpc.utils.trace import log_debug, log_info, log_error, trace_ring

def hot(msg):
    log_debug("got %r", msg)            # unguarded: formatting always runs
    log_info("state %s", msg)           # unguarded
    log_error("broken: %s", msg)        # error paths are cold: exempt
    if trace_ring:
        log_debug("guarded %r", msg)    # behind the flag: fine
    if trace_ring.enabled:
        log_info("also guarded %s", msg)
    trace_ring.log("flag-local %r", msg)  # TraceFlag.log checks enabled
'''


def test_log_rule_flags_unguarded_hot_logging():
    vs = lint_source(LOG_SRC, "tpurpc/core/ring.py")
    assert _rules(vs) == ["log"]
    assert len(vs) == 2  # the two unguarded log_debug/log_info calls
    assert {v.line for v in vs} == {5, 6}


def test_log_rule_scoped_to_hot_modules():
    # the same source off the hot-path module set is fine
    assert lint_source(LOG_SRC, "tpurpc/rpc/server.py") == []
    assert lint_source(LOG_SRC, "fixture.py") == []
    # ...and every declared hot module enforces it
    for mod in ("tpurpc/core/pair.py", "tpurpc/core/poller.py",
                "tpurpc/wire/grpc_h2.py"):
        assert _rules(lint_source(LOG_SRC, mod)) == ["log"]


def test_log_rule_suppression_comment():
    ok = LOG_SRC.replace('log_debug("got %r", msg)',
                         'log_debug("got %r", msg)  # tpr: allow(log)')
    ok = ok.replace('log_info("state %s", msg)',
                    'log_info("state %s", msg)  # tpr: allow(log)')
    assert lint_source(ok, "tpurpc/core/ring.py") == []


def test_log_rule_hot_modules_are_clean():
    import tpurpc.core.pair
    import tpurpc.core.poller
    import tpurpc.core.ring
    import tpurpc.wire.grpc_h2

    for mod in (tpurpc.core.ring, tpurpc.core.pair, tpurpc.core.poller,
                tpurpc.wire.grpc_h2):
        with open(mod.__file__, "r", encoding="utf-8") as f:
            vs = lint_source(f.read(), mod.__file__)
        assert [v for v in vs if v.rule == "log"] == []


# ---------------------------------------------------------------------------
# lint: no blocking calls on the inline dispatch path (ISSUE 3)
# ---------------------------------------------------------------------------

BLOCK_SRC = '''
import time

class _ServerConnection:
    def _run_handler_inner(self, handler, st, ctx, path):
        time.sleep(0.1)
        item = st.requests.get()
        self._lock.acquire()
        st._credits.wait()
        self._thread.join()

    def off_path_helper(self):
        time.sleep(1)          # not an inline-dispatch function: allowed
'''

BLOCK_BOUNDED = '''
class _ServerStream:
    def next_request(self, timeout=None):
        item = self.requests.get(timeout=timeout)
        self._credits.acquire(timeout=0.25)
        self._credits.acquire(blocking=False)
        self._done.wait(timeout=1.0)
        self._thread.join(5)
        return item
'''


def test_block_rule_flags_unbounded_calls_on_dispatch_path():
    vs = lint_source(BLOCK_SRC, "tpurpc/rpc/server.py")
    assert _rules(vs) == ["block"]
    # sleep, bare .get(), bare .acquire(), bare .wait(), bare .join() —
    # and ONLY inside the configured inline-path functions
    assert len(vs) == 5
    assert all("_run_handler_inner" in v.message for v in vs)


def test_block_rule_bounded_waits_pass():
    assert lint_source(BLOCK_BOUNDED, "tpurpc/rpc/server.py") == []


def test_block_rule_scoped_to_inline_dispatch_module():
    # the same source outside rpc/server.py is not on the dispatch path
    assert lint_source(BLOCK_SRC, "tpurpc/rpc/channel.py") == []
    assert lint_source(BLOCK_SRC, "fixture.py") == []


def test_block_rule_suppression_comment():
    src = BLOCK_BOUNDED.replace(
        "item = self.requests.get(timeout=timeout)",
        "item = self.requests.get()  # tpr: allow(block)")
    assert lint_source(src, "tpurpc/rpc/server.py") == []
    # without the annotation the same line is a finding
    bare = BLOCK_BOUNDED.replace(
        "item = self.requests.get(timeout=timeout)",
        "item = self.requests.get()")
    assert _rules(lint_source(bare, "tpurpc/rpc/server.py")) == ["block"]


def test_block_rule_real_server_module_is_clean():
    import importlib

    server_mod = importlib.import_module("tpurpc.rpc.server")
    path = server_mod.__file__
    with open(path, "r", encoding="utf-8") as f:
        vs = lint_source(f.read(), path)
    assert [v for v in vs if v.rule == "block"] == []


# ---------------------------------------------------------------------------
# the repo-wide gate
# ---------------------------------------------------------------------------

def test_tree_is_lint_clean():
    violations = lint.lint_tree()
    assert violations == [], "\n".join(map(str, violations))


# ---------------------------------------------------------------------------
# runtime lock-order detector
# ---------------------------------------------------------------------------

@pytest.fixture(autouse=True)
def _clean_lock_state():
    locks.reset_lock_state()
    yield
    locks.reset_lock_state()


def test_checked_lock_passthrough_semantics():
    lk = locks.CheckedLock("t.lk")
    with lk:
        assert lk.locked()
    assert not lk.locked()
    assert lk.acquire(blocking=False)
    lk.release()


def test_lock_order_cycle_reported():
    a = locks.CheckedLock("t.A")
    b = locks.CheckedLock("t.B")

    def order_ab():
        with a:
            with b:
                pass

    def order_ba():
        with b:
            with a:
                pass

    t1 = threading.Thread(target=order_ab)
    t1.start()
    t1.join()
    t2 = threading.Thread(target=order_ba)
    t2.start()
    t2.join()
    v = locks.lock_violations()
    assert any("lock-order cycle" in m and "t.A" in m and "t.B" in m
               for m in v), v


def test_lock_order_cycle_by_name_across_instances():
    """Lockdep-style: two INSTANCES of the same named lock form one graph
    node, so the cycle is caught without the same objects ever deadlocking."""
    a1, a2 = locks.CheckedLock("t.A"), locks.CheckedLock("t.A")
    b = locks.CheckedLock("t.B")
    with a1:
        with b:
            pass
    with b:
        with a2:
            pass
    assert any("lock-order cycle" in m for m in locks.lock_violations())


def test_no_cycle_no_violation():
    a = locks.CheckedLock("t.A")
    b = locks.CheckedLock("t.B")
    for _ in range(3):
        with a:
            with b:
                pass
    assert locks.lock_violations() == []


def test_self_deadlock_trapped():
    lk = locks.CheckedLock("t.self")
    with lk:
        with pytest.raises(RuntimeError, match="re-acquire"):
            lk.acquire()
    assert any("self-deadlock" in m for m in locks.lock_violations())


def test_cv_wait_while_holding_other_lock_flagged():
    other = locks.CheckedLock("t.other")
    cv = locks.checked_condition("t.cv")
    with other:
        with cv:
            cv.wait(timeout=0.01)
    assert any("cv-wait" in m and "t.other" in m
               for m in locks.lock_violations())


def test_cv_wait_alone_is_clean():
    cv = locks.checked_condition("t.cv")
    with cv:
        cv.wait(timeout=0.01)
    assert locks.lock_violations() == []


def test_note_blocking_flags_held_locks(monkeypatch):
    monkeypatch.setattr(locks, "ENABLED", True)
    lk = locks.CheckedLock("t.held")
    locks.note_blocking("socket recv")  # nothing held: no violation
    assert locks.lock_violations() == []
    with lk:
        locks.note_blocking("socket recv")
    assert any("held across blocking call" in m
               for m in locks.lock_violations())


def test_factories_are_zero_overhead_when_disabled(monkeypatch):
    monkeypatch.setattr(locks, "ENABLED", False)
    assert type(locks.make_lock("x")) is type(threading.Lock())
    assert isinstance(locks.make_condition("x"), threading.Condition)
    monkeypatch.setattr(locks, "ENABLED", True)
    assert isinstance(locks.make_lock("x"), locks.CheckedLock)
    assert isinstance(locks.make_condition("x"), locks.CheckedCondition)


# ---------------------------------------------------------------------------
# ring model checker
# ---------------------------------------------------------------------------

def test_ring_protocol_exhaustive_ok():
    for res in ringcheck.default_suite():
        assert res.ok, repr(res)
        assert res.states > 0


def test_ring_capacity4_exhausts_with_wrap():
    # 3 messages x span 3 through a 4-word ring: every offset wraps twice
    res = ringcheck.check_ring(4, [1, 1, 1])
    assert res.ok and res.states > 0


def test_batched_write_many_protocol_ok():
    res = ringcheck.check_ring(8, [1, 1, 1], batched=True)
    assert res.ok, repr(res)


@pytest.mark.parametrize("mutant", ringcheck.MUTANTS)
def test_every_seeded_mutant_is_killed(mutant):
    kills = ringcheck.mutant_kill_suite()
    assert kills[mutant], f"mutant {mutant} survived the checker"


def test_publish_before_write_is_torn_read():
    res = ringcheck.check_ring(8, [1, 1], mutant="publish_before_write")
    assert not res.ok and res.violation.kind == "torn"
    assert res.violation.trace  # a concrete interleaving is reported


def test_ignore_credits_is_overwrite():
    res = ringcheck.check_ring(4, [1, 1, 1], mutant="ignore_credits")
    assert not res.ok and res.violation.kind in ("overwrite", "torn")


def test_cli_default_gate_exits_zero():
    from tpurpc.analysis.__main__ import main

    assert main([]) == 0


# ---------------------------------------------------------------------------
# regressions for the fixes the new passes surfaced
# ---------------------------------------------------------------------------

def test_poller_concurrent_start_stop_regression():
    """start() used to flip _running outside the cv lock; racing starts or a
    start/stop overlap could wedge the scan threads."""
    from tpurpc.core.poller import Poller

    p = Poller(thread_num=2)
    threads = [threading.Thread(target=p.start) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert p._running and len(p._threads) == 2
    p.stop()
    assert not p._running and p._threads == []


def test_channelz_counter_snapshot_regression():
    """as_dict() used to read the counters unlocked — a snapshot could pair
    a call count with the previous call's timestamp."""
    from tpurpc.rpc.channelz import CallCounters

    c = CallCounters()
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            c.on_start()
            c.on_finish(True)

    t = threading.Thread(target=hammer)
    t.start()
    try:
        for _ in range(200):
            snap = c.as_dict()
            if snap["calls_started"]:
                assert snap["last_call_started"] > 0.0
            assert snap["calls_succeeded"] <= snap["calls_started"]
    finally:
        stop.set()
        t.join()


def test_xds_subscription_swap_under_load_regression():
    """The v3 reader thread now compares AND swaps `subscribed` inside the
    servicer lock; set_endpoints churn concurrent with subscription reads
    must never tear (the round-5 xds.py:161 bug class)."""
    from tpurpc.rpc.xds import XdsServicer

    s = XdsServicer()
    stop = threading.Event()

    def churn():
        i = 0
        while not stop.is_set():
            s.set_endpoints("svc", [f"h{i}:1"])
            i += 1

    t = threading.Thread(target=churn)
    t.start()
    try:
        for _ in range(300):
            eps = s.get_endpoints("svc")
            assert len(eps) <= 1
    finally:
        stop.set()
        t.join()


def test_lockmap_declarations_hold_on_declaring_modules():
    """The regression guard for the declared lock maps: the modules that
    declare _GUARDED_BY must stay clean under the lock-map pass."""
    import tpurpc.core.poller as poller_mod
    import tpurpc.rpc.channelz as channelz_mod
    import tpurpc.rpc.xds as xds_mod

    for mod in (poller_mod, channelz_mod, xds_mod):
        path = mod.__file__
        with open(path, "r", encoding="utf-8") as f:
            vs = [v for v in lint_source(f.read(), path) if v.rule == "lock"]
        assert vs == [], vs


# ---------------------------------------------------------------------------
# lint: shard confinement (tpurpc-manycore, ISSUE 7)
# ---------------------------------------------------------------------------

SHARD_OK = '''
class Sub:
    _GUARDED_BY = {"out": "done"}

class Merger:
    _MERGE_BOUNDARY = ("_merge_loop", "_resolve")

    def _merge_loop(self):
        sub = self.ring.take()
        self._resolve(sub)

    def _resolve(self, sub):
        sub.out = 1        # cross-shard write INSIDE the boundary: legal
'''

SHARD_CROSS_MUTATION = '''
class Sub:
    _GUARDED_BY = {"out": "done"}

class Merger:
    _MERGE_BOUNDARY = ("_merge_loop",)

    def _merge_loop(self):
        pass

    def helper(self, sub):
        sub.out = 1        # cross-shard write OUTSIDE the boundary
'''

SHARD_MUTATOR_CALL = '''
class Shard:
    _GUARDED_BY = {"_queue": "_lock"}

class Merger:
    _MERGE_BOUNDARY = ("_merge_loop",)

    def _merge_loop(self):
        pass

    def steal(self, other):
        other._queue.append(1)   # reaching into another shard's queue
'''

SHARD_SELF_OK = '''
class Shard:
    _GUARDED_BY = {"_queue": "_lock"}
    _MERGE_BOUNDARY = ("_merge_loop",)

    def _merge_loop(self):
        pass

    def local(self):
        with self._lock:
            self._queue.append(1)   # shard-LOCAL mutation: the lock map rules
'''

SHARD_NOT_ARMED = '''
class Shard:
    _GUARDED_BY = {"_queue": "_lock"}

def elsewhere(other):
    other._queue.append(1)   # no _MERGE_BOUNDARY in module: rule silent
'''


def test_shard_rule_boundary_mutation_passes():
    assert "shard" not in _rules(lint_source(SHARD_OK, "x.py"))


def test_shard_rule_flags_cross_shard_mutation():
    v = [x for x in lint_source(SHARD_CROSS_MUTATION, "x.py")
         if x.rule == "shard"]
    assert len(v) == 1 and "Sub.out" in v[0].message


def test_shard_rule_flags_mutator_calls():
    v = [x for x in lint_source(SHARD_MUTATOR_CALL, "x.py")
         if x.rule == "shard"]
    assert len(v) == 1 and "Shard._queue" in v[0].message


def test_shard_rule_self_mutation_is_lock_maps_job():
    assert "shard" not in _rules(lint_source(SHARD_SELF_OK, "x.py"))


def test_shard_rule_only_armed_with_merge_boundary():
    assert "shard" not in _rules(lint_source(SHARD_NOT_ARMED, "x.py"))


def test_shard_rule_suppression_comment():
    src = SHARD_CROSS_MUTATION.replace(
        "sub.out = 1 ", "sub.out = 1  # tpr: allow(shard)")
    assert "shard" not in _rules(lint_source(src, "x.py"))


def test_shard_rule_jaxshim_service_is_clean():
    """The real merge module must satisfy its own declared boundary."""
    import os

    import tpurpc

    path = os.path.join(os.path.dirname(tpurpc.__file__), "jaxshim",
                        "service.py")
    with open(path, "r", encoding="utf-8") as f:
        src = f.read()
    assert "_MERGE_BOUNDARY" in src  # the rule is ARMED there
    assert "shard" not in _rules(lint_source(src, path))


# ---------------------------------------------------------------------------
# ringcheck: MPMC handoff model (tpurpc-manycore, ISSUE 7)
# ---------------------------------------------------------------------------

def test_handoff_protocol_exhaustive_ok():
    res = ringcheck.check_handoff(n_producers=2, items_per_producer=2,
                                  capacity=2, words=2)
    assert res.ok, res


def test_handoff_three_producers_ok():
    res = ringcheck.check_handoff(n_producers=3, items_per_producer=1,
                                  capacity=2, words=2)
    assert res.ok, res


@pytest.mark.parametrize("mutant", ringcheck.HANDOFF_MUTANTS)
def test_every_handoff_mutant_is_killed(mutant):
    kills = ringcheck.handoff_mutant_kill_suite()
    assert kills[mutant], f"handoff mutant {mutant} survived"


def test_handoff_read_uncommitted_is_torn():
    res = ringcheck.check_handoff(n_producers=2, items_per_producer=2,
                                  capacity=2, words=2,
                                  mutant="handoff_read_uncommitted")
    assert not res.ok and res.violation.kind == "torn"


def test_handoff_runtime_matches_model_shape():
    """The runtime HandoffRing implements the modeled protocol: claim via
    one atomic ticket, commit stamp after payload, ticket-order consume,
    lap-free stamp — spot-check the stamps through one lap."""
    from tpurpc.core.handoff import HandoffRing

    ring = HandoffRing(capacity=2)
    assert ring._seq == [0, 1]         # lap-0 free stamps
    assert ring.publish("a")
    assert ring._seq[0] == 1           # commit stamp t+1
    assert ring.take() == "a"
    assert ring._seq[0] == 2           # freed for lap 1 (h + capacity)
    assert ring.publish("b") and ring.publish("c")
    assert ring.take() == "b" and ring.take() == "c"
    ring.close()


# ---------------------------------------------------------------------------
# lint: static stage/hop registrations + pure-int hop accounting (ISSUE 8)
# ---------------------------------------------------------------------------

STAGE_OK = '''
from tpurpc.obs import lens as _lens
from tpurpc.obs import profiler as _profiler

_LENS_WIRE_BYTES, _LENS_WIRE_NS, _LENS_WIRE_COPY = _lens.hop_counters("wire")

_LENS_STAGES = {"write": "wire", "read": "wire"}
_profiler.register_stages(__file__, _LENS_STAGES)
_profiler.register_stages("socketserver.py", {"serve_forever": "idle"})


def site(n, t0, t1):
    dt = t1 - t0
    _LENS_WIRE_NS.inc(dt)
    _LENS_WIRE_BYTES.inc(n)
'''


def test_stage_rule_static_registrations_pass():
    assert lint_source(STAGE_OK, "fixture.py") == []


def test_stage_rule_flags_registration_inside_function():
    src = STAGE_OK + '''

def late(profiler):
    profiler.register_stages(__file__, _LENS_STAGES)
'''
    vs = lint_source(src, "fixture.py")
    assert _rules(vs) == ["stage"] and "module-level" in vs[0].message


def test_stage_rule_flags_dynamic_strings():
    src = '''
from tpurpc.obs import profiler as _profiler

name = "ring" + "-write"
_profiler.register_stages(__file__, {"writev": name})
'''
    vs = lint_source(src, "fixture.py")
    assert _rules(vs) == ["stage"] and "static" in vs[0].message


def test_stage_rule_flags_non_constant_mapping_name():
    src = '''
from tpurpc.obs import profiler as _profiler


def build():
    return {"writev": "ring-write"}


_MAPPING = build()
_profiler.register_stages(__file__, _MAPPING)
'''
    assert _rules(lint_source(src, "fixture.py")) == ["stage"]


def test_stage_rule_flags_dynamic_hop_name():
    src = '''
from tpurpc.obs import lens as _lens

hop = "wire"
_LENS_X_B, _LENS_X_NS, _LENS_X_C = _lens.hop_counters(hop)
'''
    vs = lint_source(src, "fixture.py")
    assert _rules(vs) == ["stage"] and "string-literal" in vs[0].message


def test_stage_rule_flags_hop_binding_inside_function():
    src = '''
from tpurpc.obs import lens as _lens


def bind():
    return _lens.hop_counters("wire")
'''
    assert _rules(lint_source(src, "fixture.py")) == ["stage"]


def test_stage_rule_flags_calls_in_hop_accounting():
    src = STAGE_OK + '''

def bad_site(views):
    _LENS_WIRE_BYTES.inc(sum(len(v) for v in views))
'''
    vs = lint_source(src, "fixture.py")
    assert _rules(vs) == ["stage"]
    assert "precompute the int" in vs[0].message


def test_stage_rule_flags_str_constant_in_hop_accounting():
    src = STAGE_OK + '''

def bad_site2():
    _LENS_WIRE_NS.inc("12")
'''
    assert _rules(lint_source(src, "fixture.py")) == ["stage"]


@pytest.mark.parametrize("site,why", [
    ('with _lens.stage("hbm", n) as st:\n        st.copy = n', None),
    ('with lens.stage("srv_recv", call=self.call, seq=self.seq):\n'
     '        pass', None),
    ('with _lens.stage(hop, n):\n        pass', "declared hop"),
    ('with _lens.stage("warp-drive", n):\n        pass', "declared hop"),
    ('with _lens.stage("hbm", len(views)):\n        pass',
     "precompute the int"),
    ('with other.stage("anything", len(views)):\n        pass', None),
    ('with _lens.stage("hbm", len(v)):  # tpr: allow(stage)\n'
     '        pass', None),
])
def test_stage_rule_holds_lens_stage_sites_to_the_same_contract(site, why):
    """ISSUE 26's form of a site: a literal declared hop, pure-int
    arguments; other objects' `.stage(...)` are none of its business."""
    src = f"""
def site(self, hop, n, v, views):
    {site}
"""
    vs = lint_source(src, "fixture.py")
    if why is None:
        assert vs == []
    else:
        assert _rules(vs) == ["stage"] and why in vs[0].message


def test_stage_rule_ignores_non_lens_counters():
    src = '''
def site(c, n):
    c.inc(len(n))          # a plain counter: not hop accounting
    _OTHER.inc(str(n))     # not a _LENS_ binding either
'''
    assert lint_source(src, "fixture.py") == []


def test_stage_rule_suppression_comment():
    src = STAGE_OK + '''

def deliberate(views):
    _LENS_WIRE_BYTES.inc(len(views))  # tpr: allow(stage)
'''
    assert lint_source(src, "fixture.py") == []


def test_stage_rule_instrumented_modules_are_clean():
    """The real hop-accounting/marker modules hold the contract."""
    import tpurpc.core.endpoint
    import tpurpc.core.pair
    import tpurpc.core.ring
    import tpurpc.jaxshim.codec
    import tpurpc.obs.profiler
    import tpurpc.tpu.endpoint
    import tpurpc.tpu.hbm_ring

    for mod in (tpurpc.core.ring, tpurpc.core.pair, tpurpc.core.endpoint,
                tpurpc.jaxshim.codec, tpurpc.tpu.hbm_ring,
                tpurpc.tpu.endpoint, tpurpc.obs.profiler):
        with open(mod.__file__, "r", encoding="utf-8") as f:
            vs = lint_source(f.read(), mod.__file__)
        assert [v for v in vs if v.rule == "stage"] == [], mod.__name__


# ---------------------------------------------------------------------------
# lint: rendezvous claim pairing (tpurpc-express, ISSUE 9)
# ---------------------------------------------------------------------------

RDV_OK = '''
def send_big(self, stream_id, flags, segs, total):
    claim = self.rdv_claim(stream_id, total, 1)
    if claim is None:
        return False
    try:
        self._rdv_write(claim, segs, total)
    except BaseException:
        self.rdv_release(claim)
        raise
    self.rdv_complete(claim, stream_id, flags, total)
    return True
'''

RDV_NO_COMPLETE = '''
def send_big(self, stream_id, total):
    claim = self.rdv_claim(stream_id, total, 1)
    self._rdv_write(claim, [], total)
'''

RDV_NO_RELEASE = '''
def send_big(self, stream_id, flags, segs, total):
    claim = self.rdv_claim(stream_id, total, 1)
    self._rdv_write(claim, segs, total)
    self.rdv_complete(claim, stream_id, flags, total)
'''

RDV_RELEASE_NOT_EXCEPTIONAL = '''
def send_big(self, stream_id, flags, segs, total):
    claim = self.rdv_claim(stream_id, total, 1)
    if bad(claim):
        self.rdv_release(claim)
        return False
    self._rdv_write(claim, segs, total)
    self.rdv_complete(claim, stream_id, flags, total)
'''


def test_rdv_pairing_positive():
    assert lint_source(RDV_OK, "fixture.py") == []


def test_rdv_missing_complete_flagged():
    vs = lint_source(RDV_NO_COMPLETE, "fixture.py")
    assert _rules(vs) == ["rdv"] and "never" in vs[0].message


def test_rdv_missing_release_flagged():
    vs = lint_source(RDV_NO_RELEASE, "fixture.py")
    assert _rules(vs) == ["rdv"] and "exception path" in vs[0].message


def test_rdv_release_outside_handler_flagged():
    # a release on a NON-exception branch does not cover the raise-between-
    # claim-and-complete window
    vs = lint_source(RDV_RELEASE_NOT_EXCEPTIONAL, "fixture.py")
    assert _rules(vs) == ["rdv"]


def test_rdv_finally_release_passes():
    src = RDV_NO_RELEASE.replace(
        "    self._rdv_write(claim, segs, total)\n",
        "    try:\n"
        "        self._rdv_write(claim, segs, total)\n"
        "    finally:\n"
        "        self.rdv_release(claim)\n")
    assert lint_source(src, "fixture.py") == []


def test_rdv_suppression():
    src = RDV_NO_COMPLETE.replace(
        "self.rdv_claim(stream_id, total, 1)",
        "self.rdv_claim(stream_id, total, 1)  # tpr: allow(rdv)")
    assert lint_source(src, "fixture.py") == []


def test_rdv_rendezvous_module_is_clean():
    """The real sender (core/rendezvous.py) holds the claim-pairing and
    flight-encoder contracts it exports."""
    import tpurpc.core.rendezvous as rdv_mod

    with open(rdv_mod.__file__, "r", encoding="utf-8") as f:
        vs = lint_source(f.read(), rdv_mod.__file__)
    assert [v for v in vs if v.rule in ("rdv", "flight")] == []


# ---------------------------------------------------------------------------
# ringcheck: rendezvous offer/claim/write/complete model (tpurpc-express)
# ---------------------------------------------------------------------------

def test_rendezvous_model_clean_configs():
    from tpurpc.analysis import ringcheck

    for cfg in (dict(messages=2, words=2, standing=True),
                dict(messages=2, words=2, standing=False),
                dict(messages=3, words=2, standing=True)):
        res = ringcheck.check_rendezvous(**cfg)
        assert res.ok, res


def test_rendezvous_model_peer_death_releases_claims():
    """Sender death explored at EVERY protocol point: the receiver's close
    must release the claimed landing region (the leaked-claim violation
    fires otherwise — proven by the mutant-free death configs passing and
    by hand-wiring a close-less variant being impossible without editing
    the model)."""
    from tpurpc.analysis import ringcheck

    for standing in (True, False):
        res = ringcheck.check_rendezvous(messages=2, words=2,
                                         standing=standing,
                                         with_death=True)
        assert res.ok, res


def test_rendezvous_mutants_killed():
    from tpurpc.analysis import ringcheck

    verdicts = ringcheck.rendezvous_mutant_kill_suite()
    assert verdicts == {"write_before_claim": True,
                       "complete_before_write": True}


def test_rendezvous_mutants_ride_default_kill_suite():
    """The CLI gate (python -m tpurpc.analysis) must exercise the
    rendezvous mutants alongside the ring + handoff ones."""
    from tpurpc.analysis import ringcheck

    verdicts = ringcheck.mutant_kill_suite()
    for mutant in ringcheck.RDV_MUTANTS:
        assert verdicts.get(mutant) is True, verdicts
    assert all(verdicts.values()), verdicts


def test_rendezvous_model_rides_default_suite():
    from tpurpc.analysis import ringcheck

    results = ringcheck.default_suite()
    rdv = [r for r in results if r.config.startswith("rendezvous")]
    assert len(rdv) >= 4 and all(r.ok for r in rdv)


# ---------------------------------------------------------------------------
# tpurpc-cadence (ISSUE 10): the decode step loop under the analysis gate
# ---------------------------------------------------------------------------

SERVING_BLOCK_SRC = '''
import time

class DecodeScheduler:
    def _step_loop(self):
        time.sleep(0.01)               # unbounded nap on the step loop
        self._lock.acquire()           # timeout-less lock

    def _boundary(self):
        self._kick.wait()              # timeout-less park

    def _run_step(self):
        out = self._inflight.get()     # timeout-less queue get

    def _off_loop_helper(self):
        time.sleep(1)                  # not a step-loop function: allowed
'''

SERVING_BLOCK_BOUNDED = '''
class DecodeScheduler:
    def _boundary(self):
        self._kick.wait(timeout=self.idle_wait_s)   # bounded slice: fine

    def _run_step(self):
        ok = self._lock.acquire(timeout=0.5)        # bounded: fine
'''


def test_serving_step_loop_under_block_rule():
    vs = lint_source(SERVING_BLOCK_SRC, "tpurpc/serving/scheduler.py")
    assert _rules(vs) == ["block"] and len(vs) == 4
    assert {v.line for v in vs} == {6, 7, 10, 13}


def test_serving_block_rule_bounded_waits_pass():
    assert lint_source(SERVING_BLOCK_BOUNDED,
                       "tpurpc/serving/scheduler.py") == []


def test_serving_block_rule_scoped_to_scheduler_module():
    # the same source elsewhere in the serving package is not on the path
    assert lint_source(SERVING_BLOCK_SRC, "tpurpc/serving/api.py") == []


def test_serving_block_rule_suppression_comment():
    ok = SERVING_BLOCK_SRC
    for needle in ('time.sleep(0.01)               # unbounded nap on the step loop',
                   'self._lock.acquire()           # timeout-less lock',
                   'self._kick.wait()              # timeout-less park',
                   'out = self._inflight.get()     # timeout-less queue get'):
        ok = ok.replace(needle, needle.split("#")[0].rstrip()
                        + "  # tpr: allow(block)")
    assert lint_source(ok, "tpurpc/serving/scheduler.py") == []


SERVING_FLIGHT_SRC = '''
from tpurpc.obs import flight as _flight

class DecodeScheduler:
    def _run_step(self):
        _flight.emit(_flight.GEN_STEP_BEGIN, self._tag,
                     len(self._running), 0)      # Call in an emit arg
        _flight.emit(_flight.GEN_SHED, self._tag, 0, "batch")  # str const

    def _ok_site(self):
        nb = 4
        _flight.emit(_flight.GEN_STEP_END, self._tag, nb, 0)  # pure ints
'''


def test_serving_flight_rule_enforced():
    vs = lint_source(SERVING_FLIGHT_SRC, "tpurpc/serving/scheduler.py")
    assert _rules(vs) == ["flight"] and len(vs) == 2
    assert {v.line for v in vs} == {6, 8}


def test_serving_flight_rule_scoped():
    # serving/api.py is transport glue, not an emission site — exempt
    assert lint_source(SERVING_FLIGHT_SRC, "tpurpc/serving/api.py") == []


def test_serving_scheduler_module_is_clean():
    import tpurpc.serving.scheduler as sched_mod

    with open(sched_mod.__file__, "r", encoding="utf-8") as f:
        vs = lint_source(f.read(), sched_mod.__file__)
    assert vs == []


# ---------------------------------------------------------------------------
# tpurpc-keystone (ISSUE 11): the kv block-alloc pairing rule
# ---------------------------------------------------------------------------

KV_OK = '''
def prefill_row(self, seq, prompt):
    kv, hit = self.mgr.alloc_for_prompt(seq, prompt)
    try:
        self.model.fold(prompt, kv)
    except BaseException:
        self.mgr.free_blocks(kv)
        raise
    return kv
'''

KV_NO_RELEASE = '''
def prefill_row(self, seq, prompt):
    kv, hit = self.mgr.alloc_for_prompt(seq, prompt)
    self.model.fold(prompt, kv)
    return kv
'''

KV_SWAP_COVERS = '''
def preempt(self, seq):
    blocks = self.mgr.alloc_blocks(seq, 2)
    try:
        fill(blocks)
    finally:
        self.mgr.swap_out(seq)
'''

KV_QUARANTINE_COVERS = '''
def receive(self, seq, n):
    blocks = self.mgr.alloc_blocks(seq, n)
    try:
        land(blocks)
    except Exception:
        self.mgr.quarantine(blocks)
        raise
'''


def test_kv_pairing_positive():
    assert lint_source(KV_OK, "fixture.py") == []


def test_kv_missing_release_flagged():
    vs = lint_source(KV_NO_RELEASE, "fixture.py")
    assert _rules(vs) == ["kv"] and "exception path" in vs[0].message


def test_kv_swap_out_counts_as_release():
    assert lint_source(KV_SWAP_COVERS, "fixture.py") == []


def test_kv_quarantine_counts_as_release():
    assert lint_source(KV_QUARANTINE_COVERS, "fixture.py") == []


def test_kv_suppression():
    src = KV_NO_RELEASE.replace(
        "self.mgr.alloc_for_prompt(seq, prompt)",
        "self.mgr.alloc_for_prompt(seq, prompt)  # tpr: allow(kv)")
    assert lint_source(src, "fixture.py") == []


def test_kv_modules_are_clean():
    """The real KV plane holds the pairing + flight-encoder contracts it
    exports (serving/kv.py and serving/disagg.py are both on the flight
    hot-module list)."""
    import tpurpc.serving.disagg as disagg_mod
    import tpurpc.serving.kv as kv_mod

    for mod in (kv_mod, disagg_mod):
        with open(mod.__file__, "r", encoding="utf-8") as f:
            vs = lint_source(f.read(), mod.__file__)
        assert [v for v in vs
                if v.rule in ("kv", "flight", "lock")] == [], mod.__name__


# ---------------------------------------------------------------------------
# ringcheck: the kv block-table handoff model (tpurpc-keystone)
# ---------------------------------------------------------------------------

def test_kv_handoff_model_clean_configs():
    from tpurpc.analysis import ringcheck

    for cfg in (dict(blocks=2), dict(blocks=3),
                dict(blocks=2, with_death=True),
                dict(blocks=3, with_death=True)):
        res = ringcheck.check_kv_handoff(**cfg)
        assert res.ok, res


def test_kv_handoff_reuse_before_quarantine_killed():
    """The ISSUE 11 seeded mutant: a dest that returns a reaped handoff's
    blocks to the free list lets a straggling one-sided write land in
    re-leased memory — the model must catch exactly that."""
    from tpurpc.analysis import ringcheck

    res = ringcheck.check_kv_handoff(blocks=2, with_death=True,
                                     mutant="kv_reuse_before_quarantine")
    assert not res.ok
    assert res.violation.kind == "stale-write"


def test_kv_handoff_free_before_complete_killed():
    from tpurpc.analysis import ringcheck

    res = ringcheck.check_kv_handoff(blocks=2,
                                     mutant="kv_free_before_complete")
    assert not res.ok
    assert res.violation.kind == "torn"


def test_kv_handoff_mutants_ride_default_kill_suite():
    from tpurpc.analysis import ringcheck

    verdicts = ringcheck.mutant_kill_suite()
    for mutant in ringcheck.KV_MUTANTS:
        assert verdicts.get(mutant) is True, verdicts
    assert all(verdicts.values()), verdicts


def test_kv_handoff_model_rides_default_suite():
    from tpurpc.analysis import ringcheck

    results = ringcheck.default_suite()
    kv = [r for r in results if r.config.startswith("kv_handoff")]
    assert len(kv) >= 4 and all(r.ok for r in kv)


# ---------------------------------------------------------------------------
# lint: rawlock (tpurpc-proof, ISSUE 12 — factory-made locks only, in
# modules that already import the factory)
# ---------------------------------------------------------------------------

RAWLOCK_BAD = '''
import threading

from tpurpc.analysis.locks import make_lock


class Pool:
    def __init__(self):
        self._lock = make_lock("Pool._lock")
        self._aux = threading.Lock()
        self._cv = threading.Condition(self._aux)
'''

RAWLOCK_UNARMED = '''
import threading


class Pool:
    def __init__(self):
        self._lock = threading.Lock()
'''

RAWLOCK_SUPPRESSED = '''
import threading

from tpurpc.analysis.locks import make_condition


class Pool:
    def __init__(self):
        self._cv = make_condition("Pool._cv")
        self._raw = threading.Lock()  # tpr: allow(rawlock)
'''


def test_rawlock_flags_raw_primitives_next_to_the_factory():
    vs = [v for v in lint_source(RAWLOCK_BAD, "x.py")
          if v.rule == "rawlock"]
    assert len(vs) == 2  # the Lock and the Condition


def test_rawlock_unarmed_without_factory_import():
    assert [v for v in lint_source(RAWLOCK_UNARMED, "x.py")
            if v.rule == "rawlock"] == []


def test_rawlock_suppression_comment():
    assert [v for v in lint_source(RAWLOCK_SUPPRESSED, "x.py")
            if v.rule == "rawlock"] == []


def test_rawlock_factory_importing_modules_are_clean():
    """The satellite fix itself: the decode scheduler and the rendezvous
    plane route every lock through the factory now — TPURPC_DEBUG_LOCKS
    and the schedule explorer finally cover them."""
    import importlib

    for name in ("tpurpc.serving.scheduler", "tpurpc.core.rendezvous",
                 "tpurpc.rpc.shard", "tpurpc.rpc.channel"):
        mod = importlib.import_module(name)
        with open(mod.__file__, "r", encoding="utf-8") as f:
            vs = lint_source(f.read(), mod.__file__)
        assert [v for v in vs if v.rule == "rawlock"] == [], name


def test_scheduler_and_rendezvous_locks_are_factory_made(monkeypatch):
    """Runtime proof of the blind-spot fix: constructing the live classes
    under the exploration factory hook yields hooked primitives."""
    from tpurpc.analysis import locks as locks_mod

    seen = []

    def hook(kind, name, lock):
        seen.append((kind, name))
        return None  # decline: normal primitives, we only observe

    locks_mod.set_factory_hook(hook)
    try:
        import numpy as np

        from tpurpc.core.rendezvous import LandingPool
        from tpurpc.serving.scheduler import DecodeScheduler

        class _M:
            def prefill(self, prompts):
                return ([np.zeros(1)] * len(prompts),
                        [1] * len(prompts))

            def step(self, states, tokens):
                return states, [int(t) + 1 for t in tokens]

        s = DecodeScheduler(_M(), name="rawlock-probe")
        s.close(timeout=2)
        pool = LandingPool("local", budget=1 << 20)
        pool.trim()
    finally:
        locks_mod.set_factory_hook(None)
    names = {n for _k, n in seen}
    assert "DecodeScheduler._lock" in names
    assert "DecodeScheduler._kick" in names
    assert "LandingPool._lock" in names


# ---------------------------------------------------------------------------
# the suppression audit (tpurpc-proof, ISSUE 12)
# ---------------------------------------------------------------------------

SUPPRESS_LIVE = '''
import time


def stamp():
    return time.time()  # tpr: allow(wallclock)
'''

SUPPRESS_STALE = '''
import time


def stamp():
    return time.monotonic()  # tpr: allow(wallclock)
'''

SUPPRESS_UNKNOWN = '''
X = 1  # tpr: allow(wallcheck)
'''

SUPPRESS_DOC_MENTION = '''
def f():
    """Docs may quote the grammar: ``# tpr: allow(wallclock)``."""
    return 1
'''


def test_audit_accepts_live_suppression():
    assert lint.audit_suppressions_source(SUPPRESS_LIVE, "x.py") == []


def test_audit_flags_stale_suppression():
    vs = lint.audit_suppressions_source(SUPPRESS_STALE, "x.py")
    assert len(vs) == 1 and vs[0].rule == "suppress"
    assert "stale" in vs[0].message


def test_audit_flags_unknown_rule_name():
    vs = lint.audit_suppressions_source(SUPPRESS_UNKNOWN, "x.py")
    assert len(vs) == 1 and "unknown rule" in vs[0].message


def test_audit_ignores_docstring_mentions():
    assert lint.audit_suppressions_source(SUPPRESS_DOC_MENTION,
                                          "x.py") == []


def test_audit_does_not_disturb_normal_linting():
    """The audit's suppression-void pass must not leak: a normal lint of
    a suppressed violation still honors the suppression afterwards."""
    lint.audit_suppressions_source(SUPPRESS_STALE, "x.py")
    assert lint_source(SUPPRESS_LIVE, "x.py") == []


def test_tree_suppressions_are_all_live():
    """Every `# tpr: allow(...)` in the tree earns its keep — the ~37
    accreted suppressions were audited and the stale ones deleted
    (ISSUE 12 satellite); new dead ones are gate failures."""
    violations = lint.audit_suppressions_tree()
    assert violations == [], "\n".join(map(str, violations))


# ---------------------------------------------------------------------------
# tpurpc-argus (ISSUE 14): the flight rule extends to the obs modules
# ---------------------------------------------------------------------------

ARGUS_FLIGHT_SRC = '''
from tpurpc.obs import flight as _flight

class SloEvaluator:
    def _transition(self, obj, track, burn):
        _flight.emit(_flight.SLO_FIRING, obj.tag,
                     int(burn * 100), 0)         # Call in an emit arg
        _flight.emit(_flight.SLO_RESOLVED, obj.tag, 0, "latency")  # str

    def _ok_site(self, obj):
        burn_pct = 240
        _flight.emit(_flight.SLO_FIRING, obj.tag, 2, burn_pct)  # pure ints
'''


@pytest.mark.parametrize("mod", ["tsdb", "slo", "bundle", "collector"])
def test_argus_flight_rule_enforced_per_module(mod):
    vs = lint_source(ARGUS_FLIGHT_SRC, f"tpurpc/obs/{mod}.py")
    assert _rules(vs) == ["flight"] and len(vs) == 2
    assert {v.line for v in vs} == {6, 8}


def test_argus_flight_rule_scoped():
    # the registry itself is not an emission module — exempt
    assert lint_source(ARGUS_FLIGHT_SRC, "tpurpc/obs/metrics.py") == []


ARGUS_FLIGHT_SUPPRESSED = '''
from tpurpc.obs import flight as _flight

class SloEvaluator:
    def _transition(self, obj, burn):
        _flight.emit(_flight.SLO_FIRING, obj.tag, int(burn), 0)  # tpr: allow(flight)
'''


def test_argus_flight_rule_suppression():
    assert lint_source(ARGUS_FLIGHT_SUPPRESSED, "tpurpc/obs/slo.py") == []


def test_argus_modules_are_clean():
    """The real tsdb sample path / slo evaluator / bundle / collector hold
    the pure-int flight contract (and every other rule) they export."""
    import tpurpc.obs.bundle as bundle_mod
    import tpurpc.obs.collector as collector_mod
    import tpurpc.obs.slo as slo_mod
    import tpurpc.obs.tsdb as tsdb_mod

    for mod in (tsdb_mod, slo_mod, bundle_mod, collector_mod):
        with open(mod.__file__, "r", encoding="utf-8") as f:
            vs = lint_source(f.read(), mod.__file__)
        assert vs == [], (mod.__name__, list(map(str, vs)))


# ---------------------------------------------------------------------------
# lint: cross-process sends route through the transport seam (ISSUE 17)
# ---------------------------------------------------------------------------

XPROC_BAD_RAW = '''
class Pair:
    def hot_notify(self, token):
        self._notify_raw(token)          # around the seam: flagged

    def _send_frame(self, payload):
        r = _transport.dispatch("frame", self, self._send_frame_raw, payload)
        if r is NotImplemented:
            return self._send_frame_raw(payload)  # seam fallback: fine
        return r

    def _send_frame_raw(self, payload):
        return self.sock.sendall(payload)         # raw impl: fine
'''

XPROC_BAD_RING = '''
class CtrlPlane:
    def post_fast(self, op, payload):
        tx = self.tx
        return tx.post(op, 0, payload, 0)  # peer-ring store, no seam
'''


def test_xproc_flags_raw_send_around_the_seam():
    vs = [v for v in lint_source(XPROC_BAD_RAW, "tpurpc/core/pair.py")
          if v.rule == "xproc"]
    assert len(vs) == 1 and vs[0].line == 4, list(map(str, vs))


def test_xproc_seam_wrapper_and_raw_impl_are_exempt():
    ok = XPROC_BAD_RAW.replace("self._notify_raw(token)",
                               '_transport.dispatch("frame", self, '
                               "self._notify_raw, token)")
    assert [v for v in lint_source(ok, "tpurpc/core/pair.py")
            if v.rule == "xproc"] == []


def test_xproc_flags_direct_peer_ring_post():
    vs = [v for v in lint_source(XPROC_BAD_RING, "tpurpc/core/ctrlring.py")
          if v.rule == "xproc"]
    assert len(vs) == 1 and "tx.post" in vs[0].message


def test_xproc_scoped_to_cross_process_modules():
    # the same source off the cross-process module set is fine
    assert lint_source(XPROC_BAD_RAW, "tpurpc/obs/flight.py") == []
    assert lint_source(XPROC_BAD_RAW, "fixture.py") == []
    # ...and every declared cross-process module enforces it
    for mod in ("tpurpc/core/pair.py", "tpurpc/core/rendezvous.py",
                "tpurpc/core/ctrlring.py", "tpurpc/serving/disagg.py"):
        assert [v.rule for v in lint_source(XPROC_BAD_RAW, mod)
                if v.rule == "xproc"] == ["xproc"]


def test_xproc_receive_side_raw_is_not_a_send():
    src = '''
class Pair:
    def drain_notifications(self):
        return self._drain_raw()   # local read of our own socket: fine
'''
    assert lint_source(src, "tpurpc/core/pair.py") == []


def test_xproc_suppression_comment():
    ok = XPROC_BAD_RAW.replace(
        "self._notify_raw(token)          # around the seam: flagged",
        "self._notify_raw(token)  # tpr: allow(xproc)")
    assert [v for v in lint_source(ok, "tpurpc/core/pair.py")
            if v.rule == "xproc"] == []


def test_xproc_modules_are_clean():
    """The real cross-process modules route every wire effect through the
    seam — the property that makes simnet's exploration exhaustive over
    their sends."""
    import tpurpc.core.ctrlring as ctrlring_mod
    import tpurpc.core.pair as pair_mod
    import tpurpc.core.rendezvous as rendezvous_mod
    import tpurpc.serving.disagg as disagg_mod

    for mod in (pair_mod, rendezvous_mod, ctrlring_mod, disagg_mod):
        with open(mod.__file__, "r", encoding="utf-8") as f:
            vs = lint_source(f.read(), mod.__file__)
        assert [v for v in vs if v.rule == "xproc"] == [], (
            mod.__name__, list(map(str, vs)))


# ---------------------------------------------------------------------------
# lint: tpr-obs — the C emission macro's discipline (tpurpc-xray, ISSUE 19)
# ---------------------------------------------------------------------------

from tpurpc.analysis.lint import lint_native_source, lint_native_tree

TPROBS_OK = '''
void Link::rdv_release(const std::shared_ptr<Claim> &c) {
  TPR_OBS(tpr_obs::kEvRdvRelease, otag_rdv_, c->lease_id, 0);
  TPR_OBS(tpr_obs::kEvCtrlStallBegin, otag_ctrl_,
          tx_.seq - head, 0);
}
'''

TPROBS_DYNAMIC_CODE = '''
void f(uint16_t code) {
  TPR_OBS(code, otag_rdv_, 1, 0);
}
'''

TPROBS_TAG_FOR = '''
void f() {
  TPR_OBS(tpr_obs::kEvRdvOffer, tpr_obs::tag_for("nrdv:x"), req, total);
}
'''

TPROBS_STRING_ARG = '''
void f() {
  TPR_OBS(tpr_obs::kEvRdvOffer, otag_rdv_, 'x', 0);
}
'''

TPROBS_CALL_ARG = '''
void f() {
  TPR_OBS(tpr_obs::kEvRdvOffer, otag_rdv_, payload.size(), 0);
}
'''

TPROBS_RAW_EMIT = '''
void f() {
  tpr_obs::emit(tpr_obs::kEvRdvOffer, otag_rdv_, 1, 0);
}
'''


def _nrules(vs):
    return sorted(v.rule for v in vs)


def test_tprobs_clean_site_passes():
    assert lint_native_source(TPROBS_OK, "native/src/tpr_rdv.cc") == []


def test_tprobs_dynamic_event_code_flagged():
    vs = lint_native_source(TPROBS_DYNAMIC_CODE, "native/src/tpr_rdv.cc")
    assert _nrules(vs) == ["tpr-obs"] and "kEv*" in vs[0].message


def test_tprobs_tag_for_in_args_flagged():
    vs = lint_native_source(TPROBS_TAG_FOR, "native/src/tpr_rdv.cc")
    assert any("interns per event" in v.message for v in vs)


def test_tprobs_string_literal_flagged():
    vs = lint_native_source(TPROBS_STRING_ARG, "native/src/tpr_rdv.cc")
    assert any("string/char literal" in v.message for v in vs)


def test_tprobs_per_event_call_flagged():
    vs = lint_native_source(TPROBS_CALL_ARG, "native/src/tpr_rdv.cc")
    assert _nrules(vs) == ["tpr-obs"] and "per event" in vs[0].message


def test_tprobs_raw_emit_outside_plane_flagged():
    vs = lint_native_source(TPROBS_RAW_EMIT, "native/src/tpr_rdv.cc")
    assert _nrules(vs) == ["tpr-obs"] and "enabled() guard" in vs[0].message


def test_tprobs_raw_emit_inside_plane_exempt():
    assert lint_native_source(TPROBS_RAW_EMIT, "native/src/tpr_obs.cc") == []


def test_tprobs_macro_definition_exempt():
    src = "#define TPR_OBS(code, tag, a1, a2) tpr_obs::emit(code, tag)\n"
    assert lint_native_source(src, "native/src/tpr_obs.h") == []


def test_tprobs_suppression_comment():
    ok = TPROBS_CALL_ARG.replace(
        "payload.size(), 0);",
        "payload.size(), 0);  // tpr: allow(tpr-obs)")
    assert lint_native_source(ok, "native/src/tpr_rdv.cc") == []


def test_tprobs_native_tree_is_clean():
    """Every real TPR_OBS site in native/src keeps the static-tag pure-int
    discipline — the same bar the `flight` rule holds the Python plane to."""
    vs = lint_native_tree()
    assert vs == [], list(map(str, vs))


# -- diag: evidence rules are read-only (tpurpc-oracle, ISSUE 20) ------------

DIAG_MUTATING = '''
def _collect_widget(planes):
    flight.emit(LEASE_RESERVE, tag, 1)
    return [("flight", "x", 1)]

def _score_widget(facts, planes):
    c.inc()
    return 0.5
'''

DIAG_CLEAN = '''
def _collect_widget(planes):
    ev = planes.flight_events()
    wins = planes.windows()
    seen = set()           # builtin set() is not the mutator set()
    return [("flight", e["event"], e["a1"]) for e in ev if ev]

def helper_outside_rule():
    flight.emit(1, 2, 3)   # not a _collect_*/_score_* function
'''


def test_diag_mutating_collect_and_score_flagged():
    vs = [v for v in lint_source(DIAG_MUTATING, "tpurpc/obs/diagnose.py")
          if v.rule == "diag"]
    assert len(vs) == 2
    assert "read-only" in vs[0].message and "emit()" in vs[0].message
    assert "inc()" in vs[1].message


def test_diag_clean_rule_and_non_rule_function_pass():
    assert [v for v in lint_source(DIAG_CLEAN, "tpurpc/obs/diagnose.py")
            if v.rule == "diag"] == []


def test_diag_scoped_to_diagnose_module():
    assert [v for v in lint_source(DIAG_MUTATING, "tpurpc/obs/other.py")
            if v.rule == "diag"] == []


def test_diag_suppression_comment():
    ok = DIAG_MUTATING.replace(
        "flight.emit(LEASE_RESERVE, tag, 1)",
        "flight.emit(LEASE_RESERVE, tag, 1)  # tpr: allow(diag)")
    vs = [v for v in lint_source(ok, "tpurpc/obs/diagnose.py")
          if v.rule == "diag"]
    assert len(vs) == 1 and "inc()" in vs[0].message


def test_diagnose_module_is_diag_flight_and_block_clean():
    """The real engine holds its own bar: read-only evidence rules,
    pure-int flight discipline, and no unbounded blocking on the
    dispatch-path functions."""
    import tpurpc.obs.diagnose as dz
    path = dz.__file__
    with open(path, "r", encoding="utf-8") as f:
        vs = lint_source(f.read(), path)
    assert vs == [], list(map(str, vs))
