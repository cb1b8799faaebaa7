"""The fan-in hand-over (ISSUE 35): no lock between the producers and the
batcher's one thread.

``submit`` appends to a deque and wakes the batcher's thread only where that
thread has parked; the thread cuts its batches with ``popleft`` and compares
signatures the producers worked out. What is held to here: per-producer
order, every row served once and every lease released once, a way in that
cannot block, the park / wake discipline under a stress, and ``submit``
against ``close()``. No rate and no duration is asserted: a bounded wait
(``result(30)``, ``join(30)``, a poll of a counter) only turns a hang into a
failure.
"""

import threading
import time

import numpy as np
import pytest

from tests.test_fanin_submit import (FakeLease, counters, device_row, moved,
                                     recorder)
from tpurpc.jaxshim.service import FanInBatcher

ROW = (4, 8)  # a row as it landed: no batch axis (``one_row``)


def host_row(value, shape=ROW):
    return {"x": np.full(shape, value, np.float32)}


def make_row(where, value, shape=ROW):
    return (device_row if where == "device" else host_row)(value, shape)


def run_threads(target, n):
    ts = [threading.Thread(target=target, args=(p,)) for p in range(n)]
    [t.start() for t in ts]
    [t.join(60) for t in ts]
    assert not any(t.is_alive() for t in ts)


def await_counter(name, before, at_least):
    """Bounded poll of a registry counter: not a timing assert, a hang
    turned into a failure."""
    deadline = time.monotonic() + 30
    while moved(before, name)[name] < at_least:
        assert time.monotonic() < deadline, f"{name} never reached {at_least}"
        time.sleep(0.0005)


# -- (a) many producers, a slow consumer ---------------------------------------

@pytest.mark.parametrize("where", ["host", "device"])
@pytest.mark.parametrize("max_batch", [3, 8])
def test_eight_producers_keep_their_order_and_every_lease_goes_back_once(
        where, max_batch):
    producers, each = 8, 200
    log, seen = [], []

    def fn(batch, rows):
        time.sleep(0.0002)  # a consumer slower than its producers
        x = np.asarray(batch["x"])
        seen.extend(int(v) for v in x[:int(rows), 0, 0])

    before = counters()
    b = FanInBatcher(fn, max_batch=max_batch, max_delay_s=0.05,
                     fixed_bucket=True, occupancy=True)
    leases = [[FakeLease(log, (p, k)) for k in range(each)]
              for p in range(producers)]
    futures = [[None] * each for _ in range(producers)]
    try:
        def produce(p):
            for k in range(each):
                futures[p][k] = b.submit(make_row(where, 1000 * p + k),
                                         leases=[leases[p][k]], one_row=True)

        run_threads(produce, producers)
        for fs in futures:
            for f in fs:
                assert f.result(30) is None
    finally:
        b.close()
    # no row lost, none doubled; each producer's rows in its own order,
    # within a batch and across batches
    assert sorted(seen) == sorted(1000 * p + k for p in range(producers)
                                  for k in range(each))
    for p in range(producers):
        mine = [v for v in seen if v // 1000 == p]
        assert mine == sorted(mine)
    assert all(ls.released == 1 for per in leases for ls in per)
    # a producer's credit goes back in its own order too
    for p in range(producers):
        back = [name[1] for _, name in log if name[0] == p]
        assert back == sorted(back)
    got = moved(before, "batcher_rows", "batcher_handoff_wakes")
    assert got["batcher_rows"] == b.rows_run == producers * each
    assert got["batcher_handoff_wakes"] <= producers * each


# -- (b) the way in cannot block ---------------------------------------------------

@pytest.mark.parametrize("where", ["host", "device"])
def test_every_submit_returns_while_the_consumer_is_blocked(where):
    producers, each = 8, 100
    gate, entered = threading.Event(), threading.Event()
    seen = []

    def fn(batch, rows):
        entered.set()
        assert gate.wait(60)
        seen.extend(int(v) for v in np.asarray(batch["x"])[:int(rows), 0, 0])

    b = FanInBatcher(fn, max_batch=8, max_delay_s=0.001, fixed_bucket=True,
                     occupancy=True)
    futures = [[None] * each for _ in range(producers)]
    try:
        first = b.submit(make_row(where, 99_999), one_row=True)
        assert entered.wait(30)  # the batcher's thread is inside fn, stuck

        def produce(p):
            for k in range(each):
                futures[p][k] = b.submit(make_row(where, 1000 * p + k),
                                         one_row=True)

        run_threads(produce, producers)  # all 800 returned: nothing blocked
        assert not gate.is_set() and not first.done()
        assert b.queue_depth() == producers * each
        assert not any(f.done() for fs in futures for f in fs)
        gate.set()
        for fs in futures:
            for f in fs:
                assert f.result(30) is None
    finally:
        gate.set()
        b.close()
    assert seen[0] == 99_999 and len(seen) == 1 + producers * each
    for p in range(producers):
        mine = [v for v in seen[1:] if v // 1000 == p]
        assert mine == list(range(1000 * p, 1000 * p + each))


# -- (c) park and wake, under a stress --------------------------------------------------

@pytest.mark.parametrize("depth_aware", [False, True])
@pytest.mark.parametrize("producers", [1, 2, 8])
def test_single_submits_against_a_batcher_that_keeps_parking(producers,
                                                             depth_aware):
    """Closed loops: every caller waits for its row before it sends the
    next, so the batcher's thread runs dry and parks over and over, and
    every arrival races a park."""
    each = 150
    waiting = [0]

    def fn(batch):
        return {"y": np.asarray(batch["x"]) + 1}

    before = counters()
    b = FanInBatcher(fn, max_batch=4, max_delay_s=0.0002,
                     inflight_fn=(lambda: waiting[0]) if depth_aware
                     else None)
    try:
        waiting[0] = producers

        def produce(p):
            for k in range(each):
                out = b(host_row(1000 * p + k, (1,) + ROW))
                assert out["y"].shape == (1,) + ROW
                assert int(out["y"][0, 0, 0]) == 1000 * p + k + 1

        run_threads(produce, producers)
    finally:
        b.close()
    got = moved(before, "batcher_parks", "batcher_handoff_wakes",
                "batcher_rows", "batcher_batches")
    rows = producers * each
    assert got["batcher_rows"] == rows == b.rows_run
    assert got["batcher_batches"] == b.batches_run <= rows
    # a submit wakes at most once, and only a thread that had parked; one
    # caller alone finds the batcher asleep or on its way there every time
    assert 0 < got["batcher_handoff_wakes"] <= rows
    assert got["batcher_parks"] >= 1
    assert set(("batcher_parks", "batcher_handoff_wakes")) <= set(counters())


# -- (d) submit against close() ------------------------------------------------------------

@pytest.mark.parametrize("where", ["host", "device"])
@pytest.mark.parametrize("slow", [False, True])
def test_a_submit_that_races_close_is_refused_or_served_never_both(where,
                                                                   slow):
    for round_ in range(20):
        log = []

        def fn(batch, rows):
            if slow:
                time.sleep(0.0005)

        b = FanInBatcher(fn, max_batch=4, max_delay_s=0.0005,
                         fixed_bucket=True, occupancy=True)
        outcomes = [[] for _ in range(8)]
        started = threading.Barrier(9)

        def produce(p):
            started.wait(30)
            for k in range(100_000):  # until close() refuses one
                lease = FakeLease(log, (p, k))
                try:
                    f = b.submit(make_row(where, k), leases=[lease],
                                 one_row=True)
                except RuntimeError:
                    outcomes[p].append((lease, None))
                    return  # closed: so is every later submit
                outcomes[p].append((lease, f))

        ts = [threading.Thread(target=produce, args=(p,)) for p in range(8)]
        [t.start() for t in ts]
        started.wait(30)
        time.sleep(0.0005 * (round_ % 4))
        b.close()
        [t.join(60) for t in ts]
        assert not any(t.is_alive() for t in ts)
        for per in outcomes:
            assert per and per[-1][1] is None  # each ended by a refusal
            for lease, f in per:
                if f is None:
                    # refused: nothing was taken, the credit is the caller's
                    assert lease.released == 0
                else:
                    # accepted: served (or failed) and released, once
                    assert f.exception(30) is None
                    assert lease.released == 1
        accepted = sum(1 for per in outcomes for _, f in per if f is not None)
        assert b.rows_run == accepted == len(log)
        with pytest.raises(RuntimeError, match="closed"):
            b.submit(make_row(where, 0), one_row=True)


# -- spent rows ---------------------------------------------------------------------------------

@pytest.mark.parametrize("where", ["host", "device"])
@pytest.mark.parametrize("may_wait,thread", [(4, "tpurpc-batcher-reap"),
                                             (0, "tpurpc-batcher")])
def test_the_rows_of_a_stacked_batch_die_on_the_reaper_thread(
        where, may_wait, thread, monkeypatch):
    """Letting go of a device array gives the interpreter up; the batcher's
    thread must not be the one that does it, eight times a batch. Only a
    reaper that is behind (here: allowed nothing) hands the rows back."""
    import weakref

    from tpurpc.jaxshim import service

    monkeypatch.setattr(service, "_SPENT_BATCHES", may_wait)
    died_on = []
    b = FanInBatcher(lambda batch, rows: None, max_batch=4, max_delay_s=60.0,
                     fixed_bucket=True, occupancy=True)
    try:
        futures = []
        for k in range(8):
            row = make_row(where, k)
            weakref.finalize(row["x"], lambda: died_on.append(
                threading.current_thread().name))
            futures.append(b.submit(row, one_row=True))
            del row
        for f in futures:
            assert f.result(30) is None
    finally:
        b.close()  # joins the reaper: every spent row has been dropped
    assert died_on == [thread] * 8


# -- (e) who pays a wake ---------------------------------------------------------------------

def test_a_saturated_queue_pays_no_wake_after_the_first():
    gate, entered = threading.Event(), threading.Event()

    def fn(batch, rows):
        entered.set()
        assert gate.wait(60)

    before = counters()
    b = FanInBatcher(fn, max_batch=8, max_delay_s=60.0, fixed_bucket=True,
                     occupancy=True)
    try:
        await_counter("batcher_parks", before, 1)  # asleep on an empty queue
        futures = [b.submit(host_row(0), one_row=True)]
        await_counter("batcher_parks", before, 2)  # asleep until the eighth
        futures += [b.submit(host_row(k), one_row=True) for k in range(1, 8)]
        assert entered.wait(30)
        # the batcher's thread is awake (inside fn): 64 more rows touch
        # nothing but the queue
        futures += [b.submit(host_row(k), one_row=True) for k in range(64)]
        gate.set()
        for f in futures:
            assert f.result(30) is None
    finally:
        gate.set()
        b.close()
    got = moved(before, "batcher_handoff_wakes", "batcher_batches",
                "batcher_flush_size")
    # the first row woke it for its timer, the eighth for the full batch
    assert got == {"batcher_handoff_wakes": 2, "batcher_batches": 9,
                   "batcher_flush_size": 9}


def test_a_closed_loop_caller_pays_one_wake_a_batch():
    before = counters()
    b = FanInBatcher(lambda batch: batch, max_batch=4, max_delay_s=0.002)
    try:
        for k in range(25):
            # asleep on an empty queue (park 2k + 1); its timer park for
            # this caller's one row is park 2k + 2
            await_counter("batcher_parks", before, 2 * k + 1)
            out = b(host_row(k, (1,) + ROW))
            assert int(out["x"][0, 0, 0]) == k
    finally:
        b.close()
    got = moved(before, "batcher_handoff_wakes", "batcher_batches",
                "batcher_flush_timer", "batcher_rows")
    assert got == {"batcher_handoff_wakes": 25, "batcher_batches": 25,
                   "batcher_flush_timer": 25, "batcher_rows": 25}


# -- (f) the flush rules are what they were ------------------------------------------------------

@pytest.mark.parametrize("reason,rows", [("size", 4), ("timer", 3),
                                         ("drained", 2), ("close", 1)])
def test_the_flush_reasons_are_unchanged(reason, rows):
    log = []
    fn, seen = recorder(log)
    before = counters()
    b = FanInBatcher(fn, max_batch=4,
                     max_delay_s=0.05 if reason == "timer" else 60.0,
                     fixed_bucket=True, occupancy=True,
                     inflight_fn=(lambda: rows) if reason == "drained"
                     else None)
    leases = [FakeLease(log, k) for k in range(rows)]
    try:
        futures = [b.submit(device_row(k), leases=[leases[k]])
                   for k in range(rows)]
        if reason == "close":
            b.close()
        for f in futures:
            assert f.result(30) is None
    finally:
        b.close()
    assert [n for _, n in seen] == [rows]
    assert [ls.released for ls in leases] == [1] * rows
    got = moved(before, *(f"batcher_flush_{r}"
                          for r in ("size", "timer", "drained", "close")))
    assert got == {f"batcher_flush_{r}": int(r == reason)
                   for r in ("size", "timer", "drained", "close")}


@pytest.mark.parametrize("bad", ["scalar", "empty", "two_devices"])
def test_a_row_that_can_stack_with_nothing_is_accepted_and_fails_alone(bad):
    """The signature is worked out inside ``submit`` now; what it finds
    still reaches the caller through the row's future, never as a raise."""
    import jax

    log = []
    fn, seen = recorder(log)
    row = {
        "scalar": {"x": np.float32(3)},
        "empty": {},
        "two_devices": {"x": device_row(1)["x"],
                        "y": device_row(1, device=jax.devices()[1])["x"]},
    }[bad]
    leases = [FakeLease(log, k) for k in range(3)]
    b = FanInBatcher(fn, max_batch=4, max_delay_s=60.0, fixed_bucket=True,
                     occupancy=True)
    try:
        futures = [b.submit(device_row(7), leases=[leases[0]]),
                   b.submit(row, leases=[leases[1]]),
                   b.submit(device_row(8), leases=[leases[2]])]
        b.close()
        with pytest.raises(ValueError):
            futures[1].result(30)
        assert futures[0].result(30) is None and futures[2].result(30) is None
    finally:
        b.close()
    (batch, n), = seen
    assert n == 2 and [int(v) for v in batch[:, 0, 0]] == [7, 8, 0, 0]
    assert [ls.released for ls in leases] == [1, 1, 1]
    assert log.index(("release", 1)) < log.index(("release", 0))
