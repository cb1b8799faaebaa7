"""``FanInBatcher.submit`` (ISSUE 33): rows handed over with their credit.

One way in, non-blocking, FIFO; device rows stacked by ONE jitted program of
ONE shape; the rows' leases released once each, after the stacked batch is
ready and before the consumer sees it; a result with no device leaf resolved
without a completion thread. No sleep is asserted and no rate: where order in
time matters it is read from an event log the fakes write.
"""

import threading

import numpy as np
import pytest

from tpurpc.jaxshim import service
from tpurpc.jaxshim.service import FanInBatcher
from tpurpc.obs import lens, metrics
from tpurpc.tpu import ledger
from tpurpc.tpu.hbm_ring import HbmRing

ROW = (1, 4, 8)  # one request: a leading axis of 1, 128 B of float32


class FakeLease:
    """Counts its releases and writes them into the test's event log."""

    def __init__(self, log, name):
        self.log, self.name, self.released = log, name, 0

    def release(self):
        self.released += 1
        self.log.append(("release", self.name))


def counters():
    return metrics.registry().counters_snapshot()


def moved(before, *names):
    after = counters()
    return {n: after.get(n, 0) - before.get(n, 0) for n in names}


def device_row(value, shape=ROW, device=None):
    """A row as a landing makes it: committed to its device."""
    import jax

    return {"x": jax.device_put(np.full(shape, value, np.float32),
                                device or jax.devices()[0])}


@pytest.fixture
def events(monkeypatch):
    """An event log that also records every ``jax.block_until_ready`` the
    batcher makes (``("ready", leading rows)``), in call order."""
    import jax

    log = []
    real = jax.block_until_ready

    def ready(tree):
        out = real(tree)
        log.append(("ready", jax.tree_util.tree_leaves(tree)[0].shape[0]))
        return out

    monkeypatch.setattr(jax, "block_until_ready", ready)
    return log


def recorder(log):
    """A consumer that keeps each batch (as numpy) and its row count, and
    returns nothing: the ingest consumer's shape."""
    seen = []

    def fn(batch, rows):
        log.append(("fn", int(rows)))
        seen.append((np.asarray(batch["x"]), int(rows)))

    return fn, seen


# -- order, and the three flushes ----------------------------------------------

@pytest.mark.parametrize("producers,each", [(1, 11), (3, 7), (8, 5)])
def test_per_producer_order_is_kept_across_and_within_batches(
        producers, each, events):
    fn, seen = recorder(events)
    b = FanInBatcher(fn, max_batch=4, max_delay_s=0.05, fixed_bucket=True,
                     occupancy=True)
    try:
        futures = []

        def produce(p):
            for k in range(each):
                futures.append(b.submit(device_row(100 * p + k)))

        ts = [threading.Thread(target=produce, args=(p,))
              for p in range(producers)]
        [t.start() for t in ts]
        [t.join(30) for t in ts]
        for f in list(futures):
            assert f.result(30) is None
    finally:
        b.close()
    order = [int(batch[i, 0, 0]) for batch, rows in seen for i in range(rows)]
    assert sorted(order) == sorted(100 * p + k for p in range(producers)
                                   for k in range(each))
    for p in range(producers):
        mine = [v for v in order if v // 100 == p]
        assert mine == sorted(mine)
    for batch, rows in seen:
        assert batch.shape == (4,) + ROW[1:] and 1 <= rows <= 4
        assert not batch[rows:].any()  # pad rows are zeros, at the end


@pytest.mark.parametrize("reason,rows", [("size", 4), ("timer", 3),
                                         ("close", 2)])
def test_flush_by_size_timer_and_close_returns_every_lease_once(
        reason, rows, events):
    fn, seen = recorder(events)
    delay = 0.05 if reason == "timer" else 60.0
    before = counters()
    b = FanInBatcher(fn, max_batch=4, max_delay_s=delay, fixed_bucket=True,
                     occupancy=True)
    leases = [FakeLease(events, k) for k in range(rows)]
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
    # the batch was ready before the first lease went back, and the leases
    # went back in queue order; the consumer was given the batch meanwhile
    assert [e for e in events if e[0] != "fn"] == (
        [("ready", 4)] + [("release", k) for k in range(rows)])
    assert events.count(("fn", rows)) == 1
    got = moved(before, f"batcher_flush_{reason}", "batcher_batches",
                "batcher_rows", "lens_batch_wait_ops", "lens_batch_stack_ops",
                "lens_batch_run_ops", "lens_batch_d2h_ops",
                "lens_batch_stack_bytes", "lens_batch_stack_copy_bytes")
    assert got == {f"batcher_flush_{reason}": 1, "batcher_batches": 1,
                   "batcher_rows": rows, "lens_batch_wait_ops": rows,
                   "lens_batch_stack_ops": 1, "lens_batch_run_ops": 1,
                   "lens_batch_d2h_ops": 0,
                   "lens_batch_stack_bytes": rows * 128,
                   "lens_batch_stack_copy_bytes": rows * 128}


@pytest.mark.parametrize("leased", [True, False])
def test_a_leased_batch_waits_once_under_batch_ready_inside_its_stack(
        leased, events, monkeypatch):
    """ISSUE 39: the batcher thread's one wait for the device is a hop of
    its own, a child of ``batch_stack`` (not taken out of it), one op a
    batch whose rows hold credit and none for a batch without leases."""
    monkeypatch.setattr(lens, "_CPU_EVERY", 1)   # every batch is clocked
    fn, seen = recorder(events)
    hops = ("lens_batch_ready_ops", "lens_batch_ready_busy_ns",
            "lens_batch_ready_cpu_ns", "lens_batch_ready_bytes",
            "lens_batch_stack_ops", "lens_batch_stack_busy_ns",
            "lens_batch_stack_cpu_ns", "lens_batch_run_ops",
            "lens_batch_run_busy_ns", "lens_batch_run_cpu_ns")
    before = counters()
    b = FanInBatcher(fn, max_batch=4, max_delay_s=60.0, fixed_bucket=True,
                     occupancy=True)
    try:
        futures = [b.submit(device_row(k), leases=[FakeLease(events, k)]
                            if leased else ()) for k in range(4)]
        for f in futures:
            assert f.result(30) is None
    finally:
        b.close()
    got = moved(before, *hops)
    assert got["lens_batch_stack_ops"] == got["lens_batch_run_ops"] == 1
    assert events.count(("ready", 4)) == (1 if leased else 0)
    if leased:
        assert got["lens_batch_ready_ops"] == 1
        assert got["lens_batch_ready_bytes"] == 4 * 128
        assert 0 < got["lens_batch_ready_busy_ns"] <= (
            got["lens_batch_stack_busy_ns"])
        assert got["lens_batch_ready_cpu_ns"] <= (
            got["lens_batch_stack_cpu_ns"])
    else:
        assert got["lens_batch_ready_ops"] == 0
    # fn's dispatch on its own, on a core at most all of the time (no
    # `> 0`: these stages are short of a step of a coarse thread clock; the
    # stack less the dispatch keeps the dispatch's own reads of the clock
    # on its CPU and not on its wall, so it is held to no such bound)
    assert 0 <= got["lens_batch_run_cpu_ns"] <= got["lens_batch_run_busy_ns"]
    assert 0 <= got["lens_batch_stack_cpu_ns"]


# -- one program, one shape ------------------------------------------------------

@pytest.mark.parametrize("one_row", [False, True])
@pytest.mark.parametrize("rows", range(1, 9))
def test_one_compiled_stack_shape_for_one_to_eight_rows(rows, one_row,
                                                        events):
    """Rows with their batch axis (``[1, 4, 8]``) and rows as they landed
    (``[4, 8]``, ``one_row``): either way one program, one shape."""
    fn, seen = recorder(events)
    shape = ROW[1:] if one_row else ROW
    program = service._stack_program(one_row)
    # the shape this file's rows give the program, compiled once by whoever
    # comes first; no occupancy may add another
    program(*[device_row(0, shape)] * 8)
    compiled = program._cache_size()
    b = FanInBatcher(fn, max_batch=8, max_delay_s=60.0, fixed_bucket=True,
                     occupancy=True)
    try:
        with ledger.track() as w:
            futures = [b.submit(device_row(k + 1, shape), one_row=one_row)
                       for k in range(rows)]
            b.close()
            [f.result(30) for f in futures]
    finally:
        b.close()
    assert program._cache_size() == compiled
    (batch, n), = seen
    assert n == rows and batch.shape == (8,) + ROW[1:]
    assert [int(v) for v in batch[:, 0, 0]] == (
        list(range(1, rows + 1)) + [0] * (8 - rows))
    # each payload byte moved on the device once, billed once a batch; pad
    # rows are not billed
    assert w["dma_d2d"] == rows * 128 and w["dma_d2d_ops"] == 1
    assert w["dma_h2d"] == 0
    # no lease: the batch is not awaited
    assert not [e for e in events if e[0] == "ready"]


# -- failures, each alone, each lease once -----------------------------------------

@pytest.mark.parametrize("bad", ["shape", "dtype", "scalar", "empty",
                                 "structure", "device"])
def test_an_incompatible_row_fails_alone_and_returns_its_credit(bad, events):
    import jax

    fn, seen = recorder(events)
    rows = [device_row(k + 1) for k in range(4)]
    rows[2] = {
        "shape": device_row(9, (1, 4, 7)),
        "dtype": {"x": jax.device_put(np.ones(ROW, np.int32),
                                      jax.devices()[0])},
        "scalar": {"x": jax.device_put(np.float32(3), jax.devices()[0])},
        "empty": {},
        "structure": {"y": rows[2]["x"]},
        "device": device_row(9, device=jax.devices()[1]),
    }[bad]
    leases = [FakeLease(events, k) for k in range(4)]
    b = FanInBatcher(fn, max_batch=4, max_delay_s=60.0, fixed_bucket=True,
                     occupancy=True)
    try:
        futures = [b.submit(r, leases=[ls]) for r, ls in zip(rows, leases)]
        with pytest.raises(ValueError):
            futures[2].result(30)
        for k in (0, 1, 3):
            assert futures[k].result(30) is None
    finally:
        b.close()
    (batch, n), = seen
    assert n == 3 and [int(v) for v in batch[:, 0, 0]] == [1, 2, 4, 0]
    assert [ls.released for ls in leases] == [1] * 4
    # the bad row's credit goes back at once, the others' after the stack
    assert [e for e in events if e[0] != "fn"] == [
        ("release", 2), ("ready", 4), ("release", 0), ("release", 1),
        ("release", 3)]


@pytest.mark.parametrize("where", ["fn", "stack"])
def test_a_failing_batch_fails_alone_and_returns_its_credit(where, events,
                                                            monkeypatch):
    calls = []

    def fn(batch, rows):
        calls.append(int(rows))
        if len(calls) == 1 and where == "fn":
            raise ValueError("boom")

    if where == "stack":
        real = service._stack_program()

        def flaky(*rows):  # the first gather fails, before anything ran
            if not calls and not flaky.done:
                flaky.done = True
                raise ValueError("boom")
            return real(*rows)

        flaky.done = False
        monkeypatch.setattr(service, "_stack_program",
                            lambda lift=False: flaky)
    b = FanInBatcher(fn, max_batch=2, max_delay_s=60.0, fixed_bucket=True,
                     occupancy=True)
    leases = [FakeLease(events, k) for k in range(4)]
    try:
        futures = [b.submit(device_row(k), leases=[leases[k]])
                   for k in range(4)]
        for f in futures[:2]:
            with pytest.raises(ValueError, match="boom"):
                f.result(30)
        for f in futures[2:]:
            assert f.result(30) is None
    finally:
        b.close()
    assert [ls.released for ls in leases] == [1] * 4
    assert calls == ([2, 2] if where == "fn" else [2])


def test_close_serves_what_is_queued_and_then_refuses(events):
    gate = threading.Event()
    seen = []

    def fn(batch, rows):
        gate.wait(30)
        seen.append(int(rows))

    b = FanInBatcher(fn, max_batch=2, max_delay_s=60.0, fixed_bucket=True,
                     occupancy=True)
    leases = [FakeLease(events, k) for k in range(5)]
    futures = [b.submit(device_row(k), leases=[leases[k]]) for k in range(5)]
    closer = threading.Thread(target=b.close)
    closer.start()
    gate.set()
    closer.join(60)
    assert not closer.is_alive()
    assert [f.result(30) for f in futures] == [None] * 5
    assert seen == [2, 2, 1]
    assert [ls.released for ls in leases] == [1] * 5
    late = FakeLease(events, "late")
    with pytest.raises(RuntimeError, match="closed"):
        b.submit(device_row(9), leases=[late])
    assert late.released == 0  # refused: the credit is still the caller's


# -- the reply -----------------------------------------------------------------------

@pytest.mark.parametrize("result", ["none", "host", "device"])
def test_a_result_without_a_device_leaf_meets_no_completion_thread(result):
    import jax

    threads = []

    def fn(batch):
        threads.append(threading.current_thread().name)
        if result == "none":
            return None
        if result == "host":
            return {"n": np.arange(4, dtype=np.int64)}
        return {"y": batch["x"] * 2}

    before = counters()
    b = FanInBatcher(fn, max_batch=4, max_delay_s=60.0, fixed_bucket=True)
    resolved_on = []
    try:
        futures = [b.submit(device_row(k + 1)) for k in range(4)]
        futures[0].add_done_callback(
            lambda f: resolved_on.append(threading.current_thread().name))
        got = [f.result(30) for f in futures]
    finally:
        b.close()
    d2h = moved(before, "lens_batch_d2h_ops")["lens_batch_d2h_ops"]
    if result == "none":
        assert got == [None] * 4 and d2h == 0
    elif result == "host":
        assert [int(g["n"][0]) for g in got] == [0, 1, 2, 3] and d2h == 0
    else:
        assert [float(g["y"][0, 0, 0]) for g in got] == [2, 4, 6, 8]
        assert all(isinstance(g["y"], np.ndarray) for g in got) and d2h == 1
    if resolved_on:  # the callback ran where the future was resolved
        assert (resolved_on[0] == "tpurpc-batcher") == (result != "device")
    assert threads == ["tpurpc-batcher"]
    assert b.batches_run == 1 and b.rows_run == 4
    assert isinstance(jax.devices()[0].platform, str)


def test_call_is_submit_then_result_on_host_rows_byte_for_byte():
    import jax

    shapes = []

    def fn(tree):
        shapes.append((type(tree["x"]), tree["x"].shape))
        return {"y": tree["x"] + 1, "z": tree["x"][:, :1]}

    rng = np.random.default_rng(7)
    rows = [rng.standard_normal((2, 5)).astype(np.float32) for _ in range(3)]
    b = FanInBatcher(fn, max_batch=3, max_delay_s=60.0)
    outs = [None] * 3
    try:
        with ledger.track() as w:
            ts = [threading.Thread(
                target=lambda i=i: outs.__setitem__(i, b({"x": rows[i]})))
                for i in range(3)]
            [t.start() for t in ts]
            [t.join(30) for t in ts]
    finally:
        b.close()
    for x, out in zip(rows, outs):
        assert out["y"].tobytes() == (x + 1).tobytes()
        assert out["z"].tobytes() == x[:, :1].tobytes()
    # host rows: numpy concat, one h2d, nothing stacked on the device
    assert shapes and issubclass(shapes[0][0], jax.Array)
    assert w["dma_d2d"] == 0


def test_host_rows_beside_device_rows_are_landed_and_stacked(events):
    fn, seen = recorder(events)
    b = FanInBatcher(fn, max_batch=4, max_delay_s=60.0, fixed_bucket=True,
                     occupancy=True)
    try:
        with ledger.track() as w:
            futures = [b.submit(device_row(1)),
                       b.submit({"x": np.full(ROW, 2, np.float32)}),
                       b.submit(device_row(3)),
                       b.submit({"x": np.full(ROW, 4, np.float32)})]
            [f.result(30) for f in futures]
    finally:
        b.close()
    (batch, n), = seen
    assert n == 4 and [int(v) for v in batch[:, 0, 0]] == [1, 2, 3, 4]
    assert w["dma_h2d"] == 2 * 128 and w["dma_d2d"] == 4 * 128


@pytest.mark.parametrize("where", ["host", "device"])
def test_a_row_without_its_axis_gets_its_reply_without_it(where):
    def fn(batch):
        assert batch["x"].shape == (4, 4, 8)
        return {"y": batch["x"] + 1}

    rows = [np.full(ROW[1:], k, np.float32) for k in range(3)]
    b = FanInBatcher(fn, max_batch=4, max_delay_s=60.0, fixed_bucket=True)
    try:
        futures = [b.submit(device_row(k, ROW[1:]) if where == "device"
                            else {"x": rows[k]}, one_row=True)
                   for k in range(3)]
        with_axis = b.submit(device_row(9))    # cannot ride with them
        b.close()
        for k, f in enumerate(futures):
            assert f.result(30)["y"].tobytes() == (rows[k] + 1).tobytes()
        with pytest.raises(ValueError, match="incompatible"):
            with_axis.result(30)
    finally:
        b.close()


# -- credit --------------------------------------------------------------------------

def test_a_fifth_landing_waits_until_a_batch_has_taken_the_first(events):
    """A 16 KiB window, 4 KiB rows: four rows of one producer sit in the
    batcher with their leases, so its fifth landing cannot fit; it goes
    through when the batch that holds the first has been stacked."""
    fn, seen = recorder(events)
    ring, other = HbmRing(16384), HbmRing(16384)
    row = np.arange(1024, dtype=np.float32)
    b = FanInBatcher(fn, max_batch=8, max_delay_s=60.0, fixed_bucket=True,
                     occupancy=True)

    def land_and_submit(r, k):
        lease = r.land(row + k, np.dtype(np.float32), (1, 1024), timeout=30)
        return b.submit({"x": lease.array}, leases=[lease])

    try:
        futures = [land_and_submit(ring, k) for k in range(4)]
        assert ring.writable() == 0
        with pytest.raises(BufferError):   # asked not to wait: it cannot fit
            ring.land(row, np.dtype(np.float32), (1, 1024))
        before = counters()
        fifth = []
        t = threading.Thread(target=lambda: fifth.append(
            land_and_submit(ring, 4)))
        t.start()
        deadline = threading.Event()
        while not ring._space._waiters and t.is_alive():
            deadline.wait(0.001)        # until the landing is parked
        assert t.is_alive() and not fifth
        assert not any(f.done() for f in futures)
        # the other producer fills the batch: it goes out by size
        futures += [land_and_submit(other, 10 + k) for k in range(4)]
        t.join(30)
        assert not t.is_alive() and len(fifth) == 1
        assert [f.result(30) for f in futures] == [None] * 8
        b.close()
        assert fifth[0].result(30) is None
    finally:
        b.close()
    assert moved(before, "lens_hbm_credit_ops")["lens_hbm_credit_ops"] == 1
    assert [n for _, n in seen] == [8, 1]
    assert [int(v) for v in seen[0][0][:, 0]] == [0, 1, 2, 3, 10, 11, 12, 13]
    for r in (ring, other):
        st = r.stats()
        assert st["head"] == st["tail"] and not st["live_spans"]

