"""tpurpc-express (ISSUE 9): one-sided rendezvous bulk-tensor plane.

Covers the landing pool's lifetime rules (weakref-finalize recycling, size
classes, budget refusal, death-path quarantine), the end-to-end transfer on
the native-framing plane (TCP and ring platforms) and the gRPC wire plane,
the copy-ledger zero-host-landing-copy proof, the framed fallback, the
flight/watchdog evidence, and the TPU-plane halves (SerializeFromDevice
into a window, descriptor-only codec)."""

import collections
import gc
import heapq
import mmap
import threading
import time
import uuid

import numpy as np
import pytest

import tpurpc.core.rendezvous as rdv
from tpurpc.tpu import ledger


@pytest.fixture
def fresh_config(monkeypatch):
    """Platform/env changes need a config rebuild; restore after."""
    from tpurpc.utils import config as config_mod

    yield monkeypatch
    config_mod.set_config(None)


def _reset_platform(monkeypatch, platform):
    from tpurpc.utils import config as config_mod

    monkeypatch.setenv("GRPC_PLATFORM_TYPE", platform)
    config_mod.set_config(None)


# ---------------------------------------------------------------------------
# landing pool
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nbytes,cls", [
    (1, 64 << 10), (64 << 10, 64 << 10),         # the floor
    ((64 << 10) + 1, 80 << 10), (100_000, 112 << 10),
    (128 << 10, 128 << 10),                      # a power of two is a class
    ((4 << 20) + 183, 5 << 20),                  # a 4 MiB tensor + header
    (5 << 20, 5 << 20), ((5 << 20) + 1, 6 << 20),
    ((7 << 20) + 1, 8 << 20), ((8 << 20) + 1, 10 << 20),
])
def test_size_class_four_an_octave(nbytes, cls):
    """A region is at most a quarter larger than its transfer, and a class
    is its own class (grants are filed under their capacity)."""
    assert rdv.size_class(nbytes) == cls
    assert rdv.size_class(cls) == cls
    assert cls % 64 == 0 and cls < nbytes * 1.25 + (64 << 10)


def test_default_budget_holds_a_window_for_eight_links():
    """8 links x _PREGRANT_DEPTH standing regions of a 4 MiB tensor message
    fit the default pool with regions to spare for one-shot claims: a
    fan-in's late link finds memory (PERF.md 6, PR 33)."""
    cls = rdv.size_class((4 << 20) + 183)
    per_region = cls + 64 + 16 + 8
    assert (256 << 20) // per_region >= 8 * rdv._PREGRANT_DEPTH + 8


def test_pool_size_classes_and_alignment():
    pool = rdv.LandingPool("local")
    lease = pool.lease(100_000, 1)
    assert lease.pr.capacity == 112 * 1024  # its class, floor 64 KiB
    wrapper = lease.deliver(100_000)
    flat = np.frombuffer(wrapper, np.uint8)
    assert flat.ctypes.data % 64 == 0  # dlpack-aliasable landing span


def test_pool_recycles_only_after_last_alias_dies():
    pool = rdv.LandingPool("local")
    lease = pool.lease(70_000, 1)
    body = lease.deliver(70_000)
    view = np.frombuffer(body, np.uint8)[10:20]  # consumer alias chain
    del body
    gc.collect()
    assert pool.stats()["free_regions"] == 0  # alias still pins the region
    del view
    gc.collect()
    assert pool.stats()["free_regions"] == 1
    # and the recycled region is reused, not re-allocated
    before = pool.stats()["allocated_bytes"]
    lease2 = pool.lease(70_000, 2)
    assert pool.stats()["allocated_bytes"] == before
    lease2.release()


def test_pool_budget_refuses_not_raises():
    pool = rdv.LandingPool("local", budget=200 * 1024)  # one 112 KiB region
    l1 = pool.lease(100_000, 1)
    assert l1 is not None
    assert pool.lease(100_000, 2) is None  # over budget: refusal
    l1.release()
    assert pool.lease(100_000, 3) is not None  # freed capacity reusable


def test_pool_discard_quarantines_instead_of_pooling():
    """The peer-death path must never re-lease a region a straggling
    window might still write (the Pair.init stale-write rule)."""
    pool = rdv.LandingPool("local")
    lease = pool.lease(65_536, 1)
    lease.release(discard=True)
    assert pool.stats()["free_regions"] == 0
    # a discarded-while-aliased region defers destruction to the alias GC
    lease2 = pool.lease(65_536, 2)
    body = lease2.deliver(65_536)
    lease2.release(discard=True)
    del body
    gc.collect()
    pool.lease(65_536, 3).release()  # sweeps zombies; no crash, no reuse


def test_standing_doorbell_rings_on_alias_death():
    pool = rdv.LandingPool("local")
    lease = pool.lease(65_536, 1)
    lease.standing = True
    db_off = lease.pr.offset + lease.pr.capacity + 16
    body = lease.deliver(1024)
    assert bytes(lease.pr.region.buf[db_off:db_off + 8]) == b"\x00" * 8
    del body
    gc.collect()
    assert lease.pr.region.buf[db_off] == 1  # consumer-freed count == 1
    # a second delivery is legal now (freed == delivered)
    body2 = lease.deliver(2048)
    # ... but a THIRD while body2 is aliased is the protocol violation
    with pytest.raises(RuntimeError):
        lease.deliver(512)
    del body2
    gc.collect()
    lease.release()


# ---------------------------------------------------------------------------
# credit flow control (ISSUE 31): a full window waits for its own doorbell
# ---------------------------------------------------------------------------

_MSG = 60_000                    # one 64 KiB-class message
_REGION = 65_536 + 64 + 16 + 8   # what the pool charges for its region
_CREDIT_COUNTERS = ("rdv_fallbacks", "rdv_claims_refused",
                    "rdv_credit_waits", "rdv_credit_expired")


class _FakeTime:
    """Stands in for the ``time`` module inside core/rendezvous.py, and for
    the link's ``pump`` seam (the sender's nap): the clock moves only when
    the sender sleeps, and the consumer's frees are events on it."""

    TICK_NS = 50_000  # what a sleep(0) of the yield-poll costs

    def __init__(self):
        self.ns = 10 ** 12
        self._due = []
        self._n = 0

    def monotonic_ns(self):
        return self.ns

    def monotonic(self):
        return self.ns / 1e9

    def ms(self):
        return (self.ns - 10 ** 12) / 1e6

    def after(self, ms, fn):
        self._n += 1
        heapq.heappush(self._due, (self.ns + int(ms * 1e6), self._n, fn))

    def run_until(self, ns, pred=None):
        while self._due and self._due[0][0] <= ns:
            at, _, fn = heapq.heappop(self._due)
            self.ns = max(self.ns, at)
            fn()
            if pred is not None and pred():
                return
        self.ns = max(self.ns, ns)

    def sleep(self, s):
        self.run_until(self.ns + max(int(s * 1e9), self.TICK_NS))

    def pump(self, pred, deadline):
        if not pred():
            self.run_until(int(round(deadline * 1e9)), pred)


class _CreditRig:
    """Real ``RdvLink`` pairs back to back (control ops delivered
    synchronously) over one ``LandingPool`` of four regions, on a fake
    clock. The consumer keeps each message for ``residence(seq)`` fake ms
    (None: until ``free()``); a send that falls back is delivered by a
    framed stand-in, so ``got`` is what the stream layer would see."""

    def __init__(self, monkeypatch, regions=4, budget=None):
        from tpurpc.obs import metrics as _metrics

        self.clock = _FakeTime()
        monkeypatch.setattr(rdv, "time", self.clock)
        self.pool = rdv.LandingPool("local", budget=budget or
                                    regions * _REGION)
        monkeypatch.setattr(rdv, "_pools", {"local": self.pool})
        self._metrics = _metrics.registry().metrics()
        self.got = []         # sequence numbers, in delivery order
        self.held = []        # [seq, body] the consumer has not let go of
        self.residence = lambda seq: 0.0
        self.ops = collections.Counter()  # (side, op) -> control ops sent
        self._tail = b""
        self.links = []
        self.a, self.b = self.pair()
        self.seq = 0
        self.base = self.counters()

    def pair(self):
        ends = {}

        def to(side, peer):
            def send(*op):
                self.ops[side, op[0]] += 1
                ends[peer].on_op(*op)
            return send

        a = rdv.RdvLink("a", to("a", "b"), lambda *m: None,
                        pool_kinds=("local",), open_kinds=("local",),
                        pump=self.clock.pump)
        b = rdv.RdvLink("b", to("b", "a"), self.deliver,
                        pool_kinds=("local",), open_kinds=("local",))
        ends["a"], ends["b"] = a, b
        a.negotiated = b.negotiated = True
        self.links += [a, b]
        return a, b

    def deliver(self, stream_id, flags, body):
        seq = int.from_bytes(bytes(body[:8]), "little")
        assert bytes(body[8:16]) == bytes([seq % 251]) * 8
        self.got.append(seq)
        cell = [seq, body]
        del body
        self.held.append(cell)
        ms = self.residence(seq)
        if ms is not None:
            self.clock.after(ms, lambda: self.free(cell))

    def free(self, cell=None):
        """Let go of one message (the oldest held, unless told which)."""
        cell = self.held[0] if cell is None else cell
        if cell in self.held:
            self.held.remove(cell)
            cell[1] = None  # the wrapper's last alias: rings the doorbell

    def next_payload(self, nbytes=_MSG):
        """A message's gather list: its sequence number and a stamp, then
        zeros."""
        seq, self.seq = self.seq, self.seq + 1
        if len(self._tail) < nbytes - 16:
            self._tail = bytes(nbytes - 16)
        head = seq.to_bytes(8, "little") + bytes([seq % 251]) * 8
        return seq, [head, memoryview(self._tail)[:nbytes - 16]]

    def send(self, link=None, nbytes=_MSG):
        """One message; True if it went one-sided."""
        _, segs = self.next_payload(nbytes)
        ok = (link or self.a).send_message(1, 0, segs, nbytes)
        if not ok:
            self.deliver(1, 0, memoryview(bytearray(b"".join(segs))))
        return ok

    def counters(self):
        return {k: self._metrics[k].snapshot() for k in _CREDIT_COUNTERS}

    def moved(self):
        now = self.counters()
        return {k.replace("rdv_", ""): now[k] - self.base[k]
                for k in _CREDIT_COUNTERS}

    def warm(self):
        """Five messages to a consumer that lets go at once: the link ends
        with ``_PREGRANT_DEPTH`` standing regions, all free, the pool with
        nothing, and no residence has been measured (every free was found
        at a send's first look)."""
        for _ in range(5):
            assert self.send()
            self.clock.sleep(0)
        assert self.pool.lease(_MSG, 0) is None
        assert len(self.a._grants[65_536]) == rdv._PREGRANT_DEPTH
        assert not self.a._residence
        self.base = self.counters()

    def learn(self, ms=1.0):
        """Fill the window for a consumer that lets go ``ms`` later and
        send once more: the yield-poll watches the doorbells ring, which
        is one measurement."""
        self.residence = lambda seq: ms
        for _ in range(5):
            assert self.send()
        assert 65_536 in self.a._residence
        self.clock.sleep(ms / 1e3)
        self.base = self.counters()

    def fill(self):
        for _ in range(rdv._PREGRANT_DEPTH):
            assert self.send()

    def close(self):
        self.held.clear()
        for link in self.links:
            link.close()
        self.pool.trim()


@pytest.fixture
def credit_rig(monkeypatch):
    rig = _CreditRig(monkeypatch)
    yield rig
    rig.close()
    rdv.window_share().drain()


def _credit_no_history(rig):
    rig.warm()
    rig.residence = lambda seq: None
    rig.fill()
    t0 = rig.clock.ms()
    assert not rig.send()                      # the parent's path, at once
    assert rig.clock.ms() - t0 < 2.2           # the 2 ms yield-poll only
    assert rig.moved() == {"fallbacks": 1, "claims_refused": 1,
                           "credit_waits": 0, "credit_expired": 0}


def _credit_free_inside_estimate(rig):
    rig.warm()
    rig.learn(1.0)
    est = rig.a._residence[65_536]
    assert 0.9e6 <= est.mean_ns <= 1.2e6 and est.bound_ns() >= 2.9e6
    rig.residence = lambda seq: 2.5    # past the poll, inside the bound
    rig.fill()
    t0 = rig.clock.ms()
    assert rig.send()                          # waited, then one-sided
    assert 2.4 <= rig.clock.ms() - t0 <= 2.8
    # ... without asking first: a full window is the link's credit
    assert rig.moved() == {"fallbacks": 0, "claims_refused": 0,
                           "credit_waits": 1, "credit_expired": 0}
    assert est.mean_ns > 1.1e6                 # the wait fed the estimate


def _credit_steady_consumer(rig):
    import random

    rig.warm()
    rng = random.Random(31)
    rig.residence = lambda seq: rng.uniform(40.0, 60.0)
    first_wait = None
    for _ in range(400):
        rig.send()
        if first_wait is None and rig.moved()["credit_waits"]:
            first_wait = rig.moved()
    end = rig.moved()
    # until a doorbell rings inside a yield-poll there is no history and
    # the sender falls back, as the parent does; from the first wait on it
    # pays neither the copy nor an expiry
    assert first_wait is not None and first_wait["fallbacks"] < 100
    assert end["fallbacks"] == first_wait["fallbacks"]
    assert end["credit_expired"] == 0
    assert end["credit_waits"] > 250
    # and a full window that knows its residence asks for nothing beyond
    # itself: the receiver is not asked again
    assert end["claims_refused"] == first_wait["claims_refused"]
    est = rig.a._residence[65_536]
    assert 40e6 <= est.mean_ns <= 70e6 and est.bound_ns() <= 120e6
    assert rig.got == list(range(rig.seq))


def _credit_consumer_stops(rig):
    rig.warm()
    rig.learn(1.0)
    bound_ms = rig.a._residence[65_536].bound_ns() / 1e6
    rig.residence = lambda seq: None           # keeps everything
    rig.fill()
    t0 = rig.clock.ms()
    assert not rig.send()                      # expired: framed, once
    assert rig.clock.ms() - t0 <= bound_ms + 0.1
    assert rig.moved() == {"fallbacks": 1, "claims_refused": 1,
                           "credit_waits": 1, "credit_expired": 1}
    for n in (2, 3):                           # one message a bound, not a
        t0 = rig.clock.ms()                    # flood: each waits again
        assert not rig.send()
        assert bound_ms - 0.1 <= rig.clock.ms() - t0 <= bound_ms + 2.2
        # ... and asks once it has: an overdue window is when to ask
        assert rig.moved() == {"fallbacks": n, "claims_refused": n,
                               "credit_waits": n, "credit_expired": n}
    rig.free()                                 # a doorbell rings
    assert rig.send()
    assert not rig.send()                      # waits again, expires again
    assert rig.moved() == {"fallbacks": 4, "claims_refused": 4,
                           "credit_waits": 4, "credit_expired": 4}
    assert rig.got == list(range(rig.seq))


def _credit_retaining_consumer(rig):
    """A handler that gathers six messages (a window and a half) before it
    lets any go, so the fifth and the sixth each wait out a bound (one
    message beyond the window a bound): every message arrives, in order,
    and the bound settles. Fed the residences as read, which hold the
    sender's own expired waits, it grows 1.75x a batch without end."""
    rig.warm()
    rig.learn(1.0)

    def gather(seq):
        if len(rig.held) == 6:
            rig.clock.after(0.5, lambda: [rig.free() for _ in range(6)])
        return None

    rig.residence = gather
    bounds = []
    for _batch in range(40):
        for _ in range(6):
            rig.send()
        bounds.append(rig.a._residence[65_536].bound_ns())
    assert rig.got == list(range(rig.seq))
    moved = rig.moved()
    assert moved["credit_expired"] == 80 and moved["fallbacks"] == 80
    assert max(bounds[20:]) <= max(bounds[:20]) <= 4 * bounds[0]


def _credit_cut(rig, how):
    rig.warm()
    rig.learn(1.0)
    rig.residence = lambda seq: None
    rig.fill()
    t0 = rig.clock.ms()
    stopped = []
    kw = {}
    if how == "closed":
        rig.clock.after(2.3, rig.a.close)
    elif how == "stopped":
        rig.clock.after(2.3, lambda: stopped.append(1))
        kw["should_stop"] = lambda: bool(stopped)
    else:
        kw["deadline"] = rig.clock.monotonic() + 0.0023
    seq, segs = rig.next_payload()
    if how == "closed":
        # the framed path is next, where the dead transport raises
        assert not rig.a.send_message(1, 0, segs, _MSG, **kw)
    else:
        with pytest.raises(rdv.SendAbandoned):
            rig.a.send_message(1, 0, segs, _MSG, **kw)
    assert 2.25 <= rig.clock.ms() - t0 <= 2.45   # not the bound's 3 ms
    moved = rig.moved()
    assert moved["credit_waits"] == 1 and moved["credit_expired"] == 0
    assert moved["fallbacks"] == (1 if how == "closed" else 0)
    assert seq not in rig.got                    # no copy was started


def _credit_no_standing_region(rig):
    """A second link on the exhausted pool (the ninth connection of eight)
    holds no standing region: refused, it falls back, whatever the first
    link has learnt."""
    rig.warm()
    rig.learn(1.0)
    a2, _b2 = rig.pair()
    rig.residence = lambda seq: None
    t0 = rig.clock.ms()
    assert not rig.send(a2)
    assert rig.clock.ms() == t0                  # no poll, no wait
    assert rig.moved() == {"fallbacks": 1, "claims_refused": 1,
                           "credit_waits": 0, "credit_expired": 0}


def _credit_non_view_domain(rig, monkeypatch):
    """A domain whose window has no host view cannot read a doorbell:
    ``_standing_free`` answers False, nothing is ever measured, and the
    refused sender takes the parent's path."""
    rig.warm()

    class _Blind:
        view = None

        def __init__(self, win):
            self.write = lambda off, data: win.view.__setitem__(
                slice(off, off + len(data)), data)

    real = rig.a._window_for
    monkeypatch.setattr(rig.a, "_window_for", lambda c: _Blind(real(c)))
    rig.residence = lambda seq: 1.0
    for _ in range(6):
        assert not rig.send()
    assert not rig.a._residence
    assert rig.moved() == {"fallbacks": 6, "claims_refused": 6,
                           "credit_waits": 0, "credit_expired": 0}


def _credit_overdue_window_is_given_more(monkeypatch):
    """A pool with room to spare (six regions, four of them the link's
    window): the full window waits for its own credit first, and the
    overdue one asks and is given a one-shot region, so the message still
    goes one-sided."""
    rig = _CreditRig(monkeypatch, regions=6)
    try:
        for _ in range(5):
            assert rig.send()
            rig.clock.sleep(0)
        assert len(rig.a._grants[65_536]) == rdv._PREGRANT_DEPTH
        rig.learn(1.0)
        bound_ms = rig.a._residence[65_536].bound_ns() / 1e6
        rig.residence = lambda seq: None       # keeps everything
        rig.fill()
        t0 = rig.clock.ms()
        assert rig.send()                      # waited, asked, one-sided
        assert 2.0 < rig.clock.ms() - t0 <= bound_ms + 0.1
        assert rig.moved() == {"fallbacks": 0, "claims_refused": 0,
                               "credit_waits": 1, "credit_expired": 1}
        assert rig.got == list(range(rig.seq))
    finally:
        rig.close()


def _credit_estimate_seeded_from_below(rig):
    """No doorbell rings inside a yield-poll (the consumer lets go 31 ms
    on, the sender looks for 2 ms of every 7): the longest a region was
    SEEN out seeds the estimate, so the link leaves the framed path after
    one lap of its window, and not never."""
    rig.warm()
    rig.residence = lambda seq: 31.0
    rig.fill()
    while not rig.a._residence:
        rig.send()
        rig.clock.sleep(0.005)
        assert rig.seq < 20
    framed = rig.moved()["fallbacks"]
    assert 4 <= framed <= 6
    est = rig.a._residence[65_536]
    assert 20e6 <= est.mean_ns <= 31e6         # from below
    for _ in range(12):
        assert rig.send()                      # waits now, and is fed
    moved = rig.moved()
    assert moved["fallbacks"] == framed and moved["credit_expired"] == 0
    assert moved["credit_waits"] >= 2 and moved["claims_refused"] == framed
    assert rig.got == list(range(rig.seq))


@pytest.mark.parametrize("case", [
    "overdue_window_is_given_more", "estimate_seeded_from_below",
    "no_history", "free_inside_estimate", "steady_consumer_40_to_60_ms",
    "consumer_stops_then_frees", "retaining_consumer", "link_closed",
    "should_stop", "deadline", "no_standing_region", "non_view_domain"])
def test_full_window_waits_for_its_credit(credit_rig, monkeypatch, case):
    {"overdue_window_is_given_more":
        lambda rig: _credit_overdue_window_is_given_more(monkeypatch),
     "estimate_seeded_from_below": _credit_estimate_seeded_from_below,
     "no_history": _credit_no_history,
     "free_inside_estimate": _credit_free_inside_estimate,
     "steady_consumer_40_to_60_ms": _credit_steady_consumer,
     "consumer_stops_then_frees": _credit_consumer_stops,
     "retaining_consumer": _credit_retaining_consumer,
     "link_closed": lambda rig: _credit_cut(rig, "closed"),
     "should_stop": lambda rig: _credit_cut(rig, "stopped"),
     "deadline": lambda rig: _credit_cut(rig, "deadline"),
     "no_standing_region": _credit_no_standing_region,
     "non_view_domain": lambda rig: _credit_non_view_domain(
         rig, monkeypatch)}[case](credit_rig)


def test_fan_in_over_a_small_pool_stays_on_rendezvous(fresh_config):
    """``stream4m_c8`` at KiB size: eight connections stream messages over
    the size bar to a slow handler, and the landing pool holds one region
    fewer than 8 x ``_PREGRANT_DEPTH``, so it is held whole by standing
    leases and every OFFER of a full window is refused. Everything arrives,
    in order, and once a link has seen a doorbell ring it waits for the
    next instead of copying the message through the framed ring."""
    _reset_platform(fresh_config, "TCP")
    conns, msgs, nbytes = 8, 60, 300 * 1024     # the 512 KiB class
    region = 512 * 1024 + 64 + 16 + 8
    fresh_config.setenv("TPURPC_RENDEZVOUS_POOL_MB", "16")
    assert (16 << 20) // region == conns * rdv._PREGRANT_DEPTH - 1
    old_pools = dict(rdv._pools)
    rdv._pools.clear()
    from tpurpc.obs import metrics as _metrics
    from tpurpc.rpc.channel import Channel
    from tpurpc.rpc.server import Server, stream_unary_rpc_method_handler

    def sink(req_iter, ctx):
        conn, want = None, 0
        for m in req_iter:
            head = bytes(m[:16])
            c, seq = (int.from_bytes(head[:8], "little"),
                      int.from_bytes(head[8:], "little"))
            assert len(m) == nbytes and seq == want and conn in (None, c)
            conn, want = c, want + 1
            del m
            time.sleep(0.004)
        return want.to_bytes(8, "little")

    srv = Server(max_workers=conns + 2, native_dataplane=False)
    srv.add_method("/rdv.S/Sink", stream_unary_rpc_method_handler(sink))
    port = srv.add_insecure_port("127.0.0.1:0")
    srv.start()
    counters = _metrics.registry().metrics()
    names = ("rdv_fallbacks", "rdv_credit_waits", "rdv_credit_expired",
             "rdv_transfers_sent")
    before = {k: counters[k].snapshot() for k in names}
    got, errors = {}, []

    def client(c):
        try:
            with Channel(f"127.0.0.1:{port}") as ch:
                mc = ch.stream_unary("/rdv.S/Sink", tpurpc_native=False)
                body = bytes([c]) * (nbytes - 16)
                out = mc((c.to_bytes(8, "little") + s.to_bytes(8, "little")
                          + body for s in range(msgs)), timeout=120)
                got[c] = int.from_bytes(bytes(out), "little")
        except Exception as exc:  # surfaced below, on the test's thread
            errors.append(exc)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(conns)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads) and not errors, errors
        assert got == {c: msgs for c in range(conns)}
        d = {k: counters[k].snapshot() - before[k] for k in names}
        assert d["rdv_credit_waits"] > 0
        assert d["rdv_fallbacks"] <= conns * msgs // 4, d   # the parent: 30%
    finally:
        srv.stop(grace=1)
        rdv._pools.clear()
        rdv._pools.update(old_pools)


# ---------------------------------------------------------------------------
# one window a link: the largest class it streams, ridden by smaller messages
# ---------------------------------------------------------------------------

_BIG = 120_000                        # one 128 KiB-class message
_BIG_REGION = 131_072 + 64 + 16 + 8
_WINDOW_COUNTERS = ("rdv_window_received", "rdv_window_rides",
                    "rdv_window_returns", "rdv_pool_reclaims",
                    "rdv_claims_refused", "rdv_fallbacks")


def _window_counts(rig):
    m = rig._metrics
    return {k.replace("rdv_", ""): m[k].snapshot() for k in _WINDOW_COUNTERS}


def _window_moved(rig, base):
    now = _window_counts(rig)
    return {k: now[k] - base[k] for k in now}


def _warm_window(rig, nbytes):
    """Messages to a consumer that lets go at once until the link holds a
    whole window of ``nbytes``'s class."""
    for _ in range(5):
        assert rig.send(nbytes=nbytes)
        rig.clock.sleep(0)
    assert len(rig.a._grants[rdv.size_class(nbytes)]) == rdv._PREGRANT_DEPTH


def _window_ride(rig):
    """A 64 KiB-class message rides a free 128 KiB standing region, with no
    claim and no grant of its own class; it never takes a region another
    send holds, nor one whose doorbell is behind."""
    _warm_window(rig, _BIG)
    base, offers = _window_counts(rig), rig.ops["a", rdv.OP_OFFER]
    assert rig.send(nbytes=_MSG)
    moved = _window_moved(rig, base)
    assert moved["window_received"] == 1 and moved["window_rides"] == 1
    assert rig.ops["a", rdv.OP_OFFER] == offers
    assert not rig.b._pregrants_out.get(65_536)
    rig.clock.sleep(0)
    rig.residence = lambda seq: None               # keeps what it gets
    for _ in range(3):
        assert rig.send(nbytes=_MSG)               # three regions behind
    window = rig.a._grants[131_072]
    free = [c for c in window if rig.a._standing_free(c)]
    assert len(free) == 1
    free[0].inflight = True                        # another send holds it
    assert rig.a._take_grant(131_072, _MSG) is None
    free[0].inflight = False
    assert rig.a._take_grant(131_072, _MSG) is free[0]
    free[0].inflight = False
    # ... and a send that finds only those takes a one-shot region, no
    # region of the window
    base, offers = _window_counts(rig), rig.ops["a", rdv.OP_OFFER]
    free[0].inflight = True
    assert rig.send(nbytes=_MSG)
    assert _window_moved(rig, base)["window_received"] == 0
    assert rig.ops["a", rdv.OP_OFFER] == offers + 1
    assert rig.got == list(range(rig.seq))


def _window_grows(rig):
    """A link's window of 64 KiB regions grows to 128 KiB at its first
    larger message: the sender hands the four small ones back before it
    OFFERs, the receiver grants four of the new class once it completes,
    and smaller messages then ride them and are granted nothing, even when
    one of them had to be claimed."""
    _warm_window(rig, _MSG)
    assert rig.b._pregrants_out == {65_536: rdv._PREGRANT_DEPTH}
    base = _window_counts(rig)
    assert rig.send(nbytes=_BIG)
    assert rig.b._pregrants_out == {65_536: 0,
                                    131_072: rdv._PREGRANT_DEPTH}
    assert not rig.a._grants[65_536]
    assert len(rig.a._grants[131_072]) == rdv._PREGRANT_DEPTH
    assert _window_moved(rig, base)["window_returns"] == 4
    offers = rig.ops["a", rdv.OP_OFFER]
    for _ in range(8):
        rig.clock.sleep(0)
        assert rig.send(nbytes=_MSG)
    assert rig.ops["a", rdv.OP_OFFER] == offers
    assert _window_moved(rig, base)["window_rides"] == 8
    rig.clock.sleep(0)
    rig.residence = lambda seq: None
    for _ in range(rdv._PREGRANT_DEPTH):
        assert rig.send(nbytes=_MSG)               # the window, full
    assert rig.send(nbytes=_MSG)                   # a one-shot claim
    assert rig.ops["a", rdv.OP_OFFER] == offers + 1
    assert rig.b._pregrants_out == {65_536: 0,
                                    131_072: rdv._PREGRANT_DEPTH}
    assert len(rig.a._grants[131_072]) == rdv._PREGRANT_DEPTH
    moved = _window_moved(rig, base)
    assert moved["window_rides"] == 12 and moved["claims_refused"] == 0
    assert rig.got == list(range(rig.seq))


def _window_hands_back_only_free(rig):
    """Before an OFFER larger than its window the sender hands back only
    its free standing grants: not the two the consumer still holds, not
    the one another send owns. Those go back once a later send finds them
    free, being smaller than the window that grew meanwhile."""
    _warm_window(rig, _MSG)
    rig.residence = lambda seq: None
    assert rig.send() and rig.send()               # two regions aliased
    small = rig.a._grants[65_536]
    busy = [c for c in small if not rig.a._standing_free(c)]
    owned = [c for c in small if c not in busy][0]
    owned.inflight = True
    base, releases = _window_counts(rig), rig.ops["a", rdv.OP_RELEASE]
    assert rig.send(nbytes=_BIG)
    assert rig.ops["a", rdv.OP_RELEASE] == releases + 1
    assert _window_moved(rig, base)["window_returns"] == 1
    assert sorted(map(id, small)) == sorted(map(id, busy + [owned]))
    assert rig.b._pregrants_out[65_536] == 3
    assert len(rig.a._grants[131_072]) == rdv._PREGRANT_DEPTH
    assert all(not rig.a._standing_free(c) for c in busy)
    owned.inflight = False
    rig.residence = lambda seq: 0.0
    while rig.held:
        rig.free()
    assert rig.send()                              # rides the new window
    assert rig.ops["a", rdv.OP_RELEASE] == releases + 4
    assert not rig.a._grants[65_536] and rig.b._pregrants_out[65_536] == 0
    assert _window_moved(rig, base)["window_returns"] == 4
    assert rig.got == list(range(rig.seq))


@pytest.mark.parametrize("case", ["ride", "grows",
                                  "hands_back_only_free"])
def test_one_window_a_link(monkeypatch, case):
    rig = _CreditRig(monkeypatch, budget=5 * _REGION + 5 * _BIG_REGION)
    try:
        {"ride": _window_ride, "grows": _window_grows,
         "hands_back_only_free": _window_hands_back_only_free}[case](rig)
    finally:
        rig.close()
        rdv.window_share().drain()


def test_pool_closes_free_regions_of_other_classes_for_budget():
    """A class with no free region is made room for by closing FREE
    regions of other classes; a leased, a standing and an aliased region
    are never closed, and where the free ones are not enough the lease is
    refused with nothing closed."""
    from tpurpc.obs import metrics as _metrics

    reclaims = _metrics.registry().metrics()["rdv_pool_reclaims"]
    pool = rdv.LandingPool("local", budget=4 * _REGION + _BIG_REGION)
    leased = pool.lease(_MSG, 1)
    aliased = pool.lease(_MSG, 2)
    body = aliased.deliver(16)
    standing = pool.lease(_BIG, 3)
    standing.standing = True
    spare = [pool.lease(_MSG, 4 + i) for i in range(2)]
    for lease in spare:
        lease.release()
    assert pool.stats() == {"allocated_bytes": 4 * _REGION + _BIG_REGION,
                            "free_regions": 2}
    r0 = reclaims.snapshot()
    big = pool.lease(_BIG, 7)                      # closes the two free
    assert big is not None and reclaims.snapshot() == r0 + 2
    assert pool.stats() == {"allocated_bytes": 2 * _REGION + 2 * _BIG_REGION,
                            "free_regions": 0}
    assert pool.lease(_BIG, 8) is None             # nothing free to close
    assert reclaims.snapshot() == r0 + 2
    for lease in (leased, aliased, standing, big):
        pr = lease.pr
        pr.region.buf[pr.offset:pr.offset + 4] = b"live"
    assert bytes(body[:4]) == b"live"              # the alias sees its region
    standing.deliver(8)
    del body
    gc.collect()
    for lease in (leased, aliased, standing, big):
        lease.release()
    gc.collect()
    assert pool.stats()["free_regions"] == 4
    pool.trim()


_PAGE = 64 * 576 * 2                  # one bf16 page of one MLA layer


def _page_messages(rng, layers=61, chunk=64):
    """One prefill worker's page messages, in pages: a warm-up of a 1- and
    a 4-page sequence, then lognormal lengths (mean 7,590 tokens, sigma 1,
    clipped to 256 to 32,768) up to the first of a whole chunk and two
    more, each chunk by chunk of 64 pages and layer by layer within a
    chunk."""
    mu = np.log(7590) - 0.5
    pages, more = [1, 4], 2
    while more:
        pages.append(-(-int(np.clip(rng.lognormal(mu, 1.0), 256, 32768))
                       // 64))
        more -= max(pages[2:]) >= chunk
    out = []
    for total in pages:
        for first in range(0, total, chunk):
            out += [min(chunk, total - first)] * layers
    return out


def test_real_page_sizes_over_eight_links_stay_one_sided(monkeypatch):
    """Eight links stream the page cell's real message sizes (1 to 64
    pages of 73,728 B) against the default 256 MiB budget, interleaved, to
    a consumer that keeps each message 0.5 to 3 ms. Once a link has sent
    its first 64-page message its window is 5 MiB regions, which every
    later message rides: no claim is refused and every message over the
    bar goes one-sided. A standing window a class, kept for good, held
    the pool whole and refused a third of the claims."""
    rig = _CreditRig(monkeypatch, budget=256 << 20)

    def alloc(nbytes):                 # pages mapped only where written
        handle = f"local:{uuid.uuid4().hex}"
        buf = mmap.mmap(-1, nbytes)
        with rdv._pair.LocalDomain._lock:
            rdv._pair.LocalDomain._registry[handle] = buf
        return rdv._pair.Region(
            handle, buf,
            lambda: rdv._pair.LocalDomain._registry.pop(handle, None))

    def place_head(win, placed):       # the stamp, not the zeros after it
        off, src = placed[0]
        win.view[off:off + 16] = src[:16]

    monkeypatch.setattr(rig.pool._domain, "alloc", alloc)
    monkeypatch.setattr(rdv, "_place_spans", place_head)
    rng = np.random.default_rng(2407000079)
    draws = np.random.default_rng(42)
    rig.residence = lambda seq: float(draws.uniform(0.5, 3.0))
    links = [rig.a] + [rig.pair()[0] for _ in range(7)]
    plans = [_page_messages(rng) for _ in links]
    windowed = [False] * len(links)
    after = {"framed": 0, "sent": 0}
    base = None
    try:
        for step in range(max(map(len, plans))):
            for i, link in enumerate(links):
                if step >= len(plans[i]):
                    continue
                nbytes = plans[i][step] * _PAGE + 183
                if nbytes < rdv.min_bytes():
                    _, segs = rig.next_payload(nbytes)   # framed, as sent
                    rig.deliver(1, 0, memoryview(bytearray(b"".join(segs))))
                    continue
                ok = rig.send(link, nbytes)
                if windowed[i]:
                    after["sent"] += 1
                    after["framed"] += not ok
                if plans[i][step] == 64 and not windowed[i]:
                    windowed[i] = True
                    if all(windowed):
                        base = _window_counts(rig)
        assert all(windowed) and after["sent"] > 1000
        assert after["framed"] == 0
        moved = _window_moved(rig, base)
        assert moved["claims_refused"] == 0 and moved["fallbacks"] == 0
        assert moved["window_rides"] > 0
        for link in links:
            assert {c.capacity for b in link._grants.values() for c in b
                    if c.standing} == {5 << 20}
        assert rig.pool.stats()["allocated_bytes"] <= 256 << 20
        assert rig.got == list(range(rig.seq))
    finally:
        rig.close()
        rdv.window_share().drain()


def test_one_class_stream_sends_the_control_frames_it_always_did(
        credit_rig):
    """A link that streams one size class (every other cell) holds its
    four regions of that class and sends, op for op, the control frames
    it sent before windows could grow: the window rides nothing, hands
    nothing back, and the pool closes nothing."""
    import random

    rig = credit_rig
    base = _window_counts(rig)
    rig.warm()
    rig.learn(1.0)
    rng = random.Random(42)
    # now and then the consumer keeps a message past the bound: waits
    # expire, claims are refused, messages go framed
    rig.residence = lambda seq: (rng.uniform(0.5, 6.0) if rng.random() < 0.9
                                 else rng.uniform(20.0, 60.0))
    for _ in range(300):
        rig.send()
    assert rig.got == list(range(rig.seq))
    assert dict(rig.ops) == {("a", rdv.OP_OFFER): 15,
                             ("b", rdv.OP_CLAIM): 19,
                             ("a", rdv.OP_COMPLETE): 296}
    assert rig.moved() == {"fallbacks": 14, "claims_refused": 14,
                           "credit_waits": 73, "credit_expired": 14}
    moved = _window_moved(rig, base)
    assert (moved["window_rides"], moved["window_returns"],
            moved["pool_reclaims"]) == (0, 0, 0)


def test_window_under_many_sender_threads_delivers_each_message_once():
    """Twelve threads send 64 and 128 KiB-class messages over one link at
    a 10 us switch interval, so windows grow, regions are handed back and
    pool regions are closed while other sends take grants: every message
    arrives once, and no standing region is completed while its previous
    delivery is aliased (``RegionLease.deliver`` refuses that, and the
    message would be missing)."""
    import sys

    pool = rdv.LandingPool("local", budget=12 * _REGION + 6 * _BIG_REGION)
    old_pools = dict(rdv._pools)
    rdv._pools.clear()
    rdv._pools["local"] = pool
    got, lock, ends = [], threading.Lock(), {}

    def deliver(stream_id, flags, body):
        with lock:
            got.append(int.from_bytes(bytes(body[:8]), "little"))

    a = rdv.RdvLink("a", lambda *op: ends["b"].on_op(*op), lambda *m: None,
                    pool_kinds=("local",), open_kinds=("local",))
    b = rdv.RdvLink("b", lambda *op: ends["a"].on_op(*op), deliver,
                    pool_kinds=("local",), open_kinds=("local",))
    ends["a"], ends["b"] = a, b
    a.negotiated = b.negotiated = True
    tail = bytes(_BIG)
    errors = []

    def sender(t):
        try:
            for i in range(60):
                seq = t * 1000 + i
                n = _BIG if i >= 10 and (seq * 7) % 5 < 2 else _MSG
                segs = [seq.to_bytes(8, "little"), tail[:n - 8]]
                if not a.send_message(1, 0, segs, n):
                    deliver(1, 0, b"".join(segs))   # the framed stand-in
        except Exception as exc:  # surfaced below, on the test's thread
            errors.append(exc)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    threads = [threading.Thread(target=sender, args=(t,))
               for t in range(12)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads) and not errors, errors
        assert sorted(got) == sorted(t * 1000 + i for t in range(12)
                                     for i in range(60))
        assert pool.stats()["allocated_bytes"] <= 12 * _REGION + \
            6 * _BIG_REGION
    finally:
        sys.setswitchinterval(interval)
        a.close()
        b.close()
        pool.trim()
        rdv._pools.clear()
        rdv._pools.update(old_pools)
        rdv.window_share().drain()


# ---------------------------------------------------------------------------
# end-to-end: native framing plane
# ---------------------------------------------------------------------------

def _echo_server(**kw):
    from tpurpc.rpc.server import Server, unary_unary_rpc_method_handler

    # the Python data plane: ring-platform servers otherwise adopt
    # connections onto the native C loop, which does not speak the
    # rendezvous control frames (negotiation correctly leaves such
    # connections on the framed path)
    kw.setdefault("native_dataplane", False)
    srv = Server(max_workers=4, **kw)
    srv.add_method("/rdv.S/Echo",
                   unary_unary_rpc_method_handler(
                       lambda req, ctx: bytes(req)))
    port = srv.add_insecure_port("127.0.0.1:0")
    srv.start()
    return srv, port


@pytest.mark.parametrize("platform", ["TCP", "RDMA_BPEV"])
def test_big_unary_roundtrip_both_directions(fresh_config, platform):
    _reset_platform(fresh_config, platform)
    from tpurpc.obs import metrics as _metrics
    from tpurpc.rpc.channel import Channel

    sent0 = _metrics.registry().metrics()["rdv_transfers_sent"].snapshot()
    srv, port = _echo_server()
    try:
        with Channel(f"127.0.0.1:{port}") as ch:
            mc = ch.unary_unary("/rdv.S/Echo", tpurpc_native=False)
            # small calls keep the framed path untouched — and the first
            # one also settles the capability hello exchange (a big send
            # racing the hello simply frames; steady state never does)
            assert bytes(mc(b"tiny", timeout=10)) == b"tiny"
            big = bytes(range(256)) * (4096 + 13)  # ~1 MiB, patterned
            out = mc(big, timeout=30)
            assert bytes(out) == big
        sent = _metrics.registry().metrics()["rdv_transfers_sent"].snapshot()
        assert sent >= sent0 + 2  # request AND response rode the bulk plane
    finally:
        srv.stop(grace=1)


def test_tensor_stream_zero_host_landing_copies(fresh_config):
    """The acceptance claim: on the rendezvous path the copy ledger shows
    the one-sided write (rdma_write) and the aliasing decode (zero_copy) —
    and ZERO host landing copies of the payload."""
    _reset_platform(fresh_config, "RDMA_BPEV")
    from tpurpc.jaxshim import TensorClient, add_tensor_method
    from tpurpc.rpc.channel import Channel
    from tpurpc.rpc.server import Server

    srv = Server(max_workers=4, native_dataplane=False)

    def consume(req_iter):
        total = 0
        checks = 0.0
        for tree in req_iter:
            arr = tree["x"]          # zero-copy view over the landing region
            total += arr.nbytes
            checks += float(arr[0, 0]) + float(arr[-1, -1])
        yield {"bytes": np.int64(total), "check": np.float64(checks)}

    add_tensor_method(srv, "Sink", consume, kind="stream_stream")
    port = srv.add_insecure_port("127.0.0.1:0")
    srv.start()
    payload = np.random.default_rng(7).standard_normal(
        (512, 512)).astype(np.float32)  # 1 MiB
    try:
        with Channel(f"127.0.0.1:{port}") as ch:
            cli = TensorClient(ch)

            def gen(k):
                for _ in range(k):
                    yield {"x": payload}

            list(cli.duplex("Sink", gen(2), native=False, timeout=60))
            n = 8
            with ledger.track() as w:
                replies = list(cli.duplex("Sink", gen(n), native=False,
                                          timeout=60))
            total = int(np.asarray(replies[-1]["bytes"]).ravel()[0])
            assert total == n * payload.nbytes
            expect = n * (float(payload[0, 0]) + float(payload[-1, -1]))
            assert abs(float(np.asarray(
                replies[-1]["check"]).ravel()[0]) - expect) < 1e-3
            # every payload byte moved by exactly one one-sided write...
            assert w["rdma_write"] >= n * payload.nbytes
            # ...and landed ZERO host copies (the small control/reply
            # frames still ride the instrumented framed path)
            assert w["host_copy"] < 64 * 1024, w.delta
    finally:
        srv.stop(grace=1)


def test_disabled_rendezvous_keeps_framed_path(fresh_config):
    _reset_platform(fresh_config, "TCP")
    fresh_config.setenv("TPURPC_RENDEZVOUS", "0")
    from tpurpc.obs import metrics as _metrics
    from tpurpc.rpc.channel import Channel

    sent0 = _metrics.registry().metrics()["rdv_transfers_sent"].snapshot()
    srv, port = _echo_server()
    try:
        with Channel(f"127.0.0.1:{port}") as ch:
            mc = ch.unary_unary("/rdv.S/Echo", tpurpc_native=False)
            big = b"q" * (1 << 20)
            assert bytes(mc(big, timeout=30)) == big
        assert _metrics.registry().metrics()[
            "rdv_transfers_sent"].snapshot() == sent0
    finally:
        srv.stop(grace=1)


def test_pool_exhaustion_falls_back_to_framed(fresh_config):
    """A refused claim degrades to the framed path — never an error,
    never a hang."""
    _reset_platform(fresh_config, "TCP")
    fresh_config.setenv("TPURPC_RENDEZVOUS_POOL_MB", "1")  # 1 MiB budget
    # fresh pools so the tiny budget binds (the process-global pool may
    # hold regions from earlier tests)
    old_pools = dict(rdv._pools)
    rdv._pools.clear()
    from tpurpc.obs import metrics as _metrics
    from tpurpc.rpc.channel import Channel

    srv, port = _echo_server()
    try:
        with Channel(f"127.0.0.1:{port}") as ch:
            mc = ch.unary_unary("/rdv.S/Echo", tpurpc_native=False)
            big = b"f" * (4 << 20)  # 4 MiB > the whole pool budget
            out = mc(big, timeout=60)
            assert bytes(out) == big
        assert _metrics.registry().metrics()[
            "rdv_fallbacks"].snapshot() >= 1
    finally:
        srv.stop(grace=1)
        rdv._pools.clear()
        rdv._pools.update(old_pools)


def test_flight_sequence_offer_claim_write_complete(fresh_config):
    _reset_platform(fresh_config, "TCP")
    from tpurpc.obs import flight
    from tpurpc.rpc.channel import Channel

    flight.RECORDER.reset()
    srv, port = _echo_server()
    try:
        with Channel(f"127.0.0.1:{port}") as ch:
            mc = ch.unary_unary("/rdv.S/Echo", tpurpc_native=False)
            assert bytes(mc(b"warm", timeout=10)) == b"warm"  # hello settles
            big = b"e" * (1 << 20)
            assert bytes(mc(big, timeout=30)) == big
        events = [e for e in flight.snapshot()
                  if e["event"].startswith("rdv-")]
        order = [e["event"] for e in events]
        for name in ("rdv-offer", "rdv-claim", "rdv-write", "rdv-complete"):
            assert name in order, order
        # per-transfer ordering: for every sender-side write, the SAME
        # link's claim of the SAME lease precedes it and its complete
        # follows (one link is sender for requests AND receiver for
        # responses, so ordering is per (tag, lease), not per tag)
        for w in [e for e in events if e["event"] == "rdv-write"]:
            tag, lease = w["tag"], w["a1"]
            t_claim = [e["t_ns"] for e in events
                       if e["event"] == "rdv-claim" and e["tag"] == tag
                       and e["a2"] == lease]
            t_done = [e["t_ns"] for e in events
                      if e["event"] == "rdv-complete" and e["tag"] == tag
                      and e["a1"] == lease]
            assert t_claim and min(t_claim) <= w["t_ns"], events
            assert t_done and w["t_ns"] <= max(t_done), events
    finally:
        srv.stop(grace=1)


def test_watchdog_names_rendezvous_stage(fresh_config):
    """A claim-starved sender (drop_offers chaos seam) must be diagnosed
    by the watchdog as stuck in the `rendezvous` stage."""
    _reset_platform(fresh_config, "TCP")
    fresh_config.setenv("TPURPC_RENDEZVOUS_CLAIM_TIMEOUT_S", "3")
    from tpurpc.obs import flight, watchdog
    from tpurpc.rpc.channel import Channel

    flight.RECORDER.reset()
    wd = watchdog.get()
    wd.reset()
    prev = (wd.min_stall_s, wd.sweep_s)
    wd.min_stall_s, wd.sweep_s = 0.3, 0.1
    srv, port = _echo_server()
    rdv.TEST_HOOKS["drop_offers"] = True
    try:
        with Channel(f"127.0.0.1:{port}") as ch:
            mc = ch.unary_unary("/rdv.S/Echo", tpurpc_native=False)
            big = b"w" * (1 << 20)
            result = {}

            def call():
                result["out"] = bytes(mc(big, timeout=30))

            t = threading.Thread(target=call)
            t.start()
            diag = None
            deadline = time.monotonic() + 10
            while diag is None and time.monotonic() < deadline:
                time.sleep(0.15)
                for d in wd.sweep_once():
                    if d["stage"] == "rendezvous":
                        diag = d
                        break
            assert diag is not None, wd.active()
            assert "offer" in diag["detail"]
            # after the claim timeout the sender falls back to the framed
            # path — the call COMPLETES despite the starved bulk plane
            t.join(timeout=30)
            assert result.get("out") == big
    finally:
        rdv.TEST_HOOKS.pop("drop_offers", None)
        wd.min_stall_s, wd.sweep_s = prev
        wd.reset()
        srv.stop(grace=1)


# ---------------------------------------------------------------------------
# end-to-end: gRPC wire plane
# ---------------------------------------------------------------------------

def test_h2_plane_big_payloads_bypass_data_frames(fresh_config):
    _reset_platform(fresh_config, "TCP")
    from tpurpc.obs import metrics as _metrics
    from tpurpc.wire.h2_client import H2Channel

    sent0 = _metrics.registry().metrics()["rdv_transfers_sent"].snapshot()
    srv, port = _echo_server()
    try:
        with H2Channel(f"127.0.0.1:{port}") as ch:
            mc = ch.unary_unary("/rdv.S/Echo")
            assert bytes(mc(b"small", timeout=10)) == b"small"  # settles
            big = bytes(range(251)) * 8192  # ~2 MiB patterned
            out = mc(big, timeout=30)
            assert bytes(out) == big
        assert _metrics.registry().metrics()[
            "rdv_transfers_sent"].snapshot() >= sent0 + 2
    finally:
        srv.stop(grace=1)


# ---------------------------------------------------------------------------
# TPU plane: SerializeFromDevice, descriptor codec
# ---------------------------------------------------------------------------

def test_device_reply_leaves_by_rendezvous_zero_host_staging(fresh_config):
    """``SerializeFromDevice`` end to end (the product path that replaced
    ``serialize_into``): a ``device=True`` reply over the size bar is read
    back once (``dma_d2h``), its gather list is placed one-sided into the
    client's landing region (``rdma_write``, ``rdv_bytes_sent``) and no host
    staging copy of the payload is made on either side."""
    import jax

    from tpurpc.jaxshim import TensorClient, add_tensor_method
    from tpurpc.obs import metrics as _metrics
    from tpurpc.rpc.channel import Channel
    from tpurpc.rpc.server import Server
    from tpurpc.tpu import serialize

    _reset_platform(fresh_config, "RDMA_TPU")
    fresh_config.setattr(serialize, "_on_device",
                         lambda x: isinstance(x, jax.Array))
    srv = Server(max_workers=2)
    add_tensor_method(srv, "Call", lambda t: {"y": t["x"] + 1},
                      device=True)
    srv.start()
    port = srv.add_insecure_port("127.0.0.1:0")
    sent = _metrics.registry().metrics()["rdv_bytes_sent"]
    x = np.arange(1 << 18, dtype=np.float32)   # 1 MiB, over the 256 KiB bar
    try:
        with Channel(f"127.0.0.1:{port}") as ch:
            cli = TensorClient(ch)
            cli.call("Call", {"x": x}, timeout=30)   # hello, grants
            sent0 = sent.snapshot()
            with ledger.track() as w:
                y = cli.call("Call", {"x": x}, timeout=30)["y"]
                assert np.array_equal(y, x + 1)
                del y
            moved = sent.snapshot() - sent0
    finally:
        srv.stop(grace=0)
    assert moved >= 2 * x.nbytes            # request and reply, one process
    assert w["rdma_write"] == moved, w.delta
    assert w["dma_d2h"] == x.nbytes and w["dma_d2h_ops"] == 1, w.delta
    assert w["host_copy"] < 4096, w.delta   # control frames, never payload


def test_codec_descriptor_only_encode_roundtrip():
    from tpurpc.jaxshim import codec

    x = np.random.default_rng(3).standard_normal((65, 3)).astype(np.float32)
    desc, payload = codec.encode_tensor_descriptor(x)
    assert len(desc) % 64 == 0          # descriptor pads to the alignment
    assert payload.nbytes == x.nbytes   # payload view aliases the array
    back = codec.decode_tensor_external(desc, payload)
    assert np.allclose(back, x)
    with pytest.raises(codec.CodecError):
        codec.decode_tensor_external(desc, payload[:100])  # short payload


def test_recv_limit_not_bypassed(fresh_config):
    """The bulk plane must not become a max_receive_message_length bypass:
    an over-limit OFFER is refused, the framed fallback carries the
    payload, and the framed oversize machinery rejects it properly."""
    _reset_platform(fresh_config, "TCP")
    from tpurpc.rpc.channel import Channel
    from tpurpc.rpc.server import Server, unary_unary_rpc_method_handler
    from tpurpc.rpc.status import RpcError, StatusCode

    srv = Server(max_workers=4, native_dataplane=False,
                 max_receive_message_length=512 * 1024)
    srv.add_method("/rdv.S/Echo",
                   unary_unary_rpc_method_handler(
                       lambda req, ctx: bytes(req)))
    port = srv.add_insecure_port("127.0.0.1:0")
    srv.start()
    try:
        with Channel(f"127.0.0.1:{port}") as ch:
            mc = ch.unary_unary("/rdv.S/Echo", tpurpc_native=False)
            assert bytes(mc(b"ok", timeout=10)) == b"ok"
            with pytest.raises(RpcError) as exc:
                mc(b"z" * (1 << 20), timeout=30)
            assert exc.value.code() == StatusCode.RESOURCE_EXHAUSTED
    finally:
        srv.stop(grace=1)


# ---------------------------------------------------------------------------
# cross-plane interop: the native (C) planes speak the same ladder
# ---------------------------------------------------------------------------
# tpurpc-ironclad: tpr_rdv.cc mirrors rendezvous.py byte for byte, so every
# pairing of {python, native} x {client, server} must move bulk payloads
# over the same OFFER/CLAIM/COMPLETE wire and the same ctrl-ring slots. The
# native ledger (tpr_rdv_counters) is process-global — both in-process C
# planes report into it.

def _native_counters():
    from tpurpc.rpc import native_client

    return native_client.rdv_counters()


def _stream_total_server(**kw):
    from tpurpc.rpc.server import Server, stream_stream_rpc_method_handler

    srv = Server(max_workers=4, **kw)

    def total(req_iter, ctx):
        n = 0
        for m in req_iter:
            n += len(m)
        yield str(n).encode()

    srv.add_method("/rdvnat.S/Total",
                   stream_stream_rpc_method_handler(total))
    port = srv.add_insecure_port("127.0.0.1:0")
    srv.start()
    return srv, port


def _require_native():
    if _native_counters() is None:
        pytest.skip("native data plane unavailable")


@pytest.mark.parametrize("platform", ["RDMA_BP", "RDMA_BPEV"])
def test_native_both_planes_stream_rendezvous(fresh_config, platform):
    """native client <-> native server: the stream's bulk payloads ride
    the C ladder — the native ledger proves zero fallbacks and (near-)zero
    host landing copies."""
    _reset_platform(fresh_config, platform)
    _require_native()
    from tpurpc.rpc.channel import Channel

    srv, port = _stream_total_server()
    payload = bytes(range(256)) * 4096  # 1 MiB, patterned
    n = 4
    try:
        with Channel(f"127.0.0.1:{port}") as ch:
            mc = ch.stream_stream("/rdvnat.S/Total")
            # a tiny warmup stream settles the capability hello (a big
            # send racing the hello frames, correctly); snapshot after
            list(mc(iter([b"warm"]), timeout=30))
            c0 = _native_counters()
            out = list(mc(iter([payload] * n), timeout=60))
        assert out[-1] == str(n * len(payload)).encode()
        c1 = _native_counters()
        assert c1["rdv_sent"] - c0["rdv_sent"] >= n
        assert c1["rdv_recv"] - c0["rdv_recv"] >= n
        assert c1["rdv_fallback"] == c0["rdv_fallback"]
        assert (c1["rdv_bytes_sent"] - c0["rdv_bytes_sent"]
                >= n * len(payload))
        # the tiny reply is the only framed payload on the negotiated link
        assert c1["host_copy_bytes"] - c0["host_copy_bytes"] < 64 * 1024
    finally:
        srv.stop(grace=1)


def test_python_client_native_server_rendezvous(fresh_config):
    """python client plane -> native server plane: the Python CtrlPeer's
    offers land in the C Link, and the C server's bulk echo comes back
    through the Python receiver — both ledgers move."""
    _reset_platform(fresh_config, "RDMA_BPEV")
    _require_native()
    from tpurpc.obs import metrics as _metrics
    from tpurpc.rpc.channel import Channel
    from tpurpc.rpc.server import Server, unary_unary_rpc_method_handler

    srv = Server(max_workers=4)  # ring platform: adopts onto the C loop
    srv.add_method("/rdvnat.S/Echo",
                   unary_unary_rpc_method_handler(
                       lambda req, ctx: bytes(req)))
    port = srv.add_insecure_port("127.0.0.1:0")
    srv.start()
    py_sent0 = _metrics.registry().metrics()["rdv_transfers_sent"].snapshot()
    try:
        c0 = _native_counters()
        with Channel(f"127.0.0.1:{port}") as ch:
            mc = ch.unary_unary("/rdvnat.S/Echo", tpurpc_native=False)
            assert bytes(mc(b"tiny", timeout=10)) == b"tiny"  # settle hello
            big = bytes(range(256)) * (4096 + 3)
            assert bytes(mc(big, timeout=60)) == big
        c1 = _native_counters()
        # the request landed in the C server's pool...
        assert c1["rdv_recv"] - c0["rdv_recv"] >= 1
        # ...and the response left through the C sender role
        assert c1["rdv_sent"] - c0["rdv_sent"] >= 1
        # the python client's own ledger saw its send
        assert _metrics.registry().metrics()[
            "rdv_transfers_sent"].snapshot() >= py_sent0 + 1
    finally:
        srv.stop(grace=1)


def test_native_client_python_server_rendezvous(fresh_config):
    """native client plane -> python server plane: the C Link's offers are
    claimed by rendezvous.py, and the bulk echo comes back the other way."""
    _reset_platform(fresh_config, "RDMA_BPEV")
    _require_native()
    from tpurpc.obs import metrics as _metrics
    from tpurpc.rpc.channel import Channel
    from tpurpc.rpc.server import Server, unary_unary_rpc_method_handler

    srv = Server(max_workers=4, native_dataplane=False)  # python loop
    srv.add_method("/rdvnat.S/Echo",
                   unary_unary_rpc_method_handler(
                       lambda req, ctx: bytes(req)))
    port = srv.add_insecure_port("127.0.0.1:0")
    srv.start()
    py_sent0 = _metrics.registry().metrics()["rdv_transfers_sent"].snapshot()
    try:
        c0 = _native_counters()
        with Channel(f"127.0.0.1:{port}") as ch:
            mc = ch.unary_unary("/rdvnat.S/Echo")  # native C client plane
            assert bytes(mc(b"tiny", timeout=10)) == b"tiny"
            big = bytes(range(256)) * (4096 + 7)
            assert bytes(mc(big, timeout=60)) == big
        c1 = _native_counters()
        assert c1["rdv_sent"] - c0["rdv_sent"] >= 1   # C sender role
        assert c1["rdv_recv"] - c0["rdv_recv"] >= 1   # C receiver role
        # the python server's ledger saw its (response) send
        assert _metrics.registry().metrics()[
            "rdv_transfers_sent"].snapshot() >= py_sent0 + 1
    finally:
        srv.stop(grace=1)


def test_native_disabled_rendezvous_stays_framed(fresh_config):
    """TPURPC_RENDEZVOUS=0: no hello, no Link — un-negotiated native peers
    move every byte framed, correctly."""
    _reset_platform(fresh_config, "RDMA_BP")
    _require_native()
    fresh_config.setenv("TPURPC_RENDEZVOUS", "0")
    from tpurpc.rpc.channel import Channel

    srv, port = _stream_total_server()
    payload = b"q" * (1 << 20)
    try:
        c0 = _native_counters()
        with Channel(f"127.0.0.1:{port}") as ch:
            mc = ch.stream_stream("/rdvnat.S/Total")
            out = list(mc(iter([payload] * 3), timeout=60))
        assert out[-1] == str(3 * len(payload)).encode()
        c1 = _native_counters()
        assert c1["rdv_sent"] == c0["rdv_sent"]
        assert c1["ctrl_posts"] == c0["ctrl_posts"]
    finally:
        srv.stop(grace=1)


def test_native_pool_exhaustion_falls_back_framed(fresh_config):
    """A C-side refused claim (budget) degrades the transfer to framed —
    byte-exact, never an error, never a hang."""
    _reset_platform(fresh_config, "RDMA_BP")
    _require_native()
    # 11 MiB rounds to a 16 MiB landing class: over this 1 MiB budget, and
    # a class no earlier test leaves in the process-global recycle cache
    fresh_config.setenv("TPURPC_RENDEZVOUS_POOL_MB", "1")
    from tpurpc.rpc.channel import Channel

    srv, port = _stream_total_server()
    payload = b"x" * (11 << 20)
    try:
        c0 = _native_counters()
        with Channel(f"127.0.0.1:{port}") as ch:
            mc = ch.stream_stream("/rdvnat.S/Total")
            # warmup settles the capability hello: an un-negotiated first
            # send frames WITHOUT offering, which is not this test's path
            list(mc(iter([b"warm"]), timeout=30))
            out = list(mc(iter([payload]), timeout=120))
        assert out[-1] == str(len(payload)).encode()
        c1 = _native_counters()
        assert (c1["rdv_refused"] > c0["rdv_refused"]
                or c1["rdv_fallback"] > c0["rdv_fallback"])
        assert c1["rdv_bytes_sent"] == c0["rdv_bytes_sent"]
    finally:
        srv.stop(grace=1)
