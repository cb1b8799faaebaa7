"""tpurpc-express (ISSUE 9): one-sided rendezvous bulk-tensor plane.

Covers the landing pool's lifetime rules (weakref-finalize recycling, size
classes, budget refusal, death-path quarantine), the end-to-end transfer on
the native-framing plane (TCP and ring platforms) and the gRPC wire plane,
the copy-ledger zero-host-landing-copy proof, the framed fallback, the
flight/watchdog evidence, and the TPU-plane halves (SerializeFromDevice
into a window, descriptor-only codec)."""

import gc
import heapq
import threading
import time

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

    def __init__(self, monkeypatch, regions=4):
        from tpurpc.obs import metrics as _metrics

        self.clock = _FakeTime()
        monkeypatch.setattr(rdv, "time", self.clock)
        self.pool = rdv.LandingPool("local", budget=regions * _REGION)
        monkeypatch.setattr(rdv, "_pools", {"local": self.pool})
        self._metrics = _metrics.registry().metrics()
        self.got = []         # sequence numbers, in delivery order
        self.held = []        # [seq, body] the consumer has not let go of
        self.residence = lambda seq: 0.0
        self.links = []
        self.a, self.b = self.pair()
        self.seq = 0
        self.base = self.counters()

    def pair(self):
        ends = {}
        a = rdv.RdvLink("a", lambda *op: ends["b"].on_op(*op),
                        lambda *m: None, pool_kinds=("local",),
                        open_kinds=("local",), pump=self.clock.pump)
        b = rdv.RdvLink("b", lambda *op: ends["a"].on_op(*op),
                        self.deliver, pool_kinds=("local",),
                        open_kinds=("local",))
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

    def next_payload(self):
        seq, self.seq = self.seq, self.seq + 1
        return seq, (seq.to_bytes(8, "little")
                     + bytes([seq % 251]) * (_MSG - 8))

    def send(self, link=None):
        """One message; True if it went one-sided."""
        _, payload = self.next_payload()
        ok = (link or self.a).send_message(1, 0, [payload], _MSG)
        if not ok:
            self.deliver(1, 0, memoryview(bytearray(payload)))
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
    seq, payload = rig.next_payload()
    if how == "closed":
        # the framed path is next, where the dead transport raises
        assert not rig.a.send_message(1, 0, [payload], _MSG, **kw)
    else:
        with pytest.raises(rdv.SendAbandoned):
            rig.a.send_message(1, 0, [payload], _MSG, **kw)
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
