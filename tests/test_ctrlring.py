"""tpurpc-pulse (ISSUE 13): shared-memory descriptor rings for the
rendezvous control plane.

Covers the ring protocol itself (post/drain ordering, seq stamping, the
frame_seq ordering gate, ring-full fallback, the parked/kick handshake,
nonce verification), the hello-blob negotiation ladder (un-negotiated
peers and garbage blobs stay framed), the end-to-end zero-control-frames
steady state, peer death with ring control in flight on both platforms,
the stale-ring (late write lands in dead memory) rule, the exhaustive
ringcheck model + its seeded mutants, the watchdog's ``ctrl-ring`` stage,
the lens ``ctrl`` hop's slowest-hop exclusion, and the coalesced framed
path (FrameWriter.batch + the migrate burst)."""

import threading
import time

import numpy as np
import pytest

import tpurpc.core.ctrlring as ctrlring
import tpurpc.core.rendezvous as rdv
import tpurpc.rpc as tps
from tpurpc.rpc.channel import Channel
from tpurpc.rpc.status import RpcError, StatusCode


@pytest.fixture
def fresh_config(monkeypatch):
    from tpurpc.utils import config as config_mod

    yield monkeypatch
    config_mod.set_config(None)


def _reset_platform(monkeypatch, platform):
    from tpurpc.utils import config as config_mod

    monkeypatch.setenv("GRPC_PLATFORM_TYPE", platform)
    config_mod.set_config(None)


def _pair_rings():
    """An (rx, tx) pair the unit tests drive directly: the consumer-owned
    ring plus a producer window opened from its descriptor."""
    rx = ctrlring.CtrlRing(kind="shm", nslots=8)
    desc = rx.descriptor()
    (nslots, slot_bytes, nbytes, nonce,
     klen) = ctrlring._DESC.unpack_from(desc)
    pos = ctrlring._DESC.size
    kind = desc[pos:pos + klen].decode()
    handle = desc[pos + klen:].decode()
    tx = ctrlring.CtrlPeer(kind, handle, nslots, slot_bytes, nbytes, nonce)
    return rx, tx


# ---------------------------------------------------------------------------
# the ring protocol
# ---------------------------------------------------------------------------

def test_post_drain_roundtrip_in_order():
    rx, tx = _pair_rings()
    try:
        for i in range(5):
            assert tx.post(3, 100 + i, bytes([i]) * (i + 1), 0) in (1, 2)
        got = []
        n = rx.drain(lambda op, sid, pl: got.append((op, sid, bytes(pl))),
                     lambda: 0)
        assert n == 5
        assert got == [(3, 100 + i, bytes([i]) * (i + 1))
                       for i in range(5)]
        assert tx.backlog() == 0  # one cons_head publish per batch
    finally:
        tx.close()
        rx.close()


def test_ring_full_refuses_then_recovers():
    rx, tx = _pair_rings()
    try:
        for i in range(rx.nslots):
            assert tx.post(1, i, b"x", 0)
        assert tx.post(1, 99, b"x", 0) == 0  # full: framed fallback
        assert tx.backlog() == rx.nslots
        got = []
        rx.drain(lambda *a: got.append(a), lambda: 0)
        assert len(got) == rx.nslots
        assert tx.post(1, 99, b"x", 0)  # space returned
    finally:
        tx.close()
        rx.close()


def test_oversized_payload_refused():
    rx, tx = _pair_rings()
    try:
        assert tx.post(1, 1, b"y" * (ctrlring.MAX_CTRL_PAYLOAD + 1), 0) == 0
        assert tx.post(1, 1, b"y" * ctrlring.MAX_CTRL_PAYLOAD, 0)
    finally:
        tx.close()
        rx.close()


def test_frame_seq_gate_defers_until_frames_dispatch():
    """A record stamped with frame_seq N is invisible until the consumer
    has dispatched N frames — the ordering seam between the ring and the
    framed path."""
    rx, tx = _pair_rings()
    try:
        assert tx.post(3, 1, b"a", 2)
        assert tx.post(3, 2, b"b", 4)
        got = []
        sink = lambda op, sid, pl: got.append(sid)  # noqa: E731
        assert rx.drain(sink, lambda: 0) == 0     # both gated
        assert rx.drain(sink, lambda: 2) == 1     # first passes
        assert got == [1]
        assert rx.drain(sink, lambda: 3) == 0     # head-of-line gates
        assert rx.drain(sink, lambda: 4) == 1
        assert got == [1, 2]
    finally:
        tx.close()
        rx.close()


def test_parked_flag_requests_kick():
    rx, tx = _pair_rings()
    try:
        rx.set_parked(False)
        assert tx.post(1, 1, b"a", 0) == 1   # consumer polling: no kick
        rx.set_parked(True)
        assert tx.post(1, 2, b"b", 0) == 2   # parked: caller must kick
    finally:
        tx.close()
        rx.close()


def test_peer_open_rejects_wrong_nonce():
    rx = ctrlring.CtrlRing(kind="shm", nslots=8)
    try:
        desc = rx.descriptor()
        (nslots, slot_bytes, nbytes, _nonce,
         klen) = ctrlring._DESC.unpack_from(desc)
        pos = ctrlring._DESC.size
        kind = desc[pos:pos + klen].decode()
        handle = desc[pos + klen:].decode()
        with pytest.raises(OSError):
            ctrlring.CtrlPeer(kind, handle, nslots, slot_bytes, nbytes,
                              b"\x00" * 16)
    finally:
        rx.close()


def test_stale_ring_write_lands_in_dead_memory():
    """The satellite claim: a late ring-slot write AFTER link death lands
    in orphaned memory — never in a ring a new link reads.  The consumer
    closes (region released on its side); the straggling producer's post
    hits its still-mapped window without error, and a FRESH ring never
    observes it."""
    rx, tx = _pair_rings()
    rx.close()                      # link death: consumer side gone
    assert tx.post(3, 7, b"late", 0) in (0, 1, 2)  # no crash either way
    # a new link allocates a NEW ring (never pooled): the straggler's
    # bytes are unobservable there
    rx2, tx2 = _pair_rings()
    try:
        got = []
        assert rx2.drain(lambda *a: got.append(a), lambda: 0) == 0
        assert got == []
        assert rx2.drain(lambda *a: got.append(a), lambda: 0) == 0
    finally:
        tx2.close()
        rx2.close()
        tx.close()
    # the dead ring's drain is inert too
    assert rx.drain(lambda *a: None, lambda: 0) == 0


def test_plane_negotiation_ladder():
    """Empty blob (peer predates rings / non-shm), garbage blob, and a
    valid blob: only the last arms; the rest stay framed."""
    a = ctrlring.CtrlPlane("test-a")
    b = ctrlring.CtrlPlane("test-b")
    try:
        assert not a.on_hello(b"")          # un-negotiated peer
        assert not a.armed
        assert not a.on_hello(b"\x07garbage")
        assert not a.armed
        assert a.on_hello(b.hello_blob())   # real descriptor: adopt
        assert a.armed
        sent = []
        assert a.post(3, 1, b"p", 0, kick=lambda: sent.append("kick"))
        got = []
        assert b.drain(lambda op, sid, pl: got.append((op, sid)),
                       lambda: 0) == 1
        assert got == [(3, 1)]
    finally:
        a.close()
        b.close()


# ---------------------------------------------------------------------------
# the consumer's wait: watching the next stamp, or parked (ISSUE 29)
# ---------------------------------------------------------------------------

_WINDOW_S = 0.002       # how long a stream's reader watches behind a record
_SLICE_S = 0.00025      # what the fake clock moves by in a spin that finds nothing
_KICK_FRAME = object()  # what the fake framed read hands back when kicked


class _WaitRig:
    """read_frame_polled over a real pair of planes, a fake framed read and
    a fake clock.  The framed read records the header's ``parked`` word on
    entry; a BLOCKING one (timeout not 0) runs the scripted way out in
    place of waiting, with the kick callback's Event as its wake-up.  Time
    moves only when a spin slice ends: no sleeps, no latency thresholds."""

    def __init__(self, monkeypatch, window_s=_WINDOW_S):
        import types

        self.now = 1000.0
        monkeypatch.setattr(ctrlring, "time", types.SimpleNamespace(
            monotonic=lambda: self.now,
            monotonic_ns=lambda: int(self.now * 1e9)))
        monkeypatch.setattr(ctrlring, "_SPIN_SLICE_US", 1)
        self.producer = ctrlring.CtrlPlane("wait-a")
        self.plane = ctrlring.CtrlPlane("wait-b")
        self.plane._watch_s = window_s
        assert self.producer.on_hello(self.plane.hello_blob())
        real_spin = self.plane.spin

        def spin():
            if self.in_slice:
                self.in_slice.pop(0)()
            moved = real_spin()
            self.log.append(("spin", moved))
            self.now += 1e-6 if moved else _SLICE_S
            return moved

        self.plane.spin = spin
        self.kicked = threading.Event()
        self.log = []        # ("drain", parked, n) / ("read", timeout, parked) / ("spin", moved)
        self.ops = []
        self.in_slice = []   # callables run at the start of successive spin slices
        self.probe_frames = []
        self.on_block = None
        self.stop = False
        self.stop_when_parked = False
        self.timeout = None

    def close(self):
        self.producer.close()
        self.plane.close()

    def parked(self):
        rx = self.plane.rx
        return ctrlring._PARKED.unpack_from(rx.region.buf,
                                            ctrlring._PARKED_OFF)[0]

    def prime(self, tag=b"earlier"):
        """A record drained a moment ago: the next one is DENSE traffic."""
        self.plane.unpark()
        assert not self.post(tag)
        assert self.look() == 1
        self.now += _WINDOW_S / 5
        self.log.clear()

    def post(self, tag):
        """One record from the producer; True when it had to kick."""
        self.kicked.clear()
        assert self.producer.post(3, 7, tag, 0, kick=self.kicked.set)
        return self.kicked.is_set()

    def drain(self):
        parked = self.parked()
        n = self.plane.drain(lambda op, sid, pl: self.ops.append(bytes(pl)),
                             lambda: 1 << 30)
        self.log.append(("drain", parked, n))
        if parked and self.stop_when_parked:
            self.stop = True
        return n

    def look(self):
        """What the reader does with its own drain, for setting a scene."""
        n = self.drain()
        if n:
            self.plane.saw(n)
        return n

    def read_frame(self, timeout=None):
        parked = self.parked()
        self.log.append(("read", timeout, parked))
        if timeout == 0:
            # a probe inside the busy window: unparked, and it never waits
            assert parked == 0, self.log
            if self.probe_frames:
                return self.probe_frames.pop(0)
            raise ctrlring.ReadTimeout()
        # the only blocking read there is: flag up, then the re-drain that
        # found nothing, then this — and for as long as the caller allows
        assert parked == 1, self.log
        assert self.log[-2] == ("drain", 1, 0), self.log
        if self.timeout is None:
            assert timeout is None
        else:
            assert 0 < timeout <= self.timeout
        return self.on_block()

    def call(self, timeout=None, with_stop=False):
        self.timeout = timeout
        return ctrlring.read_frame_polled(
            self.read_frame, self.drain, self.plane, timeout,
            (lambda: self.stop) if with_stop else None)

    def blocking_reads(self):
        return [e for e in self.log if e[0] == "read" and e[1] != 0]

    def probes(self):
        return [e for e in self.log if e[0] == "read" and e[1] == 0]


@pytest.fixture
def wait_rig(monkeypatch):
    from tpurpc.core import _native

    if _native.load_spin() is None:
        pytest.skip("no native library: no GIL-free spin, so no busy window")
    rig = _WaitRig(monkeypatch)
    yield rig
    rig.close()


def _wait_cases():
    for state in ("cold", "sparse", "hit", "expired"):
        for timeout in (None, 5.0):
            for with_stop in (False, True):
                for way_out in ("frame", "exception", "timeout", "stop"):
                    if way_out == "timeout" and timeout is None:
                        continue
                    if way_out == "stop" and not with_stop:
                        continue
                    yield pytest.param(
                        state, timeout, with_stop, way_out,
                        id=f"{state}-{'finite' if timeout else 'untimed'}-"
                           f"{'stop' if with_stop else 'nostop'}-{way_out}")


@pytest.mark.parametrize("state,timeout,with_stop,way_out", _wait_cases())
def test_reader_is_watching_the_stamp_or_parked(wait_rig, state, timeout,
                                                with_stop, way_out):
    """The invariant of ``read_frame_polled``: a reader that is not looking
    at its descriptor ring is in a bounded spin on the next stamp, or has
    ``parked`` up behind a re-drain.  Every blocking framed read is entered
    parked and after the re-drain; every way out leaves ``parked`` 0; a
    record posted while the reader is blocked kicks, and is dispatched
    before the frame that woke the reader."""
    rig = wait_rig
    if state == "sparse":
        # a record is waiting, the first for a long while: no window
        rig.post(b"first")
    elif state == "hit":
        # a record is waiting, close behind another: the first drain starts
        # the watch, in which a third is found with no kick
        rig.prime()
        rig.post(b"first")
        rig.in_slice = [lambda: rig.__setattr__(
            "second_kicked", rig.post(b"second"))]
    elif state == "expired":
        rig.prime()
        rig.post(b"first")
        assert rig.look() == 1  # starts a watch ...
        rig.now += 2 * _WINDOW_S  # ... that ran out before this call
        rig.log.clear()
    parks0 = ctrlring._PARKS.snapshot()

    def blocked_frame():
        assert rig.post(b"late"), "parked consumer: the post must kick"
        assert rig.kicked.wait(0)
        return _KICK_FRAME

    def blocked_raises(exc):
        def on_block():
            raise exc
        return on_block

    if way_out == "frame":
        rig.on_block = blocked_frame
        assert rig.call(timeout, with_stop) is _KICK_FRAME
        assert rig.ops[-1] == b"late"  # dispatched before the frame is
    elif way_out == "exception":
        rig.on_block = blocked_raises(OSError("link died"))
        with pytest.raises(OSError):
            rig.call(timeout, with_stop)
    elif way_out == "timeout":
        rig.on_block = blocked_raises(ctrlring.ReadTimeout())
        with pytest.raises(TimeoutError):
            rig.call(timeout, with_stop)
    else:
        rig.stop_when_parked = True
        rig.on_block = lambda: pytest.fail("blocked past should_stop")
        with pytest.raises(TimeoutError):
            rig.call(timeout, with_stop)

    assert rig.parked() == 0
    assert len(rig.blocking_reads()) == (0 if way_out == "stop" else 1)
    assert ctrlring._PARKS.snapshot() - parks0 == 1
    if state == "hit":
        assert rig.second_kicked is False
        assert rig.ops[:3] == [b"earlier", b"first", b"second"]
        # the window was watched to its end, probe and spin in turn: the
        # slice that found the second record, then one window of them
        kinds = [e[0] for e in rig.log if e[0] in ("read", "spin")]
        n = len(rig.probes())
        assert n == 1 + round(_WINDOW_S / _SLICE_S)
        assert kinds[:2 * n] == ["read", "spin"] * n
    else:
        assert rig.probes() == []  # nothing to watch for: park at once
        if state == "sparse":
            assert rig.ops[0] == b"first"


def test_reader_without_a_busy_window_parks_at_once(monkeypatch):
    """No native library or one CPU: no window, so a hit changes nothing —
    the next wait raises ``parked`` before it blocks, never spins."""
    rig = _WaitRig(monkeypatch, window_s=0.0)
    try:
        rig.prime()
        rig.post(b"first")
        rig.on_block = lambda: _KICK_FRAME
        assert rig.call() is _KICK_FRAME
        assert rig.ops == [b"earlier", b"first"]
        assert rig.probes() == [] and len(rig.blocking_reads()) == 1
        assert [e for e in rig.log if e[0] == "spin"] == []
        assert rig.parked() == 0
    finally:
        rig.close()


def test_deadline_inside_the_busy_window_leaves_unparked(wait_rig):
    rig = wait_rig
    rig.prime()
    rig.post(b"first")
    rig.on_block = lambda: pytest.fail("a window outlasting the deadline")
    with pytest.raises(TimeoutError):
        rig.call(timeout=_WINDOW_S / 2)
    assert rig.parked() == 0 and rig.blocking_reads() == []


def test_records_faster_than_the_window_never_park(wait_rig):
    """PR 13's steady state: a consumer whose records arrive inside its
    busy window never raises ``parked``, so no producer ever kicks."""
    rig = wait_rig
    rig.on_block = lambda: pytest.fail("parked in a steady stream")
    rig.prime()
    rig.post(b"cold")
    rig.probe_frames.append(_KICK_FRAME)
    assert rig.call() is _KICK_FRAME  # its drain opens the window
    n = 40
    kicks = []
    # a record in every second slice: 250 us after the one before
    rig.in_slice = [
        (lambda i=i: kicks.append(rig.post(b"r%d" % i))) if i % 2
        else (lambda: None) for i in range(2 * n)]
    rig.in_slice.append(lambda: rig.probe_frames.append(_KICK_FRAME))
    parks0 = ctrlring._PARKS.snapshot()
    hits0 = ctrlring._SPIN_HITS.snapshot()
    records0 = ctrlring._RECORDS.snapshot()
    assert rig.call() is _KICK_FRAME
    assert kicks == [False] * n
    assert rig.ops[-n:] == [b"r%d" % i for i in range(1, 2 * n, 2)]
    assert ctrlring._PARKS.snapshot() == parks0
    assert ctrlring._RECORDS.snapshot() - records0 == n
    assert ctrlring._SPIN_HITS.snapshot() - hits0 == n
    assert rig.blocking_reads() == [] and rig.parked() == 0


def test_a_stream_slower_than_a_slice_costs_two_kicks_and_no_more(wait_rig):
    """A record that comes within the watch of the one before is a stream's:
    the first is sparse, the second shows the gap, and from then on the
    reader is watching when the next arrives; after a silence longer than
    the watch a record is sparse again."""
    rig = wait_rig
    period = 3  # slices before each record: 750 us
    assert _SLICE_S < period * _SLICE_S < _WINDOW_S
    kicks = []

    def blocked():  # parked: the record kicks
        kicks.append(rig.post(b"k%d" % len(kicks)))
        return _KICK_FRAME

    rig.on_block = blocked
    assert rig.call() is _KICK_FRAME       # cold: the first record, no watch
    assert not rig.plane.watching()
    rig.now += period * _SLICE_S
    assert rig.call() is _KICK_FRAME       # the second: the gap is seen
    assert kicks == [True, True] and rig.probes() == []
    assert rig.plane._hot_until == pytest.approx(rig.now + _WINDOW_S)
    parks0 = ctrlring._PARKS.snapshot()
    n = 12
    rig.in_slice = [
        (lambda i=i: kicks.append(rig.post(b"s%d" % i)))
        if i % (period + 1) == period else (lambda: None)
        for i in range((period + 1) * n)]
    rig.in_slice.append(lambda: rig.probe_frames.append(_KICK_FRAME))
    assert rig.call() is _KICK_FRAME
    assert kicks[2:] == [False] * n and ctrlring._PARKS.snapshot() == parks0
    rig.now += 10 * _WINDOW_S
    rig.post(b"late")
    assert rig.look() == 1 and not rig.plane.watching()


@pytest.mark.parametrize("watching", [False, True])
def test_a_senders_drain_leaves_the_readers_state_alone(wait_rig, watching):
    """``RdvLink.ctrl_drain`` drains from sender threads (a sender waiting
    for a grant): it dispatches, and neither starts nor stretches the
    reader's watch, nor counts as the reader's find."""
    rig = wait_rig
    if watching:
        rig.prime()
        rig.post(b"first")
        assert rig.look() == 1 and rig.plane.watching()
    before = (rig.plane._hot_until, rig.plane._last_record,
              ctrlring._SPIN_HITS.snapshot(), ctrlring._PARKS.snapshot())
    for tag in (b"a", b"b"):  # two, close together: a stream's, had the
        rig.now += _SLICE_S   # reader found them
        rig.post(tag)
        assert rig.drain() == 1
    assert rig.ops[-2:] == [b"a", b"b"]
    assert before == (rig.plane._hot_until, rig.plane._last_record,
                      ctrlring._SPIN_HITS.snapshot(),
                      ctrlring._PARKS.snapshot())


# ---------------------------------------------------------------------------
# end to end
# ---------------------------------------------------------------------------

def _sink_server():
    from tpurpc.jaxshim import add_tensor_method

    srv = tps.Server(max_workers=4, native_dataplane=False)

    def consume(req_iter):
        total = 0
        for tree in req_iter:
            total += np.asarray(tree["x"]).nbytes
        yield {"bytes": np.int64(total)}

    add_tensor_method(srv, "Sink", consume, kind="stream_stream")
    port = srv.add_insecure_port("127.0.0.1:0")
    srv.start()
    return srv, port


@pytest.mark.parametrize("platform", ["TCP", "RDMA_BPEV"])
def test_steady_state_stream_zero_control_frames(fresh_config, platform):
    """The tentpole claim end to end: after warmup, a stream of standing
    transfers does one one-sided write + one ring slot per message —
    ``rdv_ctrl_frames`` stays flat and every control op rides the ring."""
    _reset_platform(fresh_config, platform)
    from tpurpc.jaxshim import TensorClient
    from tpurpc.obs import flight, metrics

    reg = metrics.registry().metrics()
    srv, port = _sink_server()
    payload = np.ones((512, 512), np.float32)  # 1 MiB
    t0 = time.monotonic_ns()
    try:
        with Channel(f"127.0.0.1:{port}") as ch:
            cli = TensorClient(ch)

            def gen(k):
                for _ in range(k):
                    yield {"x": payload}

            list(cli.duplex("Sink", gen(2), native=False, timeout=60))
            frames0 = reg["rdv_ctrl_frames"].snapshot()
            posts0 = reg["ctrl_ring_posts"].snapshot()
            sent0 = reg["rdv_transfers_sent"].snapshot()
            replies = list(cli.duplex("Sink", gen(8), native=False,
                                      timeout=120))
            total = int(np.asarray(replies[-1]["bytes"]).ravel()[0])
            assert total == 8 * payload.nbytes
            assert reg["rdv_transfers_sent"].snapshot() - sent0 == 8
            assert reg["rdv_ctrl_frames"].snapshot() - frames0 == 0
            assert reg["ctrl_ring_posts"].snapshot() - posts0 >= 8
        evs = [e["event"] for e in flight.snapshot(since_ns=t0)]
        assert "ctrl-adopt" in evs
        # the declared ctrl machines hold over everything this emitted
        from tpurpc.analysis import protocol

        assert protocol.check_events(flight.snapshot(since_ns=t0),
                                     strict=False) == []
    finally:
        srv.stop(grace=1)


def test_disabled_env_keeps_framed_control(fresh_config):
    """TPURPC_CTRL_RING=0: the PR 9 framed control path exactly as it
    was — transfers still rendezvous, control ops frame."""
    _reset_platform(fresh_config, "RDMA_BPEV")
    fresh_config.setenv("TPURPC_CTRL_RING", "0")
    from tpurpc.jaxshim import TensorClient
    from tpurpc.obs import metrics

    reg = metrics.registry().metrics()
    srv, port = _sink_server()
    payload = np.ones((512, 512), np.float32)
    try:
        with Channel(f"127.0.0.1:{port}") as ch:
            cli = TensorClient(ch)

            def gen(k):
                for _ in range(k):
                    yield {"x": payload}

            frames0 = reg["rdv_ctrl_frames"].snapshot()
            sent0 = reg["rdv_transfers_sent"].snapshot()
            list(cli.duplex("Sink", gen(4), native=False, timeout=60))
            assert reg["rdv_transfers_sent"].snapshot() > sent0
            assert reg["rdv_ctrl_frames"].snapshot() > frames0
    finally:
        srv.stop(grace=1)


@pytest.mark.parametrize("platform", ["TCP", "RDMA_BPEV"])
def test_peer_death_with_ring_control_in_flight(fresh_config, platform):
    """The chaos satellite: kill the peer while descriptor-ring control is
    mid-transfer (claim observed, COMPLETE never sent).  The victim gets a
    status (never hangs), the claimed region releases/quarantines, and the
    protocol checker holds over the dump — ctrl machines included."""
    from tpurpc.obs import flight

    _reset_platform(fresh_config, platform)
    flight.RECORDER.reset()
    srv = tps.Server(max_workers=4, native_dataplane=False)
    big = b"\x6b" * (1 << 20)
    srv.add_method("/pulse.S/Big", tps.unary_unary_rpc_method_handler(
        lambda req, ctx: big))
    srv.add_method("/pulse.S/Warm", tps.unary_unary_rpc_method_handler(
        lambda req, ctx: b"ok"))
    port = srv.add_insecure_port("127.0.0.1:0")
    srv.start()
    wedge = threading.Event()  # never set: the sender wedges after claim
    outcome: list = []
    try:
        with Channel(f"127.0.0.1:{port}") as ch:
            mc = ch.unary_unary("/pulse.S/Big", tpurpc_native=False)
            warm = ch.unary_unary("/pulse.S/Warm", tpurpc_native=False)
            assert bytes(warm(b"w", timeout=30)) == b"ok"
            t_armed = time.monotonic_ns()
            # ring control must actually be in flight for this scenario
            deadline = time.monotonic() + 10
            adopted = False
            while not adopted and time.monotonic() < deadline:
                adopted = any(e["event"] == "ctrl-adopt"
                              for e in flight.snapshot())
                time.sleep(0.02)
            assert adopted, "descriptor ring never adopted"
            rdv.TEST_HOOKS["wedge_after_claim"] = wedge

            def call():
                try:
                    mc(b"x", timeout=60)
                    outcome.append(("ok",))
                except RpcError as exc:
                    outcome.append(("status", exc.code()))

            t = threading.Thread(target=call)
            t.start()
            claimed = None
            deadline = time.monotonic() + 15
            while claimed is None and time.monotonic() < deadline:
                time.sleep(0.05)
                for e in flight.snapshot(since_ns=t_armed):
                    if e["event"] == "rdv-claim" and e["a1"] != 0:
                        claimed = e
                        break
            assert claimed is not None, "claim never observed"
            srv.stop(grace=0)  # peer dies with ring control in flight
            t.join(timeout=30)
            assert not t.is_alive(), "call hung after peer death"
            assert outcome and outcome[0][0] == "status", outcome
            assert outcome[0][1] in (StatusCode.UNAVAILABLE,
                                     StatusCode.CANCELLED,
                                     StatusCode.DEADLINE_EXCEEDED), outcome
            from tpurpc.analysis import protocol

            events = flight.snapshot()
            tag, lease = claimed["tag"], claimed["a2"]
            protocol.assert_ordered(
                events,
                [("rdv-claim", {"tag": tag, "a2": lease}),
                 (("conn-dead", "peer-death"), {}),
                 ("rdv-release", {"tag": tag, "a1": lease})],
                since_ns=t_armed)
            assert protocol.check_events(events, strict=False) == []
    finally:
        rdv.TEST_HOOKS.pop("wedge_after_claim", None)
        wedge.set()
        srv.stop(grace=0)


def test_async_domain_complete_stays_framed():
    """Regression (caught live by the tcpw cross-process test): a COMPLETE
    whose payload rode an ASYNC landing domain (no host-addressable view —
    tcp_window records, verbs WRs) must ride the framed path, which the
    shared record stream sequences after the payload; a ring-posted
    COMPLETE would overtake the bytes and deliver a torn region."""
    from tpurpc.core import pair as pair_mod

    framed = []
    ring = []
    link = rdv.RdvLink("t", lambda op, sid, pl: framed.append(op),
                       lambda sid, fl, body: None)
    link.ctrl_post = lambda op, sid, pl: ring.append(op) or True

    def mk_claim(view):
        c = rdv._Claim(7, "k", "h", 0, 1 << 20, b"", standing=False)
        link._windows[("k", "h")] = pair_mod.Window(
            write=lambda off, data: None, view=view)
        return c

    # async domain: no view -> framed COMPLETE
    link.rdv_complete(mk_claim(None), 1, 0, 64)
    assert framed == [rdv.OP_COMPLETE] and ring == []
    # sync (view-backed) domain: ring COMPLETE
    framed.clear()
    link.rdv_complete(mk_claim(memoryview(bytearray(8))), 1, 0, 64)
    assert ring == [rdv.OP_COMPLETE] and framed == []


# ---------------------------------------------------------------------------
# the model, the watchdog stage, the lens hop
# ---------------------------------------------------------------------------

def test_ringcheck_ctrl_model_clean():
    from tpurpc.analysis import ringcheck

    for res in ringcheck.ctrl_default_suite():
        assert res.ok, res


def test_ringcheck_ctrl_mutants_all_killed():
    from tpurpc.analysis import ringcheck

    kills = ringcheck.ctrl_mutant_kill_suite()
    assert set(kills) == set(ringcheck.CTRL_MUTANTS)
    assert all(kills.values()), kills


def test_watchdog_names_ctrl_ring_stage():
    """An aged ring-full stall bracket (or backlog behind an aged
    rendezvous edge) attributes to `ctrl-ring`, outranking the generic
    rendezvous story."""
    from tpurpc.obs import watchdog as wdmod

    wd = wdmod.StallWatchdog(sweep_s=10, min_stall_s=0.2)
    now = time.monotonic_ns()
    ev = {
        "now_ns": now, "open_lease": 0, "open_edges": {},
        "open_rdv": {(7, "o", 1): now - int(2e9)},
        "open_ctrl": {7: now - int(2e9)},
        "ctrl_ring_backlog": 3,
        "open_swap": {}, "open_mig": {}, "open_step": {},
        "last_step_end_ns": 0, "last_step_batch": 0, "last_h2_ns": 0,
        "pairs_write_stalled": 0, "batcher_queue_depth": 0,
        "pairs_msg_waiting": 0, "decode_waiting": 0, "decode_running": 0,
    }
    stage, detail = wd._attribute(ev, "client", int(2e9))
    assert stage == "ctrl-ring", (stage, detail)
    # without ring evidence the rendezvous story is untouched
    ev2 = dict(ev, open_ctrl={}, ctrl_ring_backlog=0)
    stage2, _ = wd._attribute(ev2, "client", int(2e9))
    assert stage2 == "rendezvous"
    assert "ctrl-ring" in wdmod.STAGES


def test_lens_ctrl_hop_declared_and_excluded_from_slowest():
    """The `ctrl` hop exists, and the <1%-of-bulk-bytes rule keeps a
    control-only hop out of the slowest-hop argmin."""
    from tpurpc.obs import lens

    assert "ctrl" in lens.HOP_NAMES
    rows = [
        {"hop": "rendezvous", "bytes": 1 << 30, "busy_ms": 500.0,
         "gbps": 2.0, "copy_bytes": 0, "what": ""},
        {"hop": "ctrl", "bytes": 4096, "busy_ms": 400.0,
         "gbps": 0.00001, "copy_bytes": 0, "what": ""},
    ]
    assert lens.slowest_hop(rows) == "rendezvous"


# ---------------------------------------------------------------------------
# the coalesced framed path (satellite: one writev per burst)
# ---------------------------------------------------------------------------

class _FakeEndpoint:
    def __init__(self):
        self.writes = []

    def write(self, segs):
        if isinstance(segs, (bytes, bytearray, memoryview)):
            segs = [segs]
        self.writes.append(b"".join(bytes(s) for s in segs))


def test_framewriter_batch_one_writev():
    from tpurpc.rpc import frame as fr

    ep = _FakeEndpoint()
    w = fr.FrameWriter(ep)
    with w.batch():
        for sid in (1, 3, 5):
            w.send_many([(fr.HEADERS, 0, sid, b"h" * 8),
                         (fr.MESSAGE, fr.FLAG_END_STREAM, sid, b"m" * 16)])
    assert len(ep.writes) == 1  # six frames, ONE gathered writev
    assert w.frames_sent == 6
    # order inside the batch is issue order
    r = fr.FrameReader(_ReplayEndpoint(ep.writes[0]))
    seen = []
    while True:
        f = r.read_frame()
        if f is None:
            break
        seen.append((f.type, f.stream_id))
    assert seen == [(fr.HEADERS, 1), (fr.MESSAGE, 1), (fr.HEADERS, 3),
                    (fr.MESSAGE, 3), (fr.HEADERS, 5), (fr.MESSAGE, 5)]


class _ReplayEndpoint:
    def __init__(self, blob):
        self._blob = memoryview(bytes(blob))
        self._pos = 0

    def read_into(self, dst, timeout=None):
        n = min(len(dst), len(self._blob) - self._pos)
        dst[:n] = self._blob[self._pos:self._pos + n]
        self._pos += n
        return n


def test_ctrl_frame_coalescer_self_clocking():
    """Ops arriving while a flush is in flight drain in ONE multi-op
    send — PR 3's self-clocking writev discipline on the control path."""
    sent_single = []
    sent_multi = []
    gate = threading.Event()
    release = threading.Event()

    def send_op(op, sid, payload):
        sent_single.append((op, sid))
        gate.set()
        release.wait(5)

    def send_ops(ops):
        sent_multi.append([o[:2] for o in ops])

    co = rdv._CtrlFrameCoalescer(send_op, send_ops)
    t = threading.Thread(target=lambda: co.send(3, 1, b"a"))
    t.start()
    assert gate.wait(5)  # first op mid-flush
    co.send(3, 2, b"b")  # queue while in flight
    co.send(3, 3, b"c")
    release.set()
    t.join(5)
    assert sent_single == [(3, 1)]
    assert sent_multi == [[(3, 2), (3, 3)]]  # one flush for the burst


def test_migrate_burst_one_writev(fresh_config):
    """The disagg satellite end to end: migrating several sequences
    flushes the OfferKv burst (and the CompleteKv burst) as coalesced
    writevs — the ctrl_call_batch histogram records multi-frame batches —
    and every sequence resumes exactly at the peer."""
    _reset_platform(fresh_config, "TCP")
    from tpurpc.jaxshim.generate import ToyDecodeModel
    from tpurpc.serving.disagg import DisaggClient, migrate, serve_decode
    from tpurpc.utils import stats as _st

    model_a = ToyDecodeModel(step_delay_s=0.004)
    model_b = ToyDecodeModel(step_delay_s=0.004)
    srv_a, port_a, sched_a, state_a = serve_decode(
        model_a, kv_blocks=256, name="pulse-src")
    srv_b, port_b, sched_b, state_b = serve_decode(
        model_b, kv_blocks=256, name="pulse-dst")
    ch_b = Channel(f"127.0.0.1:{port_b}")
    try:
        prompts = [[3, 1, 4, 1], [2, 7, 1, 8], [1, 6, 1, 8]]
        streams = [sched_a.submit(np.array(p, np.int32), max_tokens=200)
                   for p in prompts]
        for s in streams:  # a few tokens so KV exists
            for _ in range(3):
                s.next(timeout=5)
        _st.reset_batch_stats()
        moved, failed = migrate(state_a, ch_b, f"127.0.0.1:{port_b}")
        assert moved == 3 and failed == 0, (moved, failed)
        hist = _st.batch_snapshot().get("ctrl_call_batch") or {}
        assert hist.get("count", 0) >= 1
        assert hist.get("p99", 0) >= 3, hist  # 3 offers in one writev
    finally:
        ch_b.close()
        for srv, sched, state in ((srv_a, sched_a, state_a),
                                  (srv_b, sched_b, state_b)):
            srv.stop(grace=0)
            sched.close()       # deregister from /healthz (test isolation)
            state.close()
            state.mgr.close()


# ---------------------------------------------------------------------------
# native planes (tpurpc-ironclad): the C consumer's drain discipline
# ---------------------------------------------------------------------------

def _native_counters():
    from tpurpc.rpc import native_client

    return native_client.rdv_counters()


@pytest.mark.parametrize("platform", ["RDMA_BP", "RDMA_BPEV"])
def test_native_steady_state_zero_control_frames(fresh_config, platform):
    """The acceptance bar on the C planes: after warmup, native bulk moves
    with ZERO framed control ops — every OFFER/CLAIM/COMPLETE rides the
    128 B descriptor ring — and (near-)zero CTRL_KICK fd wakeups (parking
    transitions at stream edges are the only legitimate kicks)."""
    _reset_platform(fresh_config, platform)
    if _native_counters() is None:
        pytest.skip("native data plane unavailable")
    from tpurpc.rpc.server import Server, stream_stream_rpc_method_handler

    srv = Server(max_workers=4)

    def total(req_iter, ctx):
        n = 0
        for m in req_iter:
            n += len(m)
        yield str(n).encode()

    srv.add_method("/ctrlnat.S/Total",
                   stream_stream_rpc_method_handler(total))
    port = srv.add_insecure_port("127.0.0.1:0")
    srv.start()
    payload = b"\xa5" * (1 << 20)
    n = 8
    try:
        with Channel(f"127.0.0.1:{port}") as ch:
            mc = ch.stream_stream("/ctrlnat.S/Total")
            list(mc(iter([payload] * 2), timeout=60))  # warmup: hello+heat
            c0 = _native_counters()
            out = list(mc(iter([payload] * n), timeout=120))
            c1 = _native_counters()
        assert out[-1] == str(n * len(payload)).encode()
        assert c1["rdv_sent"] - c0["rdv_sent"] >= n
        # ZERO control ops fell back to frames...
        assert c1["ctrl_frames"] == c0["ctrl_frames"]
        # ...the ring carried them — steady state on a standing grant is
        # ONE COMPLETE descriptor per message (no OFFER/CLAIM at all)...
        assert c1["ctrl_posts"] - c0["ctrl_posts"] >= n
        assert c1["ctrl_records"] - c0["ctrl_records"] >= n
        # ...and fd kicks happened at most at the stream's cold edges,
        # never once per message (the wakeup the ring exists to delete)
        assert c1["ctrl_kicks"] - c0["ctrl_kicks"] <= n // 2
    finally:
        srv.stop(grace=1)


def test_native_ctrl_disabled_still_rendezvous(fresh_config):
    """TPURPC_CTRL_RING=0 on the native planes: transfers still ride the
    rendezvous ladder, control ops go framed — correct, just chattier."""
    _reset_platform(fresh_config, "RDMA_BP")
    if _native_counters() is None:
        pytest.skip("native data plane unavailable")
    fresh_config.setenv("TPURPC_CTRL_RING", "0")
    from tpurpc.rpc.server import Server, stream_stream_rpc_method_handler

    srv = Server(max_workers=4)

    def total(req_iter, ctx):
        n = 0
        for m in req_iter:
            n += len(m)
        yield str(n).encode()

    srv.add_method("/ctrlnat.S/Total2",
                   stream_stream_rpc_method_handler(total))
    port = srv.add_insecure_port("127.0.0.1:0")
    srv.start()
    payload = b"\x3c" * (1 << 20)
    try:
        with Channel(f"127.0.0.1:{port}") as ch:
            mc = ch.stream_stream("/ctrlnat.S/Total2")
            list(mc(iter([b"warm"]), timeout=30))
            c0 = _native_counters()
            out = list(mc(iter([payload] * 3), timeout=60))
            c1 = _native_counters()
        assert out[-1] == str(3 * len(payload)).encode()
        assert c1["rdv_sent"] - c0["rdv_sent"] >= 3   # ladder still on
        assert c1["ctrl_posts"] == c0["ctrl_posts"]   # no ring
        assert c1["ctrl_frames"] > c0["ctrl_frames"]  # framed control
    finally:
        srv.stop(grace=1)
