"""The rendezvous sender's placement (ISSUE 38): ``place_released`` copies a
message's gather segments into the peer's window with the interpreter
released, through ``tpr_place`` or, without the native library, numpy.

What is held here: the bytes land exactly where a memoryview slice
assignment put them, for every kind of segment the codec and the frame layer
hand over; the nonce is still checked before the first byte; simnet still
sees one ``write`` event a placement; the two counters count what was placed;
the copy really gives the interpreter up (no timing assert: a thread that
only counts makes progress, and makes none under a slice assignment); and a
window closed from another thread while a placement is in flight is closed
after the copy, not under it."""

import os
import sys
import threading
import time

import numpy as np
import pytest

import tpurpc.core.rendezvous as rdv
from tpurpc.core import _native
from tpurpc.core import pair as _pair
from tpurpc.core import transport as _transport
from tpurpc.obs import metrics as _metrics

_PLANES = ("native", "numpy")
_KINDS = ("shm", "local")
_LEAF = 256 << 10


@pytest.fixture(params=_PLANES)
def plane(request, monkeypatch):
    """Both copies: ``tpr_place`` on the handle that releases the
    interpreter, and the fallback a process without the native library
    runs (``TPURPC_NATIVE=0`` and a fresh load, undone afterwards)."""
    if request.param == "native":
        if _native.load_spin() is None:
            pytest.skip(f"no native library: {_native.status()}")
        yield "native"
        return
    monkeypatch.setenv("TPURPC_NATIVE", "0")
    _native.reset_for_tests()
    assert _native.load_spin() is None
    try:
        yield "numpy"
    finally:
        monkeypatch.undo()
        _native.reset_for_tests()


@pytest.fixture
def hooks():
    yield rdv.TEST_HOOKS
    rdv.TEST_HOOKS.pop("place_pinned", None)


def _counters():
    m = _metrics.registry().metrics()
    return {k: m[k].snapshot() for k in (
        "rdv_place_released", "rdv_place_released_bytes", "rdv_bytes_sent",
        "rdv_fallbacks")}


def _moved(before):
    now = _counters()
    return {k: now[k] - before[k] for k in now}


def _pattern(n, salt):
    return (np.arange(n, dtype=np.uint32) * 2654435761 + salt).astype(
        np.uint8)


def _row_of_larger():
    a = np.frombuffer(_pattern(8 * _LEAF, 3), dtype=np.float32)
    return [a.reshape(8, -1)[5]]


def _read_only():
    a = _pattern(_LEAF + 7, 4)
    a.setflags(write=False)
    return [a]


#: every kind of gather segment ``_rdv_write`` is handed: what the codec
#: emits (a header's bytes, typed array views), what the frame layer passes
#: through (bytes, bytearray, memoryview slices), and the degenerate ones
_SEGMENTS = {
    "bytes": lambda: [bytes(_pattern(_LEAF + 1, 0))],
    "bytearray": lambda: [bytearray(_pattern(_LEAF + 3, 1))],
    "memoryview": lambda: [memoryview(bytes(_pattern(_LEAF + 64, 2)))[13:-5]],
    "numpy_row_of_a_larger_array": _row_of_larger,
    "read_only_array": _read_only,
    "zero_length_segment": lambda: [
        b"abc", b"", _pattern(_LEAF, 5), np.empty(0, np.float32), b"z"],
    "header_and_leaf": lambda: [
        bytes(_pattern(183, 6)),
        np.frombuffer(_pattern(_LEAF, 7), dtype=np.float32)],
    "several_leaves": lambda: [
        bytes(_pattern(183, 8)), _pattern(_LEAF, 9),
        np.frombuffer(_pattern(5 * 4096, 10), dtype=np.int64),
        bytearray(_pattern(777, 11))],
}


def _spans(segs, off):
    placed = []
    for seg in segs:
        sv = memoryview(seg).cast("B")
        placed.append((off, sv))
        off += len(sv)
    return placed, off


@pytest.mark.parametrize("case", sorted(_SEGMENTS))
@pytest.mark.parametrize("off", [1, 4096 + 183])
def test_placement_is_byte_identical_to_slice_assignment(plane, case, off):
    """Into a window at offsets that are no multiple of a page (nor of 8):
    the window reads what a bytearray filled by slice assignment reads, and
    not a byte beside the placement moved."""
    segs = _SEGMENTS[case]()
    placed, end = _spans(segs, off)
    size = end + 4096
    want = bytearray(b"\xa5" * size)
    for at, sv in placed:
        want[at:at + len(sv)] = sv
    dom = _pair.make_domain("shm")
    region = dom.alloc(size)
    try:
        region.buf[:] = b"\xa5" * size
        win = dom.open_window(region.handle, size)
        try:
            before = _counters()
            n = rdv.place_released(win.view, placed)
            assert n == end - off
            assert bytes(region.buf[:size]) == bytes(want)
            moved = _moved(before)
            assert moved["rdv_place_released"] == 1
            assert moved["rdv_place_released_bytes"] == n
        finally:
            win.close()
    finally:
        region.close()


def test_placement_outside_the_window_raises_before_any_byte(plane):
    """A raw address has no bounds of its own: the span is checked against
    the window first, as the slice assignment's shape check did."""
    dom = _pair.make_domain("local")
    region = dom.alloc(4096)
    win = dom.open_window(region.handle, 4096)
    try:
        good = memoryview(b"x" * 100)
        with pytest.raises(ValueError):
            rdv.place_released(win.view, [(0, good), (4000, good)])
        assert bytes(region.buf[:100]) == b"\0" * 100
        with pytest.raises(ValueError):
            rdv.place_released(win.view, [(-1, good)])
        assert rdv.place_released(win.view, [(5, memoryview(b""))]) == 0
    finally:
        win.close()
        region.close()


def _place_return():
    snap = _metrics.registry().counters_snapshot()
    return {k: snap[f"lens_place_return_{k}"]
            for k in ("ops", "busy_ns", "bytes", "cpu_ns")}


def test_a_native_placement_is_one_op_of_place_return_and_numpy_none(plane):
    """ISSUE 39: the price of the give-up is measured where it is paid. The
    native copy stamps its own end and ``place_released`` reads the clock
    as its first act on return: one op a placement, its bytes the bytes
    placed, a time that cannot be negative and no thread's CPU; numpy's
    copy has no stamp and makes no op. Empty placements copy nothing and
    count nothing."""
    dom = _pair.make_domain("local")
    region = dom.alloc(1 << 20)
    win = dom.open_window(region.handle, 1 << 20)
    try:
        placed, end = _spans(_SEGMENTS["header_and_leaf"](), 64)
        before = _place_return()
        t0 = time.monotonic_ns()
        n = rdv.place_released(win.view, placed)
        t1 = time.monotonic_ns()
        assert rdv.place_released(win.view, [(5, memoryview(b""))]) == 0
        after = _place_return()
        got = {k: after[k] - before[k] for k in after}
        if plane == "native":
            assert got["ops"] == 1 and got["bytes"] == n == end - 64
            assert 0 <= got["busy_ns"] <= t1 - t0
            assert got["cpu_ns"] == 0
        else:
            assert got == {"ops": 0, "busy_ns": 0, "bytes": 0, "cpu_ns": 0}
    finally:
        win.close()
        region.close()


def test_the_native_stamp_is_on_the_callers_clock():
    """``tpr_place`` returns ``CLOCK_MONOTONIC`` as ``time.monotonic_ns``
    reads it: taken after the copy, it lies between the caller's own
    stamps before the call and after it."""
    import ctypes

    spin = _native.load_spin()
    if spin is None:
        pytest.skip(f"no native library: {_native.status()}")
    dst, base = _native.pin(bytearray(_LEAF), writable=True)
    src, addr = _native.pin(bytes(_pattern(_LEAF, 12)), writable=False)
    one = ctypes.c_uint64 * 1
    for _ in range(3):
        t0 = time.monotonic_ns()
        stamp = spin.tpr_place(base, one(0), (ctypes.c_void_p * 1)(addr),
                               one(_LEAF), 1)
        t1 = time.monotonic_ns()
        assert t0 <= stamp <= t1
    assert bytes(dst) == bytes(src)


@pytest.mark.parametrize("kind", _KINDS)
def test_first_touch_is_made_once_and_changes_no_byte(plane, kind):
    """``_place_spans`` touches the pages a window has not written yet
    before the released copy, and only then: the bytes land as without
    it, nothing beside the placement moves, the mark is the end of what
    was placed, and a placement under the mark touches nothing (a span
    laid over it by hand survives everywhere but where the copy goes)."""
    segs = _SEGMENTS["several_leaves"]()
    placed, end = _spans(segs, 4096 + 183)
    size = end + 3 * 4096
    dom = _pair.make_domain(kind)
    region = dom.alloc(size)
    win = dom.open_window(region.handle, size)
    try:
        region.buf[:size] = b"\xa5" * size
        want = bytearray(b"\xa5" * size)
        for at, sv in placed:
            want[at:at + len(sv)] = sv
        assert win.touched == 0
        rdv._place_spans(win, placed)
        assert win.touched == end
        assert bytes(region.buf[:size]) == bytes(want)
        # under the mark: only the copy writes
        region.buf[:size] = b"\x5a" * size
        short = [(4096 + 183, memoryview(b"q" * 10))]
        rdv._place_spans(win, short)
        assert win.touched == end
        got = bytes(region.buf[:size])
        assert got[4096 + 183:4096 + 193] == b"q" * 10
        assert got.count(b"\x5a") == size - 10
    finally:
        win.close()
        region.close()


class _Rig:
    """Two real ``RdvLink`` ends back to back (control ops delivered
    synchronously) over a landing pool of one domain kind; the consumer
    copies each message out and lets it go at once."""

    def __init__(self, monkeypatch, kind):
        self.kind = kind
        self.pool = rdv.LandingPool(kind, budget=64 << 20)
        monkeypatch.setattr(rdv, "_pools", {kind: self.pool})
        self.got = []
        ends = {}
        self.a = rdv.RdvLink("a", lambda *op: ends["b"].on_op(*op),
                             lambda *m: None, pool_kinds=(kind,),
                             open_kinds=(kind,))
        self.b = rdv.RdvLink("b", lambda *op: ends["a"].on_op(*op),
                             self._deliver, pool_kinds=(kind,),
                             open_kinds=(kind,))
        ends["a"], ends["b"] = self.a, self.b
        self.a.negotiated = self.b.negotiated = True

    def _deliver(self, stream_id, flags, body):
        self.got.append(bytes(body))

    def send(self, segs):
        total = sum(memoryview(s).nbytes for s in segs)
        return self.a.send_message(1, 0, segs, total)

    def close(self):
        self.a.close()
        self.b.close()
        self.pool.trim()
        rdv.window_share().drain()


@pytest.fixture(params=_KINDS)
def rig(request, monkeypatch):
    r = _Rig(monkeypatch, request.param)
    yield r
    r.close()


@pytest.mark.parametrize("case", ["header_and_leaf", "several_leaves",
                                  "zero_length_segment"])
def test_a_message_arrives_whole_and_is_counted(plane, rig, case):
    """Through ``send_message``, twice (a solicited claim, then the
    standing region it left): the consumer reads the gather list joined,
    and ``rdv_place_released_bytes`` moves with ``rdv_bytes_sent``."""
    before = _counters()
    sent = 0
    for _ in range(2):
        segs = _SEGMENTS[case]()
        want = b"".join(bytes(memoryview(s).cast("B")) for s in segs)
        assert rig.send(segs)
        assert rig.got.pop() == want
        sent += len(want)
    moved = _moved(before)
    assert moved == {"rdv_place_released": 2,
                     "rdv_place_released_bytes": sent,
                     "rdv_bytes_sent": sent, "rdv_fallbacks": 0}


def test_nonce_mismatch_raises_before_any_byte(plane, rig):
    """A claim whose handle resolves to other memory on this host: the
    trailer's nonce is compared before the first byte goes, so the region
    reads afterwards what it read before."""
    lease = rig.pool.lease(_LEAF, 1)
    try:
        kind, handle, offset, capacity, nonce, _ = lease.claim_fields()
        wrong = bytes(b ^ 0xFF for b in nonce)
        claim = rdv._Claim(1, kind, handle, offset, capacity, wrong)
        buf = lease.pr.region.buf
        was = bytes(buf[offset:offset + capacity])
        before = _counters()
        with pytest.raises(OSError, match="nonce"):
            rig.a._rdv_write(claim, [b"h" * 183, _pattern(_LEAF - 183, 1)],
                             _LEAF)
        assert bytes(buf[offset:offset + capacity]) == was
        assert _moved(before)["rdv_place_released"] == 0
        claim.nonce = nonce
        rig.a._rdv_write(claim, [b"h" * 183, _pattern(_LEAF - 183, 1)],
                         _LEAF)
        assert bytes(buf[offset:offset + 183]) == b"h" * 183
    finally:
        lease.release()


def test_simnet_sees_one_write_event_a_placement(plane, rig):
    """The whole gather list is ONE deliverable event on the transport
    seam, however many segments it has."""
    points = []

    def hook(point, obj, fn, args, kwargs):
        points.append(point)
        return NotImplemented

    assert rig.send(_SEGMENTS["several_leaves"]())  # opens the window
    _transport.set_transport_hook(hook)
    try:
        assert rig.send(_SEGMENTS["several_leaves"]())
    finally:
        _transport.set_transport_hook(None)
    assert points.count("write") == 1


def test_block_grants_place_through_the_same_copy(plane):
    """``GrantWriter.write_blocks`` (the KV hand-off's scatter of blocks)
    calls the one placement routine: the blocks land, and the counters say
    the copy ran released."""
    dom = _pair.make_domain("shm")
    block, n = 16 << 10, 4
    size = block * n + 64
    region = dom.alloc(size)
    nonce = os.urandom(16)
    region.buf[block * n:block * n + 16] = nonce
    writer = rdv.GrantWriter()
    try:
        offsets = [3 * block, 0, 2 * block]
        grant = rdv.BlockGrant(7, "shm", region.handle, block, offsets,
                               size, nonce, block * n)
        chunks = [_pattern(block, 1), bytes(_pattern(block - 5, 2)),
                  _pattern(100, 3)]
        before = _counters()
        assert writer.write_blocks(grant, chunks) == 2 * block + 95
        for off, chunk in zip(offsets, chunks):
            assert bytes(region.buf[off:off + len(chunk)]) == bytes(chunk)
        moved = _moved(before)
        assert moved["rdv_place_released"] == 1
        assert moved["rdv_place_released_bytes"] == 2 * block + 95
    finally:
        writer.close()
        rdv.window_share().drain()
        region.close()


def test_the_copy_gives_the_interpreter_up(plane):
    """With the switch interval at seconds nothing is preempted: a thread
    gets the interpreter only when its holder lets go. A second thread that
    counts (and offers the interpreter back each step) stands still while
    the first makes slice assignments of 4 MiB back to back, and counts
    while it places the same bytes through ``place_released``."""
    size = (4 << 20) + 4096
    dom = _pair.make_domain("shm")
    region = dom.alloc(size)
    win = dom.open_window(region.handle, size)
    view = win.view
    hdr = memoryview(bytes(_pattern(183, 1)))
    leaf = memoryview(_pattern(4 << 20, 2))
    count = [0]
    stop = []

    def counter():
        import time

        while not stop:
            count[0] += 1
            time.sleep(0)  # lets go, and stands in line again

    was = sys.getswitchinterval()
    t = threading.Thread(target=counter, daemon=True)
    try:
        sys.setswitchinterval(5.0)
        t.start()
        while count[0] == 0:  # the counter runs and waits for a turn
            pass
        c0 = count[0]
        for _ in range(20):  # the parent's copy: the interpreter held
            view[101:284] = hdr
            view[284:284 + len(leaf)] = leaf
        held = count[0] - c0
        c0 = count[0]
        for _ in range(20):
            rdv.place_released(view, [(101, hdr), (284, leaf)])
        released = count[0] - c0
    finally:
        sys.setswitchinterval(was)
        stop.append(True)
        t.join(timeout=30)
    assert not t.is_alive()
    assert bytes(view[284:284 + 4096]) == bytes(leaf[:4096])
    win.close()
    region.close()
    assert held == 0, "the control: a slice assignment never lets go"
    assert released > 0, "no turn in twenty 4 MiB placements"


def _hold_next_placement(hooks):
    """The next placement stops with its window pinned and nothing copied
    until ``go`` is set; ``entered`` says it is there."""
    entered, go = threading.Event(), threading.Event()

    def pinned():
        hooks.pop("place_pinned", None)
        entered.set()
        assert go.wait(30)

    hooks["place_pinned"] = pinned
    return entered, go


def _run(fn, errors):
    def body():
        try:
            fn()
        except BaseException as exc:  # reported by the test's assert
            errors.append(exc)

    t = threading.Thread(target=body, daemon=True)
    t.start()
    return t


def _close_waits_for_the_copy(closer, kind):
    """A shared-memory window is a mapping of its own, and unmapping it is
    what the pin refuses: the close cannot return before the copy. A local
    window's memory is a ``bytearray`` of this process that the pin keeps
    alive, so its close may return at once and nothing is lost."""
    closer.join(timeout=0.2)
    if kind == "shm":
        assert closer.is_alive(), "the close went through a pinned window"


@pytest.mark.parametrize("kind", _KINDS)
def test_window_closed_under_a_placement_closes_after_it(plane, hooks, kind):
    """A ``_WindowShare`` window released to its close (a private window's
    path, an eviction's, ``drain``'s) while another thread's placement has
    it pinned: the close does not raise, and where it would unmap memory
    it does not return before the copy has; the bytes land in memory that
    is still there, and the window is shut to the placement after it."""
    dom = _pair.make_domain(kind)
    region = dom.alloc(_LEAF + 4096)
    share = rdv._WindowShare()
    win = share.acquire(kind, region.handle, _LEAF + 4096)
    payload = memoryview(_pattern(_LEAF, 5))
    entered, go = _hold_next_placement(hooks)
    errors = []
    try:
        placer = _run(lambda: rdv.place_released(win.view, [(77, payload)]),
                      errors)
        assert entered.wait(30)

        def close():
            share.release(kind, region.handle, win)  # parks it idle
            share.drain()                            # closes it

        closer = _run(close, errors)
        _close_waits_for_the_copy(closer, kind)
        go.set()
        placer.join(timeout=30)
        closer.join(timeout=30)
        assert not placer.is_alive() and not closer.is_alive()
        assert errors == []
        assert bytes(region.buf[77:77 + _LEAF]) == bytes(payload)
        with pytest.raises(ValueError):
            rdv.place_released(win.view, [(77, payload)])
    finally:
        go.set()
        region.close()


def test_link_closed_under_a_placement_then_falls_back(plane, hooks, rig):
    """The link's own ``close`` and the share's close of its windows, from
    another thread, while a sender thread's placement is in flight:
    ``send_message`` returns (placed or fallen back, never an exception
    and never a crash), ``close`` returns after it, and a send after the
    close falls back."""
    segs = _SEGMENTS["header_and_leaf"]
    assert rig.send(segs())  # the window is open and cached
    entered, go = _hold_next_placement(hooks)
    errors, sent = [], []
    sender = _run(lambda: sent.append(rig.send(segs())), errors)
    assert entered.wait(30)

    def close():
        rig.a.close()
        rdv.window_share().drain()

    closer = _run(close, errors)
    _close_waits_for_the_copy(closer, rig.kind)
    go.set()
    sender.join(timeout=30)
    closer.join(timeout=30)
    assert not sender.is_alive() and not closer.is_alive()
    assert errors == [] and sent in ([True], [False])
    before = _counters()
    assert rig.send(segs()) is False
    assert _moved(before)["rdv_place_released"] == 0


class _Wedge:
    """``wedge_after_claim``'s event: says when a sender stands there."""

    def __init__(self):
        self.entered, self.go = threading.Event(), threading.Event()

    def wait(self, timeout=None):
        self.entered.set()
        return self.go.wait(timeout)


def test_placement_after_the_window_closed_falls_back(plane, hooks, rig):
    """A sender holds its claim, and the share closes the link's windows
    (``drain``) before it places: the placement meets a released view
    before any byte moves and takes ``send_message``'s fall-back (grant
    dropped, region released, ``False``)."""
    segs = _SEGMENTS["header_and_leaf"]
    assert rig.send(segs())
    wedge = hooks["wedge_after_claim"] = _Wedge()
    errors, sent = [], []
    try:
        sender = _run(lambda: sent.append(rig.send(segs())), errors)
        assert wedge.entered.wait(30)
        rdv.window_share().drain()
        before = _counters()
    finally:
        hooks.pop("wedge_after_claim", None)
        wedge.go.set()
    sender.join(timeout=30)
    assert not sender.is_alive() and errors == [] and sent == [False]
    moved = _moved(before)
    assert moved["rdv_fallbacks"] == 1 and moved["rdv_place_released"] == 0
