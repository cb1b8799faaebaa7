"""TPU data plane: copy ledger accounting, HBM ring leases, device serialization."""

import numpy as np
import pytest

from tpurpc.jaxshim.codec import to_jax
from tpurpc.tpu import HbmRing, ledger
from tpurpc.tpu.serialize import deserialize_to_device, serialize_from_device


# -- ledger ------------------------------------------------------------------

def test_ledger_track_window():
    with ledger.track() as w:
        ledger.host_copy(100)
        ledger.dma_h2d(40)
    assert w["host_copy"] == 100 and w["dma_h2d"] == 40 and w["dma_d2h"] == 0


def test_rpc_path_reports_to_ledger():
    """An end-to-end tensor RPC over loopback rings reports its copies."""
    import jax

    from tpurpc.jaxshim import TensorClient, serve_jax
    from tpurpc.rpc.channel import Channel

    srv, port, _ = serve_jax(lambda t: t, "127.0.0.1:0")
    try:
        x = np.ones((256, 256), np.float32)  # 256KiB — AT the rendezvous bar
        with Channel(f"127.0.0.1:{port}") as ch, ledger.track() as w:
            TensorClient(ch).call("Call", {"x": x}, timeout=30)
        # request+response cross the wire: every payload byte's movement
        # must be visible and bounded (no hidden O(n) blowup). Since
        # tpurpc-express (ISSUE 9), payloads at/over the size bar move as
        # one-sided rendezvous writes (rdma_write) instead of framed
        # assembly copies (host_copy) — a racing first-message hello may
        # still frame a direction, so the TOTAL movement is the invariant.
        moved = w["host_copy"] + w["rdma_write"]
        assert moved >= 2 * x.nbytes
        assert moved <= 8 * x.nbytes
    finally:
        srv.stop(grace=0)


# -- serialize ---------------------------------------------------------------

def test_serialize_from_device_roundtrip():
    import jax.numpy as jnp

    x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
    with ledger.track() as w:
        segs = serialize_from_device(x)
    assert w["dma_d2h"] == 0  # host backend: no movement
    assert w["zero_copy"] == x.nbytes
    buf = b"".join(bytes(s) for s in segs)
    y, end = deserialize_to_device(buf)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


def test_deserialize_bills_its_one_movement_once():
    """One wire record in, one ledger entry out: an alias when the payload
    sits 64-byte aligned in a writable buffer, one h2d copy when it does
    not — never both, never twice, and no host memcpy either way."""
    from tpurpc.jaxshim import codec

    x = np.arange(1024, dtype=np.float32)
    wire = codec.encode_tensor_bytes(x)
    raw = np.zeros(len(wire) + 128, np.uint8)
    start = -raw.ctypes.data % 64          # record (and payload) 64B-aligned
    for shift, kind in ((0, "zero_copy"), (4, "dma_h2d")):
        buf = raw[start + shift:start + shift + len(wire)]
        buf[:] = np.frombuffer(wire, np.uint8)
        with ledger.track() as w:
            y, _ = deserialize_to_device(buf)
        assert w[kind] == x.nbytes and w[kind + "_ops"] == 1, w.delta
        assert w["zero_copy"] + w["dma_h2d"] == x.nbytes
        assert w["host_copy"] == 0
        np.testing.assert_array_equal(np.asarray(y), x)


# -- HBM ring ----------------------------------------------------------------

def test_hbm_ring_place_view_roundtrip():
    ring = HbmRing(1 << 16)
    x = np.arange(512, dtype=np.float32)
    off, n = ring.place(x)
    with ring.view(off, n, np.float32, (512,)) as arr:
        np.testing.assert_array_equal(np.asarray(arr), x)


def test_hbm_ring_wrap_and_reuse():
    cap = 1 << 12  # 4KiB ring
    ring = HbmRing(cap)
    rng = np.random.default_rng(0)
    for i in range(10):  # 10 x 1.5KiB through a 4KiB ring forces wraps
        x = rng.standard_normal(384).astype(np.float32)  # 1536B
        off, n = ring.place(x)
        lease = ring.view(off, n, np.float32, (384,))
        np.testing.assert_array_equal(np.asarray(lease.array), x)
        lease.release()
    st = ring.stats()
    assert st["live_spans"] == 0 and st["writable"] == cap


def test_hbm_ring_lease_pins_span():
    ring = HbmRing(1 << 12)
    x = np.ones(256, np.float32)  # 1KiB
    off, n = ring.place(x)
    lease = ring.view(off, n)
    ring.place(x)  # second message fits
    before = ring.stats()["writable"]
    lease2 = ring.view(off, n)      # second lease on the same span
    lease.release()
    assert ring.stats()["writable"] == before  # still pinned by lease2
    lease2.release()
    assert ring.stats()["writable"] > before   # first span freed


def test_hbm_ring_full_raises():
    ring = HbmRing(1 << 12)
    with pytest.raises(BufferError):
        ring.place(np.zeros(5000, np.uint8))


def test_hbm_ring_ordered_head_advance():
    """Later spans released first must not advance the head past an earlier
    still-unconsumed span (credit ordering, pair.cc:276-284 analog)."""
    ring = HbmRing(1 << 12)
    a = ring.place(np.ones(128, np.uint8))
    b = ring.place(np.ones(128, np.uint8))
    lb = ring.view(*b)
    lb.release()
    assert ring.stats()["head"] == 0  # span a not consumed yet
    la = ring.view(*a)
    la.release()
    assert ring.stats()["head"] == a[1] + b[1]


def test_view_unwrapped_is_dlpack_alias_zero_copy():
    """Round-5 north star half two (VERDICT r4 next #3): an unwrapped span's
    view ALIASES ring memory — ledger zero_copy, no view-side d2d, and the
    aliasing is pointer-verifiable, not asserted on faith."""
    ring = HbmRing(1 << 16)
    x = np.arange(1024, dtype=np.float32)
    off, n = ring.place(x)
    with ledger.track() as w:
        lease = ring.view(off, n, np.float32, (1024,))
    assert lease.aliased, "CPU-backed unwrapped view should be a dlpack alias"
    assert w["zero_copy"] == x.nbytes and w["zero_copy_ops"] == 1
    assert w["dma_d2d"] == 0 and w["dma_d2d_ops"] == 0
    np.testing.assert_array_equal(np.asarray(lease.array), x)
    # independent pointer proof
    ring_ptr = ring._ptr_of(ring.buf)
    view_ptr = ring._ptr_of(lease.array)
    if ring_ptr is not None and view_ptr is not None:
        assert view_ptr == ring_ptr + (off & (ring.capacity - 1))
    lease.release()
    assert ring._aliased == 0


def test_view_alias_survives_later_placements():
    """The stability invariant in practice: placements donate/rebind the
    ring while an aliased lease is live; the lease's bytes must stay
    correct (the allocation is reused in place, and place() asserts it)."""
    ring = HbmRing(1 << 14)
    x = np.arange(512, dtype=np.float32)
    off, n = ring.place(x)
    lease = ring.view(off, n, np.float32, (512,))
    assert lease.aliased
    for i in range(6):  # further traffic through the ring
        o2, n2 = ring.place(np.full(256, i, np.float32))
        ring.view(o2, n2).release()
    np.testing.assert_array_equal(np.asarray(lease.array), x)
    lease.release()


def test_view_wrapped_span_billed_as_d2d():
    """A wrapped span cannot alias (two discontiguous segments): the view
    is a materialization and the ledger must say so."""
    cap = 1 << 12
    ring = HbmRing(cap)
    filler = ring.place(np.zeros(900, np.uint8))
    ring.view(*filler).release()
    big = np.arange(900, dtype=np.float32)  # 3600B from offset 900: wraps
    off, n = ring.place(big)
    assert (off & (cap - 1)) + n > cap, "span did not wrap"
    with ledger.track() as w:
        lease = ring.view(off, n, np.float32, (900,))
    assert not lease.aliased
    assert w["zero_copy"] == 0 and w["dma_d2d"] >= n
    np.testing.assert_array_equal(np.asarray(lease.array), big)
    lease.release()


def test_view_failure_does_not_leak_credit():
    """A poison view request (dtype/shape inconsistent with nbytes —
    wire-reachable through decode_tensor_to_ring's header) must raise
    WITHOUT pinning the span: credit accounting survives, and a correct
    view of the same span still works (reviewer finding, round 5)."""
    ring = HbmRing(1 << 12)
    off, n = ring.place(np.arange(10, dtype=np.uint8))  # 10 bytes
    with pytest.raises(Exception):
        ring.view(off, n, np.float32)  # 10 % 4 != 0: shaping must fail
    # the failed attempt took no lease: a real consume-and-release drains it
    lease = ring.view(off, n)
    assert bytes(np.asarray(lease.array)) == bytes(range(10))
    lease.release()
    st = ring.stats()
    assert st["live_spans"] == 0 and st["head"] == st["tail"]


def test_view_alias_env_opt_out(monkeypatch):
    monkeypatch.setenv("TPURPC_DLPACK_VIEW", "0")
    ring = HbmRing(1 << 14)
    off, n = ring.place(np.ones(256, np.float32))
    with ledger.track() as w:
        lease = ring.view(off, n, np.float32, (256,))
    assert not lease.aliased and w["zero_copy"] == 0 and w["dma_d2d"] == n
    lease.release()


def test_end_to_end_rx_into_hbm_ring_zero_host_copy_after_assembly():
    """North-star shape: wire buffer → HBM placement → device view, with the
    ledger proving no host memcpy after frame assembly."""
    from tpurpc.jaxshim import codec

    x = np.arange(4096, dtype=np.float32)
    wire = bytearray(codec.encode_tensor_bytes(x))
    arr_view, _ = codec.decode_tensor(wire)      # zero-copy parse

    ring = HbmRing(1 << 16)
    with ledger.track() as w:
        off, n = ring.place(arr_view.view(np.uint8))
        with ring.view(off, n, np.float32, (4096,)) as dev:
            np.testing.assert_array_equal(np.asarray(dev), x)
    assert w["host_copy"] == 0
    assert w["dma_h2d"] == x.nbytes


def test_place_is_single_landing_write_all_spans():
    """Every placement must be exactly ONE in-ring
    landing write (dma_d2d op), wrapped or not — the reference's placement
    is always one RDMA WRITE (pair.cc:587-622). The op-count ledger makes
    it assertable; on kernel-ineligible configs the fallback chain pays
    two writes for wrapped spans and the ledger says so honestly."""
    ring = HbmRing(32768)  # >= the kernel's 2*9*512 floor

    # unwrapped span
    with ledger.track() as w:
        off, n = ring.place(bytes(range(256)) * 16)  # 4KiB, fits at 0
    assert (w["dma_h2d_ops"], w["dma_d2d_ops"]) == (1, 1), w.delta
    lease = ring.view(off, n)
    assert bytes(np.asarray(lease.array)) == bytes(range(256)) * 16
    lease.release()

    # drive tail near the end so the next span WRAPS
    filler = 32768 - (ring.tail & (32768 - 1)) - 2048
    off2, n2 = ring.place(b"\0" * filler)
    ring.view(off2, n2).release()
    payload = bytes(range(256)) * 16  # 4KiB > the 2KiB left before the edge
    with ledger.track() as w:
        off3, n3 = ring.place(payload)
    assert (off3 & (32768 - 1)) + n3 > 32768, "span did not wrap"
    # kernel-eligible configs land the wrap in ONE aliased write; on
    # ineligible ones (TPURPC_PALLAS=0, a backend that is neither cpu nor
    # tpu) the chain pays two and the ledger says so
    kernel = ring._pallas_ok(off3 & (32768 - 1), n3, 2 * 9 * 512)
    expect = 1 if kernel else 2
    assert (w["dma_h2d_ops"], w["dma_d2d_ops"]) == (1, expect), w.delta
    lease3 = ring.view(off3, n3)
    assert bytes(np.asarray(lease3.array)) == payload
    lease3.release()


# -- placement: JAX's default device, not device 0 ----------------------------

def test_to_jax_and_default_ring_follow_default_device():
    """``to_jax`` (writable AND read-only input) and a default ``HbmRing()``
    land on JAX's default device. On the 8-device CPU mesh this reproduces
    without a chip what happened on one: the dlpack import ignored the
    default device and put every writable view on CPU device 0."""
    import jax

    d3 = jax.devices()[3]
    x = np.arange(4096, dtype=np.float32)
    frozen = x.view()
    frozen.setflags(write=False)
    with jax.default_device(d3):
        for arr in (x, frozen):
            out = to_jax(arr)
            assert out.devices() == {d3}
            np.testing.assert_array_equal(np.asarray(out), x)
        ring = HbmRing(1 << 16)
        assert ring.device == d3 and ring.buf.devices() == {d3}
        off, n = ring.place(x)
        with ring.view(off, n, np.float32, x.shape) as arr:
            assert arr.devices() == {d3}
            np.testing.assert_array_equal(np.asarray(arr), x)


def test_to_jax_bills_what_happened():
    """An aligned writable view on a CPU device is an alias (zero_copy,
    pointer-proven); a read-only one, and a dtype dlpack cannot carry, is
    one copy (dma_h2d) — decided up front, never by catching an import
    error."""
    import ml_dtypes

    raw = np.zeros(4096 + 64, np.uint8)
    start = -raw.ctypes.data % 64
    aligned = raw[start:start + 4096].view(np.float32)
    aligned[:] = np.arange(1024)
    with ledger.track() as w:
        out = to_jax(aligned)
    assert (w["zero_copy"], w["dma_h2d"]) == (4096, 0)
    assert out.unsafe_buffer_pointer() == aligned.ctypes.data
    frozen = aligned.view()
    frozen.setflags(write=False)
    with ledger.track() as w:
        to_jax(frozen)
    assert (w["zero_copy"], w["dma_h2d"]) == (0, 4096)
    bf16 = np.ones(64, ml_dtypes.bfloat16)
    with ledger.track() as w:
        out = to_jax(bf16)
    assert (w["zero_copy"], w["dma_h2d"]) == (0, bf16.nbytes)
    assert out.dtype == bf16.dtype


# -- a failing kernel is an error, not a detour -------------------------------

class _FakeTpu:
    """Stands in for ``ring.device`` where only ``.platform`` is read."""
    platform = "tpu"


def test_kernel_failure_propagates_on_tpu_platform(monkeypatch):
    """On a ring whose device says ``tpu`` the kernels are asked for
    compiled (interpret=False), and an exception out of either one reaches
    the caller: no latch, no warning, no slice chain taking over."""
    import jax

    import tpurpc.ops as ops_pkg
    import tpurpc.ops.ring_scatter as scatter_mod
    from tpurpc.obs import metrics

    cap = 32768
    ring = HbmRing(cap)
    off, n = ring.place(b"\0" * (cap - 2048))
    ring.view(off, n).release()
    payload = bytes(range(256)) * 16          # 4 KiB over the 2 KiB left
    off, n = ring.place(payload)              # lands wrapped, via the
    assert (off & (cap - 1)) + n > cap        # interpreted kernel (CPU)

    asked = []

    def boom(*_a, interpret, **_kw):
        asked.append(interpret)
        raise RuntimeError("kernel boom")

    monkeypatch.setattr(ops_pkg, "ring_window", boom)
    monkeypatch.setattr(scatter_mod, "ring_scatter", boom)
    before = metrics.registry().counters_snapshot()
    ring.device = _FakeTpu()
    with pytest.raises(RuntimeError, match="kernel boom"):
        ring.view(off, n)
    dev_payload = jax.device_put(np.frombuffer(payload, np.uint8))
    with pytest.raises(RuntimeError, match="kernel boom"):
        with ring._lock:
            ring._land(dev_payload, off & (cap - 1), n)
    assert asked == [False, False]
    after = metrics.registry().counters_snapshot()
    assert after["hbm_view_concat"] == before["hbm_view_concat"]
    assert after["hbm_place_split"] == before["hbm_place_split"]
    # the failed view took no lease: with the kernel back, the span reads
    monkeypatch.undo()
    ring.device = jax.devices()[0]
    with ring.view(off, n) as arr:
        assert bytes(np.asarray(arr)) == payload


# -- direct landing: a ring whose views cannot alias it -----------------------

@pytest.fixture
def direct_ring(monkeypatch):
    """A ring factory on the path every TPU ring takes: no view can alias the
    ring (here by ``TPURPC_DLPACK_VIEW=0``, read once as the ring is made),
    so ``land_many`` puts each leaf straight into its final array."""
    monkeypatch.setenv("TPURPC_DLPACK_VIEW", "0")

    def make(capacity=1 << 16):
        ring = HbmRing(capacity)
        assert not ring._aliasing
        return ring
    return make


def _path_counters():
    from tpurpc.obs import metrics

    snap = metrics.registry().counters_snapshot()
    return {k: v for k, v in snap.items()
            if k.startswith(("hbm_place_", "hbm_view_"))}


def _moved(before):
    """The path counters that moved since ``before = _path_counters()``."""
    return {k: v - before[k] for k, v in _path_counters().items()
            if v != before[k]}


def test_aliasing_is_decided_once_per_ring(monkeypatch):
    ring = HbmRing(1 << 12)
    assert ring._aliasing  # CPU device, default settings
    monkeypatch.setenv("TPURPC_DLPACK_VIEW", "0")
    assert ring._aliasing and not HbmRing(1 << 12)._aliasing


@pytest.mark.parametrize("dtype, shape", [
    (np.float32, (32, 32)), (np.uint8, (4096,)), (np.int32, (3, 5, 7)),
    ("bfloat16", (8, 16)), (np.float32, (0, 3)), (np.float16, ())])
def test_land_direct_one_transfer_no_ring_program(direct_ring, dtype, shape):
    import jax
    import ml_dtypes

    dt = np.dtype(ml_dtypes.bfloat16 if dtype == "bfloat16" else dtype)
    x = (np.arange(int(np.prod(shape)), dtype=np.float64) * 3 - 7).astype(
        dt).reshape(shape)
    wire = bytearray(x.tobytes())  # the wire buffer, reused below
    ring = direct_ring()
    before = _path_counters()
    with ledger.track() as w:
        lease = ring.land(wire, dt, shape)
    wire[:] = bytes(len(wire))  # the array must not alias the wire buffer
    arr = lease.array
    assert isinstance(arr, jax.Array) and arr.devices() == {ring.device}
    assert arr.dtype == dt and arr.shape == tuple(shape)
    assert not lease.aliased
    np.testing.assert_array_equal(np.asarray(arr), x)
    assert w["dma_h2d"] == x.nbytes and w["dma_h2d_ops"] == bool(x.nbytes)
    assert w["dma_d2d"] == w["zero_copy"] == w["host_copy"] == 0
    assert _moved(before) == {
        "hbm_place_direct": 1, "hbm_view_direct": 1, "hbm_place_msgs": 1,
        **({"hbm_place_bytes": x.nbytes} if x.nbytes else {})}
    assert ring.stats()["live_spans"] == bool(x.nbytes)
    assert ring.stats()["tail"] == x.nbytes
    lease.release()
    lease.release()  # idempotent
    st = ring.stats()
    assert st["live_spans"] == 0 and st["head"] == st["tail"] == x.nbytes


def test_land_direct_credit_window(direct_ring):
    """16 KiB of credit, 8 KiB messages: the third landing blocks until a
    release; leases released out of order advance the head in order; over
    capacity raises at once; a timeout raises and changes nothing."""
    import threading
    import time

    ring = direct_ring(1 << 14)
    msg = np.arange(2048, dtype=np.float32)  # 8 KiB
    f32 = np.dtype(np.float32)
    a = ring.land(msg, f32, (2048,))
    b = ring.land(msg, f32, (2048,))
    assert ring.writable() == 0
    t0 = time.monotonic()
    with pytest.raises(BufferError, match="ring full"):
        ring.land(msg, f32, (2048,), timeout=0.05)
    assert 0.04 <= time.monotonic() - t0 < 2
    with pytest.raises(BufferError):
        ring.land(msg, f32, (2048,))  # timeout=None never waits
    assert ring.stats() == {"capacity": 1 << 14, "head": 0, "tail": 1 << 14,
                            "live_spans": 2, "writable": 0}
    t0 = time.monotonic()
    with pytest.raises(BufferError, match="capacity"):
        ring.land(np.zeros((1 << 14) + 4, np.uint8), np.dtype(np.uint8),
                  ((1 << 14) + 4,), timeout=30)
    assert time.monotonic() - t0 < 1
    # out of order: b's release frees nothing while a is held
    b.release()
    assert ring.stats()["head"] == 0 and ring.writable() == 0
    timer = threading.Timer(0.1, a.release)
    timer.start()
    t0 = time.monotonic()
    c = ring.land(msg, f32, (2048,), timeout=10)  # blocks, then lands
    assert time.monotonic() - t0 >= 0.05
    timer.join(timeout=10)
    assert not timer.is_alive()
    np.testing.assert_array_equal(np.asarray(c.array), msg)
    st = ring.stats()
    assert st["head"] == 1 << 14 and st["live_spans"] == 1
    c.release()
    assert ring.writable() == 1 << 14
    # the arrays handed out are snapshots: they outlive their credit
    np.testing.assert_array_equal(np.asarray(a.array), msg)


@pytest.mark.parametrize("aliasing", [True, False], ids=["alias", "direct"])
def test_land_misfit_returns_every_byte_of_credit(monkeypatch, aliasing):
    """A leaf whose dtype or shape does not fit its bytes (wire-reachable:
    the header is the sender's) raises, and no credit stays behind, whether
    it is the only leaf or sits between two good ones."""
    if not aliasing:
        monkeypatch.setenv("TPURPC_DLPACK_VIEW", "0")
    ring = HbmRing(1 << 12)
    assert ring._aliasing == aliasing
    good = (np.arange(64, dtype=np.float32), np.dtype(np.float32), (64,))
    for bad in ((np.zeros(10, np.uint8), np.dtype(np.float32), (2,)),
                (np.zeros(16, np.uint8), np.dtype(np.float32), (5,))):
        with pytest.raises(Exception):
            ring.land(*bad)
        with pytest.raises(Exception):
            ring.land_many([good, bad, good])
        st = ring.stats()
        assert st["live_spans"] == 0 and st["head"] == st["tail"], st
    (lease,) = ring.land_many([good])
    np.testing.assert_array_equal(np.asarray(lease.array), good[0])
    lease.release()
    assert ring.writable() == ring.capacity


def test_place_view_and_lease_region_keep_their_paths_on_a_direct_ring(
        direct_ring):
    """``place`` / ``view`` / ``place_many`` / ``lease_region`` called
    directly still go through the ring's bytes, whatever ``land_many``
    does: update + slice, counted and billed as before."""
    ring = direct_ring()
    x = np.arange(256, dtype=np.float32)
    before = _path_counters()
    with ledger.track() as w:
        off, n = ring.place(x)
        with ring.view(off, n, np.float32, (256,)) as arr:
            np.testing.assert_array_equal(np.asarray(arr), x)
        region = ring.lease_region(x.nbytes)
        region.fill(x)
        with region.view(np.float32, (256,)) as arr:
            np.testing.assert_array_equal(np.asarray(arr), x)
        region.release()
    moved = _moved(before)
    assert moved["hbm_place_update"] == 2 and moved["hbm_view_slice"] == 2
    assert "hbm_place_direct" not in moved and "hbm_view_direct" not in moved
    assert w["dma_h2d"] == 2 * x.nbytes and w["dma_d2d"] == 4 * x.nbytes
    assert ring.writable() == ring.capacity
