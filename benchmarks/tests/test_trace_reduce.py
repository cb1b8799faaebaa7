"""The reduction from a trace to busy, idle and roofline, on a made-up trace."""

import pytest

import os
import sys

from benchmarks.harness import trace_reduce as tr

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from run import load_reader  # noqa: E402  (benchmarks/run.py, as it is run)


def test_union_merges_overlaps_and_drops_empty():
    assert tr.union([(3, 4), (0, 2), (1, 2.5), (5, 5)]) == [(0, 2.5), (3, 4)]
    assert tr.total(tr.union([(0, 1), (0.5, 1.5)])) == 1.5


def test_gaps_cover_what_busy_leaves_open():
    busy = tr.union([(1, 2), (4, 5)])
    assert tr.gaps(busy, 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert tr.gaps([], 0, 3) == [(0, 3)]


def _trace():
    ops = [("%dynamic_update_slice.1 = u8[16]{0} dynamic-update-slice(u8[16] "
            "%buf)", 1.0, 1.2),
           ("%fusion.3 = u32[] fusion(f32[4] %x)", 1.1, 1.5),  # overlaps
           ("%copy.2 = u32[8] copy(u32[8] %c)", 3.0, 3.5),
           ("%copy.2 = u32[8] copy(u32[8] %c)", 9.5, 11.0)]    # past the end
    modules = [("jit_update(123456)", 1.0, 1.5), ("jit_put(99)", 3.0, 3.5)]
    spans = {"bench.wait_next_message": [(0.0, 0.9), (1.6, 2.6)],
             "bench.pool_put": [(2.6, 3.0)]}
    return ops, modules, spans


def test_reduce_busy_idle_and_breakdown():
    ops, modules, spans = _trace()
    out = tr.reduce(ops, modules, spans, (0.0, 10.0))
    assert out["window_s"] == 10.0
    assert out["busy_s"] == pytest.approx(0.5 + 0.5 + 0.5)  # clipped at 10
    assert out["longest_gap_s"] == pytest.approx(6.0)       # 3.5 .. 9.5
    names = dict(out["device_ops"])
    assert names["jit_update"] == pytest.approx(0.5)
    assert names["%copy.2 copy"] == pytest.approx(1.0)
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    idle = dict(out["idle_gaps"])
    assert idle["bench.wait_next_message"] == pytest.approx(0.9 + 1.0)
    assert idle["bench.pool_put"] == pytest.approx(0.4)
    assert sum(idle.values()) == pytest.approx(10.0 - out["busy_s"])
    assert idle[tr.UNATTRIBUTED] == pytest.approx(8.5 - 2.3)


def test_overlapping_host_spans_never_exceed_the_gap():
    spans = {"bench.a": [(0, 10)], "bench.b": [(0, 10)]}
    out = tr.attribute_gaps([(2, 4)], spans)
    assert out["bench.a"] == pytest.approx(1.0)
    assert out["bench.b"] == pytest.approx(1.0)
    assert out[tr.UNATTRIBUTED] == 0.0


def test_modules_stand_in_when_the_trace_has_no_op_line():
    _, modules, spans = _trace()
    out = tr.reduce([], modules, spans, (0.0, 10.0))
    assert out["busy_s"] == pytest.approx(1.0)


def test_short_name():
    assert tr.short_name("jit_shaped(14239072248760587848)") == "jit_shaped"
    assert tr.short_name(
        "%copy-done = u8[16]{0:T(1024)} copy-done((u8[16]) %s)"
    ) == "%copy-done copy-done"


def _run(busy, window, payload):
    return {"peaks": {"hbm_bytes_per_s": 819e9},
            "trace": {"busy_s": busy, "window_s": window,
                      "payload_bytes": payload, "messages": 1}}


def test_roofline_and_idle_arithmetic():
    # 819 MB need 1 ms at the peak; the device was busy 10 ms: 10%
    run = _run(0.010, 1.0, 819_000_000)
    assert load_reader("hbm_landing_roofline.stream")(run) == pytest.approx(10.0)
    assert load_reader("device_idle_pct.stream")(run) == pytest.approx(99.0)


def test_a_reader_with_nothing_to_read_returns_none_never_zero():
    assert load_reader("hbm_landing_roofline.stream")(_run(0.0, 1.0, 100)) is None
    assert load_reader("hbm_landing_roofline.stream")(_run(0.1, 1.0, 0)) is None
    assert load_reader("device_idle_pct.stream")(_run(0.0, 1.0, 1)) is None
    assert load_reader("hbm_landing_roofline.stream")({"trace": None, "peaks": {}}) is None


def test_counter_readers():
    run = {"payload_bytes": 1000, "messages": 10,
           "server_ledger": {"host_copy": 30, "dma_h2d": 1000,
                             "dma_d2d": 2000, "dma_h2d_ops": 10,
                             "dma_d2d_ops": 20},
           "client_ledger": {"host_copy": 20}}
    assert load_reader("host_copy_b_per_b.stream")(run) == 0.05
    assert load_reader("landing_b_per_b.stream")(run) == 3.0
    assert load_reader("landing_b_per_b.stream")(dict(run, payload_bytes=0)) is None
