"""The nine per-layer readers of ISSUE 26 (server stage counters) against
made-up runs: the value each gives, nothing where its count is 0 or its
counters are absent (the parent commit has none of them and must print a line
without them), and a CPU rehearsal that names them all."""

import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.tests.test_rehearsal import ROOT, rehearse

MSGS = 1000
COUNTERS = {
    # one stream of 1000 messages of 4 MiB: 1.9 ms a message
    "rdv_bytes_received": MSGS * (4194304 + 128),
    "lens_srv_recv_busy_ns": 150_000 * (MSGS + 1),
    "lens_srv_recv_ops": MSGS + 1,
    "lens_srv_queue_busy_ns": 3_000_000 * MSGS, "lens_srv_queue_ops": MSGS,
    "lens_srv_handler_busy_ns": 1_700_000 * MSGS,
    "lens_srv_handler_ops": MSGS,
    "lens_srv_send_busy_ns": 400_000, "lens_srv_send_ops": 1,
    "lens_srv_call_busy_ns": 1_900_000 * MSGS, "lens_srv_call_ops": 1,
    "lens_decode_busy_ns": 1_400_000 * MSGS, "lens_decode_ops": MSGS,
    "lens_hbm_credit_busy_ns": 10_000 * MSGS, "lens_hbm_credit_ops": 20,
    "lens_hbm_busy_ns": 800_000 * MSGS, "lens_hbm_ops": MSGS,
    "lens_hbm_view_busy_ns": 500_000 * MSGS, "lens_hbm_view_ops": MSGS,
}
WANT = {
    "rdv_bytes_pct.stream": 100.0 * (4194304 + 128) / 4194304,
    "srv_recv_wait_us.stream": 150.0,
    "srv_queue_wait_us.stream": 3000.0,
    "srv_handler_self_us.stream": 300.0,
    "srv_unattributed_pct.stream": 100.0 * (1 - (150_000 * 1001 + 1.7e9
                                                 + 400_000) / 1.9e9),
    "decode_self_us.stream": 90.0,
    "hbm_credit_wait_us.stream": 10.0,
    "hbm_place_us.stream": 800.0,
    "hbm_view_us.stream": 500.0,
}


def made_up(counters):
    return {"cell": "stream4m_c1", "messages": MSGS,
            "payload_bytes": MSGS * 4194304, "counters": dict(counters),
            "server_ledger": {}, "client_ledger": {}, "peaks": None,
            "trace": None}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_value_and_nothing_on_a_zero_count(name):
    read = bench_run.load_reader(name)
    assert read(made_up(COUNTERS)) == pytest.approx(WANT[name], rel=1e-9)
    # the parent commit: none of the counters exists, nothing is read
    assert read(made_up({"lens_decode_busy_ns": 5, "lens_hbm_busy_ns": 3,
                         "hbm_place_msgs": MSGS})) is None
    assert read(dict(made_up(COUNTERS), payload_bytes=0, counters={})) is None


def test_queue_and_rendezvous_readers_take_whichever_plane_carried_it():
    native = {"native_srv_queue_ns": 7_000_000, "native_srv_queue_msgs": 10,
              "native_rdv_recv_bytes": 2 * 4194304}
    run = dict(made_up(native), payload_bytes=4 * 4194304)
    assert bench_run.load_reader("srv_queue_wait_us.stream")(run) == 700.0
    assert bench_run.load_reader("rdv_bytes_pct.stream")(run) == 50.0
    both = dict(native, lens_srv_queue_busy_ns=3_000_000,
                lens_srv_queue_ops=10, rdv_bytes_received=4194304)
    run = dict(made_up(both), payload_bytes=4 * 4194304)
    assert bench_run.load_reader("srv_queue_wait_us.stream")(run) == 500.0
    assert bench_run.load_reader("rdv_bytes_pct.stream")(run) == 75.0


def test_credit_wait_reads_zero_where_no_placement_blocked():
    run = made_up({"lens_hbm_ops": 50, "lens_hbm_busy_ns": 1000})
    assert bench_run.load_reader("hbm_credit_wait_us.stream")(run) == 0.0


def test_the_manifest_lists_the_nine_for_both_cells_and_nothing_moved():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    names = [e["name"] for e in m["per_layer"]]
    assert names[:4] == ["host_copy_b_per_b.stream", "landing_b_per_b.stream",
                         "hbm_landing_roofline.stream",
                         "device_idle_pct.stream"]
    assert sorted(names[4:]) == sorted(WANT)
    for e in m["per_layer"][4:]:
        assert e["source"] == "program_counter"
        assert e["moves"] == "hbm_gbytes_s"
        assert e["workloads"] == ["stream4m_c1", "stream4m_c8"]


def test_a_traced_rehearsal_names_every_metric_a_cpu_can_read():
    """All thirteen are asked for; the two that read the device's trace
    (`hbm_landing_roofline.stream`, `device_idle_pct.stream`) find no device
    op on the CPU, the eleven counter metrics are all named."""
    _, lines, _ = rehearse(ROOT, "stream4m_c1", trace="1", seconds="2")
    (named,) = [ln for ln in lines if "metrics are not printed" in ln]
    for name in list(WANT) + ["host_copy_b_per_b.stream",
                              "landing_b_per_b.stream"]:
        assert f"'{name}'" in named, named
