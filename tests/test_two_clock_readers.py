"""The eleven per-layer readers of ISSUE 39 (thread CPU beside wall, the
price of a give-up, the observers' share, the process's cores): each against
a made-up traced run, against a bare one, and against what a tree WITHOUT the
second clock hands it (the parent commit, where every one of them has to find
nothing and say so); and the manifest with the eleven entries appended."""

import copy
import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.tests import manifest_check  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)

B, MSGS = 900, 7200          # batches, messages (8 a batch)
WALL = 15_300_000_000        # between the harness's two snapshots

#: the counters a tree without the second clock has in such a window: every
#: reader must read nothing here, though the wall-clock twins are all there
PARENT = {
    "lens_hbm_busy_ns": 2_400_000 * MSGS, "lens_hbm_ops": MSGS,
    "lens_decode_busy_ns": 3_000_000 * MSGS, "lens_decode_ops": MSGS,
    "lens_srv_handler_busy_ns": 3_200_000 * MSGS,
    "lens_srv_handler_ops": MSGS,
    "lens_srv_recv_busy_ns": 250_000 * MSGS, "lens_srv_recv_ops": MSGS,
    "lens_srv_send_busy_ns": 3_900_000 * MSGS, "lens_srv_send_ops": MSGS,
    "lens_d2h_busy_ns": 1_900_000 * MSGS, "lens_d2h_ops": MSGS,
    "lens_batch_stack_busy_ns": 5_400_000 * B, "lens_batch_stack_ops": B,
    "lens_batch_run_busy_ns": 10_700_000 * B, "lens_batch_run_ops": B,
    "lens_batch_d2h_busy_ns": 120_000 * B, "lens_batch_d2h_ops": B,
    "rdv_place_released": MSGS, "batcher_batches": B,
}
CHANGE = dict(
    PARENT,
    lens_hbm_cpu_ns=600_000 * MSGS, lens_decode_cpu_ns=700_000 * MSGS,
    lens_srv_handler_cpu_ns=760_000 * MSGS,
    lens_srv_recv_cpu_ns=20_000 * MSGS, lens_srv_send_cpu_ns=800_000 * MSGS,
    lens_d2h_cpu_ns=1_000_000 * MSGS,
    lens_batch_stack_cpu_ns=650_000 * B, lens_batch_run_cpu_ns=1_100_000 * B,
    lens_batch_d2h_cpu_ns=30_000 * B,
    lens_batch_ready_busy_ns=2_000_000 * B, lens_batch_ready_ops=B,
    lens_batch_ready_cpu_ns=15_000 * B,
    lens_place_return_busy_ns=1_900_000 * MSGS, lens_place_return_ops=MSGS,
    proc_cpu_ns=2 * WALL, proc_wall_ns=WALL,
    obs_bg_cpu_ns=WALL // 100, obs_bg_ticks=780,
    lens_cpu_clock_reads=2 * 7 * (MSGS + B) // 31,
)
_STAGES_CPU = (20_000 + 760_000 + 800_000) * MSGS + (
    650_000 + 1_100_000 + 30_000) * B
EXPECT = {
    "hbm_place_cpu_us": 600.0,
    "srv_handler_self_cpu_us": 60.0,
    "batch_stack_cpu_us": 650.0,
    "batch_run_cpu_us": 1100.0,
    "batch_ready_us": 2000.0,
    "srv_send_cpu_us": 800.0,
    "place_return_us": 1900.0,
    "d2h_cpu_us": 1000.0,
    "host_cores_busy": 2.0,
    "stage_cpu_cover_pct": 100.0 * _STAGES_CPU / (2 * WALL),
    "obs_bg_cpu_pct": 100.0 * (WALL // 100) / WALL,
}
CELLS = {
    "hbm_place_cpu_us": ["stream4m_c1", "stream4m_c8", "fanin4m_c8",
                         "fanex4m_c8"],
    "srv_handler_self_cpu_us": ["stream4m_c1", "stream4m_c8", "fanin4m_c8",
                                "fanex4m_c8"],
    "batch_stack_cpu_us": ["fanin4m_c8", "fanex4m_c8"],
    "batch_run_cpu_us": ["fanin4m_c8", "fanex4m_c8"],
    "batch_ready_us": ["fanin4m_c8", "fanex4m_c8"],
    "srv_send_cpu_us": ["pingpong4m_c1", "fanex4m_c8"],
    "place_return_us": ["pingpong4m_c1", "fanex4m_c8"],
    "d2h_cpu_us": ["pingpong4m_c1"],
    "host_cores_busy": [w["name"] for w in MANIFEST["workloads"][:5]],
    "stage_cpu_cover_pct": [w["name"] for w in MANIFEST["workloads"][:5]],
    "obs_bg_cpu_pct": [w["name"] for w in MANIFEST["workloads"][:5]],
}


def run_of(counters):
    return {"cell": "fanex4m_c8", "payload_bytes": MSGS * 4194304,
            "messages": MSGS, "server_ledger": {}, "client_ledger": {},
            "counters": dict(counters), "peaks": {"hbm_bytes_per_s": 819e9},
            "trace": {"busy_s": 0.05, "window_s": 2.0, "messages": 960,
                      "payload_bytes": 960 * 4194304, "device_ops": []}}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_a_reader_reads_the_change(name):
    run = run_of(CHANGE)
    assert reader(name)(copy.deepcopy(run)) == pytest.approx(EXPECT[name])
    assert run == run_of(CHANGE)  # a reader changes nothing it is handed


@pytest.mark.parametrize("name", sorted(EXPECT))
@pytest.mark.parametrize("tree", ["bare", "parent"])
def test_a_reader_finds_nothing_on_a_tree_without_its_counters(name, tree):
    counters = {} if tree == "bare" else PARENT
    assert reader(name)(run_of(counters)) is None


def test_a_cpu_twin_never_reads_over_its_wall_twin_on_the_made_up_run():
    """The acceptance check made of a traced chip run, against the shapes
    here: every ``_cpu_us`` reader under the wall-clock reader it twins."""
    run = run_of(CHANGE)
    for cpu, wall in (("hbm_place_cpu_us", "hbm_place_us.fanex"),
                      ("batch_stack_cpu_us", "batch_stack_us.fanex"),
                      ("batch_run_cpu_us", "batch_run_us.fanex"),
                      ("srv_send_cpu_us", "srv_send_us.fanex"),
                      ("d2h_cpu_us", "d2h_us.pingpong"),
                      ("srv_handler_self_cpu_us", "srv_handoff_us.fanex")):
        assert reader(cpu)(run) <= reader(wall)(run)


#: the readers of a thread's CPU clock, and the counters each divides
CPU_TWINS = {
    "hbm_place_cpu_us": ("lens_hbm_cpu_ns",),
    "srv_handler_self_cpu_us": ("lens_srv_handler_cpu_ns",
                                "lens_decode_cpu_ns"),
    "batch_stack_cpu_us": ("lens_batch_stack_cpu_ns",),
    "batch_run_cpu_us": ("lens_batch_run_cpu_ns",),
    "srv_send_cpu_us": ("lens_srv_send_cpu_ns",),
    "d2h_cpu_us": ("lens_d2h_cpu_ns",),
}


@pytest.mark.parametrize("name", sorted(CPU_TWINS))
def test_a_cpu_twin_whose_counter_did_not_move_reads_zero_not_nothing(name):
    """The harness hands a reader ``delta(after, before)``, which drops every
    counter that did not move. One message in N is clocked and the chip
    host's thread clock steps in 10 ms, so a stage of little CPU (``d2h``:
    a wait) may see no step in a whole window: the driver's traced run of
    ``pingpong4m_c1`` did, the line lacked ``d2h_cpu_us`` and the PR was
    refused. The stage ran, the clock was read: that is 0, not nothing."""
    still = {k: v for k, v in CHANGE.items() if k not in CPU_TWINS[name]}
    assert reader(name)(run_of(still)) == 0.0
    # and nothing where the clock was never read, whatever else is there
    unread = {k: v for k, v in CHANGE.items() if k != "lens_cpu_clock_reads"}
    assert reader(name)(run_of(unread)) is None


def test_the_cover_and_the_cores_read_with_a_stage_that_did_not_move():
    still = {k: v for k, v in CHANGE.items()
             if k != "lens_srv_handler_cpu_ns"}
    assert reader("stage_cpu_cover_pct")(run_of(still)) == pytest.approx(
        100.0 * (_STAGES_CPU - 760_000 * MSGS) / (2 * WALL))
    idle = {k: v for k, v in CHANGE.items() if k != "proc_cpu_ns"}
    assert reader("host_cores_busy")(run_of(idle)) == 0.0


def test_the_observers_share_reads_zero_where_they_are_off():
    """``TPURPC_LENS=0`` and its kin: the process clocks are there, no
    observer ticked, and the share is 0, not missing."""
    quiet = {k: v for k, v in CHANGE.items() if not k.startswith("obs_bg")}
    assert reader("obs_bg_cpu_pct")(run_of(quiet)) == 0.0


def test_the_manifest_gained_eleven_entries_at_its_end_and_lost_nothing():
    assert manifest_check.problems(MANIFEST, ROOT) == []
    names = [e["name"] for e in MANIFEST["per_layer"]]
    first = names.index("hbm_place_cpu_us")
    new = MANIFEST["per_layer"][first:first + 11]
    assert [e["name"] for e in new] == [
        "hbm_place_cpu_us", "srv_handler_self_cpu_us", "batch_stack_cpu_us",
        "batch_run_cpu_us", "batch_ready_us", "srv_send_cpu_us",
        "place_return_us", "d2h_cpu_us", "host_cores_busy",
        "stage_cpu_cover_pct", "obs_bg_cpu_pct"]
    assert names[first - 1] == "batch_stack_roofline.fanex"  # PR 36's last
    for e in new:
        assert e["source"] == "program_counter"
        assert e["moves"] == "hbm_gbytes_s"
        assert e["workloads"] == CELLS[e["name"]]
        # the two cell tests that filter the manifest by an exact list
        assert e["workloads"] not in (["fanin4m_c8"], ["fanex4m_c8"])
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "layer_metrics", e["name"] + ".py"))
    layers = {e["layer"] for e in MANIFEST["per_layer"][:first]}
    assert {e["layer"] for e in new} <= layers
    assert sorted(EXPECT) == sorted(e["name"] for e in new)
