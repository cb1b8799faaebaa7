"""Tier-1's share of the benchmark cell ``fanin4m_c8`` (ISSUE 33): the plain
reference ``benchmarks/configs/fanin_reference.py`` against pools the
handler's own consumer program filled on the CPU at byte sizes (one sound, one
for each guarantee broken), the cell's per-layer readers with and without the
program's counters, the manifest's new entries, and the cell end to end at
KiB sizes (``run.py --rehearsal-cpu``), sound and with faults planted."""

import copy
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.configs import fanin_reference as ref  # noqa: E402
from benchmarks.handlers import pool_batch  # noqa: E402
from benchmarks.harness.payloads import Bank  # noqa: E402
from benchmarks.tests import manifest_check  # noqa: E402

SEED = 3600000901          # the driver's seeds are over 2**31
CONFIG = {
    "rpc": "stream_stream", "bank_messages": 4,
    "message": {"dtype": "float32", "shape": [8, 8], "bytes": 256},
    "batch": {"max_rows": 8, "fixed_bucket": True, "max_delay_ms": 50,
              "log_batches": 64},
    "pool": {"bytes": 5 * 8 * 256}, "audit": {"sampled_slots": 3},
}
TRAFFIC = {"connections": 3}
EACH = 23                   # messages a connection: 69 rows, 9 or more batches


def interleave(seed, each=EACH, conns=3):
    """A seeded valid interleaving, cut into batches of 1 to 8 rows."""
    rng = np.random.default_rng(seed)
    order = np.repeat(np.arange(conns), each)
    rng.shuffle(order)
    nxt, rows = [0] * conns, []
    for c in order:
        rows.append((int(c), nxt[c]))
        nxt[c] += 1
    batches = []
    while rows:
        n = int(rng.integers(1, 9))
        batches.append(rows[:n])
        rows = rows[n:]
    return batches


def fill(batches, fault=None, counts=None):
    """What the server would report after consuming ``batches`` (lists of
    ``(conn, seq)``) by the handler's own program: ``(facts, sample,
    blobs)``."""
    import jax

    ctx = types.SimpleNamespace(config=CONFIG, traffic=TRAFFIC, seed=SEED,
                                device=jax.devices()[0], fault=fault)
    h = pool_batch.PoolBatch(ctx)
    try:
        banks = [Bank(SEED, c, CONFIG) for c in range(3)]
        for rows in batches:
            batch = np.zeros((8, 8, 8), np.float32)
            for i, (c, k) in enumerate(rows):
                batch[i] = banks[c].message_copy(k)
            h._step({"x": jax.device_put(batch, ctx.device)},
                    jax.device_put(np.int32(len(rows)), ctx.device))
        for c, cn in enumerate(h.per_conn):
            cn.n = counts[c] if counts else EACH
        sample = ref.plan_sample(CONFIG, TRAFFIC, SEED, h.counts())
        facts, blobs = h.audit(sample)
        return json.loads(json.dumps(facts)), sample, blobs
    finally:
        h.free()


@pytest.fixture(scope="module")
def sound():
    return fill(interleave(11))


def check(facts, sample, blobs, acked=(EACH,) * 3):
    return ref.check(CONFIG, TRAFFIC, SEED, facts, sample, blobs,
                     list(acked))


def test_reference_accepts_a_sound_pool(sound):
    facts, sample, blobs = sound
    assert facts["batches"] == len(facts["log_rows"]) >= 9
    assert len(sample) == 3 and len(blobs[0]) == 8 * 256
    assert check(*sound) == dict.fromkeys(ref.LIMITS, 0)


def _drop(b):
    return [[r for r in rows if r != (1, 6)] for rows in b]


def _dup(b):
    at = next(i for i, rows in enumerate(b) if len(rows) < 8)
    return [rows + [rows[-1]] if i == at else rows
            for i, rows in enumerate(b)]


def _reorder(b):
    flat = [r for rows in b for r in rows]
    i, j = flat.index((2, 3)), flat.index((2, 4))
    flat[i], flat[j] = flat[j], flat[i]
    it = iter(flat)
    return [[next(it) for _ in rows] for rows in b]


@pytest.mark.parametrize("fault,reshape,caught", [
    ("approx_bf16", None, {"folds_wrong", "slots_wrong",
                           "sample_bytes_wrong"}),
    ("alter", None, {"folds_wrong", "slots_wrong"}),
    ("drop", _drop, {"log_wrong", "folds_wrong"}),
    ("reorder", _reorder, {"log_wrong", "folds_wrong"}),
    ("dup", _dup, {"log_wrong", "folds_wrong"}),
])
def test_each_planted_fault_is_caught(fault, reshape, caught):
    """The five faults of the control, each where it is produced: the two
    the consumer program plants by its ``fault``, the three the handler
    plants on a row's way to the batcher by the batches it would make."""
    batches = interleave(11)
    if reshape is not None:
        batches, fault = reshape(batches), None
        assert [r for rows in batches for r in rows] != [
            r for rows in interleave(11) for r in rows]
    got = check(*fill(batches, fault))
    wrong = {k for k, v in got.items() if v > ref.LIMITS[k]}
    assert caught <= wrong and "acks_wrong" not in wrong


def _mutate(name):
    def edit(facts, blobs, acked):
        if name == "pad_row_inside":
            b = next(i for i, n in enumerate(facts["log_rows"]) if n >= 2)
            facts["log_rows"][b] -= 1     # its last request row is now a pad
        elif name == "batch_over_max_rows":
            facts["log_rows"][0] = 9
        elif name == "empty_batch":
            facts["log_rows"][1] = 0
        elif name == "unknown_connection":
            facts["log_stamps"][0][0][1] = 7
        elif name == "lost_batches":
            facts["batches"] += 2
        elif name == "unacknowledged":
            acked[1] -= 1
        elif name == "fold":
            facts["acc"][2] ^= 1
        elif name == "stray_write":
            facts["row_sums"][4][7] ^= 1
        elif name == "sampled_byte":
            blobs[1] = blobs[1][:100] + bytes([blobs[1][100] ^ 1]) + blobs[
                1][101:]
        elif name == "short_blob":
            blobs[2] = blobs[2][:-4]
        elif name == "missing_blob":
            blobs.pop()
        elif name == "message_missing_below_the_count":
            facts["n"][0] += 1
            acked[0] += 1
    return edit


@pytest.mark.parametrize("name,counter", [
    ("pad_row_inside", "log_wrong"), ("batch_over_max_rows", "log_wrong"),
    ("empty_batch", "log_wrong"), ("unknown_connection", "log_wrong"),
    ("lost_batches", "log_wrong"), ("unacknowledged", "acks_wrong"),
    ("fold", "folds_wrong"), ("stray_write", "slots_wrong"),
    ("sampled_byte", "sample_bytes_wrong"),
    ("short_blob", "sample_bytes_wrong"),
    ("missing_blob", "sample_bytes_wrong"),
    ("message_missing_below_the_count", "log_wrong"),
])
def test_reference_refuses_each_guarantee_broken(sound, name, counter):
    facts, sample, blobs = copy.deepcopy(sound)
    acked = [EACH] * 3
    _mutate(name)(facts, blobs, acked)
    assert check(facts, sample, blobs, acked)[counter] > 0


def test_a_slot_no_batch_reached_holds_the_seeded_words():
    facts, sample, blobs = fill(interleave(11)[:2], counts=[0, 0, 0])
    rows = [r for b in interleave(11)[:2] for r in b]
    counts = [sum(1 for c, _ in rows if c == k) for k in range(3)]
    # the log is judged against the counts: make them what was consumed
    facts["n"] = counts
    got = check(facts, sample, blobs, counts)
    # the folds expect messages 0..n-1 of each connection, which a cut of a
    # shuffled interleaving is; every untouched row checks against the seed
    assert got["slots_wrong"] == 0 and got["sample_bytes_wrong"] == 0
    facts["row_sums"][4][0] ^= 1          # slot 4 was never written
    assert check(facts, sample, blobs, counts)["slots_wrong"] > 0


def test_plan_sample_is_seeded_and_in_range():
    a = ref.plan_sample(CONFIG, TRAFFIC, SEED, [1, 2, 3])
    assert a == ref.plan_sample(CONFIG, TRAFFIC, SEED, [9, 9, 9])
    assert a == sorted(set(a)) and len(a) == 3 and max(a) < 5
    assert a != ref.plan_sample(CONFIG, TRAFFIC, SEED + 1, [1, 2, 3]) or (
        ref.plan_sample(CONFIG, TRAFFIC, SEED + 2, [1, 2, 3]) != a)
    assert ref.last_batch(3, 3, 5) is None and ref.last_batch(3, 14, 5) == 13


def test_the_handler_ends_the_server_where_the_batcher_cannot_take_rows(
        monkeypatch, capfdbinary):
    import jax

    from tpurpc.jaxshim import FanInBatcher

    monkeypatch.delattr(FanInBatcher, "submit")
    ctx = types.SimpleNamespace(config=CONFIG, traffic=TRAFFIC, seed=SEED,
                                device=jax.devices()[0], fault=None)
    with pytest.raises(SystemExit) as exc:
        pool_batch.build(ctx)
    assert exc.value.code == 3
    out = capfdbinary.readouterr().out.decode()
    assert out.startswith("@fatal ") and "submit" in out


# -- the cell's readers and entries ------------------------------------------------------

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
FANIN = [e for e in MANIFEST["per_layer"]
         if e.get("workloads") == ["fanin4m_c8"]]

#: what a traced run of the change hands a reader, and of a tree without
#: the program's counters
RUN = {
    "cell": "fanin4m_c8", "payload_bytes": 1344 * 8 * 4194304,
    "messages": 1344 * 8,
    "server_ledger": {"dma_d2d": 1344 * 8 * 4194304, "host_copy": 4194304,
                      "dma_h2d": 1344 * 8 * 4194304},
    "client_ledger": {"host_copy": 4194304},
    "counters": {
        "batcher_batches": 1344, "batcher_rows": 1344 * 8 - 4,
        "batcher_flush_size": 1343, "batcher_flush_timer": 1,
        "lens_batch_wait_busy_ns": 5_000_000 * 10748,
        "lens_batch_wait_ops": 10748,
        "lens_batch_stack_busy_ns": 1_500_000 * 1344,
        "lens_batch_stack_ops": 1344,
        "lens_batch_run_busy_ns": 300_000 * 1344, "lens_batch_run_ops": 1344,
        "lens_srv_call_busy_ns": 8 * 15_120_000_000, "lens_srv_call_ops": 8,
        "lens_hbm_busy_ns": 2_400_000 * 10752, "lens_hbm_ops": 10752,
        "lens_hbm_credit_busy_ns": 100_000 * 10752,
        "lens_decode_busy_ns": 3_000_000 * 10752, "lens_decode_ops": 10752,
        "lens_srv_handler_busy_ns": 3_200_000 * 10752,
        "lens_srv_handler_ops": 10752,
        "lens_srv_recv_busy_ns": 250_000 * 10768, "lens_srv_recv_ops": 10768,
        "lens_srv_queue_busy_ns": 40_000_000 * 10752,
        "lens_srv_queue_ops": 10752,
        "rdv_bytes_received": 1344 * 8 * (4194304 + 183)},
    "peaks": {"hbm_bytes_per_s": 819e9},
    "trace": {"busy_s": 0.05, "window_s": 2.0, "messages": 1440,
              "payload_bytes": 1440 * 4194304,
              "device_ops": [["jit_consume", 0.027],
                             ["jit_tpurpc_batch_stack", 0.0205]]},
}
BARE = {"cell": "fanin4m_c8", "payload_bytes": 0, "messages": 0,
        "server_ledger": {}, "client_ledger": {}, "counters": {},
        "peaks": {"hbm_bytes_per_s": 819e9},
        "trace": {"busy_s": 0.0, "window_s": 2.0, "messages": 0,
                  "payload_bytes": 0, "device_ops": []}}
EXPECT = {
    "batch_rows_mean.fanin": (1344 * 8 - 4) / 1344,
    "flush_timer_pct.fanin": 100 / 1344, "batch_wait_us.fanin": 5000.0,
    "batch_stack_us.fanin": 1500.0, "batch_run_us.fanin": 300.0,
    "batch_period_us.fanin": 15_120_000 / 1344,
    "stack_d2d_b_per_b.fanin": 1.0, "hbm_credit_wait_us.fanin": 100.0,
    "hbm_place_us.fanin": 2400.0,
    "rdv_bytes_pct.fanin": 100 * (4194304 + 183) / 4194304,
    "host_copy_b_per_b.fanin": 2 / (1344 * 8),
    "device_idle_pct.fanin": 97.5,
    "batch_stack_roofline.fanin":
        100 * 2 * 1440 * 4194304 / 819e9 / 0.0205,
    "landing_b_per_b.fanin": 1.0,
    "hbm_landing_roofline.fanin": 100 * 1440 * 4194304 / 819e9 / 0.05,
    "srv_recv_wait_us.fanin": 250.0, "srv_queue_wait_us.fanin": 40000.0,
    "decode_self_us.fanin": 3000.0 - 2400.0 - 100.0,
    "srv_handoff_us.fanin": 200.0,
}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_a_reader_reads_the_change_and_nothing_of_a_bare_tree(name):
    read = reader(name)
    assert read(copy.deepcopy(RUN)) == pytest.approx(EXPECT[name])
    assert read(copy.deepcopy(BARE)) is None
    if "roofline" in name:
        assert 0 < EXPECT[name] < 100


def test_the_manifest_gained_the_cell_and_lost_nothing():
    assert manifest_check.problems(MANIFEST, ROOT) == []
    assert sorted(e["name"] for e in FANIN) == sorted(EXPECT)
    assert all(e["moves"] == "hbm_gbytes_s" for e in FANIN)
    # found by name: cells and configurations appended later change no test
    (cell,) = [w for w in MANIFEST["workloads"] if w["name"] == "fanin4m_c8"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tensor_fanin_batch_4m", "stream_c8", 1)
    assert "fanin4m_c8" in MANIFEST["end_to_end"][0]["workloads"]
    (entry,) = [c for c in MANIFEST["configs"]
                if c["name"] == "tensor_fanin_batch_4m"]
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["source"] == entry["source"] and cfg["reduced"] == []
    assert cfg["batch"] == {"max_rows": 8, "fixed_bucket": True,
                            "max_delay_ms": 50, "log_batches": 16384}
    assert cfg["pool"]["bytes"] == 192 * 8 * 4194304 == 6442450944
    assert "batch.max_delay_ms" in cfg["assumed"] and cfg["guarantees"]
    assert pool_batch.FAULTS == ("approx_bf16", "drop", "alter", "reorder",
                                 "dup")


# -- the cell end to end, at KiB sizes ------------------------------------------------------

@pytest.mark.parametrize("fault", [None, "drop", "dup"])
def test_rehearsal_of_the_cell(fault):
    if shutil.which("g++") is None:
        pytest.skip("no g++: run.py builds the data plane")
    argv = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
            "--workload", "fanin4m_c8", "--seed", str(SEED), "--seconds", "1",
            "--trace", "0", "--rehearsal-cpu"]
    if fault:
        argv += ["--fault", fault]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    done = subprocess.run(argv, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal_cpu"] is True and line["correct"] is False
    assert line["failed"] == 0 and line["attempted"] > 8 * 8
    assert line["would_be_correct"] is (fault is None), line["compared"]
    if fault:
        assert line["compared"]["log_wrong"]["value"] > 0
