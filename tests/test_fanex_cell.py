"""Tier-1's share of the benchmark cell ``fanex4m_c8`` (ISSUE 36): the plain
reference ``benchmarks/configs/fanex_reference.py`` against pools the
handler's own consumer program filled on the CPU at byte sizes and replies it
handed back (one sound, one for each guarantee broken, one for each planted
fault), the cell's per-layer readers with and without the program's counters,
the manifest's new entries, and the cell end to end at KiB sizes (``run.py
--rehearsal-cpu``), sound and with two faults planted."""

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

from benchmarks.configs import fanex_reference as ref  # noqa: E402
from benchmarks.handlers import pool_batch_exchange  # noqa: E402
from benchmarks.harness.payloads import Bank, checksum_np  # noqa: E402
from benchmarks.tests import manifest_check  # noqa: E402

SEED = 3600000907          # the driver's seeds are over 2**31
CONFIG = {
    "rpc": "stream_stream", "bank_messages": 4,
    "message": {"dtype": "float32", "shape": [8, 8], "bytes": 256},
    "batch": {"max_rows": 8, "fixed_bucket": True, "log_batches": 64},
    "pool": {"bytes": 5 * 8 * 256}, "audit": {"sampled_slots": 3},
}
TRAFFIC = {"connections": 3,
           "reply_sample": {"below": 1, "above": 3, "horizon": 8}}
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


def fill(batches, fault=None):
    """What the server would report after answering ``batches`` (lists of
    ``(conn, seq)``) by the handler's own program, each client having
    received its rows of every batch's result in order and reported them as
    the traffic kind does: ``(facts, sample, blobs)``."""
    import jax

    ctx = types.SimpleNamespace(config=CONFIG, traffic=TRAFFIC, seed=SEED,
                                device=jax.devices()[0], fault=fault)
    h = pool_batch_exchange.PoolBatchExchange(ctx)
    try:
        banks = [Bank(SEED, c, CONFIG) for c in range(3)]
        replies = [[] for _ in range(3)]
        for rows in batches:
            batch = np.zeros((8, 8, 8), np.float32)
            for i, (c, k) in enumerate(rows):
                batch[i] = banks[c].message_copy(k)
            out = h._step({"x": jax.device_put(batch, ctx.device)},
                          jax.device_put(np.int32(len(rows)), ctx.device))
            y = np.asarray(out["y"])
            for i, (c, _) in enumerate(rows):
                replies[c].append(y[i].copy())
        for c, cn in enumerate(h.per_conn):
            cn.n = len(replies[c])
            plan = ref.plan_replies(CONFIG, TRAFFIC, SEED, c)
            kept = {k: replies[c][k] for k in plan if k < len(replies[c])}
            cn.report = {
                "first": 0,
                "stamps": [[int(w) for w in y.reshape(-1)[:2].view(
                    np.uint32)] for y in replies[c]],
                "sampled": sorted(kept),
                "sample_sums": [checksum_np(kept[k]) for k in sorted(kept)],
                "sample_bytes_wrong": ref.sampled_bytes_wrong(
                    CONFIG, TRAFFIC, SEED, kept)}
        sample = ref.plan_sample(CONFIG, TRAFFIC, SEED, h.counts())
        facts, blobs = h.audit(sample)
        return json.loads(json.dumps(facts)), sample, blobs
    finally:
        h.free()


@pytest.fixture(scope="module")
def sound():
    return fill(interleave(11))


def check(facts, sample, blobs, acked=None):
    return ref.check(CONFIG, TRAFFIC, SEED, facts, sample, blobs,
                     list(acked or facts["n"]))


def wrong(got):
    return {k for k, v in got.items() if v > ref.LIMITS[k]}


def test_reference_accepts_a_sound_run(sound):
    facts, sample, blobs = sound
    assert facts["batches"] == len(facts["log_rows"]) >= 9
    assert facts["n"] == [EACH] * 3
    # the pool has 5 batch slots: replies of both kinds were checked, and a
    # pad row's zeros came back for a request whose slot held a short batch
    assert any(st == [0, 0] for r in facts["client"] for st in r["stamps"])
    assert all(len(r["sampled"]) >= 2 for r in facts["client"])
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
                           "sample_bytes_wrong", "reply_folds_wrong"}),
    ("alter", None, {"folds_wrong", "slots_wrong"}),
    ("drop", _drop, {"log_wrong", "folds_wrong"}),
    ("reorder", _reorder, {"log_wrong", "folds_wrong"}),
    ("dup", _dup, {"log_wrong", "folds_wrong"}),
    ("reply_swap", None, {"reply_stamps_wrong", "reply_sample_sums_wrong"}),
    ("reply_stale", None, {"reply_folds_wrong", "reply_stamps_wrong"}),
    ("reply_bf16", None, {"reply_folds_wrong", "reply_stamps_wrong",
                          "reply_sample_bytes_wrong"}),
])
def test_each_planted_fault_is_caught(fault, reshape, caught):
    """The eight faults of the control, each where it is produced: the five
    the consumer program plants by its ``fault``, the three the handler
    plants on a row's way to the batcher by the batches it would make. A
    fault on the way out leaves the pool's five limits alone."""
    batches = interleave(11)
    if reshape is not None:
        batches, fault = reshape(batches), None
    got = wrong(check(*fill(batches, fault)))
    assert caught <= got and "acks_wrong" not in got
    if fault and fault.startswith("reply_"):
        assert got <= {k for k in ref.LIMITS if k.startswith("reply_")}


def _mutate(name):
    def edit(facts, blobs, acked):
        client = facts["client"]
        if name == "unacknowledged":
            acked[1] -= 1
        elif name == "fold_in":
            facts["acc"][2] ^= 1
        elif name == "fold_out":
            facts["acc_out"][0] ^= 1
        elif name == "stray_write":
            facts["row_sums"][4][7] ^= 1
        elif name == "sampled_byte":
            blobs[1] = blobs[1][:100] + bytes([blobs[1][100] ^ 1]) + blobs[
                1][101:]
        elif name == "pad_row_inside":
            b = next(i for i, n in enumerate(facts["log_rows"]) if n >= 2)
            facts["log_rows"][b] -= 1
        elif name == "replies_out_of_order":
            st = client[1]["stamps"]
            st[3], st[4] = st[4], st[3]
        elif name == "reply_lost":
            client[2]["stamps"].pop()
        elif name == "reply_twice":
            client[0]["stamps"].insert(2, client[0]["stamps"][2])
        elif name == "no_report":
            client[1] = None
        elif name == "kept_reply_differs":
            client[0]["sample_bytes_wrong"] = 3
        elif name == "kept_reply_of_another_place":
            client[2]["sample_sums"][0] ^= 1
        elif name == "kept_reply_missing":
            client[1]["sampled"].pop()
            client[1]["sample_sums"].pop()
    return edit


@pytest.mark.parametrize("name,counter", [
    ("unacknowledged", "acks_wrong"), ("fold_in", "folds_wrong"),
    ("fold_out", "reply_folds_wrong"), ("stray_write", "slots_wrong"),
    ("sampled_byte", "sample_bytes_wrong"), ("pad_row_inside", "log_wrong"),
    ("replies_out_of_order", "reply_stamps_wrong"),
    ("reply_lost", "reply_stamps_wrong"),
    ("reply_twice", "reply_stamps_wrong"),
    ("no_report", "reply_stamps_wrong"),
    ("kept_reply_differs", "reply_sample_bytes_wrong"),
    ("kept_reply_of_another_place", "reply_sample_sums_wrong"),
    ("kept_reply_missing", "reply_sample_missing"),
])
def test_reference_refuses_each_guarantee_broken(sound, name, counter):
    facts, sample, blobs = copy.deepcopy(sound)
    acked = list(facts["n"])
    _mutate(name)(facts, blobs, acked)
    assert check(facts, sample, blobs, acked)[counter] > 0


def test_a_kept_reply_is_held_to_what_its_own_stamps_name():
    """The client's side of the comparison: seeded words by the row that
    starts with them, a bank message of ANY connection by its stamp words, a
    pad row by being zeros; one flipped bit in each shows, and a reply that
    names nothing is wrong whole."""
    from benchmarks.handlers.pool_sink import init_words_np

    words = 64
    seeded = init_words_np(SEED, 0, 11 * words, words).view(np.float32)
    message = Bank(SEED, 2, CONFIG).message_copy(17)
    pad = np.zeros((8, 8), np.float32)
    kept = {0: seeded.reshape(8, 8), 5: message, 9: pad}
    assert ref.sampled_bytes_wrong(CONFIG, TRAFFIC, SEED, kept) == 0
    for k in kept:
        bad = {j: np.array(v) for j, v in kept.items()}
        bad[k].reshape(-1).view(np.uint32)[40] ^= 1 << 5
        got = ref.sampled_bytes_wrong(CONFIG, TRAFFIC, SEED, bad)
        # a pad row with a bit set reads as message 0 of connection 0
        assert got == 1 if k != 9 else got > 100
    stranger = np.array(message)
    stranger.reshape(-1).view(np.uint32)[1] = 7      # no such connection
    assert ref.sampled_bytes_wrong(CONFIG, TRAFFIC, SEED,
                                   {1: stranger}) == 256


def test_plan_replies_is_seeded_and_straddles_the_first_lap():
    a = ref.plan_replies(CONFIG, TRAFFIC, SEED, 1)
    assert a == ref.plan_replies(CONFIG, TRAFFIC, SEED, 1)
    assert a == sorted(set(a)) and len(a) == 4
    lap = ref.lap(CONFIG, TRAFFIC)
    assert lap == 5 * 8 // 3 and a[0] < lap <= a[1] and a[-1] < lap + 8
    assert a != ref.plan_replies(CONFIG, TRAFFIC, SEED, 2) or (
        a != ref.plan_replies(CONFIG, TRAFFIC, SEED + 1, 1))


def test_the_handler_ends_the_server_where_a_stream_cannot_answer_later(
        monkeypatch, capfdbinary):
    import jax

    monkeypatch.setattr(pool_batch_exchange, "takes_futures", lambda: False)
    ctx = types.SimpleNamespace(config=CONFIG, traffic=TRAFFIC, seed=SEED,
                                device=jax.devices()[0], fault=None)
    with pytest.raises(SystemExit) as exc:
        pool_batch_exchange.build(ctx)
    assert exc.value.code == 3
    out = capfdbinary.readouterr().out.decode()
    assert out.startswith("@fatal ") and "future" in out
    monkeypatch.undo()
    assert pool_batch_exchange.takes_futures()


# -- the cell's readers and entries ------------------------------------------------------

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
FANEX = [e for e in MANIFEST["per_layer"]
         if e.get("workloads") == ["fanex4m_c8"]]

#: what a traced run of the change hands a reader, and of a tree without
#: the program's counters
M, B = 840 * 8, 840      # messages and batches of a window
RUN = {
    "cell": "fanex4m_c8", "payload_bytes": M * 4194304, "messages": M,
    "server_ledger": {"dma_d2d": M * 4194304, "host_copy": 4194304,
                      "dma_h2d": M * 4194304, "dma_d2h": B * 8 * 4194304},
    "client_ledger": {"host_copy": 2 * 4194304},
    "counters": {
        "batcher_batches": B, "batcher_rows": M - 4,
        "batcher_flush_size": B - 2, "batcher_flush_timer": 2,
        "lens_batch_wait_busy_ns": 5_000_000 * M, "lens_batch_wait_ops": M,
        "lens_batch_stack_busy_ns": 1_500_000 * B, "lens_batch_stack_ops": B,
        "lens_batch_run_busy_ns": 300_000 * B, "lens_batch_run_ops": B,
        "lens_batch_d2h_busy_ns": 11_000_000 * B, "lens_batch_d2h_ops": B,
        "lens_srv_call_busy_ns": 8 * 15_120_000_000 + 8 * 3_000_000,
        "lens_srv_call_ops": 16,
        "lens_hbm_busy_ns": 2_400_000 * M, "lens_hbm_ops": M,
        "lens_hbm_credit_busy_ns": 100_000 * M,
        "lens_decode_busy_ns": 3_000_000 * M, "lens_decode_ops": M,
        "lens_srv_handler_busy_ns": 3_200_000 * M, "lens_srv_handler_ops": M,
        "lens_srv_send_busy_ns": 900_000 * (M + 8),
        "lens_srv_send_ops": M + 8,
        "lens_srv_reply_wait_busy_ns": 2_000_000 * M,
        "lens_srv_reply_wait_ops": M,
        "srv_replies_deferred": M, "srv_replies_overtaken": M // 10,
        "rdv_bytes_received": M * (4194304 + 183),
        "rdv_bytes_sent": M * (4194304 + 183)},
    "peaks": {"hbm_bytes_per_s": 819e9},
    "trace": {"busy_s": 0.06, "window_s": 2.0, "messages": 900,
              "payload_bytes": 900 * 4194304,
              "device_ops": [["jit_swap", 0.03],
                             ["jit_tpurpc_batch_stack", 0.0128]]},
}
BARE = {"cell": "fanex4m_c8", "payload_bytes": 0, "messages": 0,
        "server_ledger": {}, "client_ledger": {}, "counters": {},
        "peaks": {"hbm_bytes_per_s": 819e9},
        "trace": {"busy_s": 0.0, "window_s": 2.0, "messages": 0,
                  "payload_bytes": 0, "device_ops": []}}
EXPECT = {
    "batch_d2h_us.fanex": 11000.0, "batch_d2h_b_per_b.fanex": 1.0,
    "reply_wait_us.fanex": 2000.0,
    "replies_overtaken_pct.fanex": 100 * (M // 10) / M,
    "srv_send_us.fanex": 900.0,
    "reply_rdv_bytes_pct.fanex": 100 * (4194304 + 183) / 4194304,
    "reply_host_copy_b_per_b.fanex": 2 / M,
    "batch_rows_mean.fanex": (M - 4) / B,
    "flush_timer_pct.fanex": 100 * 2 / B, "batch_wait_us.fanex": 5000.0,
    "batch_stack_us.fanex": 1500.0, "batch_run_us.fanex": 300.0,
    "batch_period_us.fanex": (15_120_000 + 3_000) / B,
    "hbm_credit_wait_us.fanex": 100.0, "hbm_place_us.fanex": 2400.0,
    "srv_handoff_us.fanex": 200.0,
    "rdv_bytes_pct.fanex": 100 * (4194304 + 183) / 4194304,
    "host_copy_b_per_b.fanex": 3 / M, "landing_b_per_b.fanex": 1.0,
    "device_idle_pct.fanex": 97.0,
    "batch_stack_roofline.fanex":
        100 * 2 * 900 * 4194304 / 819e9 / 0.0128,
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


def test_a_share_of_replies_that_is_zero_is_still_a_reading():
    run = copy.deepcopy(RUN)
    del run["counters"]["srv_replies_overtaken"]  # a window's delta drops 0
    del run["client_ledger"]["host_copy"]
    assert reader("replies_overtaken_pct.fanex")(run) == 0.0
    assert reader("reply_host_copy_b_per_b.fanex")(run) == 0.0


def test_the_manifest_gained_the_cell_and_lost_nothing():
    assert manifest_check.problems(MANIFEST, ROOT) == []
    assert sorted(e["name"] for e in FANEX) == sorted(EXPECT)
    assert all(e["moves"] == "hbm_gbytes_s" for e in FANEX)
    (cell,) = [w for w in MANIFEST["workloads"] if w["name"] == "fanex4m_c8"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tensor_fanin_exchange_4m", "exchange_c8", 1)
    assert "fanex4m_c8" in MANIFEST["end_to_end"][0]["workloads"]
    assert [w["name"] for w in MANIFEST["workloads"]][:4] == [
        "stream4m_c1", "stream4m_c8", "pingpong4m_c1", "fanin4m_c8"]
    (entry,) = [c for c in MANIFEST["configs"]
                if c["name"] == "tensor_fanin_exchange_4m"]
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["source"] == entry["source"] and cfg["reduced"] == []
    # the batcher's own defaults are judged: the file states none of them
    assert cfg["batch"] == {"max_rows": 8, "fixed_bucket": True,
                            "log_batches": 16384}
    assert cfg["pool"]["bytes"] == 192 * 8 * 4194304 == 6442450944
    assert cfg["handler"] == "pool_batch_exchange" and cfg["guarantees"]
    assert cfg["reference"] == "fanex_reference"
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "exchange_c8.json")) as f:
        mix = json.load(f)
    assert (mix["kind"], mix["connections"], mix["in_flight"],
            mix["warmup_messages"]) == ("exchange", 8, 8, 8)
    assert pool_batch_exchange.FAULTS == (
        "approx_bf16", "drop", "alter", "reorder", "dup", "reply_swap",
        "reply_stale", "reply_bf16")


# -- the cell end to end, at KiB sizes ------------------------------------------------------

@pytest.mark.parametrize("fault", [None, "dup", "reply_swap"])
def test_rehearsal_of_the_cell(fault):
    if shutil.which("g++") is None:
        pytest.skip("no g++: run.py builds the data plane")
    argv = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
            "--workload", "fanex4m_c8", "--seed", str(SEED), "--seconds", "1",
            "--trace", "0", "--rehearsal-cpu"]
    if fault:
        argv += ["--fault", fault]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    done = subprocess.run(argv, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=300)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal_cpu"] is True and line["correct"] is False
    assert "metrics" not in line and line["attempted"] > 64
    over = {k for k, v in line["compared"].items() if v["value"] > v["limit"]}
    if fault is None:
        assert line["would_be_correct"] is True and not over
        assert line["failed"] == 0
    elif fault == "dup":
        assert line["would_be_correct"] is False and "log_wrong" in over
    else:
        assert line["would_be_correct"] is False
        assert "reply_stamps_wrong" in over
        assert not over & {"log_wrong", "folds_wrong", "slots_wrong"}
