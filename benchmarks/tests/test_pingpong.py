"""``tensor_pingpong_4m`` / ``pingpong4m_c1`` (ISSUE 28) at KiB sizes on the
CPU: the reference against hand-built pools and replies, the swap program
against numpy, the seven ``.pingpong`` readers on made-up counters, and the
cell through ``--rehearsal-cpu`` sound and with each planted fault."""

import json
import os
import types

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.configs import pingpong_reference as ref
from benchmarks.handlers import pool_exchange, pool_sink
from benchmarks.harness.payloads import Bank, checksum_np, fold
from benchmarks.tests.test_rehearsal import LABEL, ROOT, rehearse

CONFIG = {"message": {"dtype": "float32", "shape": [8, 16], "bytes": 512},
          "pool": {"bytes": 512 * 12}, "bank_messages": 5,
          "audit": {"sampled_slots": 4}, "rpc": "stream_stream"}
TRAFFIC = {"connections": 2,
           "reply_sample": {"below": 1, "above": 3, "horizon": 64}}
SEED = 3_000_000_019
CELL = "pingpong4m_c1"


# -- the reference ----------------------------------------------------------------

def _simulate(counts, fault=None):
    """The handler's semantics in numpy, what its audit would report, and
    what a client that saw every reply would report."""
    conns, slots, words = ref.geometry(CONFIG, TRAFFIC)
    facts, pools = [], []
    for c in range(conns):
        pool = pool_sink.init_words_np(SEED, c, 0, slots * words).reshape(
            slots, words)
        bank, acc, acc_out = Bank(SEED, c, CONFIG), 0, 0
        stamps, kept = [], {}
        plan = ref.plan_replies(CONFIG, TRAFFIC, SEED, c)
        for k in range(counts[c]):
            msg = bank.message_copy(k).reshape(-1).view(np.uint32)
            out = pool[k % slots].copy()
            if fault == "stale" and k == counts[c] - 1:
                out = msg.copy()
            if fault == "bit" and k in plan:
                out[9] ^= 1 << 3
            acc, acc_out = (fold(acc, checksum_np(msg)),
                            fold(acc_out, checksum_np(out)))
            pool[k % slots] = msg
            stamps.append([int(out[0]), int(out[1])])
            if k in plan and fault != "unkept":
                kept[k] = out.view(np.float32).reshape(8, 16)
        report = {"first": 0, "stamps": stamps, "sampled": sorted(kept),
                  "sample_bytes_wrong": ref.sampled_bytes_wrong(
                      CONFIG, TRAFFIC, SEED, c, kept)}
        facts.append({"n": counts[c], "acc": acc, "acc_out": acc_out,
                      "slot_sums": [checksum_np(row) for row in pool],
                      "client": None if fault == "silent" else report})
        pools.append(pool)
    sample = ref.plan_sample(CONFIG, TRAFFIC, SEED, counts)
    blobs = [pools[c][s].tobytes() for c in range(conns) for s in sample[c]]
    return facts, sample, blobs


@pytest.mark.parametrize("counts", [[0, 0], [3, 5], [6, 6], [17, 40]])
def test_reference_accepts_sound_pools_and_replies(counts):
    facts, sample, blobs = _simulate(counts)
    got = ref.check(CONFIG, TRAFFIC, SEED, facts, sample, blobs, counts)
    assert got == {k: 0 for k in ref.LIMITS}
    assert set(ref.LIMITS) > set(ref.pool_reference.LIMITS)


@pytest.mark.parametrize("fault,fails", [
    # the last reply is the slot's new content, not what it held
    ("stale", ("reply_folds_wrong", "reply_stamps_wrong")),
    # one bit of every reply the client keeps whole
    ("bit", ("reply_folds_wrong", "reply_sample_bytes_wrong")),
    # a client that kept none of the replies the seed names
    ("unkept", ("reply_sample_missing",)),
    # a client that never reported: every reply counts as unchecked
    ("silent", ("reply_stamps_wrong", "reply_sample_missing")),
])
def test_reference_refuses_wrong_replies(fault, fails):
    counts = [17, 40]
    facts, sample, blobs = _simulate(counts, fault)
    got = ref.check(CONFIG, TRAFFIC, SEED, facts, sample, blobs, counts)
    for name in fails:
        assert got[name] > ref.LIMITS[name], (name, got)
    for name in ref.pool_reference.LIMITS:      # the pool itself is sound
        assert got[name] == 0, (name, got)


def test_reply_plan_is_seeded_and_reaches_both_sides_of_slots():
    _, slots, _ = ref.geometry(CONFIG, TRAFFIC)
    plan = ref.plan_replies(CONFIG, TRAFFIC, SEED, 0)
    assert plan == ref.plan_replies(CONFIG, TRAFFIC, SEED, 0)
    assert plan != ref.plan_replies(CONFIG, TRAFFIC, SEED + 1, 0)
    assert len(plan) == 4
    assert sum(k < slots for k in plan) == 1
    assert all(slots <= k < slots + 64 for k in plan[1:])


def test_chunked_initial_checksums_are_the_plain_ones():
    words = 3 * 32768              # three chunks a slot
    got = ref.init_checksums(SEED, 1, 2, words)
    for s in range(2):
        assert got[s] == checksum_np(
            pool_sink.init_words_np(SEED, 1, s * words, words))
    assert ref.init_checksums(SEED, 0, 3, 128) == [
        checksum_np(pool_sink.init_words_np(SEED, 0, s * 128, 128))
        for s in range(3)]


def test_expected_stamps_on_both_sides_of_slots():
    _, slots, words = ref.geometry(CONFIG, TRAFFIC)
    got = ref.expected_stamps(SEED, 1, 0, 20, slots, words)
    bank = Bank(SEED, 1, CONFIG)
    for k in range(20):
        want = ref.expected_reply(bank, SEED, k, slots, words)[:2]
        assert got[k].tolist() == want.tolist()
    assert got[slots + 3].tolist() == [3, 1]


# -- the handler's program ----------------------------------------------------------

def _swap_run(fault, n=9):
    import jax.numpy as jnp

    shape, slots = (8, 16), 6
    swap = pool_exchange.swap_program(shape, "float32", slots, fault)
    pool = pool_sink.programs(shape, "float32", slots)[0](
        np.uint32(SEED & 0xFFFFFFFF), np.uint32(1))
    acc, out_acc, seq = jnp.uint32(0), jnp.uint32(0), jnp.uint32(0)
    bank, replies = Bank(SEED, 1, CONFIG), []
    for k in range(n):
        pool, acc, out_acc, seq, y = swap(pool, acc, out_acc, seq,
                                          bank.message_copy(k))
        replies.append(np.asarray(y))
    return bank, slots, np.asarray(pool), int(acc), int(out_acc), replies


def test_swap_program_agrees_with_numpy():
    bank, slots, pool, acc, out_acc, replies = _swap_run(None)
    ref_acc = ref_out = 0
    for k, y in enumerate(replies):
        want = ref.expected_reply(bank, SEED, k, slots, 128)
        assert y.reshape(-1).view(np.uint32).tolist() == want.tolist()
        ref_acc = fold(ref_acc, checksum_np(bank.message_copy(k)))
        ref_out = fold(ref_out, checksum_np(want))
    assert (acc, out_acc) == (ref_acc, ref_out)
    for s in range(slots):
        assert pool[s].tobytes() == bank.message_copy(
            ref.pool_reference.last_seq(s, 9, slots)).tobytes()


@pytest.mark.parametrize("fault", ["stale_reply", "reply_bf16"])
def test_reply_faults_change_the_reply_and_leave_the_pool_exact(fault):
    bank, slots, pool, acc, out_acc, replies = _swap_run(fault)
    sound = _swap_run(None)
    assert pool.tobytes() == sound[2].tobytes() and acc == sound[3]
    assert out_acc != sound[4]
    wrong = [k for k, y in enumerate(replies)
             if y.tobytes() != sound[5][k].tobytes()]
    assert wrong == list(range(9))


@pytest.mark.parametrize("fault", pool_sink.FAULTS)
def test_pool_faults_change_what_is_stored(fault):
    _, _, pool, acc, _, _ = _swap_run(fault, n=7)
    sound = _swap_run(None, n=7)
    assert pool.tobytes() != sound[2].tobytes() or acc != sound[3]


def test_handler_answers_only_on_a_stream_and_knows_its_faults():
    import jax

    ctx = types.SimpleNamespace(config=dict(CONFIG, rpc="unary_unary"),
                                traffic=TRAFFIC, seed=SEED,
                                device=jax.devices()[0], fault=None)
    with pytest.raises(ValueError, match="stream_stream"):
        pool_exchange.build(ctx)
    ctx.config = CONFIG
    ctx.fault = "no_such_fault"
    with pytest.raises(ValueError, match="no fault"):
        pool_exchange.build(ctx)
    ctx.fault = None
    store = pool_exchange.build(ctx)
    with pytest.raises(RuntimeError, match="not on"):
        store._exchange(store.shards[0], np.zeros((8, 16), np.float32))
    assert store.counts() == [0, 0]


# -- the readers ----------------------------------------------------------------------

MSGS = 5000
PAYLOAD = MSGS * 4194304
RUN = {"cell": CELL, "messages": MSGS, "payload_bytes": PAYLOAD,
       "counters": {
           "lens_d2h_busy_ns": 700_000 * MSGS, "lens_d2h_ops": MSGS,
           "lens_d2h_bytes": PAYLOAD,
           "rdv_bytes_sent": MSGS * (4194304 + 192),
           "lens_srv_send_busy_ns": 480_000 * (MSGS + 1),
           "lens_srv_send_ops": MSGS + 1,
           "lens_srv_recv_busy_ns": 650_000 * (MSGS + 2),
           "lens_srv_recv_ops": MSGS + 2,
           "lens_srv_handler_busy_ns": 1_500_000 * (MSGS + 1),
           "lens_srv_handler_ops": MSGS + 1},
       "server_ledger": {"dma_d2h": PAYLOAD, "dma_h2d": PAYLOAD,
                         "host_copy": 4096},
       "client_ledger": {"host_copy": 1024}, "peaks": None, "trace": None}
WANT = {
    "d2h_us.pingpong": 700.0,
    "d2h_b_per_b.pingpong": 1.0,
    "reply_host_copy_b_per_b.pingpong": 5120 / PAYLOAD,
    "reply_rdv_bytes_pct.pingpong": 100.0 * (4194304 + 192) / 4194304,
    "srv_send_us.pingpong": 480.0,
    "srv_recv_wait_us.pingpong": 650.0,
    "srv_handler_us.pingpong": 1500.0,
}
#: what the parent commit's program gives the same readers: no `d2h` stage,
#: no `dma_d2h` on the reply path, no `rdv_bytes_sent`
PARENT = dict(RUN, counters={k: v for k, v in RUN["counters"].items()
                             if "d2h" not in k and k != "rdv_bytes_sent"},
              server_ledger={"dma_h2d": PAYLOAD, "host_copy": 4096})
NEW_IN_THE_PROGRAM = ("d2h_us.pingpong", "d2h_b_per_b.pingpong",
                      "reply_rdv_bytes_pct.pingpong")


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_value_nothing_on_the_parent_nothing_on_an_empty_run(name):
    read = bench_run.load_reader(name)
    assert read(RUN) == pytest.approx(WANT[name], rel=1e-12)
    if name in NEW_IN_THE_PROGRAM:
        assert read(PARENT) is None
    else:
        assert read(PARENT) == pytest.approx(WANT[name], rel=1e-12)
    assert read(dict(RUN, payload_bytes=0, counters={}, server_ledger={},
                     client_ledger={})) is None


def test_the_manifest_has_the_cell_and_its_seven_readers_at_the_end():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    assert m["configs"][-1]["name"] == "tensor_pingpong_4m"
    assert m["configs"][-1]["reduced"] == []
    assert m["workloads"][-1] == {
        "name": CELL, "config": "tensor_pingpong_4m",
        "traffic": "pingpong_c1", "chips": 1,
        "why": m["workloads"][-1]["why"]}
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert e2e["hbm_gbytes_s"]["workloads"][-1] == CELL
    tail = m["per_layer"][-7:]
    assert sorted(e["name"] for e in tail) == sorted(WANT)
    for e in tail:
        assert e["workloads"] == [CELL] and e["moves"] == "hbm_gbytes_s"
        assert e["source"] == "program_counter"
    for text in (m["configs"][-1]["source"], m["configs"][-1]["why"],
                 m["workloads"][-1]["why"]):
        assert len(text) <= 200 and text.isascii() and text.isprintable()
    with open(os.path.join(ROOT, m["configs"][-1]["file"])) as f:
        cfg = json.load(f)
    assert cfg["source"] == m["configs"][-1]["source"]
    assert cfg["message"]["bytes"] == 4194304
    assert cfg["pool"]["bytes"] == 6442450944
    assert (cfg["handler"], cfg["reference"], cfg["rpc"]) == (
        "pool_exchange", "pingpong_reference", "stream_stream")


# -- the cell, end to end at KiB sizes -------------------------------------------------

def test_rehearsal_compares_clean_and_names_the_readers_a_cpu_can_feed():
    last, lines, err = rehearse(ROOT, CELL, trace="1", seconds="2")
    assert all(line.startswith(LABEL) for line in lines)
    assert last["rehearsal_cpu"] is True and last["correct"] is False
    assert last["would_be_correct"] is True, last
    assert last["attempted"] > 0 and last["failed"] == 0
    assert set(last["compared"]) == set(ref.LIMITS) | {
        "rpc_failed", "compiles_in_window", "degraded_leaves"}
    for name, pair in last["compared"].items():
        assert pair["value"] <= pair["limit"], name
        assert f"compared {name} = {pair['value']} (limit" in err
    # a host backend reads nothing back: the two d2h readers stay silent,
    # as on the parent commit; the other five are named
    (named,) = [ln for ln in lines if "metrics are not printed" in ln]
    for name in WANT:
        assert (f"'{name}'" in named) == (not name.startswith("d2h")), named


@pytest.mark.parametrize("fault,fails", [
    ("approx_bf16", "sample_bytes_wrong"),
    ("drop", "slots_wrong"),
    ("alter", "folds_wrong"),
    ("reorder", "slots_wrong"),
    # the two on the reply: the pool stays exact, the replies do not
    ("stale_reply", "reply_stamps_wrong"),
    ("reply_bf16", "reply_sample_bytes_wrong"),
])
def test_a_broken_exchange_reads_not_correct(fault, fails):
    last, _, _ = rehearse(ROOT, CELL, "--fault", fault)
    assert last["would_be_correct"] is False
    pair = last["compared"][fails]
    assert pair["value"] > pair["limit"], last["compared"]
    if fault in ("stale_reply", "reply_bf16"):
        for name in ref.pool_reference.LIMITS:
            assert last["compared"][name]["value"] == 0, last["compared"]
        assert last["compared"]["reply_folds_wrong"]["value"] == 1
        # what the client saw reaches `failed` by itself too
        assert last["compared"]["rpc_failed"]["value"] > 0
