"""Tier-1's share of the benchmark cell ``kvh_hyb_c8``: the plain reference
``benchmarks/configs/kvhyb_reference.py`` against what the handler's audit
reports after prefill streams of pages and states were driven through the
product's cache with state slots on the CPU at KiB sizes (one sound, one for
each planted fault, one for each guarantee broken in the facts), the traffic
kind's message order and the reference's checksums against the device's,
the cell's per-layer readers with and without the program's counters, the
manifest's new entries, and the cell end to end (``run.py
--rehearsal-cpu``), sound and with faults planted."""

import copy
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import threading
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.configs import kvhyb_reference as ref  # noqa: E402
from benchmarks.handlers import kv_hybrid  # noqa: E402
from benchmarks.tests import manifest_check  # noqa: E402

SEED = 4300000907          # seeds of a run are over 2**31
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "kv_handoff_kda_mla_hybrid.json")) as _f:
    FULL = json.load(_f)
CONFIG = dict(FULL, **{k: v for k, v in FULL["rehearsal_cpu"].items()
                       if k != "env"})
with open(os.path.join(ROOT, "benchmarks", "traffic",
                       "kv_handoff_hybrid_c8.json")) as _f:
    MIX = json.load(_f)
TRAFFIC = dict(MIX, connections=2)
TRACE = 4                  # sequences of the trace a connection, after warm-up


def generator(conn):
    """The traffic kind's generator of connection ``conn``, at the
    rehearsal's sizes."""
    from benchmarks.traffic_kinds import kv_handoff_hybrid as kind

    argv = sys.argv
    sys.argv = [argv[0], json.dumps({"seed": SEED})]
    try:
        return kind._Gen(types.SimpleNamespace(config=CONFIG,
                                               traffic=TRAFFIC, conn=conn))
    finally:
        sys.argv = argv


def drive(fault=None):
    """Two prefill streams through the handler's cache, each its warm-up and
    ``TRACE`` sequences of its trace, as the traffic kind makes them;
    ``(facts, sample, blobs, acked)`` as the harness would hand them to the
    reference."""
    import jax
    from tpurpc.serving.pages import KvPageCache

    ctx = types.SimpleNamespace(config=CONFIG, traffic=TRAFFIC, seed=SEED,
                                device=jax.devices()[0], fault=fault)
    h = kv_hybrid.KvHybrid(ctx, KvPageCache)
    n_seqs = len(TRAFFIC["warmup_pages"]) + TRACE
    acked, futures, errors = [], [], []

    def stream(conn):
        gen = generator(conn)

        def trees():
            for o in range(n_seqs):
                for tree in gen.messages_of(o):
                    yield {k: jax.device_put(v, ctx.device)
                           for k, v in tree.items()}

        try:
            futures.extend(h._receiver(conn)(trees()))
        except Exception as exc:  # the test's assertion says which
            errors.append(exc)
        window = sum(gen.units(o) for o in range(
            len(TRAFFIC["warmup_pages"]), n_seqs))
        acked.append((conn, int(TRAFFIC["warmup_messages"]) + window))

    threads = [threading.Thread(target=stream, args=(c,)) for c in (0, 1)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors and not any(t.is_alive() for t in threads)
        for fut in futures:
            fut.result(timeout=60)
        acked = [a for _, a in sorted(acked)]
        sample = ref.plan_sample(CONFIG, TRAFFIC, SEED, h.counts())
        facts, blobs = h.audit(sample)
        return facts, sample, blobs, acked
    finally:
        h.free()


@pytest.fixture(scope="module")
def sound():
    return drive()


def check(facts, sample, blobs, acked):
    return ref.check(CONFIG, TRAFFIC, SEED, facts, sample, blobs, acked)


def wrong(got):
    return {k for k, v in got.items() if v > ref.LIMITS[k]}


def test_the_reference_accepts_a_sound_run(sound):
    facts, sample, blobs, acked = sound
    assert check(*sound) == dict.fromkeys(ref.LIMITS, 0)
    kinds = [e[0] for e in facts["log"]]
    assert kinds.count("admit") == kinds.count("ack") == 2 * (
        len(TRAFFIC["warmup_pages"]) + TRACE)
    # six slots for twelve sequences: slots, not pages, made room
    assert "evict" in kinds and "drop" not in kinds
    assert len(facts["sampled_states"]) == len(sample["states"]) == 8
    assert len(blobs) == len(facts["sampled"]) + 8 and facts["resident"]


@pytest.mark.parametrize("fault,caught", [
    ("drop", {"pages_wrong"}),
    ("reorder", {"states_wrong"}),
    ("evict_unacked", {"log_wrong"}),
    ("stale_page", {"pages_wrong"}),
    ("state_drop", {"states_wrong"}),
    ("stale_slot", {"states_wrong"}),
])
def test_each_planted_fault_is_caught(fault, caught):
    assert caught <= wrong(check(*drive(fault)))


def _mutate(name):
    def edit(facts, blobs, acked):
        admits = [e for e in facts["log"] if e[0] == "admit"]
        n_pages = len(facts["sampled"])
        if name == "slot_in_two_sequences":
            admits[-1][6] = facts["resident"][0][6]
        elif name == "admitted_without_slot":
            del admits[-1][6]
        elif name == "slot_out_of_range":
            admits[-1][6] = 99
        elif name == "resident_slot":
            facts["resident"][0][6] = (facts["resident"][0][6] + 1) % 6
        elif name == "free_slots_miscounted":
            facts["free_slots"] += 1
        elif name == "ack_unseen":
            acked[1] += 1
        elif name == "states_miscounted":
            facts["states_written"][0] -= 1
        elif name == "pages_miscounted":
            facts["written"][1] += 1
        elif name == "state_checksum":
            facts["state_sums"][0][2][1] ^= 1
        elif name == "state_unsummed":
            facts["state_gids"].pop()
            facts["state_sums"].pop()
        elif name == "state_byte":
            b = blobs[n_pages + 1]
            blobs[n_pages + 1] = b[:5] + bytes([b[5] ^ 0x40]) + b[6:]
        elif name == "state_from_another_slot":
            facts["sampled_states"][0][2] = (
                facts["sampled_states"][0][2] + 1) % 6
        elif name == "state_missing":
            blobs.pop()
        elif name == "page_checksum":
            facts["sums"][0][1][0] ^= 1
    return edit


@pytest.mark.parametrize("name,counter", [
    ("slot_in_two_sequences", "slots_wrong"),
    ("admitted_without_slot", "slots_wrong"),
    ("slot_out_of_range", "slots_wrong"),
    ("resident_slot", "slots_wrong"),
    ("free_slots_miscounted", "slots_wrong"),
    ("ack_unseen", "acks_wrong"),
    ("states_miscounted", "written_wrong"),
    ("pages_miscounted", "written_wrong"),
    ("state_checksum", "states_wrong"),
    ("state_unsummed", "states_wrong"),
    ("state_byte", "state_sample_bytes_wrong"),
    ("state_from_another_slot", "state_sample_bytes_wrong"),
    ("state_missing", "state_sample_bytes_wrong"),
    ("page_checksum", "pages_wrong"),
])
def test_the_reference_refuses_each_guarantee_broken(sound, name, counter):
    facts, sample, blobs, acked = copy.deepcopy(sound)
    _mutate(name)(facts, blobs, acked)
    assert check(facts, sample, blobs, acked)[counter] > 0


def test_the_generator_the_order_and_the_device_checksum_agree():
    """A state as the traffic kind makes it, summed as the handler's device
    program sums a slot, is the reference's shortcut checksum; every planted
    pattern is in every state; states of one run differ; the last chunk
    goes in model layer order with the MTP layer last."""
    import jax

    states = ref.States(FULL, SEED, 3)
    layers, leaves, _ = ref.state_geometry(FULL)
    assert len(layers) == 35 and [n for n, _, _ in leaves] == ["state",
                                                               "conv"]
    got = []
    for j, (_, shape, dt) in enumerate(leaves):
        store = np.stack([states.words_of(5, layer)[j] for layer in range(3)])
        sums, take = kv_hybrid.state_programs(shape)
        got.append(np.asarray(sums(jax.device_put(store[:, None])))[:, 0])
        assert np.array_equal(np.asarray(take(jax.device_put(store[:, None]),
                                              np.int32(2), np.int32(0))),
                              store[2])
    want = states.checksums(5)[:3]
    assert np.array_equal(np.stack(got, 1).astype(np.int64), want)
    words = states.words_of(5, 7)
    assert words[0].dtype == np.uint32 and words[0].shape == (32, 128, 128)
    assert words[1].dtype == np.uint16 and words[1].shape == (288, 128)
    assert list(words[0].reshape(-1)[:3]) == [5, 3, 7]
    for w, (_, _, dt) in zip(words, leaves):
        assert set(ref._PATTERNS[dt.itemsize]) <= set(w.reshape(-1).tolist())
    assert (words[0].reshape(-1) != states.words_of(6, 7)[0].reshape(-1)
            ).any()
    order = ref.order(FULL, 70)                 # two chunks of 64 pages
    assert order[:8] == [("p", layer, 0, 64) for layer in range(8)]
    last = order[8:]
    assert len(last) == 43 and last[0] == ("s", 0)
    assert [m[1] for m in last if m[0] == "p"] == list(range(8))
    assert [i for i, m in enumerate(last) if m[0] == "p"] == [
        5, 11, 17, 23, 29, 35, 41, 42]
    assert last[-1] == ("p", 7, 64, 6)
    assert ref.units(FULL) == (9, 265)
    assert ref.warm_units(FULL, MIX) == MIX["warmup_messages"] == 18910
    assert ref.seq_units(FULL, 1) * 8192 == 8 * 73728 + 35 * 2170880


def test_the_kind_sends_what_the_cache_expects():
    """The traffic kind's messages of a sequence, fed to a cache of the
    rehearsal's geometry, are accepted one by one and acknowledged."""
    import jax
    from tpurpc.serving.pages import KvPageCache

    gen = generator(0)
    layers, leaves, slots = ref.state_geometry(CONFIG)
    page = CONFIG["page"]
    c = KvPageCache(CONFIG["cache"]["layers"], CONFIG["cache"]["pages"],
                    page["tokens"],
                    page["width"], chunk_pages=CONFIG["chunk_tokens"]
                    // page["tokens"], state_layers=layers,
                    state_leaves={n: (s, d) for n, s, d in leaves},
                    slots=slots)
    try:
        ordinal = next(o for o in range(2, 200) if gen.page_count(o) > 64)
        futures = list(c.receiver(0)(iter(list(gen.messages_of(ordinal)))))
        assert futures[0].result(timeout=30) == {
            "seq": ordinal, "pages": gen.page_count(ordinal)}
        assert gen.units(ordinal) * CONFIG["message"]["bytes"] == (
            gen.page_count(ordinal) * c.layers * c.page_bytes
            + len(layers) * c.state_bytes)
        assert jax.devices()[0].platform == "cpu"
    finally:
        c.close()


# -- the cell's readers and entries ------------------------------------------------------

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
HYB = [e for e in MANIFEST["per_layer"] if e.get("workloads") == ["kvh_hyb_c8"]]

SB = 2170880
RUN = {
    "cell": "kvh_hyb_c8", "payload_bytes": 0, "messages": 0,
    "server_ledger": {}, "client_ledger": {},
    "counters": {
        "kv_seqs_admitted": 200, "kv_admit_waits_slots": 30,
        "kv_states_written": 7_000, "kv_state_bytes_written": 7_000 * SB,
        "kv_pages_written": 60_000,
        "lens_kv_state_write_busy_ns": 7_000 * 900_000,
        "lens_kv_state_write_ops": 7_000},
    "peaks": {"hbm_bytes_per_s": 819e9},
    "trace": {"busy_s": 0.05, "window_s": 2.0, "messages": 300_000,
              "payload_bytes": 300_000 * 8192,
              "device_ops": [["jit_tpurpc_kv_pages", 0.02],
                             ["jit_tpurpc_kv_state", 0.03]]},
}
BARE = {"cell": "kvh_hyb_c8", "payload_bytes": 0, "messages": 0,
        "server_ledger": {}, "client_ledger": {}, "counters": {},
        "peaks": {"hbm_bytes_per_s": 819e9},
        "trace": {"busy_s": 0.0, "window_s": 2.0, "messages": 0,
                  "payload_bytes": 0, "device_ops": []}}
_SHARE = 7_000 * SB / (7_000 * SB + 60_000 * 73728)
EXPECT = {
    "kv_state_write_us.kvhyb": 900.0,
    "kv_state_roofline.kvhyb": 100 * 2 * 300_000 * 8192 * _SHARE / 819e9
    / 0.03,
    "kv_slot_wait_pct.kvhyb": 15.0,
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


#: the writing thread's counters in a window where runs formed: 9,000
#: messages in 2,000 page dispatches and 1,600 state dispatches
RUNS = {"kv_messages_written": 9_000, "lens_kv_write_ops": 2_000,
        "lens_kv_write_busy_ns": 2_000 * 1_000_000,
        "lens_kv_state_write_ops": 1_600,
        "lens_kv_state_write_busy_ns": 1_600 * 1_500_000}
EXPECT_RUNS = {"kv_msgs_per_dispatch.kvh": 9_000 / 3_600,
               "kv_write_msg_us.kvh": (2e9 + 2.4e9) / 9_000 / 1e3}


@pytest.mark.parametrize("name", sorted(EXPECT_RUNS))
def test_a_run_reader_reads_the_change_and_nothing_of_a_bare_tree(name):
    """Both cells' readers of the writing thread's runs: the change's
    counters read, a bare tree and the parent's program (the same stages,
    no count of messages) read nothing, and the page cache alone reads its
    page dispatches."""
    read = reader(name)
    run = copy.deepcopy(RUN)
    run["counters"].update(RUNS)
    assert read(run) == pytest.approx(EXPECT_RUNS[name])
    assert read(copy.deepcopy(BARE)) is None
    assert read(copy.deepcopy(RUN)) is None
    run["counters"] = {k: v for k, v in RUNS.items() if "state" not in k}
    run["counters"]["kv_messages_written"] = 2_500
    assert read(run) == pytest.approx(
        {"kv_msgs_per_dispatch.kvh": 1.25, "kv_write_msg_us.kvh": 800.0}[name])
    entry = MANIFEST["per_layer"][-2 + sorted(EXPECT_RUNS).index(name)]
    assert (entry["name"], entry["layer"], entry["source"], entry["moves"],
            entry["workloads"]) == (name, "paged KV cache", "program_counter",
                                    "hbm_gbytes_s", ["kvh_mla_c8",
                                                     "kvh_hyb_c8"])


def test_slots_never_short_read_zero_and_a_page_cache_reads_nothing():
    run = copy.deepcopy(RUN)
    del run["counters"]["kv_admit_waits_slots"]   # a delta drops a 0
    assert reader("kv_slot_wait_pct.kvhyb")(run) == 0.0
    # kvh_mla_c8's program: sequences admitted, no state written
    run["counters"] = {"kv_seqs_admitted": 70, "kv_pages_written": 9_000}
    assert reader("kv_slot_wait_pct.kvhyb")(run) is None
    run["trace"]["device_ops"] = [["jit_tpurpc_kv_pages", 0.02]]
    assert reader("kv_state_roofline.kvhyb")(run) is None


def test_the_manifest_gained_the_cell_and_lost_nothing():
    assert manifest_check.problems(MANIFEST, ROOT) == []
    assert [e["name"] for e in HYB] == list(EXPECT)
    start = MANIFEST["per_layer"].index(HYB[0])
    assert MANIFEST["per_layer"][start:start + len(EXPECT)] == HYB
    assert all(e["moves"] == "hbm_gbytes_s" for e in HYB)
    assert [w["name"] for w in MANIFEST["workloads"]][-2:] == [
        "kvh_mla_c8", "kvh_hyb_c8"]
    cell = MANIFEST["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kv_handoff_kda_mla_hybrid", "kv_handoff_hybrid_c8", 1)
    assert MANIFEST["end_to_end"][0]["workloads"][-2:] == [
        "kvh_mla_c8", "kvh_hyb_c8"]
    entry = MANIFEST["configs"][-1]
    assert entry["name"] == "kv_handoff_kda_mla_hybrid"
    assert FULL["source"] == entry["source"] and FULL["reduced"] == []
    assert FULL["cache"]["bytes"] == 8 * 5769 * 73728 == 3402694656
    assert FULL["state"]["bytes"] == 40 * 35 * SB == 3039232000
    assert FULL["pool"]["bytes"] == 6441926656 == (
        FULL["cache"]["bytes"] + FULL["state"]["bytes"])
    assert FULL["pool"]["slots_in_all"] * FULL["message"]["bytes"] == (
        FULL["pool"]["bytes"])
    assert FULL["handler"] == "kv_hybrid" and FULL["guarantees"]
    assert FULL["state_program"] == "jit_tpurpc_kv_state"
    # the drawn model's keys, as the catalog has them
    assert (FULL["num_hidden_layers"], FULL["layer_group_size"],
            FULL["kv_lora_rank"], FULL["qk_rope_head_dim"],
            FULL["num_attention_heads"], FULL["head_dim"],
            FULL["short_conv_kernel_size"],
            FULL["num_nextn_predict_layers"], FULL["mtp_use_kda"]) == (
        42, 6, 512, 64, 32, 128, 4, 1, False)
    assert FULL["state"]["layers"] == [i for i in range(42)
                                       if (i + 1) % 6 != 0]
    assert FULL["state"]["model_layers"] == 43
    assert FULL["cache"]["layers"] == 8 == 43 - 35
    assert (MIX["kind"], MIX["connections"], MIX["in_flight"]) == (
        "kv_handoff_hybrid", 8, 2)
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "kv_handoff_c8.json")) as f:
        assert json.load(f)["lengths"] == MIX["lengths"]
    assert kv_hybrid.FAULTS == ("drop", "reorder", "evict_unacked",
                                "stale_page", "state_drop", "stale_slot")


# -- the cell end to end, at KiB sizes ------------------------------------------------------

@pytest.mark.parametrize("fault", [None, "reorder", "stale_slot"])
def test_rehearsal_of_the_cell(fault):
    if shutil.which("g++") is None:
        pytest.skip("no g++: run.py builds the data plane")
    argv = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
            "--workload", "kvh_hyb_c8", "--seed", str(SEED), "--seconds",
            "1", "--trace", "0", "--rehearsal-cpu"]
    if fault:
        argv += ["--fault", fault]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    done = subprocess.run(argv, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=300)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal_cpu"] is True and line["correct"] is False
    assert "metrics" not in line and line["attempted"] > 0
    over = {k for k, v in line["compared"].items() if v["value"] > v["limit"]}
    if fault is None:
        assert line["would_be_correct"] is True and not over
        assert line["failed"] == 0
    else:
        assert line["would_be_correct"] is False and over <= {
            "states_wrong", "state_sample_bytes_wrong"} and over
