"""Tier-1's share of the benchmark cell ``kvh_mla_c8``: the plain reference
``benchmarks/configs/kvh_reference.py`` against what the handler's audit
reports after prefill streams were driven through the product's page cache
on the CPU at KiB sizes (one sound, one for each planted fault, one for each
guarantee broken in the facts), the traffic's generator against the
reference's checksums, the cell's per-layer readers with and without the
program's counters, the manifest's new entries, and the cell end to end
(``run.py --rehearsal-cpu``), sound and with every fault planted."""

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

from benchmarks.configs import kvh_reference as ref  # noqa: E402
from benchmarks.handlers import kv_pages  # noqa: E402
from benchmarks.tests import manifest_check  # noqa: E402

SEED = 4100000907          # seeds of a run are over 2**31
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "kv_handoff_mla_paged.json")) as _f:
    FULL = json.load(_f)
CONFIG = dict(FULL, **{k: v for k, v in FULL["rehearsal_cpu"].items()
                       if k != "env"})
with open(os.path.join(ROOT, "benchmarks", "traffic",
                       "kv_handoff_c8.json")) as _f:
    MIX = json.load(_f)
TRAFFIC = dict(MIX, connections=2)
TRACE = 4                  # sequences of the trace a connection, after warm-up


def drive(fault=None):
    """Two prefill streams through the handler's cache, each its warm-up and
    ``TRACE`` sequences of its trace; ``(facts, sample, blobs, acked)`` as
    the harness would hand them to the reference."""
    import jax
    from tpurpc.serving.pages import KvPageCache

    ctx = types.SimpleNamespace(config=CONFIG, traffic=TRAFFIC, seed=SEED,
                                device=jax.devices()[0], fault=fault)
    h = kv_pages.KvPages(ctx, KvPageCache)
    layers, _, tokens, width, chunk = ref.geometry(CONFIG)
    n_seqs = len(TRAFFIC["warmup_pages"]) + TRACE
    acked, futures, errors = [], [], []

    def stream(conn):
        offers = ref.Offers(CONFIG, TRAFFIC, conn)
        pages = ref.Pages(CONFIG, SEED, conn)

        def trees():
            for o in range(n_seqs):
                total = -(-offers.tokens(o) // tokens)
                yield {"offer": np.array([o, offers.tokens(o)], np.int32)}
                for first in range(0, total, chunk):
                    n = min(chunk, total - first)
                    for layer in range(layers):
                        x = np.empty((n, tokens * width), np.uint16)
                        pages.fill(x, o, layer, first)
                        yield {"pages": jax.device_put(
                            x.reshape(n, tokens, width), ctx.device)}

        try:
            futures.extend(h._receiver(conn)(trees()))
        except Exception as exc:  # the test's assertion says which
            errors.append(exc)
        window = sum(-(-offers.tokens(o) // tokens) for o in range(
            len(TRAFFIC["warmup_pages"]), n_seqs)) * layers
        acked.append((conn, int(TRAFFIC["warmup_messages"]) + window))

    # two prefill workers at once: each has a sequence in flight while the
    # other is admitted
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
    assert "evict" in kinds and "drop" not in kinds
    assert len(blobs) == len(sample) == 8 and facts["resident"]


@pytest.mark.parametrize("fault,caught", [
    ("drop", {"pages_wrong"}),
    ("reorder", {"pages_wrong"}),
    ("evict_unacked", {"log_wrong"}),
    ("stale_page", {"pages_wrong"}),
])
def test_each_planted_fault_is_caught(fault, caught):
    assert caught <= wrong(check(*drive(fault)))


def _mutate(name):
    def edit(facts, blobs, acked):
        log = facts["log"]
        admits = [e for e in log if e[0] == "admit"]
        if name == "lost_event":
            log.pop(3)
        elif name == "evicted_out_of_order":
            ev = [i for i, e in enumerate(log) if e[0] == "evict"]
            log[ev[0]][1], log[ev[1]][1] = log[ev[1]][1], log[ev[0]][1]
        elif name == "page_in_two_tables":
            admits[-1][5][0] = facts["resident"][0][5][-1]
        elif name == "admitted_out_of_order":
            mine = [e for e in admits if e[2] == 0]
            mine[1][3], mine[2][3] = mine[2][3], mine[1][3]
        elif name == "another_length":
            admits[4][4] += 1
        elif name == "a_drop":
            log.append(["drop", admits[-1][1]])
        elif name == "resident_lost":
            facts["resident"].pop()
        elif name == "free_miscounted":
            facts["free"] += 1
        elif name == "ack_unseen":
            acked[1] += 1
        elif name == "written_miscounted":
            facts["written"][0] -= 4
        elif name == "page_checksum":
            facts["sums"][0][1][0] ^= 1
        elif name == "sampled_byte":
            blobs[2] = blobs[2][:9] + bytes([blobs[2][9] ^ 1]) + blobs[2][10:]
        elif name == "sample_elsewhere":
            facts["sampled"][0][3] = (facts["sampled"][0][3] + 1) % 600
        elif name == "sample_missing":
            blobs.pop()
    return edit


@pytest.mark.parametrize("name,counter", [
    ("lost_event", "log_wrong"),
    ("evicted_out_of_order", "log_wrong"),
    ("page_in_two_tables", "log_wrong"),
    ("admitted_out_of_order", "log_wrong"),
    ("another_length", "log_wrong"),
    ("a_drop", "log_wrong"),
    ("resident_lost", "resident_wrong"),
    ("free_miscounted", "resident_wrong"),
    ("ack_unseen", "acks_wrong"),
    ("written_miscounted", "written_wrong"),
    ("page_checksum", "pages_wrong"),
    ("sampled_byte", "sample_bytes_wrong"),
    ("sample_elsewhere", "sample_bytes_wrong"),
    ("sample_missing", "sample_bytes_wrong"),
])
def test_the_reference_refuses_each_guarantee_broken(sound, name, counter):
    facts, sample, blobs, acked = copy.deepcopy(sound)
    _mutate(name)(facts, blobs, acked)
    assert check(facts, sample, blobs, acked)[counter] > 0


def test_the_generator_and_the_device_checksum_agree():
    """A page as the traffic kind fills it, summed as the handler's device
    program sums the store, is the reference's shortcut checksum; pages of
    one run differ from each other, and the trace is the traffic file's."""
    import jax

    pages = ref.Pages(FULL, SEED, 3)
    words = np.stack([pages.words_of(5, layer, p)
                      for layer in range(3) for p in range(4)])
    store = words.reshape(3, 4, 288, 128)
    sums, _ = kv_pages.programs((288, 128))
    got = np.asarray(sums(jax.device_put(store)))
    want = pages.checksums(5, 4)[:3]
    assert np.array_equal(got.astype(np.int64), want)
    assert ref.checksum_np(words[7]) == want[1, 3]
    assert len({w.tobytes() for w in words}) == 12
    lengths = ref.lengths(MIX, 0)
    assert np.array_equal(lengths, ref.lengths(MIX, 0))
    assert not np.array_equal(lengths, ref.lengths(MIX, 1))
    assert lengths.min() >= 256 and lengths.max() <= 32768
    offers = ref.Offers(FULL, MIX, 0)
    warm = len(MIX["warmup_pages"])
    assert [offers.tokens(o) for o in range(warm + 1)] == [
        64 * p for p in MIX["warmup_pages"]] + [int(lengths[0])]
    assert ref.warm_layers(FULL, MIX) == MIX["warmup_messages"] == 305


# -- the cell's readers and entries ------------------------------------------------------

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
KVH = [e for e in MANIFEST["per_layer"] if e.get("workloads") == ["kvh_mla_c8"]]

M = 12_000 * 50                   # page-layers of a window
RUN = {
    "cell": "kvh_mla_c8", "payload_bytes": M * 73728, "messages": M,
    "server_ledger": {"host_copy": 3 * 73728,
                      "dma_h2d": M * 73728 + 100 * 8},
    "client_ledger": {"host_copy": 73728},
    "counters": {
        "kv_seqs_admitted": 100, "lens_kv_admit_busy_ns": 100 * 4_000_000,
        "lens_kv_admit_ops": 30,
        "lens_kv_write_busy_ns": 12_000 * 500_000, "lens_kv_write_ops":
        12_000, "lens_kv_write_cpu_ns": 12_000 * 300_000,
        "kv_pages_written": M,
        "lens_kv_ack_busy_ns": 100 * 2_000_000, "lens_kv_ack_ops": 100,
        "lens_hbm_busy_ns": 12_100 * 2_400_000, "lens_hbm_ops": 12_100,
        "lens_hbm_credit_busy_ns": 12_100 * 100_000,
        "lens_srv_queue_busy_ns": 6_000 * 50_000, "lens_srv_queue_ops": 6_000,
        "native_srv_queue_ns": 6_100 * 30_000, "native_srv_queue_msgs": 6_100,
        "lens_srv_handler_busy_ns": 12_100 * 3_000_000,
        "lens_srv_handler_cpu_ns": 12_100 * 1_000_000,
        "lens_decode_busy_ns": 12_100 * 2_500_000,
        "lens_decode_cpu_ns": 12_100 * 800_000,
        "lens_srv_handler_ops": 12_100, "lens_cpu_clock_reads": 800,
        "native_rdv_recv_bytes": M * 73728 * 0.97},
    "peaks": {"hbm_bytes_per_s": 819e9},
    "trace": {"busy_s": 0.06, "window_s": 2.0, "messages": 80_000,
              "payload_bytes": 80_000 * 73728,
              "device_ops": [["jit_tpurpc_kv_pages", 0.04],
                             ["copy", 0.01]]},
}
BARE = {"cell": "kvh_mla_c8", "payload_bytes": 0, "messages": 0,
        "server_ledger": {}, "client_ledger": {}, "counters": {},
        "peaks": {"hbm_bytes_per_s": 819e9},
        "trace": {"busy_s": 0.0, "window_s": 2.0, "messages": 0,
                  "payload_bytes": 0, "device_ops": []}}
EXPECT = {
    "kv_admit_wait_us.kvh": 4000.0, "kv_write_us.kvh": 500.0,
    "kv_ack_us.kvh": 2000.0,
    "kv_scatter_roofline.kvh": 100 * 2 * 80_000 * 73728 / 819e9 / 0.04,
    "hbm_place_us.kvh": 2400.0, "rdv_bytes_pct.kvh": 97.0,
    "host_copy_b_per_b.kvh": 4 / M,
    "landing_b_per_b.kvh": (M * 73728 + 800) / (M * 73728),
    "device_idle_pct.kvh": 97.0,
    "kv_write_cpu_us.kvh": 300.0,
    "srv_queue_wait_us.kvh": (6_000 * 50 + 6_100 * 30) / 12_100,
    "hbm_credit_wait_us.kvh": 100.0,
    "srv_handler_self_us.kvh": 500.0,
    "srv_handler_self_cpu_us.kvh": 200.0,
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


def test_an_admission_that_never_waited_reads_zero():
    run = copy.deepcopy(RUN)
    del run["counters"]["lens_kv_admit_busy_ns"]  # a delta drops a 0
    assert reader("kv_admit_wait_us.kvh")(run) == 0.0


def test_the_manifest_gained_the_cell_and_lost_nothing():
    assert manifest_check.problems(MANIFEST, ROOT) == []
    assert [e["name"] for e in KVH] == list(EXPECT)
    start = MANIFEST["per_layer"].index(KVH[0])
    assert MANIFEST["per_layer"][start:start + len(EXPECT)] == KVH
    assert all(e["moves"] == "hbm_gbytes_s" for e in KVH)
    assert [w["name"] for w in MANIFEST["workloads"]][:6] == [
        "stream4m_c1", "stream4m_c8", "pingpong4m_c1", "fanin4m_c8",
        "fanex4m_c8", "kvh_mla_c8"]
    cell = MANIFEST["workloads"][5]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kv_handoff_mla_paged", "kv_handoff_c8", 1)
    assert MANIFEST["end_to_end"][0]["workloads"][5] == "kvh_mla_c8"
    entry = MANIFEST["configs"][4]
    assert entry["name"] == "kv_handoff_mla_paged"
    assert FULL["source"] == entry["source"] and FULL["reduced"] == []
    assert FULL["cache"]["bytes"] == 61 * 1432 * 73728 == 6440288256
    assert FULL["pool"]["slots_in_all"] * FULL["message"]["bytes"] == (
        FULL["pool"]["bytes"])
    assert FULL["handler"] == "kv_pages" and FULL["guarantees"]
    assert FULL["program"] == "jit_tpurpc_kv_pages"
    assert (MIX["kind"], MIX["connections"], MIX["in_flight"]) == (
        "kv_handoff", 8, 2)
    assert kv_pages.FAULTS == ("drop", "reorder", "evict_unacked",
                               "stale_page")


# -- the cell end to end, at KiB sizes ------------------------------------------------------

@pytest.mark.parametrize("fault", [None, "drop", "reorder", "evict_unacked",
                                   "stale_page"])
def test_rehearsal_of_the_cell(fault):
    if shutil.which("g++") is None:
        pytest.skip("no g++: run.py builds the data plane")
    argv = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
            "--workload", "kvh_mla_c8", "--seed", str(SEED), "--seconds",
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
        assert line["would_be_correct"] is False and over
