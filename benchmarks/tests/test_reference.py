"""The plain reference, the payload generator and the handler's device
programs agree with each other at KiB sizes; a broken pool does not pass."""

import types

import numpy as np
import pytest

from benchmarks.configs import pool_reference as ref
from benchmarks.handlers import pool_sink
from benchmarks.harness.payloads import Bank, checksum_np, fold

CONFIG = {"message": {"dtype": "float32", "shape": [8, 16], "bytes": 512},
          "pool": {"bytes": 512 * 12}, "bank_messages": 5,
          "audit": {"sampled_slots": 4}, "rpc": "stream_stream"}
TRAFFIC = {"connections": 2}
SEED = 3_000_000_019


def test_stamped_messages_differ_and_the_shortcut_checksum_is_exact():
    bank = Bank(SEED, 1, CONFIG)
    ck = bank.checksums()
    seen = set()
    for seq in (0, 1, 4, 5, 6, 2 ** 32 + 3):
        msg = bank.message_copy(seq)
        words = msg.reshape(-1).view(np.uint32)
        assert (words[0], words[1]) == (seq & 0xFFFFFFFF, 1)
        assert ck.of(seq) == checksum_np(msg)
        seen.add(msg.tobytes())
    assert len(seen) == 6
    assert Bank(SEED, 1, CONFIG).message_copy(3).tobytes() == \
        bank.message_copy(3).tobytes()
    assert Bank(SEED + 1, 1, CONFIG).message_copy(3).tobytes() != \
        bank.message_copy(3).tobytes()


def test_a_bank_entry_still_held_is_never_restamped_under_its_holder():
    """A client that buffers messages (the array, a view or a memoryview of
    it) gets stamped copies once the bank comes round; one that lets go gets
    the entries themselves, with no copy."""
    bank = Bank(SEED, 0, CONFIG)                       # 5 entries
    for seq in range(12):                              # nobody holds on
        assert bank.message(seq).reshape(-1).view(np.uint32)[0] == seq
    assert bank.copies == 0
    held = [{"x": bank.message(12)}, bank.message(13).reshape(-1),
            memoryview(bank.message(14))]
    later = [bank.message(seq) for seq in range(15, 25)]
    assert bank.copies == 8     # 17..19 over the three held, 20..24 over later
    for seq, h in zip((12, 13, 14), held):
        got = np.asarray(h["x"] if isinstance(h, dict) else h)
        assert got.tobytes() == bank.message_copy(seq).tobytes()
    for seq, msg in zip(range(15, 25), later):
        assert msg.tobytes() == bank.message_copy(seq).tobytes()
    del held, later, h, got, msg
    before = bank.copies
    for seq in range(25, 40):
        bank.message(seq)
    assert bank.copies == before


def test_last_seq():
    assert ref.last_seq(3, 3, 6) is None
    assert ref.last_seq(3, 4, 6) == 3
    assert ref.last_seq(3, 9, 6) == 3
    assert ref.last_seq(3, 10, 6) == 9
    assert ref.last_seq(0, 13, 6) == 12


def _simulate(counts, fault=None):
    """The handler's semantics in numpy, and what its audit would report."""
    conns, slots, words = ref.geometry(CONFIG, TRAFFIC)
    facts, pools = [], []
    for c in range(conns):
        pool = pool_sink.init_words_np(SEED, c, 0, slots * words).reshape(
            slots, words)
        bank, acc = Bank(SEED, c, CONFIG), 0
        for k in range(counts[c]):
            msg = bank.message_copy(k).reshape(-1).view(np.uint32)
            if fault == "alter" and k == counts[c] - 1:
                msg[7] ^= 1
            acc = fold(acc, checksum_np(msg))
            if fault == "drop" and k == 2:
                continue
            pool[(k + (fault == "shift")) % slots] = msg
        facts.append({"n": counts[c], "acc": acc, "slot_sums": [
            checksum_np(row) for row in pool]})
        pools.append(pool)
    sample = ref.plan_sample(CONFIG, TRAFFIC, SEED, counts)
    blobs = [pools[c][s].tobytes() for c in range(conns) for s in sample[c]]
    return facts, sample, blobs


@pytest.mark.parametrize("counts", [[0, 0], [3, 5], [6, 6], [17, 40]])
def test_reference_accepts_a_sound_pool(counts):
    facts, sample, blobs = _simulate(counts)
    got = ref.check(CONFIG, TRAFFIC, SEED, facts, sample, blobs, counts)
    assert got == {k: 0 for k in ref.LIMITS}
    for c, picks in enumerate(sample):
        if counts[c]:
            assert (counts[c] - 1) % 6 in picks  # the last-written slot


@pytest.mark.parametrize("fault,fails", [
    ("alter", ("folds_wrong", "slots_wrong", "sample_bytes_wrong")),
    ("drop", ("slots_wrong",)),
    ("shift", ("slots_wrong",)),
])
def test_reference_refuses_a_broken_pool(fault, fails):
    counts = [5, 5]
    facts, sample, blobs = _simulate(counts, fault)
    got = ref.check(CONFIG, TRAFFIC, SEED, facts, sample, blobs, counts)
    for name in fails:
        assert got[name] > ref.LIMITS[name], (name, got)


def test_reference_counts_acknowledgements_the_server_never_saw():
    counts = [4, 4]
    facts, sample, blobs = _simulate(counts)
    got = ref.check(CONFIG, TRAFFIC, SEED, facts, sample, blobs, [4, 6])
    assert got["acks_wrong"] == 2


def test_device_programs_agree_with_numpy():
    import jax

    shape, slots = (8, 16), 6
    init, put, sums, take = pool_sink.programs(shape, "float32", slots)
    pool = init(np.uint32(SEED & 0xFFFFFFFF), np.uint32(1))
    want = pool_sink.init_words_np(SEED, 1, 0, slots * 128)
    assert np.array_equal(np.asarray(pool).reshape(-1).view(np.uint32), want)
    bank, ref_acc = Bank(SEED, 1, CONFIG), 0
    acc, seq = jax.numpy.uint32(0), jax.numpy.uint32(0)
    for k in range(9):
        msg = bank.message_copy(k)
        pool, acc, seq = put(pool, acc, seq, msg)
        ref_acc = fold(ref_acc, checksum_np(msg))
    assert int(acc) == ref_acc and int(seq) == 9
    got = np.asarray(sums(pool))
    for s in range(slots):
        assert int(got[s]) == checksum_np(bank.message_copy(
            ref.last_seq(s, 9, slots)))
    assert np.asarray(take(pool, np.int32(2))).tobytes() == \
        bank.message_copy(8).tobytes()


@pytest.mark.parametrize("fault", ["approx_bf16", "alter"])
def test_planted_device_faults_change_what_is_stored(fault):
    shape, slots = (8, 16), 6
    _, put, _, take = pool_sink.programs(shape, "float32", slots, fault)
    init = pool_sink.programs(shape, "float32", slots)[0]
    pool = init(np.uint32(1), np.uint32(0))
    msg = Bank(SEED, 0, CONFIG).message_copy(4)
    import jax.numpy as jnp

    pool, _, _ = put(pool, jnp.uint32(0), jnp.uint32(4), msg)
    assert np.asarray(take(pool, np.int32(4))).tobytes() != msg.tobytes()


def test_handler_refuses_a_leaf_that_is_not_on_the_device():
    import jax

    ctx = types.SimpleNamespace(
        config=dict(CONFIG, rpc="unary_unary"), traffic=TRAFFIC, seed=SEED,
        device=jax.devices()[0], fault=None)
    sink = pool_sink.build(ctx)
    with pytest.raises(RuntimeError, match="not on"):
        sink._store(sink.shards[0], np.zeros((8, 16), np.float32))
    assert sink.counts() == [0, 0]
