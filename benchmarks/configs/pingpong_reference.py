"""The plain reference of ``tensor_pingpong_4m``: what the pool must hold, and
what every reply must have been.

Semantics (the configuration's guarantees): the pool is ``pool_reference``'s
(message ``k`` of connection ``c`` lives, bit-exact, in slot ``k mod slots``
until a later one takes the slot), and **the reply to message ``k`` is,
bit-exact, what that slot held before it**: the seeded initial words of the
slot for ``k < slots``, message ``k - slots`` after. numpy and the benchmark's
payload generator only; nothing the server made is used to compute an
expectation.

Compared, each with the limit 0. On the pool, ``pool_reference``'s four
(``folds_wrong``, ``slots_wrong``, ``sample_bytes_wrong``, ``acks_wrong``).
On the replies:

``reply_folds_wrong``         connections whose device fold over EVERYTHING
                              that left (computed inside the swap program)
                              differs from the fold of what must have left
``reply_stamps_wrong``        replies whose first two words, as the client
                              received them, are not the expected reply's
                              (every reply of the run, warm-up included; a
                              client that reported nothing counts them all)
``reply_sample_bytes_wrong``  differing bytes among the replies the client
                              kept whole: a seeded sample by sequence number,
                              compared in the client process by
                              ``sampled_bytes_wrong`` below against the seed
``reply_sample_missing``      sampled replies the plan demands for this
                              many messages that the client did not keep

The client also counts every wrong stamp and every differing sampled reply as
a failed message, so they reach ``rpc_failed`` (limit 0) too.
"""

from __future__ import annotations

import numpy as np

from benchmarks.configs import pool_reference
from benchmarks.handlers.pool_sink import init_words_np
from benchmarks.harness.payloads import Bank, fold

LIMITS = dict(pool_reference.LIMITS, reply_folds_wrong=0,
              reply_stamps_wrong=0, reply_sample_bytes_wrong=0,
              reply_sample_missing=0)
plan_sample = pool_reference.plan_sample
geometry = pool_reference.geometry

#: words of the seeded pool hashed per numpy call: small enough to stay in
#: the cache (a whole 4 MiB slot at once is five times slower per word)
_CHUNK = 32768


def init_checksums(seed: int, conn: int, count: int, words: int) -> list[int]:
    """``checksum_np`` of the seeded initial content of slots ``0 .. count``
    of connection ``conn``'s shard, chunk by chunk."""
    step = min(_CHUNK, words)
    if words % step:
        step = words
    weights = [np.arange(o, o + step, dtype=np.uint32) * np.uint32(2)
               + np.uint32(1) for o in range(0, words, step)]
    out = []
    for s in range(count):
        acc = 0
        for j, w in enumerate(weights):
            v = init_words_np(seed, conn, s * words + j * step, step)
            acc += int((v * w).sum(dtype=np.uint32))
        out.append(acc & 0xFFFFFFFF)
    return out


def init_stamps(seed: int, conn: int, slots: int, words: int) -> np.ndarray:
    """The first two words of every slot as set-up made them,
    ``uint32[slots, 2]``."""
    return np.stack([init_words_np(seed, conn, s * words, 2)
                     for s in range(slots)])


def expected_stamps(seed: int, conn: int, first: int, count: int, slots: int,
                    words: int) -> np.ndarray:
    """The first two words of the replies to messages ``first .. first +
    count``, ``uint32[count, 2]``."""
    seqs = np.arange(first, first + count, dtype=np.int64)
    out = np.empty((count, 2), np.uint32)
    fresh = seqs < slots
    if fresh.any():
        out[fresh] = init_stamps(seed, conn, slots, words)[seqs[fresh]]
    out[~fresh, 0] = ((seqs[~fresh] - slots) & 0xFFFFFFFF).astype(np.uint32)
    out[~fresh, 1] = conn
    return out


def expected_reply(bank: Bank, seed: int, seq: int, slots: int,
                   words: int) -> np.ndarray:
    """The reply to message ``seq`` as 32-bit words."""
    if seq < slots:
        return init_words_np(seed, bank.conn, seq * words, words)
    return bank.message_copy(seq - slots).reshape(-1).view(np.uint32)


def plan_replies(config: dict, traffic: dict, seed: int,
                 conn: int) -> list[int]:
    """Sequence numbers of the replies a client keeps whole, drawn from the
    seed before the window: ``reply_sample.below`` of them below ``slots``
    (the reply is seeded initial words) and ``reply_sample.above`` at or
    above it (the reply is an earlier message), the latter log-uniform over
    ``reply_sample.horizon`` messages so that a short run and a long one
    both reach some. The client keeps those the run gets to."""
    _, slots, _ = geometry(config, traffic)
    spec = traffic["reply_sample"]
    rng = np.random.default_rng([seed, conn, 0x9E917])
    picks: set[int] = set()
    while len(picks) < min(int(spec["below"]), slots):
        picks.add(int(rng.integers(slots)))
    want = len(picks) + int(spec["above"])
    span = np.log(float(spec["horizon"]))
    while len(picks) < want:
        picks.add(slots + int(np.exp(rng.uniform(0.0, span))) - 1)
    return sorted(picks)


def sampled_bytes_wrong(config: dict, traffic: dict, seed: int, conn: int,
                        kept: dict[int, np.ndarray]) -> int:
    """Differing bytes between the replies a client kept (``seq -> copy``)
    and what the seed says each must have been. A bank of its own: nothing
    the client sent with is read."""
    _, slots, words = geometry(config, traffic)
    bank = Bank(seed, conn, config)
    wrong = 0
    for seq, got in kept.items():
        want = expected_reply(bank, seed, seq, slots, words).view(np.uint8)
        got = np.ascontiguousarray(got).reshape(-1).view(np.uint8)
        wrong += (int((got != want).sum()) if got.size == want.size
                  else want.size)
    return wrong


def check(config: dict, traffic: dict, seed: int, facts: list[dict],
          sample: list[list[int]], blobs: list[bytes],
          acked: list[int]) -> dict:
    out = pool_reference.check(config, traffic, seed, facts, sample, blobs,
                               acked)
    conns, slots, words = geometry(config, traffic)
    folds = stamps = sample_bytes = missing = 0
    for c in range(conns):
        n = int(facts[c]["n"])
        ck = Bank(seed, c, config).checksums()
        acc = 0
        for s in init_checksums(seed, c, min(n, slots), words):
            acc = fold(acc, s)
        for k in range(slots, n):
            acc = fold(acc, ck.of(k - slots))
        folds += acc != int(facts[c]["acc_out"])
        due = [k for k in plan_replies(config, traffic, seed, c) if k < n]
        report = facts[c].get("client")
        if not report or int(report["first"]) != 0:
            stamps += n
            missing += len(due)
            continue
        got = np.array(report["stamps"], np.uint32).reshape(-1, 2)
        want = expected_stamps(seed, c, 0, n, slots, words)
        both = min(len(got), n)
        stamps += int((got[:both] != want[:both]).any(axis=1).sum())
        stamps += abs(len(got) - n)
        sample_bytes += int(report["sample_bytes_wrong"])
        missing += len(set(due) ^ set(int(k) for k in report["sampled"]))
    out.update(reply_folds_wrong=folds, reply_stamps_wrong=stamps,
               reply_sample_bytes_wrong=sample_bytes,
               reply_sample_missing=missing)
    return out
