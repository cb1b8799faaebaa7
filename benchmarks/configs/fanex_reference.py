"""The plain reference of ``tensor_fanin_exchange_4m``: what a pool filled a
batch at a time must hold, and what every reply must have been.

Semantics (the configuration's guarantees). The pool is
``fanin_reference``'s: the consumer took whole batches of at most
``max_rows`` rows, request rows first and zero pad rows after, wrote batch
``b`` into batch slot ``b mod slots`` and kept a log of every batch (its row
count and its rows' two stamp words), which is read here and held to what it
MUST be, a valid interleaving of the streams. On top of it: **the reply to
the request stacked at row ``r`` of batch ``b`` is, bit-exact, what slot
``(b mod slots, r)`` held before batch ``b``**: the seeded words of that row
for ``b < slots``, else row ``r`` of batch ``b - slots`` (a message, or a pad
row's zeros). A connection's replies arrive in the order of its requests, so
its ``k``-th reply answers its ``k``-th request, whose place ``(b, r)`` the
log gives. numpy and the benchmark's payload generator only (and
``init_words_np``, the seeded pool's formula); nothing the server made is
used to compute an expectation.

Compared, each with the limit 0. On the pool, ``fanin_reference``'s five
(``acks_wrong``, ``log_wrong``, ``folds_wrong``, ``slots_wrong``,
``sample_bytes_wrong``). On the replies:

``reply_folds_wrong``         connections whose device fold over every row
                              that left for them (computed inside the
                              consumer's program) differs from the fold of
                              what must have left, in order
``reply_stamps_wrong``        replies whose first two words, as the client
                              received them and in the order it received
                              them, are not the expected reply's (every
                              reply of the run, warm-up included; a client
                              that reported nothing counts them all)
``reply_sample_bytes_wrong``  differing bytes among the replies the client
                              kept whole (a seeded sample by sequence
                              number), each compared in the client process
                              by ``sampled_bytes_wrong`` below with what its
                              own stamp words name: a seeded row, a bank
                              message of any connection, or a pad row
``reply_sample_sums_wrong``   kept replies whose checksum as received is not
                              that of what the log says the slot held: ties
                              the comparison above to the place
``reply_sample_missing``      sampled replies the plan demands for this many
                              messages that the client did not keep

The client also counts every misshapen reply and every differing kept reply
as a failed message, so they reach ``rpc_failed`` (limit 0) too.
"""

from __future__ import annotations

import numpy as np

from benchmarks.configs import fanin_reference
from benchmarks.configs.pingpong_reference import init_checksums
from benchmarks.handlers.pool_sink import init_words_np
from benchmarks.harness.payloads import Bank, fold

LIMITS = dict(fanin_reference.LIMITS, reply_folds_wrong=0,
              reply_stamps_wrong=0, reply_sample_bytes_wrong=0,
              reply_sample_sums_wrong=0, reply_sample_missing=0)
plan_sample = fanin_reference.plan_sample
geometry = fanin_reference.geometry


def lap(config: dict, traffic: dict) -> int:
    """A connection's share of the pool's first lap, in messages: about how
    many of its replies are seeded words."""
    conns, slots, max_rows, _ = geometry(config, traffic)
    return max(1, slots * max_rows // conns)


def plan_replies(config: dict, traffic: dict, seed: int,
                 conn: int) -> list[int]:
    """Sequence numbers of the replies a client keeps whole, drawn from the
    seed before the window (``pingpong_reference``'s rule):
    ``reply_sample.below`` of them below a connection's share of the first
    lap and ``reply_sample.above`` at or above it, the latter log-uniform
    over ``reply_sample.horizon`` messages so that a short run and a long
    one both reach some. Which of them turn out to be seeded words depends
    on the batches the run makes; the client keeps those the run gets to."""
    first = lap(config, traffic)
    spec = traffic["reply_sample"]
    rng = np.random.default_rng([seed, conn, 0xFA9E8])
    picks: set[int] = set()
    while len(picks) < min(int(spec["below"]), first):
        picks.add(int(rng.integers(first)))
    want = len(picks) + int(spec["above"])
    span = np.log(float(spec["horizon"]))
    while len(picks) < want:
        picks.add(first + int(np.exp(rng.uniform(0.0, span))) - 1)
    return sorted(picks)


def seeded_stamps(config: dict, traffic: dict, seed: int) -> np.ndarray:
    """The first two words of every pool row as set-up made them,
    ``uint32[slots * max_rows, 2]``."""
    _, slots, max_rows, words = geometry(config, traffic)
    return np.stack([init_words_np(seed, 0, row * words, 2)
                     for row in range(slots * max_rows)])


def sampled_bytes_wrong(config: dict, traffic: dict, seed: int,
                        kept: dict[int, np.ndarray]) -> int:
    """Differing bytes between the replies a client kept (``seq -> copy``)
    and what each one's own stamp words name: the seeded words of the pool
    row that starts with them, else message ``word 0`` of connection ``word
    1`` (a bank of its own: nothing the client sent with is read), else, for
    a reply that is all zeros, a pad row. A reply that names nothing is
    wrong in every byte. Which reply belongs where is ``check``'s to say."""
    conns, _, _, words = geometry(config, traffic)
    rows = {tuple(int(w) for w in st): row for row, st in enumerate(
        seeded_stamps(config, traffic, seed))} if kept else {}
    banks: dict[int, Bank] = {}    # made on demand: half a second each
    wrong = 0
    for got in kept.values():
        got = np.ascontiguousarray(got).reshape(-1).view(np.uint32)
        if got.size != words:
            wrong += words * 4
            continue
        w0, w1 = int(got[0]), int(got[1])
        if (w0, w1) in rows:
            want = init_words_np(seed, 0, rows[w0, w1] * words, words)
        elif not got.any():
            continue
        elif w1 < conns:
            if w1 not in banks:
                banks[w1] = Bank(seed, w1, config)
            want = banks[w1].message_copy(w0).reshape(-1).view(np.uint32)
        else:
            wrong += words * 4
            continue
        wrong += int((got.view(np.uint8) != want.view(np.uint8)).sum())
    return wrong


def check(config: dict, traffic: dict, seed: int, facts: dict,
          sample: list[int], blobs: list[bytes], acked: list[int]) -> dict:
    out = fanin_reference.check(config, traffic, seed, facts, sample, blobs,
                                acked)
    conns, slots, max_rows, words = geometry(config, traffic)
    _, rows = fanin_reference.read_log(facts, conns, max_rows)
    sums = [Bank(seed, c, config).checksums() for c in range(conns)]
    fresh_stamps = seeded_stamps(config, traffic, seed)
    fresh_sums = init_checksums(seed, 0, min(len(rows), slots) * max_rows,
                                words)

    def held_before(b: int, r: int) -> tuple[tuple[int, int], int]:
        """``(stamp words, checksum)`` of what slot ``(b mod slots, r)``
        held before batch ``b``."""
        if b < slots:
            row = b * max_rows + r
            return (tuple(int(w) for w in fresh_stamps[row]),
                    fresh_sums[row])
        old = rows[b - slots]
        named = old[r] if r < len(old) else None
        if named is None:
            return (0, 0), 0
        seq, c = named
        return (seq & 0xFFFFFFFF, c), sums[c].of(seq)

    # the place of every request, in each connection's own order
    places: list[list] = [[] for _ in range(conns)]
    for b, batch in enumerate(rows):
        for r, named in enumerate(batch):
            if named is not None:
                places[named[1]].append((b, r))

    folds = stamps = sample_bytes = sample_sums = missing = 0
    for c in range(conns):
        n = int(facts["n"][c])
        want = [held_before(b, r) for b, r in places[c][:n]]
        acc = 0
        for _, s in want:
            acc = fold(acc, s)
        folds += acc != int(facts["acc_out"][c])
        due = [k for k in plan_replies(config, traffic, seed, c) if k < n]
        report = facts["client"][c]
        if not report or int(report["first"]) != 0:
            stamps += n
            missing += len(due)
            continue
        got = [tuple(int(w) for w in st) for st in report["stamps"]]
        both = min(len(got), len(want))
        stamps += sum(got[k] != want[k][0] for k in range(both))
        stamps += abs(len(got) - n) + abs(len(want) - n)
        sample_bytes += int(report["sample_bytes_wrong"])
        kept = [int(k) for k in report["sampled"]]
        missing += len(set(due) ^ set(kept))
        for k, s in zip(kept, report["sample_sums"]):
            sample_sums += k >= len(want) or int(s) != want[k][1]
    out.update(reply_folds_wrong=int(folds), reply_stamps_wrong=int(stamps),
               reply_sample_bytes_wrong=int(sample_bytes),
               reply_sample_sums_wrong=int(sample_sums),
               reply_sample_missing=int(missing))
    return out
