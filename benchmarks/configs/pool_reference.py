"""The plain reference of the pool configurations: what the pool must hold.

Semantics (the configuration's guarantees): connection ``c`` sent messages
``0 .. n_c - 1`` in order; message ``k`` lives, bit-exact, in slot ``k mod
slots`` of shard ``c`` unless a later message took the slot; a slot no message
reached still holds what set-up made from the seed. Nothing is dropped or
approximated, so the running fold over all ``n_c`` message checksums is fixed
too. numpy and the benchmark's payload generator only: nothing of tpurpc, and
nothing the server made, is used to compute an expectation.

Compared, each with the limit 0 (exact comparisons):

``folds_wrong``         connections whose device fold over ALL messages of the
                        run differs from the reference's
``slots_wrong``         slots whose device checksum differs: every slot a
                        message reached, and a seeded sample of the others
``sample_bytes_wrong``  differing bytes among the sampled slots read back whole
                        (each connection's last-written slot among them)
``acks_wrong``          messages a client holds acknowledged that the server
                        does not count, and the reverse
"""

from __future__ import annotations

import numpy as np

from benchmarks.handlers.pool_sink import init_words_np
from benchmarks.harness.payloads import Bank, checksum_np, fold

LIMITS = {"folds_wrong": 0, "slots_wrong": 0, "sample_bytes_wrong": 0,
          "acks_wrong": 0}
#: untouched slots checked per connection (they are no answer of the window;
#: a stray write into one also leaves a reached slot wrong)
UNTOUCHED_SAMPLE = 32


def geometry(config: dict, traffic: dict) -> tuple[int, int, int]:
    """``(connections, slots per connection, words per message)``."""
    conns = int(traffic["connections"])
    nbytes = int(np.prod(config["message"]["shape"])) * 4
    return conns, int(config["pool"]["bytes"]) // nbytes // conns, nbytes // 4


def last_seq(slot: int, n: int, slots: int) -> int | None:
    """The newest message below ``n`` that belongs in ``slot``."""
    if slot >= n:
        return None
    return slot + (n - 1 - slot) // slots * slots


def plan_sample(config: dict, traffic: dict, seed: int,
                counts: list[int]) -> list[list[int]]:
    """Slots to read back whole, per connection, drawn from the seed: the
    last-written slot of each connection and others up to the configuration's
    ``audit.sampled_slots`` in all."""
    conns, slots, _ = geometry(config, traffic)
    per_conn = max(1, int(config["audit"]["sampled_slots"]) // conns)
    rng = np.random.default_rng([seed, 0xA0D17])
    out = []
    for c in range(conns):
        picks = {(counts[c] - 1) % slots} if counts[c] else set()
        while len(picks) < min(per_conn, slots):
            picks.add(int(rng.integers(slots)))
        out.append(sorted(picks))
    return out


def slot_words(bank: Bank, seed: int, slot: int, n: int, slots: int,
               words: int) -> np.ndarray:
    """What ``slot`` must hold after ``n`` messages, as 32-bit words."""
    k = last_seq(slot, n, slots)
    if k is None:
        return init_words_np(seed, bank.conn, slot * words, words)
    return bank.message_copy(k).reshape(-1).view(np.uint32)


def check(config: dict, traffic: dict, seed: int, facts: list[dict],
          sample: list[list[int]], blobs: list[bytes],
          acked: list[int]) -> dict:
    """The numbers compared, from what the device reported after the window
    (``facts``: per connection ``n``, ``acc``, ``slot_sums``; ``blobs``: the
    sampled slots' bytes in ``sample`` order) and what each client holds
    acknowledged (``acked``)."""
    conns, slots, words = geometry(config, traffic)
    rng = np.random.default_rng([seed, 0x51075])
    folds_wrong = slots_wrong = bytes_wrong = acks_wrong = 0
    blob = iter(blobs)
    for c in range(conns):
        bank = Bank(seed, c, config)
        ck = bank.checksums()
        n = int(facts[c]["n"])
        acks_wrong += abs(n - int(acked[c]))
        acc = 0
        for k in range(n):
            acc = fold(acc, ck.of(k))
        folds_wrong += acc != int(facts[c]["acc"])
        sums = facts[c]["slot_sums"]
        if len(sums) != slots:
            raise ValueError(f"{len(sums)} slot sums for {slots} slots")
        for s in range(min(n, slots)):
            slots_wrong += ck.of(last_seq(s, n, slots)) != int(sums[s])
        untouched = range(n, slots)
        if len(untouched) > UNTOUCHED_SAMPLE:
            untouched = sorted(int(i) for i in rng.choice(
                np.arange(n, slots), UNTOUCHED_SAMPLE, replace=False))
        for s in untouched:
            want = init_words_np(seed, c, s * words, words)
            slots_wrong += checksum_np(want) != int(sums[s])
        for s in sample[c]:
            want = slot_words(bank, seed, s, n, slots, words)
            got = np.frombuffer(next(blob), np.uint8)
            bytes_wrong += int((got != want.view(np.uint8)).sum()) if (
                got.size == want.nbytes) else want.nbytes
            # the plain checksum of the plainly built slot ties the
            # shortcut above to the device's sum
            slots_wrong += (last_seq(s, n, slots) is not None
                            and checksum_np(want) != int(sums[s]))
    return {"folds_wrong": folds_wrong, "slots_wrong": slots_wrong,
            "sample_bytes_wrong": bytes_wrong, "acks_wrong": acks_wrong}
