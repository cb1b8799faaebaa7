"""The plain reference of ``tensor_fanin_batch_4m``: what a pool filled a
batch at a time must hold, whatever batches the messages rode in.

Semantics (the configuration's guarantees): connection ``c`` sent messages
``0 .. n_c - 1`` in order, and the server's one reply a stream acknowledged
them. The consumer took whole batches of at most ``max_rows`` rows, request
rows first and zero pad rows after, and wrote batch ``b`` into batch slot ``b
mod slots``; it kept a log of every batch (its row count, and the two stamp
words ``(sequence number, connection)`` of each of its rows). Which message
rode in which batch depends on arrival and cannot be known beforehand: it is
read from that log, and the log is then held to what it MUST be (a valid
interleaving of the eight streams), and the device's folds and the pool's
content to the log and to the seed. From the seed every message of every
connection is regenerated here. numpy and the benchmark's payload generator
only (and ``init_words_np``, the seeded pool's formula): nothing else of the
handler, and nothing the server made, is used to compute an expectation.

Compared, each with the limit 0 (exact comparisons):

``acks_wrong``          messages a client holds acknowledged that the server
                        does not count, and the reverse
``log_wrong``           faults of the batch log as an interleaving: a batch
                        of no row or of more than ``max_rows``; a request
                        row whose connection does not exist or whose
                        sequence number is not the next of its connection (a
                        row left out, stacked twice, or out of order); a pad
                        row that is not zero or not at its batch's end;
                        messages below a connection's acknowledged count that
                        no batch holds; a log that lost batches
``folds_wrong``         connections whose device fold over ALL their messages
                        differs from the fold of the regenerated messages in
                        order
``slots_wrong``         pool rows whose device checksum is not that of the
                        message the log puts there (0 for a pad row; the
                        seeded words for a slot no batch reached, on a
                        seeded sample of those)
``sample_bytes_wrong``  differing bytes among the sampled batch slots read
                        back whole, against their rows' regenerated messages
"""

from __future__ import annotations

import numpy as np

from benchmarks.handlers.pool_sink import init_words_np
from benchmarks.harness.payloads import Bank, checksum_np, fold

LIMITS = {"acks_wrong": 0, "log_wrong": 0, "folds_wrong": 0,
          "slots_wrong": 0, "sample_bytes_wrong": 0}
#: rows of never-written slots whose checksum is computed from the seed
UNTOUCHED_SAMPLE = 32


def geometry(config: dict, traffic: dict) -> tuple[int, int, int, int]:
    """``(connections, batch slots, rows a batch, words a row)``."""
    words = int(np.prod(config["message"]["shape"]))
    max_rows = int(config["batch"]["max_rows"])
    return (int(traffic["connections"]),
            int(config["pool"]["bytes"]) // (words * 4 * max_rows),
            max_rows, words)


def plan_sample(config: dict, traffic: dict, seed: int,
                counts: list[int]) -> list[int]:
    """Batch slots to read back whole, drawn from the seed: as many as the
    configuration's ``audit.sampled_slots`` (or all there are)."""
    _, slots, _, _ = geometry(config, traffic)
    want = min(int(config["audit"]["sampled_slots"]), slots)
    rng = np.random.default_rng([seed, 0xFA9133])
    return sorted(int(s) for s in rng.choice(slots, want, replace=False))


def last_batch(slot: int, batches: int, slots: int) -> int | None:
    """The newest batch below ``batches`` that was written into ``slot``."""
    if slot >= batches:
        return None
    return slot + (batches - 1 - slot) // slots * slots


def read_log(facts: dict, conns: int, max_rows: int):
    """``(faults, rows)``: the log's faults as an interleaving, and per
    batch its request rows as ``(seq, conn)`` pairs (None for a row the log
    cannot name)."""
    n_rows, stamps = facts["log_rows"], facts["log_stamps"]
    faults = abs(int(facts["batches"]) - len(n_rows))
    nxt = [0] * conns
    rows = []
    for n, batch in zip(n_rows, stamps):
        n = int(n)
        faults += not 1 <= n <= max_rows
        n = min(max(n, 0), max_rows)
        named = []
        for seq, c in batch[:n]:
            if not 0 <= c < conns:
                faults += 1
                named.append(None)
                continue
            faults += seq != nxt[c] & 0xFFFFFFFF
            nxt[c] = seq + 1
            named.append((int(seq), int(c)))
        faults += sum(tuple(st) != (0, 0) for st in batch[n:])
        rows.append(named)
    faults += sum(abs(nxt[c] - int(facts["n"][c])) for c in range(conns))
    return faults, rows


def check(config: dict, traffic: dict, seed: int, facts: dict,
          sample: list[int], blobs: list[bytes], acked: list[int]) -> dict:
    """The numbers compared, from what the device reported after the window
    (``facts``: per connection ``n`` and ``acc``; ``batches``, ``log_rows``,
    ``log_stamps``; ``row_sums`` of every pool row; ``blobs``: the sampled
    batch slots' bytes in ``sample`` order) and what each client holds
    acknowledged (``acked``)."""
    conns, slots, max_rows, words = geometry(config, traffic)
    banks = [Bank(seed, c, config) for c in range(conns)]
    sums = [b.checksums() for b in banks]
    batches = len(facts["log_rows"])
    log_wrong, rows = read_log(facts, conns, max_rows)

    acks_wrong = folds_wrong = 0
    for c in range(conns):
        n = int(facts["n"][c])
        acks_wrong += abs(n - int(acked[c]))
        acc = 0
        for k in range(n):
            acc = fold(acc, sums[c].of(k))
        folds_wrong += acc != int(facts["acc"][c])

    def row_words(slot: int, i: int) -> np.ndarray:
        """What row ``i`` of ``slot`` must hold, as 32-bit words."""
        b = last_batch(slot, batches, slots)
        if b is None:
            return init_words_np(seed, 0, (slot * max_rows + i) * words,
                                 words)
        if i >= len(rows[b]) or rows[b][i] is None:
            return np.zeros(words, np.uint32)
        seq, c = rows[b][i]
        return banks[c].message_copy(seq).reshape(-1).view(np.uint32)

    row_sums = facts["row_sums"]
    if len(row_sums) != slots or any(len(r) != max_rows for r in row_sums):
        raise ValueError(f"{len(row_sums)} slots of row sums for {slots}")
    slots_wrong = 0
    untouched = []
    for s in range(slots):
        b = last_batch(s, batches, slots)
        if b is None:
            untouched += [(s, i) for i in range(max_rows)]
            continue
        for i in range(max_rows):
            named = rows[b][i] if i < len(rows[b]) else None
            want = sums[named[1]].of(named[0]) if named else 0
            slots_wrong += want != int(row_sums[s][i])
    if len(untouched) > UNTOUCHED_SAMPLE:
        rng = np.random.default_rng([seed, 0x51075])
        untouched = [untouched[int(j)] for j in sorted(rng.choice(
            len(untouched), UNTOUCHED_SAMPLE, replace=False))]
    for s, i in untouched:
        slots_wrong += checksum_np(row_words(s, i)) != int(row_sums[s][i])

    bytes_wrong = 0
    for s, blob in zip(sample, blobs):
        got = np.frombuffer(blob, np.uint8)
        if got.size != max_rows * words * 4:
            bytes_wrong += max_rows * words * 4
            continue
        for i in range(max_rows):
            want = row_words(s, i)
            bytes_wrong += int((got[i * words * 4:(i + 1) * words * 4]
                                != want.view(np.uint8)).sum())
            # the plain checksum of the plainly built row ties the
            # shortcut above to the device's sum
            slots_wrong += checksum_np(want) != int(row_sums[s][i])
    bytes_wrong += max(0, len(sample) - len(blobs)) * max_rows * words * 4
    return {"acks_wrong": acks_wrong, "log_wrong": int(log_wrong),
            "folds_wrong": int(folds_wrong), "slots_wrong": int(slots_wrong),
            "sample_bytes_wrong": bytes_wrong}
