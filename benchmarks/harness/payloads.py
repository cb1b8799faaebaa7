"""Message payloads from the seed: what the clients send and the reference
expects. numpy only; nothing of the program is imported here.

A connection's messages come from a *bank* of seeded standard-normal tensors,
made once in set-up so that the generator stays cheaper than the path it
feeds. Message ``seq`` of connection ``conn`` is bank entry ``seq mod bank``
with its first two 32-bit words replaced by ``seq`` and ``conn``: every
message differs from every other, and a message in the wrong slot or on the
wrong connection shows. The stamp goes into the entry itself only while the
client has let go of it (see ``Bank.message``).
"""

from __future__ import annotations

import sys

import numpy as np


def checksum_np(x) -> int:
    """Position-weighted sum of ``x``'s 32-bit words, mod 2**32 (odd weights:
    any one changed word changes it). The handler's device fold is the same
    sum in jnp; this is the plain one."""
    u = np.ascontiguousarray(x).reshape(-1).view(np.uint32)
    w = np.arange(u.size, dtype=np.uint32) * np.uint32(2) + np.uint32(1)
    return int((u * w).sum(dtype=np.uint32))


def fold(acc: int, checksum: int) -> int:
    return (acc * 31 + checksum) & 0xFFFFFFFF


class Bank:
    def __init__(self, seed: int, conn: int, config: dict):
        msg = config["message"]
        if np.dtype(msg["dtype"]).itemsize != 4:
            raise ValueError("payload stamps need a 32-bit dtype")
        self.conn = conn
        rng = np.random.default_rng([seed, conn])
        self.entries = []
        for _ in range(int(config["bank_messages"])):
            e = rng.standard_normal(tuple(msg["shape"]), dtype=np.float32)
            e = e.astype(msg["dtype"], copy=False)
            e.reshape(-1).view(np.uint32)[:2] = 0
            self.entries.append(e)
        del e
        self.copies = 0
        self._idle_refs = self._refs(0)  # with nobody else holding it

    def _refs(self, k: int) -> int:
        return sys.getrefcount(self.entries[k])

    def _stamp(self, e: np.ndarray, seq: int) -> np.ndarray:
        words = e.reshape(-1).view(np.uint32)
        words[0] = seq & 0xFFFFFFFF
        words[1] = self.conn
        return e

    def message(self, seq: int) -> np.ndarray:
        """Message ``seq``. Stamped in place in its bank entry while nobody
        else holds the entry (the array, a view or a memoryview of it all
        count as references); a client that still holds the entry from
        ``bank`` messages ago gets a stamped copy instead, counted in
        ``copies``, so a stamp is never written under a message in flight."""
        k = seq % len(self.entries)
        if self._refs(k) > self._idle_refs:
            self.copies += 1
            return self._stamp(self.entries[k].copy(), seq)
        return self._stamp(self.entries[k], seq)

    def message_copy(self, seq: int) -> np.ndarray:
        return self._stamp(self.entries[seq % len(self.entries)].copy(), seq)

    def checksums(self) -> "MessageChecksums":
        return MessageChecksums(self)


class MessageChecksums:
    """``of(seq)`` without building the message: the unstamped entry's
    checksum plus the two stamp words times their weights (1 and 3). Exact
    integer arithmetic; the tests hold it to ``checksum_np`` of the built
    message."""

    def __init__(self, bank: Bank):
        self.conn = bank.conn
        self.base = []
        for e in bank.entries:
            words = e.reshape(-1).view(np.uint32)
            keep = words[:2].copy()
            words[:2] = 0
            self.base.append(checksum_np(e))
            words[:2] = keep

    def of(self, seq: int) -> int:
        return (self.base[seq % len(self.base)] + (seq & 0xFFFFFFFF)
                + 3 * self.conn) & 0xFFFFFFFF
