"""The plain reference of ``kv_handoff_kda_mla_hybrid``: what a decode
instance's hybrid cache must hold after eight prefill workers streamed their
sequences into it, and what its record of them must say.

Semantics (the configuration's guarantees). The pages are
``kvh_reference``'s: connection ``c`` offers its sequences in a fixed order
(the warm-up's, then the traffic file's trace), sequence ``s`` has ``ceil(
tokens / T)`` pages in every page layer, each page a seeded bank page
stamped ``(s mod 2**16, c, page layer, p)``. Each sequence also carries one
state a state layer, whose leaves (``config["state"]["leaves"]``: the KDA
layer's recurrent state as its 32-bit words, the short convolution's last
inputs as their 16-bit words) are ``States.words_of``: a seeded bank state
of the connection (``--seed``; every word a random pattern, and the classes
a float's words can fall in, NaNs, denormals, infinities and both zeros,
planted besides) with the first three words of each leaf stamped ``(s, c,
state layer)``. The cache admits a sequence whole, with one slot that no
other live sequence holds, evicts only acknowledged sequences and those
oldest first, freeing pages and slot together, and acknowledges a sequence
only once every page and every state of it is resident. numpy only; nothing
the server made is used to compute an expectation.

Compared, each with the limit 0, ``kvh_reference``'s ``log_wrong``,
``resident_wrong``, ``pages_wrong`` and ``sample_bytes_wrong`` as that file
computes them (its replay of the record and its page checksums), and:

``slots_wrong``        an admission in the record without a slot, with one
                       out of range or held by another live sequence; a
                       resident sequence whose slot is not the record's;
                       free slots that are not the slots less the live
                       sequences
``acks_wrong``         units a client counted as acknowledged that the
                       record's acknowledgements of its sequences do not
                       hold, or the other way round, per connection (a
                       sequence is its pages in every page layer and its
                       states, in units of ``message.bytes``)
``written_wrong``      page-layers and states the cache says it wrote for a
                       connection that are not its admitted sequences'
``states_wrong``       a resident sequence's state, in any state layer and
                       leaf, whose checksum as the device computed it over
                       its slot is not that of the state the seed gives
``state_sample_bytes_wrong`` differing bytes in states read back whole (a
                       seeded sample of resident sequences and state
                       layers), each against ``States.words_of``; one read
                       from another slot than the record's, or missing,
                       counts whole
"""

from __future__ import annotations

import numpy as np

from benchmarks.configs import kvh_reference as kvh
from benchmarks.configs.kvh_reference import (  # noqa: F401 (the kind's)
    Offers, Pages, geometry, lengths)

LIMITS = dict(log_wrong=0, resident_wrong=0, slots_wrong=0, acks_wrong=0,
              written_wrong=0, pages_wrong=0, sample_bytes_wrong=0,
              states_wrong=0, state_sample_bytes_wrong=0)

#: a connection's bank states are picked by this mix of sequence and layer
_MIX = (7, 3)
_BANK_SALT = 0x4B44
_SAMPLE_SALT = 0x5A3C
#: stamped words at the head of every leaf: sequence, connection, layer
_STAMPS = 3
#: planted after the stamps in every bank state, so that each class of a
#: float's words is there whatever the seed: +0, -0, the smallest and the
#: largest denormal of each sign, quiet and signalling NaNs of each sign,
#: both infinities, and 1.0
_PATTERNS = {
    4: (0x00000000, 0x80000000, 0x00000001, 0x807FFFFF, 0x007FFFFF,
        0x80000001, 0x7FC00001, 0xFFC00000, 0x7F800001, 0xFF800001,
        0x7F800000, 0xFF800000, 0x3F800000),
    2: (0x0000, 0x8000, 0x0001, 0x807F, 0x007F, 0x8001, 0x7FC1, 0xFFC0,
        0x7F81, 0xFF81, 0x7F80, 0xFF80, 0x3F80),
}


def state_geometry(config: dict) -> tuple:
    """``(state layers, leaves, slots)``: the model-layer indices that keep
    a state, ``[(name, shape, dtype), ...]`` in the message's order, and the
    slots."""
    spec = config["state"]
    leaves = [(name, tuple(int(d) for d in leaf["shape"]),
               np.dtype(leaf["dtype"])) for name, leaf in spec["leaves"].items()]
    return [int(i) for i in spec["layers"]], leaves, int(spec["slots"])


def units(config: dict) -> tuple[int, int]:
    """``(page-layer, state)`` in units of ``message.bytes``."""
    unit = int(config["message"]["bytes"])
    page = int(config["page"]["tokens"]) * int(config["page"]["width"]) * 2
    state = sum(int(np.prod(shape)) * dt.itemsize
                for _, shape, dt in state_geometry(config)[1])
    if page % unit or state % unit:
        raise ValueError(f"a page-layer ({page} B) and a state ({state} B) "
                         f"are whole units of {unit} B")
    return page // unit, state // unit


def seq_units(config: dict, pages: int) -> int:
    """A sequence of ``pages`` pages, resident: its units."""
    page, state = units(config)
    return pages * geometry(config)[0] * page + len(
        state_geometry(config)[0]) * state


def warm_units(config: dict, traffic: dict) -> int:
    """Units a connection's warm-up writes."""
    return sum(seq_units(config, int(p)) for p in traffic["warmup_pages"])


def order(config: dict, pages: int) -> list:
    """A sequence's messages after its offer, in the order they go: ``("p",
    page layer, first page, n)`` or ``("s", state layer)``. Chunks but the
    last as their page layers; the last in model layer order."""
    layers, _, _, _, chunk = geometry(config)
    stateful = set(state_geometry(config)[0])
    model = layers + len(stateful)
    out = []
    firsts = list(range(0, pages, chunk))
    for first in firsts[:-1]:
        out += [("p", layer, first, chunk) for layer in range(layers)]
    last, p, s = firsts[-1], 0, 0
    for i in range(model):
        if i in stateful:
            out.append(("s", s))
            s += 1
        else:
            out.append(("p", p, last, pages - last))
            p += 1
    return out


def _checksum(words: np.ndarray) -> np.ndarray:
    """Each row's checksum: its words times 1, 3, 5, ... summed mod
    2**32, as the handler's device program computes it over a slot."""
    w = np.arange(words.shape[-1], dtype=np.uint32) * np.uint32(2) + 1
    return (words.astype(np.uint32) * w).sum(axis=-1, dtype=np.uint32)


class States:
    """Connection ``conn``'s states: a bank of ``bank_states`` seeded random
    states, each leaf as its words, each message's picked from it and
    stamped."""

    def __init__(self, config: dict, seed: int, conn: int):
        self.layers, self.leaves, _ = state_geometry(config)
        self.conn = conn
        rng = np.random.default_rng([seed, conn, _BANK_SALT])
        n = int(config["bank_states"])
        self.bank, self.base = [], []
        for _, shape, dt in self.leaves:
            words = rng.integers(0, 1 << (8 * dt.itemsize),
                                 size=(n, int(np.prod(shape))), dtype=dt)
            planted = np.array(_PATTERNS[dt.itemsize], dt)
            words[:, _STAMPS:_STAMPS + len(planted)] = planted
            words[:, :_STAMPS] = 0
            self.bank.append(words)
            self.base.append(_checksum(words).astype(np.int64))

    def _pick(self, ordinal, layer):
        a, b = _MIX
        return (ordinal * a + layer * b) % len(self.bank[0])

    def _stamps(self, ordinal: int, layer: int, dt: np.dtype) -> list:
        mask = (1 << (8 * dt.itemsize)) - 1
        return [ordinal & mask, self.conn, layer]

    def words_of(self, ordinal: int, layer: int) -> list:
        """State layer ``layer`` of sequence ``ordinal``: one array of words
        a leaf, in the leaves' shapes."""
        out = []
        for (_, shape, dt), bank in zip(self.leaves, self.bank):
            words = bank[self._pick(ordinal, layer)].copy()
            words[:_STAMPS] = self._stamps(ordinal, layer, dt)
            out.append(words.reshape(shape))
        return out

    def checksums(self, ordinal: int) -> np.ndarray:
        """``int64[state layers, leaves]``: every state's checksum, without
        building a state."""
        layer = np.arange(len(self.layers))
        out = np.empty((len(self.layers), len(self.leaves)), np.int64)
        for j, ((_, _, dt), base) in enumerate(zip(self.leaves, self.base)):
            stamp = sum(int(v) * (2 * i + 1) for i, v in enumerate(
                self._stamps(ordinal, 0, dt)[:2]))
            out[:, j] = (base[self._pick(ordinal, layer)] + stamp
                         + 5 * layer) & 0xFFFFFFFF
        return out


def plan_sample(config: dict, traffic: dict, seed: int,
                counts: list) -> dict:
    """What to read back whole: ``pages`` as ``kvh_reference`` draws them,
    and ``states``, each ``[a, b]``: the handler takes resident sequence
    ``a`` mod their number (in admission order) and state layer ``b`` mod
    the state layers."""
    rng = np.random.default_rng([seed, _SAMPLE_SALT])
    n = int(config["audit"]["sampled_states"])
    return {"pages": kvh.plan_sample(config, traffic, seed, counts),
            "states": rng.integers(0, 1 << 31, size=(n, 2)).tolist()}


def _slots(config: dict, facts: dict) -> int:
    """Breaches of the slots: the record replayed, then the state."""
    _, _, slots = state_geometry(config)
    wrong, live = 0, {}
    for ev in facts["log"]:
        kind, gid = ev[0], int(ev[1])
        if kind == "admit":
            slot = int(ev[6]) if len(ev) > 6 else -1
            wrong += not 0 <= slot < slots or slot in live.values()
            live[gid] = slot
        elif kind in ("evict", "drop"):
            live.pop(gid, None)
    for r in facts["resident"]:
        wrong += len(r) < 7 or live.get(int(r[0])) != int(r[6])
    wrong += int(facts.get("free_slots", -1)) != slots - len(live)
    return wrong


def check(config: dict, traffic: dict, seed: int, facts: dict,
          sample: dict, blobs: list, acked: list) -> dict:
    layers, _, tokens, _, _ = geometry(config)
    state_layers, leaves, _ = state_geometry(config)
    conns = int(traffic["connections"])
    n_pages = len(facts["sampled"])
    pages = kvh.check(config, traffic, seed, facts, sample["pages"],
                      blobs[:n_pages], [0] * conns)
    _, live, _, admitted = kvh._replay(config, traffic, facts)

    # acknowledgements and writes, per connection, against the record
    acked_units = [0] * conns
    written = [[0, 0] for _ in range(conns)]
    for ev in facts["log"]:
        if ev[0] == "ack" and int(ev[1]) in admitted:
            conn, _, n = admitted[int(ev[1])]
            if 0 <= conn < conns:
                acked_units[conn] += seq_units(config, n)
    for conn, _, n in admitted.values():
        if 0 <= conn < conns:
            written[conn][0] += n * layers
            written[conn][1] += len(state_layers)
    window = [int(a) - int(traffic["warmup_messages"]) for a in acked]
    acks_wrong = sum(abs(w + warm_units(config, traffic) - got)
                     for w, got in zip(window, acked_units))
    said = list(zip(facts["written"], facts.get("states_written", [])))
    written_wrong = sum(abs(int(p) - x[0]) + abs(int(s) - x[1])
                        for (p, s), x in zip(said, written))
    written_wrong += abs(len(said) - conns)

    # every resident sequence's states, by their checksums on the device
    banks: dict = {}

    def bank(conn: int) -> States:
        if conn not in banks:
            banks[conn] = States(config, seed, conn)
        return banks[conn]

    states_wrong = 0
    for gid, sums in zip(facts["state_gids"], facts["state_sums"]):
        s = live.get(int(gid))
        got = np.asarray(sums, np.int64)
        if s is None or not 0 <= s[0] < conns or got.shape != (
                len(state_layers), len(leaves)):
            states_wrong += got.size or 1
            continue
        states_wrong += int((got != bank(s[0]).checksums(s[1])).sum())
    states_wrong += len(set(live) - {int(g) for g in facts["state_gids"]})

    # states read back whole
    nbytes = sum(int(np.prod(shape)) * dt.itemsize
                 for _, shape, dt in leaves)
    got_blobs = blobs[n_pages:]
    where = facts["sampled_states"]
    want = len(sample["states"]) if live else 0
    sample_wrong = abs(want - len(got_blobs)) * nbytes
    slots = {int(r[0]): int(r[6]) for r in facts["resident"] if len(r) > 6}
    for got, at in zip(got_blobs, where):
        gid, layer, slot = (int(v) for v in at)
        s = live.get(gid)
        if (s is None or not 0 <= s[0] < conns
                or not 0 <= layer < len(state_layers)
                or slots.get(gid) != slot or len(got) != nbytes):
            sample_wrong += nbytes
            continue
        exp = b"".join(w.tobytes() for w in bank(s[0]).words_of(s[1], layer))
        sample_wrong += int((np.frombuffer(got, np.uint8)
                             != np.frombuffer(exp, np.uint8)).sum())
    return dict(log_wrong=pages["log_wrong"],
                resident_wrong=pages["resident_wrong"],
                slots_wrong=_slots(config, facts), acks_wrong=int(acks_wrong),
                written_wrong=int(written_wrong),
                pages_wrong=pages["pages_wrong"],
                sample_bytes_wrong=pages["sample_bytes_wrong"],
                states_wrong=int(states_wrong),
                state_sample_bytes_wrong=int(sample_wrong))
