"""The paged KV cache with state slots (``tpurpc/serving/pages.py``, a
cache given ``state_layers``) on the CPU, at a model of five layers (three
keep a state, two keep pages), 4-token pages and states of two leaves:
admission gated by slots alone, by pages alone and by both; FIFO eviction
freeing a sequence's slot and pages together; the acknowledgement waiting
for the last write of either kind; the last chunk's model layer order; a
stream that ends inside a sequence giving its slot back behind its own
writes; a float state refused; every class of 32-bit and 16-bit pattern
stored bit-exact; many streams at once; the cache over RPC; and a cache
given no state layers keeping exactly the page cache's programs, record and
counters."""

import sys
import threading
import time

import ml_dtypes
import numpy as np
import pytest

from tpurpc.obs import lens, metrics
from tpurpc.serving.pages import KvPageCache

L, T, C, W = 2, 4, 4, 8        # page layers, tokens a page, chunk pages, width
STATE_LAYERS = (0, 1, 3)       # of a model of 5: layers 2 and 4 keep pages
LEAVES = {"state": ((2, 4, 8), np.uint32), "conv": ((3, 8), np.uint16)}
S = len(STATE_LAYERS)
BF16 = np.dtype(ml_dtypes.bfloat16)


def counters(*names):
    snap = metrics.registry().counters_snapshot()
    return [snap.get(n, 0) for n in names]


def cache(pages=40, slots=4, state_layers=STATE_LAYERS):
    return KvPageCache(L, pages, T, W, chunk_pages=C,
                       state_layers=state_layers, state_leaves=LEAVES,
                       slots=slots)


class Seq:
    """A sequence's words: pages ``uint16[L, n, T * W]`` and one state a
    state layer, seeded."""

    def __init__(self, seed, n_pages, states=S):
        rng = np.random.default_rng(seed)
        self.n = n_pages
        self.pages = rng.integers(0, 1 << 16, size=(L, n_pages, T * W),
                                  dtype=np.uint16)
        self.states = [
            {name: rng.integers(0, 1 << (8 * np.dtype(dt).itemsize),
                                size=shape, dtype=dt)
             for name, (shape, dt) in LEAVES.items()}
            for _ in range(states)]

    def messages(self, state_layers=STATE_LAYERS):
        """The messages in the protocol's order, as trees: page messages as
        committed device arrays, state messages as dicts of them."""
        import jax

        dev = jax.devices()[0]
        out = []
        firsts = list(range(0, self.n, C))
        for first in firsts[:-1]:
            for layer in range(L):
                out.append({"pages": jax.device_put(self._pages(
                    layer, first), dev)})
        p = s = 0
        for i in range(L + len(state_layers)):
            if i in state_layers:
                out.append({k: jax.device_put(v, dev)
                            for k, v in self.states[s].items()})
                s += 1
            else:
                out.append({"pages": jax.device_put(self._pages(
                    p, firsts[-1]), dev)})
                p += 1
        return out

    def _pages(self, layer, first):
        n = min(C, self.n - first)
        return self.pages[layer, first:first + n].reshape(n, T, W)


def feed(c, seq, words, hold=False):
    """Hand the sequence's messages over; with ``hold`` all but the last,
    which is returned."""
    msgs = words.messages(c.state_layers)
    for tree in msgs[:-1]:
        assert c.submit(seq, tree.get("pages", tree)) is False
    last = msgs[-1].get("pages", msgs[-1])
    if hold:
        return last
    assert c.submit(seq, last) is True
    return None


def write_all(c, key, ordinal, words, hold=False):
    seq = c.admit(key, ordinal, words.n * T)
    return seq, feed(c, seq, words, hold)


def held(c, seq, words):
    """What the cache holds of the sequence: its pages and, from its slot,
    its states."""
    c.sync()
    pages = np.asarray(c.store)[:, seq.pages].reshape(L, len(seq.pages), -1)
    states = [{name: np.asarray(store)[s, seq.slot]
               for name, store in zip(LEAVES, c.states)}
              for s in range(len(c.state_layers))]
    return pages, states


def assert_holds(c, seq, words):
    pages, states = held(c, seq, words)
    assert np.array_equal(pages, words.pages)
    for got, want in zip(states, words.states):
        for name in LEAVES:
            assert got[name].dtype == want[name].dtype
            assert np.array_equal(got[name], want[name])


def events(c, kind):
    return [e[1] for e in c.snapshot()["log"] if e[0] == kind]


def stream(words_list, state_layers=STATE_LAYERS):
    for s, words in enumerate(words_list):
        yield {"offer": np.array([s, words.n * T], np.int32)}
        yield from words.messages(state_layers)


@pytest.mark.parametrize("n_pages", [1, 3, 4, 5, 9])
def test_the_last_chunk_goes_in_model_layer_order(n_pages):
    """Every chunk but the last as its page layers, the last in model layer
    order: each state lands in the sequence's slot at its state layer and
    each page where its table says; the rest of the stores do not move."""
    c = cache()
    try:
        before = [np.asarray(s).copy() for s in c.states]
        words = Seq(n_pages, n_pages)
        seq = c.admit(0, 7, n_pages * T)
        chunks = -(-n_pages // C)
        assert seq.messages == chunks * L + S
        kinds = [c.is_state(seq, k) for k in range(seq.messages)]
        assert kinds == [False] * (chunks - 1) * L + [
            i in STATE_LAYERS for i in range(L + S)]
        feed(c, seq, words)
        assert seq.future.result(timeout=30) == {"seq": 7, "pages": n_pages}
        assert_holds(c, seq, words)
        for got, was in zip(c.states, before):
            got = np.asarray(got).copy()
            got[:, seq.slot] = was[:, seq.slot]
            assert np.array_equal(got, was)
    finally:
        c.close()


def test_a_message_of_the_other_kind_fails_the_stream():
    c = cache()
    try:
        words = Seq(1, 5)
        trees = list(stream([words]))
        # the last chunk opens with state layer 0: a page there is refused
        last = 1 + L
        bad = trees[:last] + [trees[2]] + trees[last + 1:]
        with pytest.raises(ValueError, match="state message 2 .* not"):
            list(c.receiver(0)(iter(bad)))
        # and a state where a page is due
        bad = trees[:1] + [trees[last]] + trees[2:]
        with pytest.raises(ValueError, match="still owes"):
            list(c.receiver(1)(iter(bad)))
        seq = c.admit(2, 0, 5 * T)
        with pytest.raises(ValueError, match="page message 0 .* not uint16"):
            c.submit(seq, trees[last])
    finally:
        c.close()


def test_a_state_sent_as_floats_is_refused():
    """A state's words travel; a float32 or bfloat16 leaf is refused, as a
    bf16 page is, having taken nothing; so is a leaf of another shape or
    name. A cache cannot be built with a float leaf."""
    c = cache()
    try:
        words = Seq(2, 2)
        trees = list(stream([words]))
        state = trees[1]
        for leaf, cast in (("state", np.float32), ("conv", BF16)):
            bad = dict(state, **{leaf: np.asarray(state[leaf]).view(cast)})
            taken = []
            seq = c.admit(9, 0, 2 * T)
            with pytest.raises(ValueError, match="a float never travels"):
                c.submit(seq, bad, lambda: taken.append(1))
            assert not taken and seq.sent == 0
            c.drop(seq)
            with pytest.raises(ValueError, match="a float never travels"):
                list(c.receiver(3)(iter([trees[0], bad])))
        wrong = {"state": state["state"][:1], "conv": state["conv"]}
        with pytest.raises(ValueError, match="not"):
            list(c.receiver(4)(iter([trees[0], wrong])))
        with pytest.raises(ValueError, match="not"):
            list(c.receiver(5)(iter([trees[0], {"state": state["state"]}])))
    finally:
        c.close()
    with pytest.raises(ValueError, match="unsigned words"):
        KvPageCache(L, 8, T, W, chunk_pages=C, state_layers=STATE_LAYERS,
                    state_leaves={"state": ((2, 4, 8), np.float32)}, slots=2)


def test_every_class_of_pattern_is_stored_bit_for_bit():
    """NaNs of both signs, quiet and signalling, denormals, infinities and
    both zeros, as 32-bit and as 16-bit words."""
    p32 = np.array([0x00000000, 0x80000000, 0x00000001, 0x807FFFFF,
                    0x007FFFFF, 0x80000001, 0x7FC00001, 0xFFC00000,
                    0x7F800001, 0xFF800001, 0x7F800000, 0xFF800000,
                    0x3F800000, 0xFFFFFFFF, 0x7FFFFFFF], np.uint32)
    p16 = np.array([0x0000, 0x8000, 0x0001, 0x807F, 0x7FC1, 0xFF81,
                    0x7F81, 0x7F80, 0xFF80, 0xFFFF, 0x3F80], np.uint16)
    c = cache()
    try:
        words = Seq(3, 6)
        for s, state in enumerate(words.states):
            state["state"] = np.resize(np.roll(p32, s), (2, 4, 8))
            state["conv"] = np.resize(np.roll(p16, s), (3, 8))
        seq, _ = write_all(c, 0, 0, words)
        seq.future.result(timeout=30)
        assert_holds(c, seq, words)
    finally:
        c.close()


def test_admission_gated_by_slots_alone():
    """Pages to spare, two slots held by unacknowledged sequences: a third
    waits (counted as waiting for slots) until one is acknowledged, then
    takes its slot by evicting it."""
    c = cache(pages=40, slots=2)
    try:
        slots0, pages0 = counters("kv_admit_waits_slots",
                                  "kv_admit_waits_pages")
        a, a_last = write_all(c, 0, 0, Seq(1, 2), hold=True)
        b, b_last = write_all(c, 1, 0, Seq(2, 2), hold=True)
        assert c.free_slots() == 0 and c.free_pages() == 36
        got = []
        t = threading.Thread(target=lambda: got.append(c.admit(2, 0, T)))
        t.start()
        time.sleep(0.3)
        assert t.is_alive() and not got
        c.submit(a, a_last)
        a.future.result(timeout=30)
        t.join(timeout=30)
        assert not t.is_alive() and got[0].slot == a.slot
        assert events(c, "evict") == [a.gid]
        assert counters("kv_admit_waits_slots", "kv_admit_waits_pages") == [
            slots0 + 1, pages0]
        c.submit(b, b_last)
        b.future.result(timeout=30)
    finally:
        c.close()


def test_admission_gated_by_pages_alone():
    """Slots to spare, the pages held: the wait is counted as one for
    pages, and ends at the acknowledgement that frees enough."""
    c = cache(pages=8, slots=4)
    try:
        slots0, pages0 = counters("kv_admit_waits_slots",
                                  "kv_admit_waits_pages")
        a, a_last = write_all(c, 0, 0, Seq(1, 6), hold=True)
        got = []
        t = threading.Thread(target=lambda: got.append(c.admit(1, 0, 4 * T)))
        t.start()
        time.sleep(0.3)
        assert t.is_alive() and c.free_slots() == 3
        c.submit(a, a_last)
        a.future.result(timeout=30)
        t.join(timeout=30)
        assert not t.is_alive() and len(got) == 1
        assert counters("kv_admit_waits_slots", "kv_admit_waits_pages") == [
            slots0, pages0 + 1]
    finally:
        c.close()


def test_admission_gated_by_both_waits_for_both():
    """Two slots and eight pages, all held: an offer of six pages that
    finds both short waits (counted once, for slots) past the
    acknowledgement that frees a slot and two pages, and is admitted at the
    one that frees the rest, evicting both, oldest first."""
    c = cache(pages=8, slots=2)
    try:
        slots0, = counters("kv_admit_waits_slots")
        a, a_last = write_all(c, 0, 0, Seq(1, 2), hold=True)
        b, b_last = write_all(c, 1, 0, Seq(2, 6), hold=True)
        got = []
        t = threading.Thread(target=lambda: got.append(c.admit(2, 0, 6 * T)))
        t.start()
        time.sleep(0.2)
        c.submit(a, a_last)
        a.future.result(timeout=30)
        time.sleep(0.3)
        assert t.is_alive() and not got    # a slot, but two pages of six
        c.submit(b, b_last)
        b.future.result(timeout=30)
        t.join(timeout=30)
        assert not t.is_alive()
        assert events(c, "evict") == [a.gid, b.gid]
        assert got[0].slot in (a.slot, b.slot)
        assert len(got[0].pages) == 6
        assert set(got[0].pages) <= set(a.pages + b.pages)
        assert counters("kv_admit_waits_slots")[0] == slots0 + 1
    finally:
        c.close()


def test_eviction_frees_slot_and_pages_together_oldest_first():
    c = cache(pages=12, slots=3)
    try:
        evicted0, = counters("kv_slots_evicted")
        seqs = []
        for k in range(3):
            seq, _ = write_all(c, k, 0, Seq(k, 3))
            seq.future.result(timeout=30)
            seqs.append(seq)
        assert c.free_slots() == 0 and c.free_pages() == 3
        # a fourth takes the oldest's slot and pages, nothing else moves
        d_words = Seq(9, 3)
        d, _ = write_all(c, 0, 1, d_words)
        d.future.result(timeout=30)
        assert events(c, "evict") == [seqs[0].gid]
        assert d.slot == seqs[0].slot
        snap = c.snapshot()
        assert snap["free_slots"] == 0 and snap["free"] == 3
        assert [r[6] for r in snap["resident"]] == [
            seqs[1].slot, seqs[2].slot, d.slot]
        admit = [e for e in snap["log"] if e[0] == "admit"][-1]
        assert admit[6] == d.slot and len(admit) == 7
        assert_holds(c, d, d_words)
        # a sequence of twelve pages evicts all three left, pages and slots
        e, _ = write_all(c, 1, 1, Seq(10, 12))
        e.future.result(timeout=30)
        assert events(c, "evict") == [s.gid for s in seqs] + [d.gid]
        assert c.free_slots() == 2 and c.free_pages() == 0
        assert counters("kv_slots_evicted")[0] == evicted0 + 4
        total, live = metrics.registry().metrics()["kv_slots_free"].collect()
        assert live >= 1 and total >= 2
    finally:
        c.close()


@pytest.mark.parametrize("layers", [STATE_LAYERS, (0, 2, 4)])
def test_the_acknowledgement_waits_for_the_last_write_of_either_kind(layers):
    """With the last message a page (model layer 4 keeps pages) or a state
    (it keeps a state), the writing thread held on that last write: every
    other write is dispatched, and still no acknowledgement."""
    c = cache(state_layers=layers)
    gate, reached = threading.Event(), threading.Event()
    last_kind = "state" if 4 in layers else "page"
    for name, kind in (("_dispatch", "page"), ("_dispatch_state", "state")):
        real = getattr(c, name)

        def held_back(seq, x, ids, real=real, kind=kind):
            if kind == last_kind and seq.done == seq.messages - 1:
                reached.set()
                gate.wait(30)
            real(seq, x, ids)
        setattr(c, name, held_back)
    try:
        words = Seq(4, 5)
        seq, _ = write_all(c, 0, 0, words)
        assert reached.wait(30)
        time.sleep(0.2)
        assert seq.done == seq.messages - 1 and not seq.future.done()
        assert not c.snapshot()["acked"]
        gate.set()
        assert seq.future.result(timeout=30) == {"seq": 0, "pages": 5}
        assert c.snapshot()["acked"] == [seq.gid]
        assert_holds(c, seq, words)
    finally:
        gate.set()
        c.close()


def test_a_stream_that_ends_inside_a_sequence_gives_its_slot_back_behind_its_writes():
    c = cache(pages=10, slots=2)
    gate = threading.Event()
    real = c._dispatch_state

    def slow(seq, xs, ids):
        gate.wait(30)
        real(seq, xs, ids)

    c._dispatch_state = slow
    try:
        words = Seq(1, 6)
        trees = list(stream([words]))
        cut = trees[:1 + L + 2]   # the offer, chunk 0, two of the states
        assert list(c.receiver(0)(iter(cut))) == []
        time.sleep(0.2)
        # the writer is held on the first state: the slot is not back yet
        assert c.free_slots() == 1 and c.free_pages() == 4
        gate.set()
        c.sync()
        assert c.free_slots() == 2 and c.free_pages() == 10
        assert [e[0] for e in c.snapshot()["log"]] == ["admit", "drop"]
    finally:
        gate.set()
        c.close()


def test_state_writes_are_dispatched_by_the_cache_s_own_thread():
    """A state message goes to the writing thread like a page; its span
    names the handler's message, the state layer and the slot; its credit
    comes back once its write is dispatched."""
    class Lease:
        def __init__(self, log):
            self.log = log

        def release(self):
            self.log.append(("release", threading.current_thread().name))

    c = cache()
    log = []
    real = c._dispatch_state

    def spy(seq, xs, ids):
        log.append(("state", threading.current_thread().name, seq.states_done,
                    ids))
        real(seq, xs, ids)

    c._dispatch_state = spy
    try:
        msgs0, bytes0, n0, ops0 = counters("kv_messages_written",
                                           "lens_kv_state_write_bytes",
                                           "kv_state_bytes_written",
                                           "lens_kv_state_write_ops")
        words = Seq(5, 2)
        seq = c.admit(0, 0, 2 * T)
        for tree in words.messages():
            with lens.stage("srv_handler", call=41, seq=2):
                c.submit(seq, tree.get("pages", tree), lambda: [Lease(log)])
        seq.future.result(timeout=30)
        states = [e for e in log if e[0] == "state"]
        assert states == [("state", "tpurpc-kv-write", s, (41, 2))
                          for s in range(S)]
        assert all(e[1] == "tpurpc-kv-write" for e in log)
        assert [e[0] for e in log].count("release") == L + S
        got = counters("kv_messages_written", "lens_kv_state_write_bytes",
                       "kv_state_bytes_written", "lens_kv_state_write_ops")
        assert got[:3] == [msgs0 + L + S, bytes0 + S * c.state_bytes,
                           n0 + S * c.state_bytes]
        # an op a dispatch: a run of states is one
        assert ops0 + 1 <= got[3] <= ops0 + S
        assert c.state_bytes == 2 * 4 * 8 * 4 + 3 * 8 * 2
    finally:
        c.close()


def test_many_streams_at_once_lose_no_write():
    """Twelve streams, more than the cores, through one cache of four slots
    with the interpreter switching threads every few microseconds: every
    acknowledgement in its stream's order, every page-layer and state
    counted once, and every resident sequence holds exactly its words."""
    c = cache(pages=120, slots=4)
    plans = {k: [Seq(100 * k + s, 1 + (7 * k + 3 * s) % 11)
                 for s in range(3)] for k in range(12)}
    acks, errors = {}, []

    def run(k):
        try:
            acks[k] = [f.result(timeout=60)
                       for f in c.receiver(k)(stream(plans[k]))]
        except Exception as exc:  # the assertion below says which
            errors.append(exc)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=run, args=(k,)) for k in plans]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors and not any(t.is_alive() for t in threads)
        for k, ws in plans.items():
            assert acks[k] == [{"seq": s, "pages": w.n}
                               for s, w in enumerate(ws)]
        snap = c.snapshot()
        assert snap["written"] == {k: L * sum(w.n for w in ws)
                                   for k, ws in plans.items()}
        assert snap["states_written"] == {k: S * 3 for k in plans}
        assert len({r[6] for r in snap["resident"]}) == len(snap["resident"])
        assert snap["free_slots"] == 4 - len(snap["resident"])
        for gid, key, ordinal, _, _, table, slot in snap["resident"]:
            seq = types_seq(gid, table, slot)
            assert_holds(c, seq, plans[key][ordinal])
    finally:
        sys.setswitchinterval(interval)
        c.close()


def types_seq(gid, table, slot):
    import types

    return types.SimpleNamespace(gid=gid, pages=table, slot=slot)


def test_nothing_compiles_after_the_warm_up():
    from tpurpc.utils import jaxenv

    jaxenv.count_compiles()
    c = cache(pages=64)
    try:
        compiles0, = counters("xla_compiles")
        futures = []
        for k, n in enumerate([1, 2, 3, 4, 5, 7, 9]):
            futures += list(c.receiver(k % 2)(stream([Seq(k, n)])))
        for f in futures:
            f.result(timeout=30)
        assert counters("xla_compiles")[0] == compiles0
    finally:
        c.close()


def test_a_cache_without_state_is_the_page_cache():
    """Given no state layers: the programs are the scatter's alone, one a
    page count, over the device state of a cursor and a table; a sequence
    takes chunks x layers messages; the record and the snapshot are the
    page cache's, and no counter of the slots moves, through a write, an
    eviction and a wait."""
    names = ("kv_states_written", "kv_state_bytes_written",
             "kv_slots_evicted", "kv_admit_waits_slots",
             "kv_admit_waits_pages", "lens_kv_state_write_ops")
    before = counters(*names)
    c = KvPageCache(3, 10, T, W, chunk_pages=C)
    try:
        assert c.states == () and c._state_write is None and c.slots == 0
        assert sorted(c._programs) == list(range(1, C + 1))
        assert all("tpurpc_kv_pages" in p.as_text()
                   for p in c._programs.values())
        a = c.admit(0, 0, 6 * T)
        assert a.slot is None and a.messages == 2 * 3
        assert c._table(a).shape == (1 + c._table_len,)
        with pytest.raises(ValueError, match="page message 0 .* not uint16"):
            c.submit(a, {"state": np.zeros((2, 4, 8), np.uint32)})
        rng = np.random.default_rng(1)
        words = rng.integers(0, 1 << 16, size=(3, 6, T * W), dtype=np.uint16)
        for first in (0, C):
            for layer in range(3):
                c.submit(a, words[layer, first:first + C].reshape(-1, T, W))
        a.future.result(timeout=30)
        c.sync()
        got = np.asarray(c.store)[:, a.pages].reshape(3, 6, -1)
        assert np.array_equal(got, words)
        b = c.admit(1, 0, 8 * T)          # evicts the acknowledged first
        waiter = threading.Thread(target=lambda: c.admit(2, 0, 4 * T))
        waiter.start()
        time.sleep(0.2)
        assert waiter.is_alive()          # 2 pages free of 4
        c.drop(b)
        waiter.join(timeout=30)
        assert not waiter.is_alive()
        snap = c.snapshot()
        assert set(snap) == {"resident", "acked", "free", "events", "log",
                             "written"}
        assert all(len(r) == 6 for r in snap["resident"])
        assert [e[0] for e in snap["log"]] == ["admit", "ack", "evict",
                                               "admit", "drop", "admit"]
        assert [len(e) for e in snap["log"] if e[0] == "admit"] == [6] * 3
        assert counters(*names) == before
    finally:
        c.close()


# -- over RPC, two connections ------------------------------------------------------

@pytest.fixture
def tpu_platform(monkeypatch):
    from tpurpc.utils import config as config_mod

    monkeypatch.setenv("GRPC_PLATFORM_TYPE", "RDMA_TPU")
    monkeypatch.setenv("TPURPC_HBM_RING_SIZE_KB", "64")
    monkeypatch.setenv("TPURPC_RENDEZVOUS_MIN_KB", "1")
    config_mod.set_config(None)
    yield
    monkeypatch.undo()
    config_mod.set_config(None)


def test_two_prefill_workers_over_rpc(tpu_platform):
    """Two connections stream three sequences each, pages and states, into
    ONE cache with six slots through ``add_tensor_method(device=True)``:
    a state message lands as its two leaves of words, every
    acknowledgement comes once its sequence is resident, in order, and the
    state hop moves."""
    from tpurpc.jaxshim import TensorClient, add_tensor_method
    from tpurpc.rpc.channel import Channel
    from tpurpc.rpc.server import Server

    c = cache(pages=200, slots=6)
    srv = Server(max_workers=8)
    for k in range(2):
        add_tensor_method(srv, f"KvHandoff{k}", c.receiver(k),
                          kind="stream_stream", device=True)
    srv.start()
    port = srv.add_insecure_port("127.0.0.1:0")
    names = ("kv_states_written", "kv_seqs_acked",
             "tensor_device_degraded", "kv_messages_written",
             "lens_kv_state_write_ops")
    before = counters(*names)
    plans = {k: [Seq(10 * k + s, n) for s, n in enumerate([3, 9 + 4 * k, 5])]
             for k in range(2)}
    seen, errors = {0: [], 1: []}, []

    def worker(k):
        def trees():
            for tree in stream(plans[k]):
                yield {key: np.asarray(v) for key, v in tree.items()}
        try:
            with Channel(f"127.0.0.1:{port}") as ch:
                for reply in TensorClient(ch).duplex(f"KvHandoff{k}",
                                                     trees(), timeout=60):
                    ordinal = int(np.ravel(reply["seq"])[0])
                    snap = c.snapshot()
                    (row,) = [r for r in snap["resident"]
                              if r[1] == k and r[2] == ordinal]
                    seen[k].append((ordinal, row[4]))
        except Exception as exc:  # the assertion below says which
            errors.append(exc)

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(2)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors and not any(t.is_alive() for t in threads)
        snap = c.snapshot()
        for _, key, ordinal, _, _, table, slot in snap["resident"]:
            assert_holds(c, types_seq(0, table, slot), plans[key][ordinal])
    finally:
        srv.stop(grace=1)
        c.close()
    for k in range(2):
        assert seen[k] == [(s, True) for s in range(3)]
    got = [a - b for a, b in zip(counters(*names), before)]
    messages = sum(-(-w.n // C) * L + S for p in plans.values() for w in p)
    assert got[:4] == [6 * S, 6, 0, messages]
    assert 1 <= got[4] <= 6 * S     # an op a dispatch, of one or a run



# -- runs: a writer behind dispatches a sequence's queued messages at once ----------

def hold(c):
    """Holds the writing thread until the returned Event is set, so that it
    then finds everything handed over meanwhile queued."""
    go = threading.Event()

    class Held(threading.Event):
        def set(self):
            go.wait(30)
            super().set()

    c._writes.put(Held())
    return go


def written(words, state_layers, behind=True):
    """A cache given the sequence ``words`` whole, with the writer held
    until all is handed over (``behind``), or one message at a time, each
    dispatched before the next goes: its stores, and the messages and the
    page and state dispatches counted."""
    c = cache(state_layers=state_layers)
    try:
        names = ("kv_messages_written", "lens_kv_write_ops",
                 "lens_kv_state_write_ops")
        before = counters(*names)
        go = hold(c) if behind else None
        seq = c.admit(0, 0, words.n * T)
        for tree in words.messages(state_layers):
            c.submit(seq, tree.get("pages", tree))
            if go is None:
                c.sync()
        if go is not None:
            go.set()
        seq.future.result(timeout=30)
        assert_holds(c, seq, words)
        got = [a - b for a, b in zip(counters(*names), before)]
        return [np.asarray(a).copy() for a in (c.store,) + c.states], got
    finally:
        c.close()


@pytest.mark.parametrize("layers, n_pages, page_ops, state_ops", [
    # states 0-1 a run ended by page layer 2; full chunks a run of 2
    (STATE_LAYERS, 4, 2, 2),
    (STATE_LAYERS, 9, 4, 2),
    # states 0-2 a run; page layers 3-4 of a full last chunk a run too
    ((0, 1, 2), 8, 2, 1),
    ((0, 1, 2), 5, 3, 1),
])
def test_a_run_stores_what_one_message_at_a_time_stores(layers, n_pages,
                                                        page_ops, state_ops):
    """A writer that finds a sequence's messages queued writes consecutive
    state layers in one dispatch, ended by a page layer, and full-chunk
    page layers of one chunk in one: the stores end bit for bit as those
    written a message a dispatch, and hold the words sent."""
    words = Seq(n_pages + 40, n_pages)
    ran, (msgs, pages, states) = written(words, layers)
    alone, got1 = written(words, layers, behind=False)
    n = -(-n_pages // C) * L + S
    assert (msgs, pages, states) == (n, page_ops, state_ops)
    assert got1[0] == got1[1] + got1[2] == n
    assert all(np.array_equal(a, b) for a, b in zip(ran, alone))


def test_a_run_of_states_keeps_to_its_sequence_and_kind():
    """Two sequences' last chunks interleaved behind a held writer: each
    state run pulls only its own sequence's next states forward, and ends
    where a page layer comes; each sequence is acknowledged."""
    c = cache()
    log = []
    flush = c._flush

    def spy(seq):
        x, _, marks, _ = c._run[0]
        kind = "state" if isinstance(x, tuple) else "page"
        log.append((seq.ordinal, kind, marks["layer"], len(c._run)))
        flush(seq)

    c._flush = spy
    try:
        a, b = c.admit(0, 0, 2 * T), c.admit(1, 1, 2 * T)
        ta = [t.get("pages", t) for t in Seq(1, 2).messages()]
        tb = [t.get("pages", t) for t in Seq(2, 2).messages()]
        go = hold(c)
        for x, y in zip(ta, tb):
            c.submit(a, x)
            c.submit(b, y)
        go.set()
        assert [f.result(timeout=30) for f in (a.future, b.future)] == [
            {"seq": 0, "pages": 2}, {"seq": 1, "pages": 2}]
        assert log == [(0, "state", 0, 2), (1, "state", 0, 2),
                       (0, "page", 0, 1), (1, "page", 0, 1),
                       (0, "state", 2, 1), (1, "state", 2, 1),
                       (0, "page", 1, 1), (1, "page", 1, 1)]
    finally:
        c.close()


@pytest.mark.parametrize("layers, runs", [(STATE_LAYERS, [2]),
                                          ((0, 1, 2), [2, 3]),
                                          ((0, 2, 4), [])])
def test_a_writer_that_keeps_up_dispatches_each_state_alone(layers, runs):
    """Fed a message at a time, the writer makes a program call a message;
    the state run programs, one a length up to the longest stretch of
    state layers, carry the state program's name."""
    c = cache(state_layers=layers)
    try:
        assert sorted(c._programs) == list(range(1, C + 1))
        assert sorted(c._state_runs) == runs
        assert all("tpurpc_kv_state" in p.as_text() for p in
                   [c._state_write] + list(c._state_runs.values()))
    finally:
        c.close()
    _, (msgs, pages, states) = written(Seq(3, 6), layers, behind=False)
    assert msgs == pages + states == 2 * L + S and states == S
