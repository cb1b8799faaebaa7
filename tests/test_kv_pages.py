"""The paged KV cache in HBM (``tpurpc/serving/pages.py``) on the CPU, at a
few layers, 4-token pages and widths of 8 and 32 (the two page layouts:
``[T, W]`` and rows of 128): the scatter against numpy for every page count
of a message, bit-exactness through denormal and NaN patterns, eviction of
acknowledged sequences only and oldest first, an admission that waits until
an acknowledgement makes room, a sequence larger than the cache failing its
own stream alone, writes dispatched by the cache's own thread with each
message's credit back once its write is, nothing compiled after the warm-up,
and the cache over RPC on two connections (``add_tensor_method(device=True)``
on ``RDMA_TPU``), where a sequence's acknowledgement comes only once all of
it is resident and the hops and counters move."""

import threading
import time

import ml_dtypes
import numpy as np
import pytest

from tpurpc.obs import lens, metrics
from tpurpc.serving.kv import KvArenaFull
from tpurpc.serving.pages import KvPageCache

L, T, C = 3, 4, 8          # layers, tokens a page, pages a chunk
BF16 = np.dtype(ml_dtypes.bfloat16)


def counters(*names):
    snap = metrics.registry().counters_snapshot()
    return [snap.get(n, 0) for n in names]


def cache(pages=40, width=8):
    return KvPageCache(L, pages, T, width, chunk_pages=C)


def sequence(seed, n_pages, width=8):
    """``uint16[L, n_pages, T * W]`` of seeded random words."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, 1 << 16, size=(L, n_pages, T * width),
                        dtype=np.uint16)


def messages(words):
    """``words``' page messages in the protocol's order: committed device
    arrays of the chunk's real pages, as a landing makes them."""
    import jax

    layers, n_pages, w = words.shape
    out = []
    for first in range(0, n_pages, C):
        n = min(C, n_pages - first)
        for layer in range(layers):
            x = words[layer, first:first + n].reshape(n, T, w // T)
            out.append(jax.device_put(x, jax.devices()[0]))
    return out


def feed(c, seq, words, hold=False):
    """Hand the sequence's page messages to the cache; with ``hold`` all but
    the last, which is returned (the sequence is then still in flight)."""
    msgs = messages(words)
    for x in msgs[:-1]:
        assert c.submit(seq, x) is False
    if hold:
        return msgs[-1]
    assert c.submit(seq, msgs[-1]) is True
    return None


def write_all(c, key, ordinal, words, hold=False):
    seq = c.admit(key, ordinal, words.shape[1] * T)
    return seq, feed(c, seq, words, hold)


def stored(c, seq):
    """The sequence's pages as the store holds them, ``[L, n, T * W]``."""
    c.sync()
    store = np.asarray(c.store)
    return store[:, seq.pages].reshape(L, len(seq.pages), -1)


@pytest.mark.parametrize("width", [8, 32])
@pytest.mark.parametrize("n_pages", [1, 2, 3, 5, 8, 13])
def test_the_scatter_writes_every_page_count(n_pages, width):
    """Messages of 1 to 8 pages (13: a full chunk and 5): the sequence's
    pages land where its table says, in every layer, and nothing else of
    the store moves."""
    c = cache(width=width)
    try:
        before = np.asarray(c.store).copy()
        words = sequence(n_pages, n_pages, width)
        seq, _ = write_all(c, 0, 7, words)
        assert seq.future.result(timeout=30) == {"seq": 7, "pages": n_pages}
        assert np.array_equal(stored(c, seq), words)
        after = np.asarray(c.store).copy()
        after[:, seq.pages] = before[:, seq.pages]
        assert np.array_equal(after, before)
        assert c.store.shape[2:] == ((1, 128) if width == 32 else (T, 8))
    finally:
        c.close()


def test_denormals_and_nans_are_stored_bit_for_bit():
    patterns = np.array([0x0001, 0x8001, 0x007F, 0x7FC1, 0xFF81, 0x7F81,
                         0xFFFF, 0x7F80, 0x8000, 0x0000, 0x3F80, 0x807F],
                        np.uint16)
    c = cache()
    try:
        words = np.resize(patterns, (L, 6, T * 8))
        seq, _ = write_all(c, 0, 0, words)
        seq.future.result(timeout=30)
        assert np.array_equal(stored(c, seq), words)
    finally:
        c.close()


def events(c, kind):
    return [e[1] for e in c.snapshot()["log"] if e[0] == kind]


def test_eviction_takes_acknowledged_sequences_oldest_first():
    c = cache(pages=10)
    try:
        a, _ = write_all(c, 0, 0, sequence(1, 4))
        b, b_last = write_all(c, 1, 0, sequence(2, 4), hold=True)
        a.future.result(timeout=30)
        # 2 pages free: A (acknowledged) goes, B (not) stays
        cc, _ = write_all(c, 0, 1, sequence(3, 4))
        assert events(c, "evict") == [a.gid]
        cc.future.result(timeout=30)
        assert c.submit(b, b_last) is True
        b.future.result(timeout=30)
        assert c.snapshot()["acked"] == [cc.gid, b.gid]
        d, _ = write_all(c, 1, 1, sequence(4, 8))
        d.future.result(timeout=30)
        # C was acknowledged before B, so C goes first
        assert events(c, "evict") == [a.gid, cc.gid, b.gid]
        snap = c.snapshot()
        assert [r[0] for r in snap["resident"]] == [d.gid]
        assert snap["free"] == 2 and c.free_pages() == 2
        assert [e[0] for e in snap["log"]].count("admit") == 4
    finally:
        c.close()


def test_an_admission_waits_until_an_acknowledgement_makes_room():
    c = cache(pages=8)
    try:
        waits0, = counters("lens_kv_admit_ops")
        a, a_last = write_all(c, 0, 0, sequence(1, 6), hold=True)
        got = []
        t = threading.Thread(target=lambda: got.append(c.admit(1, 0, 4 * T)))
        t.start()
        time.sleep(0.3)
        assert t.is_alive() and not got   # A is not acknowledged
        c.submit(a, a_last)
        a.future.result(timeout=30)
        t.join(timeout=30)
        assert not t.is_alive() and len(got) == 1
        assert events(c, "evict") == [a.gid]
        assert counters("lens_kv_admit_ops")[0] == waits0 + 1
    finally:
        c.close()


def test_admissions_are_served_in_the_order_offers_came():
    """A large offer that waits is not overtaken by a small one behind it."""
    c = cache(pages=8)
    try:
        a, a_last = write_all(c, 0, 0, sequence(1, 6), hold=True)
        order = []
        big = threading.Thread(target=lambda: order.append(
            ("big", c.admit(1, 0, 8 * T))))
        big.start()
        time.sleep(0.2)
        small = threading.Thread(target=lambda: order.append(
            ("small", c.admit(2, 0, 1 * T))))
        small.start()
        time.sleep(0.2)
        assert not order        # 2 pages are free, but the small one waits
        c.submit(a, a_last)
        a.future.result(timeout=30)
        big.join(timeout=30)
        assert order and order[0][0] == "big"
        feed(c, order[0][1], sequence(5, 8))
        order[0][1].future.result(timeout=30)
        small.join(timeout=30)
        assert [name for name, _ in order] == ["big", "small"]
    finally:
        c.close()


def stream(words_list):
    """A prefill worker's stream: an offer and the page messages a
    sequence."""
    for s, words in enumerate(words_list):
        yield {"offer": np.array([s, words.shape[1] * T], np.int32)}
        for x in messages(words):
            yield {"pages": x}


def test_a_sequence_larger_than_the_cache_fails_its_own_stream_alone():
    c = cache(pages=10)
    try:
        big = c.receiver(0)(stream([sequence(1, 11)]))
        with pytest.raises(KvArenaFull, match="holds 10"):
            next(big)
        acks = list(c.receiver(1)(stream([sequence(2, 5),
                                          sequence(3, 5)])))
        assert [f.result(timeout=30) for f in acks] == [
            {"seq": 0, "pages": 5}, {"seq": 1, "pages": 5}]
        assert c.free_pages() == 0
    finally:
        c.close()


def test_a_stream_that_ends_inside_a_sequence_gives_its_pages_back():
    c = cache(pages=10)
    try:
        words = sequence(1, 6)
        cut = list(stream([words]))[:3]   # the offer and two of three messages
        assert list(c.receiver(0)(iter(cut))) == []
        c.sync()                          # the release waits behind its writes
        assert c.free_pages() == 10
        assert [e[0] for e in c.snapshot()["log"]] == ["admit", "drop"]
    finally:
        c.close()


def test_a_message_of_the_wrong_shape_fails_the_stream():
    c = cache()
    try:
        words = sequence(1, 3)
        trees = list(stream([words]))
        trees[2] = {"pages": trees[2]["pages"][:2]}   # 2 pages, not 3
        with pytest.raises(ValueError, match="not uint16"):
            list(c.receiver(0)(iter(trees)))
        # the pages' bf16 itself is refused: on a chip no program could
        # make it bits again without rewriting its NaNs and denormals
        trees = list(stream([words]))
        trees[1] = {"pages": np.asarray(trees[1]["pages"]).view(BF16)}
        with pytest.raises(ValueError, match="words of bf16 pages"):
            list(c.receiver(2)(iter(trees)))
        with pytest.raises(ValueError, match="opens each sequence"):
            list(c.receiver(1)(iter(trees[1:])))
    finally:
        c.close()


def test_writes_are_dispatched_by_the_cache_s_own_thread():
    """No handler dispatches: each message goes to the writing thread, and
    its credit comes back once, after its write was dispatched."""
    class Lease:
        def __init__(self, log):
            self.log = log

        def release(self):
            self.log.append(("release", threading.current_thread().name))

    c = cache()
    log = []
    dispatch = c._dispatch

    def spy(seq, x, ids):
        log.append(("write", threading.current_thread().name))
        assert ids == (77, 3)   # the span names the handler's message
        dispatch(seq, x, ids)

    c._dispatch = spy
    try:
        seq = c.admit(0, 0, 3 * T)
        # a message found unsound is refused with its credit still the
        # caller's
        with pytest.raises(ValueError, match="not uint16"):
            c.submit(seq, messages(sequence(1, 2))[0],
                     lambda: log.append("taken"))
        for x in messages(sequence(1, 3)):
            with lens.stage("srv_handler", call=77, seq=3):
                c.submit(seq, x, lambda: [Lease(log)])
        seq.future.result(timeout=30)
        assert log == [("write", "tpurpc-kv-write"),
                       ("release", "tpurpc-kv-write")] * L
    finally:
        c.close()


def test_a_message_of_host_leaves_is_copied_before_it_is_handed_over():
    """A call that reached the method without a device ring hands numpy
    views of a receive buffer that the next message reuses: the writer gets
    a copy, so the pages stored are those sent."""
    c = cache()
    try:
        words = sequence(2, 5)
        seq = c.admit(0, 0, 5 * T)
        buf = np.empty((5, T, 8), np.uint16)
        for layer in range(L):
            buf[...] = words[layer].reshape(5, T, 8)
            c.submit(seq, buf)
        buf[...] = 0
        seq.future.result(timeout=30)
        assert np.array_equal(stored(c, seq), words)
    finally:
        c.close()


def test_many_streams_at_once_lose_no_write():
    """Sixteen streams, more than the cores, through one cache with the
    interpreter switching threads every few microseconds: every stream's
    acknowledgements come in its order, every page-layer is counted once,
    and every sequence still resident holds exactly its pages."""
    import sys

    c = cache(pages=120)
    plans = {k: [sequence(100 * k + s, 1 + (7 * k + 3 * s) % 13)
                 for s in range(3)] for k in range(16)}
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
        for k, words_list in plans.items():
            assert acks[k] == [{"seq": s, "pages": w.shape[1]}
                               for s, w in enumerate(words_list)]
        snap = c.snapshot()
        assert snap["written"] == {k: L * sum(w.shape[1] for w in ws)
                                   for k, ws in plans.items()}
        c.sync()
        store = np.asarray(c.store)
        for _, key, ordinal, _, _, table in snap["resident"]:
            got = store[:, table].reshape(L, len(table), -1)
            assert np.array_equal(got, plans[key][ordinal])
    finally:
        sys.setswitchinterval(interval)
        c.close()


def test_nothing_compiles_after_the_warm_up():
    from tpurpc.utils import jaxenv

    jaxenv.count_compiles()
    c = cache(pages=64)
    try:
        compiles0, = counters("xla_compiles")
        futures = []
        for k, n in enumerate([1, 2, 3, 4, 5, 6, 7, 8, 9, 17, 20]):
            futures += list(c.receiver(k % 2)(stream([sequence(k, n)])))
        for f in futures:
            f.result(timeout=30)
        assert counters("xla_compiles")[0] == compiles0
    finally:
        c.close()


def test_counters_count_pages_and_sequences():
    c = cache(pages=12)
    try:
        names = ("kv_pages_written", "kv_seqs_admitted", "kv_seqs_acked",
                 "kv_seqs_evicted", "kv_pages_evicted", "kv_messages_written",
                 "lens_kv_ack_ops", "lens_kv_write_ops")
        before = counters(*names)
        # 5 pages and 3 pages: one message a layer each
        acks = list(c.receiver(0)(stream([sequence(1, 5), sequence(2, 3)])))
        [f.result(timeout=30) for f in acks]
        acks = list(c.receiver(1)(stream([sequence(3, 9)])))
        [f.result(timeout=30) for f in acks]
        got = [a - b for a, b in zip(counters(*names), before)]
        # 9 pages: 8 + 1; it evicts the first sequence (5 pages)
        assert got[:-1] == [L * 17, 3, 3, 1, 5, L * 4, 3]
        # an op a dispatch: a run of full chunks is one
        assert 1 <= got[-1] <= L * 4
        assert c.snapshot()["written"] == {0: L * 8, 1: L * 9}
        total, live = metrics.registry().metrics()["kv_pages_free"].collect()
        assert live >= 1 and total >= c.free_pages() == 12 - 3 - 9
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
    """Two connections stream three sequences each into ONE cache through
    ``add_tensor_method(device=True)``; every acknowledgement arrives only
    when its whole sequence is resident, in the connection's order, and
    the hops and counters move."""
    from tpurpc.jaxshim import TensorClient, add_tensor_method
    from tpurpc.rpc.channel import Channel
    from tpurpc.rpc.server import Server

    c = KvPageCache(L, 200, T, 32, chunk_pages=C)
    srv = Server(max_workers=8)
    for k in range(2):
        add_tensor_method(srv, f"KvHandoff{k}", c.receiver(k),
                          kind="stream_stream", device=True)
    srv.start()
    port = srv.add_insecure_port("127.0.0.1:0")
    names = ("kv_messages_written", "lens_kv_ack_ops", "kv_seqs_acked",
             "kv_pages_written", "tensor_device_degraded", "hbm_place_msgs",
             "lens_kv_write_ops")
    before = counters(*names)
    plans = {k: [sequence(10 * k + s, n, 32)
                 for s, n in enumerate([3, 9 + 8 * k, 20])] for k in range(2)}
    seen, errors = {0: [], 1: []}, []

    def worker(k):
        def trees():
            for s, words in enumerate(plans[k]):
                yield {"offer": np.array([s, words.shape[1] * T], np.int32)}
                for first in range(0, words.shape[1], C):
                    n = min(C, words.shape[1] - first)
                    for layer in range(L):
                        x = words[layer, first:first + n]
                        yield {"pages": x.reshape(n, T, 32)}
        try:
            with Channel(f"127.0.0.1:{port}") as ch:
                for reply in TensorClient(ch).duplex(f"KvHandoff{k}",
                                                     trees(), timeout=60):
                    ordinal = int(np.ravel(reply["seq"])[0])
                    # acknowledged, so resident, before the reply reached us
                    snap = c.snapshot()
                    (row,) = [r for r in snap["resident"]
                              if r[1] == k and r[2] == ordinal]
                    seen[k].append((ordinal, int(np.ravel(reply["pages"])[0]),
                                    row[4], row[5]))
        except Exception as exc:  # the assertion below says which
            errors.append(exc)

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(2)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        c.sync()
        store = np.asarray(c.store)
    finally:
        srv.stop(grace=1)
        c.close()
    assert not errors and not any(t.is_alive() for t in threads)
    for k in range(2):
        assert [row[:3] for row in seen[k]] == [
            (s, w.shape[1], True) for s, w in enumerate(plans[k])]
        for (s, _, _, table), words in zip(seen[k], plans[k]):
            got = store[:, table].reshape(L, len(table), -1)
            assert np.array_equal(got, words)
    got = [a - b for a, b in zip(counters(*names), before)]
    pages = sum(w.shape[1] for p in plans.values() for w in p)
    messages_ = sum(-(-w.shape[1] // C) * L
                    for p in plans.values() for w in p)
    assert got[0] == messages_ and got[1] == 6 and got[2] == 6
    assert got[3] == pages * L and got[4] == 0
    assert got[5] >= messages_ + 6   # every message landed: pages and offers
    assert 1 <= got[6] <= messages_  # an op a dispatch, of one or a run


# -- runs: a writer behind dispatches a sequence's queued messages at once ----------

DEEP = 6                   # layers of a cache deeper than the longest run


def deep_words(seed, n_pages, width=8):
    """``uint16[DEEP, n_pages, T * W]`` of seeded random words."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, 1 << 16, size=(DEEP, n_pages, T * width),
                        dtype=np.uint16)


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


def deep(words, held=True):
    """A cache of ``DEEP`` layers given the sequence ``words`` whole, with
    the writer held until all is handed over, or (``held`` false) one
    message at a time, each dispatched before the next goes: its store, the
    sequence's table, and the messages and dispatches counted."""
    c = KvPageCache(DEEP, 64, T, words.shape[2] // T, chunk_pages=C)
    try:
        names = ("kv_messages_written", "lens_kv_write_ops")
        before = counters(*names)
        go = hold(c) if held else None
        seq = c.admit(0, 0, words.shape[1] * T)
        for x in messages(words):
            c.submit(seq, x)
            if go is None:
                c.sync()
        if go is not None:
            go.set()
        seq.future.result(timeout=30)
        c.sync()
        got = [a - b for a, b in zip(counters(*names), before)]
        return np.asarray(c.store).copy(), list(seq.pages), got
    finally:
        c.close()


@pytest.mark.parametrize("width", [8, 32])
@pytest.mark.parametrize("n_pages, dispatches", [
    (8, 2),            # one chunk: a run cut at four layers, one to its end
    (16, 4),           # two chunks: the same in each, never across
    (19, 4 + DEEP),    # and a last chunk of 3 pages: a message a dispatch
])
def test_a_run_stores_what_one_message_at_a_time_stores(n_pages,
                                                        dispatches, width):
    """A writer that finds a sequence's messages queued writes full chunks
    in runs of up to four layers within a chunk, and the last chunk's a
    message at a time: the store ends bit for bit as one written a message
    a dispatch, and holds the words sent."""
    words = deep_words(n_pages + width, n_pages, width)
    ran, table, (msgs, ops) = deep(words)
    alone, table1, (msgs1, ops1) = deep(words, held=False)
    n = -(-n_pages // C) * DEEP
    assert (msgs, ops) == (n, dispatches) and (msgs1, ops1) == (n, n)
    assert table == table1 and np.array_equal(ran, alone)
    assert np.array_equal(ran[:, table].reshape(DEEP, n_pages, -1), words)


def test_a_run_keeps_to_its_sequence_chunk_and_barriers():
    """Two sequences' messages interleaved behind a held writer, a
    ``sync``'s barrier and a give-up among them: a run pulls only its own
    sequence's next messages forward and ends at a chunk's end and at a
    barrier, the barrier is reached once everything before it is
    dispatched and before anything after it, and the given-up sequence's
    pages go back only behind its queued writes; ``sync`` returns with
    every write dispatched."""
    c = KvPageCache(DEEP, 64, T, 8, chunk_pages=C)
    log = []
    flush, drop = c._flush, c.drop

    def spy_flush(seq):
        log.append((seq.ordinal, c._run[0][2]["layer"], len(c._run)))
        flush(seq)

    def spy_drop(seq):
        log.append(("drop", seq.ordinal))
        drop(seq)

    c._flush, c.drop = spy_flush, spy_drop
    try:
        a = c.admit(0, 0, 2 * C * T)
        b = c.admit(1, 1, C * T)
        xa = messages(deep_words(1, 2 * C))
        xb = messages(deep_words(2, C))
        go = hold(c)
        for seq, x in ((a, xa[0]), (a, xa[1]), (b, xb[0]), (a, xa[2]),
                       (b, xb[1])):
            c.submit(seq, x)
        reached = []

        class Barrier(threading.Event):   # what sync() hands over
            def set(self):
                reached.append(list(log))
                super().set()

        c._writes.put(Barrier())
        for x in xa[3:]:
            c.submit(a, x)
        for x in xb[2:4]:
            c.submit(b, x)
        c.give_up(b)
        go.set()
        a.future.result(timeout=30)
        c.sync()
        assert log == [(0, 0, 3), (1, 0, 2),         # the barrier
                       (0, 3, 3),                    # the chunk's end
                       (0, 0, 4), (0, 4, 2),         # chunk 1: cut at four
                       (1, 2, 2), ("drop", 1)]       # then the give-up
        assert reached == [log[:2]]
        assert c.free_pages() == 64 - 2 * C
    finally:
        c.close()


def test_a_writer_that_keeps_up_dispatches_each_message_alone():
    """Fed a message at a time, the writer makes a program call a message,
    each page count's own program; the run programs, one a length up to
    four, carry the scatter's name, as the single ones do."""
    c = KvPageCache(DEEP, 64, T, 8, chunk_pages=C)
    try:
        assert sorted(c._programs) == list(range(1, C + 1))
        assert sorted(c._page_runs) == [2, 3, 4]
        assert all("tpurpc_kv_pages" in p.as_text() for p in
                   list(c._programs.values()) + list(c._page_runs.values()))
    finally:
        c.close()
    _, _, (msgs, ops) = deep(deep_words(5, 2 * C + 3), held=False)
    assert msgs == ops == 3 * DEEP
