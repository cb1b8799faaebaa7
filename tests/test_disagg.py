"""tpurpc-keystone (ISSUE 11): disaggregated prefill/decode + migration.

The handoff protocol end-to-end (prefill tier computes KV, blocks land
one-sided in the decode arena, client re-attaches and streams exact
tokens), prefix-cache hits across the wire (shipped bytes shrink), live
migration between decode servers with index/value continuity, the
drain-hook wiring, registry reaping (pending => quarantine, parked =>
free), and the chaos satellite: decode-server death mid-migration fails
the sequence ALONE with UNAVAILABLE — never a hang — and the dead
handoff's blocks are quarantined, never reused. On TCP and RDMA_BPEV."""

import threading
import time

import numpy as np
import pytest

import tpurpc.serving.disagg as disagg
from tpurpc.jaxshim.generate import ToyDecodeModel, reference_decode
from tpurpc.analysis import protocol
from tpurpc.obs import flight
from tpurpc.rpc.channel import Channel
from tpurpc.rpc.status import RpcError, StatusCode
from tpurpc.serving import (DisaggClient, migrate, serve_decode,
                            serve_prefill)
from tpurpc.serving.scheduler import TokenStream
from tpurpc.tpu import ledger


@pytest.fixture(autouse=True)
def _fast_streams():
    old = TokenStream.MAX_IDLE_S
    TokenStream.MAX_IDLE_S = 10.0
    yield
    TokenStream.MAX_IDLE_S = old
    disagg.TEST_HOOKS.clear()


def _poll(pred, timeout=8.0, interval=0.005):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        got = pred()
        if got:
            return got
        time.sleep(interval)
    return pred()


class _Stack:
    """One prefill + N decode servers with channels, torn down in order."""

    def __init__(self, n_decode=1, step_delay_s=0.0, **decode_kw):
        decode_kw.setdefault("kv_blocks", 128)
        decode_kw.setdefault("block_bytes", 256)
        self.decodes = []
        for i in range(n_decode):
            srv, port, sched, state = serve_decode(
                ToyDecodeModel(step_delay_s=step_delay_s),
                name=f"dec{i}", **decode_kw)
            self.decodes.append((srv, port, sched, state))
        self.d_ch = Channel(f"127.0.0.1:{self.decodes[0][1]}")
        self.p_srv, self.p_port, self.p_state = serve_prefill(
            ToyDecodeModel(), self.d_ch,
            f"127.0.0.1:{self.decodes[0][1]}")
        self.p_ch = Channel(f"127.0.0.1:{self.p_port}")
        self.client = DisaggClient(self.p_ch,
                                   f"127.0.0.1:{self.decodes[0][1]}")

    def close(self):
        self.client.close()
        self.p_srv.stop(grace=0)
        self.p_state.close()
        for srv, _port, sched, state in self.decodes:
            srv.stop(grace=0)
            sched.close()
            state.close()
            state.mgr.close()
        self.p_ch.close()
        self.d_ch.close()


# -- the handoff end-to-end ---------------------------------------------------

def test_disagg_stream_exact_tokens_and_ship_accounting():
    # the flight recorder is the process's: judge this test's events only,
    # not those an earlier file left in the same worker (tests/test_protocol.py
    # emits kv-ship events with the same ids)
    t0 = time.monotonic_ns()
    st = _Stack()
    try:
        prompt = list(range(20))
        with ledger.track() as w:
            pairs = list(st.client.generate_with_meta(prompt,
                                                      max_tokens=12,
                                                      timeout=20))
        assert [i for i, _ in pairs] == list(range(12))
        assert [t for _, t in pairs] == reference_decode(prompt, 12)
        # 21 entries of 16 bytes went one-sided into the decode arena
        assert w["rdma_write"] >= 21 * 16, w.delta
        snap = flight.snapshot(since_ns=t0)
        protocol.assert_ordered(snap, ["kv-ship-offer",
                                       "kv-ship-complete"])
        assert protocol.check_events(snap, strict=False) == []
    finally:
        st.close()


def test_disagg_repeated_prompt_scores_prefix_hit_and_ships_less():
    st = _Stack()
    try:
        prompt = list(range(32))   # 33 entries; aligned span = 32
        list(st.client.generate(prompt, max_tokens=4, timeout=20))
        shipped_cold = st.p_state.shipped_bytes
        list(st.client.generate(prompt, max_tokens=4, timeout=20))
        shipped_warm = st.p_state.shipped_bytes - shipped_cold
        _srv, _port, _sched, state = st.decodes[0]
        assert state.prefix_hits >= 1, state.stats()
        assert st.p_state.prefix_skipped_entries >= 32
        # only the uncached tail shipped the second time
        assert shipped_warm < shipped_cold, (shipped_warm, shipped_cold)
        assert shipped_warm == 16  # exactly the first-token entry
    finally:
        st.close()


def test_disagg_concurrent_streams_no_crosstalk():
    st = _Stack(step_delay_s=0.001)
    try:
        out = {}

        def run(i):
            out[i] = list(st.client.generate([i, i], max_tokens=10,
                                             timeout=20))

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(5)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(20)
        for i in range(5):
            assert out[i] == reference_decode([i, i], 10), i
    finally:
        st.close()


def test_resume_unknown_seq_is_not_found():
    st = _Stack()
    try:
        from tpurpc.jaxshim import codec

        mc = st.client._channel(
            f"127.0.0.1:{st.decodes[0][1]}").unary_stream(
            "/tpurpc.Kv/ResumeSeq", codec.tree_serializer,
            codec.tree_deserializer)
        with pytest.raises(RpcError) as ei:
            list(mc({"seq_key": np.int64(424242),
                     "max_tokens": np.int32(4)}, timeout=10))
        assert ei.value.code() is StatusCode.NOT_FOUND
    finally:
        st.close()


def test_reap_pending_quarantines_parked_frees():
    st = _Stack(pending_ttl_s=0.05, parked_ttl_s=0.05)
    try:
        _srv, port, _sched, state = st.decodes[0]
        mgr = state.mgr
        # a parked sequence nobody resumes: prefill only (max_tokens big,
        # but never call ResumeSeq)
        from tpurpc.jaxshim import codec

        pre = st.p_ch.unary_unary("/tpurpc.Kv/Prefill",
                                  codec.tree_serializer,
                                  codec.tree_deserializer)
        pre({"prompt": np.asarray([1, 2, 3], np.int32)}, timeout=10)
        assert state.stats()["parked"] == 1
        # a pending handoff whose sender vanished: offer, never complete
        offer = st.p_ch  # reuse transports? offer directly to decode
        och = st.client._channel(f"127.0.0.1:{port}")
        omc = och.unary_unary("/tpurpc.Kv/OfferKv", codec.tree_serializer,
                              codec.tree_deserializer)
        resp = omc({"seq_key": np.int64(777),
                    "prompt": np.asarray([9, 9], np.int32),
                    "n_tokens": np.int32(3)}, timeout=10)
        assert int(np.asarray(resp["ok"]).ravel()[0]) == 1
        assert state.stats()["pending"] == 1
        time.sleep(0.1)
        nq, nf = state.reap()
        assert nq >= 1, "pending handoff blocks were not quarantined"
        assert nf >= 1, "parked sequence was not freed"
        assert mgr.quarantined_count() >= 1
        # parked blocks came BACK (freed), pending blocks did NOT
        assert state.stats()["pending"] == 0
        assert state.stats()["parked"] == 0
    finally:
        st.close()


# -- live migration -----------------------------------------------------------

def test_migration_continues_stream_exact_on_peer():
    flight.RECORDER.reset()
    st = _Stack(n_decode=2, step_delay_s=0.003)
    try:
        a = st.decodes[0]
        b = st.decodes[1]
        b_ch = Channel(f"127.0.0.1:{b[1]}")
        out = {}

        def run():
            out["pairs"] = list(st.client.generate_with_meta(
                [5, 6], max_tokens=50, timeout=30))

        t = threading.Thread(target=run)
        t.start()
        assert _poll(lambda: a[2].running_depth() > 0)
        time.sleep(0.03)
        moved, failed = migrate(a[3], b_ch, f"127.0.0.1:{b[1]}")
        t.join(30)
        assert (moved, failed) == (1, 0)
        pairs = out["pairs"]
        assert [i for i, _ in pairs] == list(range(50))
        assert [v for _, v in pairs] == reference_decode([5, 6], 50)
        assert b[2].tokens_out > 0, "peer never stepped the migrated seq"
        snap = flight.snapshot()
        protocol.assert_ordered(snap, ["migration-begin",
                                       ("migration-end", {"a2": 1})])
        assert protocol.check_events(snap, strict=False) == []
        # the source arena let go of the sequence (prefix cache may hold
        # the block-aligned prompt span; [5,6] is below the span bar)
        assert _poll(lambda: a[3].mgr.used_count() == 0), a[3].mgr.stats()
        b_ch.close()
    finally:
        st.close()


def test_drain_hook_migrates_live_streams():
    """Server.drain on a decode server with migrate_to wired moves live
    sequences to the peer — the zero-failed-RPC drain, stateful
    edition."""
    b_srv, b_port, b_sched, b_state = serve_decode(
        ToyDecodeModel(step_delay_s=0.003), name="drainB",
        kv_blocks=128, block_bytes=256)
    b_ch = Channel(f"127.0.0.1:{b_port}")
    a_srv, a_port, a_sched, a_state = serve_decode(
        ToyDecodeModel(step_delay_s=0.003), name="drainA",
        kv_blocks=128, block_bytes=256,
        migrate_to=lambda: (b_ch, f"127.0.0.1:{b_port}"))
    a_ch = Channel(f"127.0.0.1:{a_port}")
    p_srv, p_port, p_state = serve_prefill(
        ToyDecodeModel(), a_ch, f"127.0.0.1:{a_port}")
    p_ch = Channel(f"127.0.0.1:{p_port}")
    cli = DisaggClient(p_ch, f"127.0.0.1:{a_port}")
    try:
        out = {}

        def run():
            out["pairs"] = list(cli.generate_with_meta(
                [3, 3], max_tokens=40, timeout=30))

        t = threading.Thread(target=run)
        t.start()
        assert _poll(lambda: a_sched.running_depth() > 0)
        time.sleep(0.03)
        a_srv.drain(linger=10.0)
        t.join(30)
        pairs = out["pairs"]
        assert [i for i, _ in pairs] == list(range(40))
        assert [v for _, v in pairs] == reference_decode([3, 3], 40)
        assert b_sched.tokens_out > 0, "drain did not migrate the stream"
    finally:
        cli.close()
        p_srv.stop(grace=0)
        p_state.close()
        a_srv.stop(grace=0)
        b_srv.stop(grace=0)
        a_sched.close()
        b_sched.close()
        a_state.close()
        b_state.close()
        a_state.mgr.close()
        b_state.mgr.close()
        for ch in (p_ch, a_ch, b_ch):
            ch.close()


# -- chaos: decode-server death mid-migration (the satellite) -----------------

@pytest.mark.parametrize("platform", ["TCP", "RDMA_BPEV"])
def test_decode_death_mid_migration_fails_alone_and_quarantines(
        monkeypatch, platform):
    """Kill the migration TARGET between the one-sided block writes and
    the COMPLETE frame: the migrating sequence fails ALONE with
    UNAVAILABLE (never hangs), sibling streams on the source finish
    exactly, and the target's claimed blocks are QUARANTINED — never
    reused (the modeled reuse-before-quarantine rule, live)."""
    monkeypatch.setenv("GRPC_PLATFORM_TYPE", platform)
    flight.RECORDER.reset()
    st = _Stack(n_decode=2, step_delay_s=0.003,
                pending_ttl_s=0.2)
    b_ch = None
    try:
        a = st.decodes[0]
        b = st.decodes[1]
        b_ch = Channel(f"127.0.0.1:{b[1]}")
        out = {}

        def run(key, prompt, n):
            try:
                out[key] = ("ok", list(st.client.generate_with_meta(
                    prompt, max_tokens=n, timeout=30)))
            except RpcError as exc:
                out[key] = ("err", exc)

        t1 = threading.Thread(target=run, args=("victim", [5, 6], 200))
        t1.start()
        assert _poll(lambda: a[2].running_depth() > 0)
        t2 = threading.Thread(target=run, args=("sibling", [7], 30))
        t2.start()
        assert _poll(lambda: a[2].running_depth() > 1)
        # wedge every shipper between write and complete, then migrate
        wedge = threading.Event()
        disagg.TEST_HOOKS["wedge_before_complete"] = wedge
        mig = {}

        def do_migrate():
            mig["r"] = migrate(a[3], b_ch, f"127.0.0.1:{b[1]}",
                               sids=[1], timeout_s=5.0)

        mt = threading.Thread(target=do_migrate)
        mt.start()
        # the target holds a PENDING handoff (blocks claimed, written,
        # not completed) — now it dies
        assert _poll(lambda: b[3].stats()["pending"] >= 1), b[3].stats()
        pending_blocks = b[3].mgr.used_count()
        assert pending_blocks > 0
        b[0].stop(grace=0)
        wedge.set()
        mt.join(20)
        assert not mt.is_alive(), "migration hung on a dead peer"
        moved, failed = mig["r"]
        assert moved == 0 and failed == 1
        # the victim failed ALONE with UNAVAILABLE...
        t1.join(20)
        assert not t1.is_alive(), "victim stream hung"
        kind, payload = out["victim"]
        assert kind == "err", payload
        assert payload.code() is StatusCode.UNAVAILABLE, payload
        # ...its sibling finished exactly...
        t2.join(20)
        kind, payload = out["sibling"]
        assert kind == "ok", payload
        assert [v for _, v in payload] == reference_decode([7], 30)
        # ...and the dead target's claimed blocks are quarantined, never
        # back on the free list
        time.sleep(0.25)
        nq, _nf = b[3].reap()
        assert nq >= 1, "dead handoff's blocks were not quarantined"
        assert b[3].mgr.quarantined_count() >= 1
        assert b[3].mgr.free_count() + b[3].mgr.used_count() \
            + b[3].mgr.quarantined_count() == b[3].mgr.n_blocks
        # the failed migration closed its bracket (a2=0 in MIG_END) and
        # the dead handoff's blocks left circulation — per-entity
        # legality via the declared machines, order via the one helper
        snap = flight.snapshot()
        protocol.assert_ordered(snap, ["migration-begin",
                                       ("migration-end", {"a2": 0})])
        protocol.assert_ordered(snap, ["kv-quarantine"])
        assert protocol.check_events(snap, strict=False) == []
    finally:
        if b_ch is not None:
            b_ch.close()
        st.close()
