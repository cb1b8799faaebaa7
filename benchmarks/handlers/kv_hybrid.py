"""kv_hybrid: the decode side of a disaggregated handoff for a hybrid model,
ONE cache in HBM that holds each sequence's linear-attention state in a
fixed slot and its latent KV in pages, streamed in by eight prefill workers.

The deployment behind ``kv_handoff_kda_mla_hybrid`` (PERF.md 1, 4): ONE
``tpurpc.serving.pages.KvPageCache`` built with the configuration's state
layers, leaves and slots, each connection's stream registered as
``KvHandoff<c>`` with ``add_tensor_method(device=True,
kind="stream_stream")`` and served by the cache's own ``receiver(c)``, as
``kv_pages`` does: admission of a slot and the pages together, the two
write programs on the one writing thread, eviction and the acknowledgement
are the product's. What is here is the harness's side: the counts (in the
configuration's unit, so that a page-layer and a state each count their
bytes), the audit (``kv_pages``'s over the pages, and every resident
sequence's state checksums computed on the device over its slot, a seeded
sample of states read back whole), and the planted faults of the control.

``build`` ends the server with ``@fatal`` where the program's page cache
keeps no state: a tree without state slots cannot run the cell.
"""

from __future__ import annotations

import functools
import inspect
import json
import sys

import numpy as np

from benchmarks.configs import kvhyb_reference as ref
from benchmarks.handlers import kv_pages

#: what ``--fault`` may plant, each in every sequence:
#:   drop           a sequence's second page message is never written,
#:                  though counted and acknowledged as if it were
#:   reorder        the first two messages of a sequence's last chunk (its
#:                  first two state layers) trade places on their way in
#:   evict_unacked  ``kv_pages``'s: room made by evicting a sequence that
#:                  is not the oldest acknowledged
#:   stale_page     ``kv_pages``'s: one page of every sequence never written
#:   state_drop     a sequence's second state message is never written,
#:                  though counted as if it were
#:   stale_slot     the slot on the device names no slot, so no state of
#:                  the sequence is written and its slot keeps what an
#:                  earlier sequence left
FAULTS = ("drop", "reorder", "evict_unacked", "stale_page", "state_drop",
          "stale_slot")


@functools.lru_cache(maxsize=None)
def state_programs(shape: tuple):
    """``(sums, take)`` over a state store ``[S, slots, *shape]`` of
    unsigned words: every state's checksum (``kvhyb_reference``'s),
    ``uint32[S, slots]``, and one state's words."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    u32 = jnp.uint32

    def sums(store):
        i, stride = u32(0), 1
        for d in range(len(shape) - 1, -1, -1):
            i = i + lax.broadcasted_iota(u32, shape, d) * u32(stride)
            stride *= shape[d]
        w = i * u32(2) + u32(1)
        return jnp.sum(store.astype(u32) * w,
                       axis=tuple(range(2, 2 + len(shape))), dtype=u32)

    def take(store, layer, slot):
        return store[layer, slot]

    return jax.jit(sums), jax.jit(take)


def faulty(cls, fault: str):
    """The product's cache with ``fault`` planted in it."""
    if fault in ("evict_unacked", "stale_page"):
        return kv_pages.faulty(cls, fault)

    class Faulty(cls):
        def _dispatch(self, seq, x, ids):
            if fault != "drop" or seq.done - seq.states_done != 1:
                return super()._dispatch(seq, x, ids)
            # counted as written, never dispatched
            self._written[seq.key] += x.shape[0]

        def _dispatch_state(self, seq, xs, ids):
            if fault != "state_drop" or seq.states_done != 1:
                return super()._dispatch_state(seq, xs, ids)
            seq.states_done += 1
            self._states_written[seq.key] += 1

        def _table(self, seq):
            state = super()._table(seq)
            if fault == "stale_slot":
                state[-1] = self.slots
            return state

    return Faulty


def _reordered(trees, cache):
    """The first two messages of every sequence's last chunk trade places:
    two state layers where the model's first two layers keep state."""
    at = held = None
    for tree in trees:
        if "offer" in tree:
            pages = -(-int(np.asarray(tree["offer"]).reshape(-1)[1])
                      // cache.page_tokens)
            at = (-(-pages // cache.chunk) - 1) * cache.layers
            yield tree
        elif at == 0:
            held, at = tree, -1
        elif held is not None:
            yield tree
            yield held
            held = None
        else:
            at -= 1
            yield tree


class KvHybrid(kv_pages.KvPages):
    def __init__(self, ctx, cache_cls):
        cfg, page = ctx.config, ctx.config["page"]
        if cfg["rpc"] != "stream_stream":
            raise ValueError(f"kv_hybrid streams, not {cfg['rpc']}")
        if ctx.fault is not None and ctx.fault not in FAULTS:
            raise ValueError(f"no fault {ctx.fault!r}: {FAULTS}")
        if ctx.fault is not None and ctx.fault != "reorder":
            cache_cls = faulty(cache_cls, ctx.fault)
        layers, leaves, slots = ref.state_geometry(cfg)
        if cfg["cache"]["layers"] + len(layers) != cfg["state"][
                "model_layers"]:
            raise ValueError("page layers and state layers are not the "
                             "model's layers")
        self.units = ref.units(cfg)
        self.fault = ctx.fault
        self.conns = int(ctx.traffic["connections"])
        self.cache = cache_cls(
            cfg["cache"]["layers"], cfg["cache"]["pages"], page["tokens"],
            page["width"], chunk_pages=cfg["chunk_tokens"] // page["tokens"],
            device=ctx.device, state_layers=layers,
            state_leaves={name: (shape, dt) for name, shape, dt in leaves},
            slots=slots)
        self.pool_bytes = int(self.cache.store.nbytes) + sum(
            int(s.nbytes) for s in self.cache.states)
        self._sums, self._take = kv_pages.programs(
            tuple(self.cache.store.shape[2:]))

    def _receiver(self, c: int):
        handoff = self.cache.receiver(c)
        if self.fault != "reorder":
            return handoff
        return lambda trees: handoff(_reordered(trees, self.cache))

    # -- what the harness asks after the window ---------------------------------

    def counts(self) -> list[int]:
        """Per connection, the units written: page-layers and states."""
        snap = self.cache.snapshot()
        page, state = self.units
        return [page * int(snap["written"].get(c, 0))
                + state * int(snap["states_written"].get(c, 0))
                for c in range(self.conns)]

    def audit(self, sample: dict):
        """``(facts, blobs)``: ``kv_pages``'s over the pages (``sample
        ["pages"]``), and every resident sequence's state checksums
        ``[state layer][leaf]`` as the device computes them over its slot,
        and the sampled states read back whole (``sampled_states``: ``[gid,
        state layer, slot]`` each, a blob its leaves' words one after the
        other); the blobs of the pages first."""
        facts, blobs = super().audit(sample["pages"])
        snap = self.cache.snapshot()
        states = self.cache.states
        sums = [np.asarray(state_programs(tuple(s.shape[2:]))[0](s))
                for s in states]
        resident = snap["resident"]
        facts.update(
            states_written=[int(snap["states_written"].get(c, 0))
                            for c in range(self.conns)],
            free_slots=snap["free_slots"],
            state_gids=[r[0] for r in resident],
            state_sums=[[[int(s[layer, r[6]]) for s in sums]
                         for layer in range(len(self.cache.state_layers))]
                        for r in resident],
            sampled_states=[])
        for a, b in sample["states"] if resident else ():
            gid, slot = resident[a % len(resident)][0::6]
            layer = b % len(self.cache.state_layers)
            facts["sampled_states"].append([gid, layer, slot])
            blobs.append(b"".join(
                np.asarray(state_programs(tuple(s.shape[2:]))[1](
                    s, np.int32(layer), np.int32(slot))).tobytes()
                for s in states))
        return json.loads(json.dumps(facts)), blobs


def build(ctx) -> KvHybrid:
    try:
        from tpurpc.serving.pages import KvPageCache
        if "state_layers" not in inspect.signature(KvPageCache).parameters:
            raise ImportError("tpurpc.serving.pages.KvPageCache keeps no "
                              "state slots")
    except ImportError as exc:
        # the server child's own way to end a run with no result
        sys.stdout.buffer.write(f"@fatal {json.dumps(str(exc))}\n".encode())
        sys.stdout.buffer.flush()
        sys.exit(3)
    return KvHybrid(ctx, KvPageCache)
