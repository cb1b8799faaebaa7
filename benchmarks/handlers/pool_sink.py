"""pool_sink: a receiver that keeps the newest N tensors resident in HBM.

The deployment behind every tensor cell (PERF.md 1): a decode worker's KV
store, a learner's replay buffer, a loader's device-side shuffle buffer. Each
connection owns a *pool shard* on the device, ``dtype[slots, *shape]``, made
in set-up by one jitted program from the seed. ``add_tensor_method(...,
device=True)`` hands the handler lease-backed arrays whose ring credit goes
back when the handler returns, so a consumer that retains data must copy it
out: one donated ``dynamic_update_slice`` into slot ``seq mod slots``. The same
program folds a position-weighted checksum of the message into a running
per-connection accumulator, which is how every message of the window, and not
only the ones still resident at its end, reaches the comparison.

A handler module gives the harness one function, ``build(ctx)``, and the
object it returns has ``register(server)``, ``sync()``, ``counts()``,
``audit(sample)`` and ``free()``. ``ctx.fault`` plants the faults the tests
and the control need (see ``FAULTS``); the benchmark's own runs plant none.
"""

from __future__ import annotations

import functools
import threading

import numpy as np

#: what ``--fault`` may plant, each one a guarantee of the configuration
#: broken where the answer is produced:
#:   approx_bf16  every payload rounded to bfloat16's precision ("approximated")
#:   drop         every 7th message acknowledged and not stored ("dropped":
#:                the step returns its state unchanged)
#:   alter        one bit flipped in one word of every 5th message
#:   reorder      messages 0 and 1 of every 16 trade slots ("order")
FAULTS = ("approx_bf16", "drop", "alter", "reorder")

_M1, _M2, _M3 = 2654435761, 2246822519, 3266489917


def init_words_np(seed: int, conn: int, first: int, count: int) -> np.ndarray:
    """Words ``first .. first+count`` of connection ``conn``'s shard as the
    device made them: the plain twin of ``_init`` below, kept here so that the
    two formulas sit side by side (the reference imports this, and numpy)."""
    i = np.arange(first, first + count, dtype=np.uint64).astype(np.uint32)
    with np.errstate(over="ignore"):
        v = ((i + np.uint32(1)) * np.uint32(_M1)
             + np.uint32(seed & 0xFFFFFFFF) * np.uint32(_M2)
             + np.uint32(conn) * np.uint32(_M3))
        v ^= v >> np.uint32(15)
        v *= np.uint32(_M2)
        v ^= v >> np.uint32(13)
    return v


@functools.lru_cache(maxsize=None)
def programs(shape: tuple, dtype: str, slots: int, fault: str | None = None):
    """``(init, put, slot_sums, take)`` for one shard geometry, jitted once
    per process. Every payload dtype here is 32 bits wide."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    dt = jnp.dtype(dtype)
    if dt.itemsize != 4:
        raise ValueError(f"pool_sink stores 32-bit elements, not {dt}")
    words = int(np.prod(shape))
    zeros = (0,) * len(shape)

    def as_u32(x):
        return x if x.dtype == jnp.uint32 else lax.bitcast_convert_type(
            x, jnp.uint32)

    def flat_index(dims):
        """Row-major index of every element of an array of shape ``dims``,
        built from iotas: no reshape, so no relayout."""
        i, stride = jnp.uint32(0), 1
        for d in range(len(dims) - 1, -1, -1):
            i = i + lax.broadcasted_iota(jnp.uint32, dims, d) * jnp.uint32(
                stride)
            stride *= dims[d]
        return i

    def weights():
        return flat_index(shape) * jnp.uint32(2) + jnp.uint32(1)

    def init(seed, conn):
        i = flat_index((slots,) + shape)
        v = ((i + jnp.uint32(1)) * jnp.uint32(_M1) + seed * jnp.uint32(_M2)
             + conn * jnp.uint32(_M3))
        v = v ^ (v >> 15)
        v = v * jnp.uint32(_M2)
        v = v ^ (v >> 13)
        return lax.bitcast_convert_type(v, dt)

    def put(pool, acc, seq, x):
        """Message ``seq`` into its slot. The sequence number lives on the
        device and comes back incremented: a scalar handed over from the host
        costs a transfer of its own per call (0.2 ms each on a v5e, probe,
        PR 25), which would be the handler's cost and not the program's."""
        slot = seq % jnp.uint32(slots)
        if fault == "reorder":
            slot = jnp.where(seq % 16 < 2, (seq ^ jnp.uint32(1))
                             % jnp.uint32(slots), slot)
        if fault == "approx_bf16":
            # not astype(bfloat16).astype(dt): the TPU compiler elides that
            # round trip (excess precision is allowed by default) and the
            # control then stores exact data (my chip run, PR 25)
            x = lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        elif fault == "alter":
            hit = (flat_index(shape) == words // 2) & (seq % 5 == 4)
            x = lax.bitcast_convert_type(
                as_u32(x) ^ jnp.where(hit, jnp.uint32(1 << 9), jnp.uint32(0)),
                dt)
        acc = acc * jnp.uint32(31) + jnp.sum(as_u32(x) * weights(),
                                             dtype=jnp.uint32)
        start = (slot.astype(jnp.int32),) + zeros
        return (lax.dynamic_update_slice(pool, x[None], start), acc,
                seq + jnp.uint32(1))

    def slot_sums(pool):
        return jnp.sum(as_u32(pool) * weights()[None],
                       axis=tuple(range(1, pool.ndim)), dtype=jnp.uint32)

    def take(pool, slot):
        return lax.dynamic_slice(pool, (slot,) + zeros, (1,) + shape)[0]

    return (jax.jit(init), jax.jit(put, donate_argnums=(0, 1, 2)),
            jax.jit(slot_sums), jax.jit(take))


class _Shard:
    __slots__ = ("pool", "acc", "seq", "n", "lock")

    def __init__(self, pool, acc, seq):
        self.pool, self.acc, self.seq, self.n = pool, acc, seq, 0
        self.lock = threading.Lock()


class PoolSink:
    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp
        from jax.profiler import TraceAnnotation

        cfg = ctx.config
        self.shape = tuple(cfg["message"]["shape"])
        self.dtype = cfg["message"]["dtype"]
        self.rpc = cfg["rpc"]
        self.conns = int(ctx.traffic["connections"])
        nbytes = int(np.prod(self.shape)) * 4
        self.slots = int(cfg["pool"]["bytes"]) // nbytes // self.conns
        if self.slots < 1:
            raise ValueError("pool smaller than one message per connection")
        self.device = ctx.device
        self.fault = ctx.fault
        self._annot = TraceAnnotation
        self._jax = jax
        init, self._put, self._sums, self._take = programs(
            self.shape, self.dtype, self.slots, self.fault)
        seed = np.uint32(ctx.seed & 0xFFFFFFFF)
        self.shards = [
            _Shard(init(seed, np.uint32(c)),
                   jax.device_put(jnp.uint32(0), self.device),
                   jax.device_put(jnp.uint32(0), self.device))
            for c in range(self.conns)]
        self.pool_bytes = self.slots * nbytes * self.conns

    # -- the timed path -------------------------------------------------------

    def _store(self, sh: _Shard, x) -> None:
        """One message into its slot: the whole of what the handler does."""
        jax = self._jax
        if not isinstance(x, jax.Array) or x.devices() != {self.device}:
            where = x.devices() if isinstance(x, jax.Array) else type(x)
            raise RuntimeError(f"device=True leaf is on {where}, not on "
                               f"{self.device}")
        with sh.lock, self._annot("bench.pool_put"):
            if self.fault == "drop" and sh.n % 7 == 6:
                sh.seq = sh.seq + 1  # acknowledged, counted, not stored
            else:
                sh.pool, sh.acc, sh.seq = self._put(
                    sh.pool, sh.acc, sh.seq, x)
            sh.n += 1

    def register(self, server) -> None:
        from tpurpc.jaxshim import add_tensor_method

        for c, sh in enumerate(self.shards):
            if self.rpc == "stream_stream":
                add_tensor_method(server, f"Put{c}", self._stream(sh),
                                  kind="stream_stream", device=True)
            elif self.rpc == "unary_unary":
                add_tensor_method(server, f"Put{c}", self._unary(sh),
                                  kind="unary_unary", device=True)
            else:
                raise ValueError(f"pool_sink has no {self.rpc} method")
            add_tensor_method(server, f"Sync{c}", self._sync(sh))

    def _stream(self, sh: _Shard):
        def sink(trees):
            it = iter(trees)
            while True:
                with self._annot("bench.wait_next_message"):
                    tree = next(it, None)
                if tree is None:
                    break
                self._store(sh, tree["x"])
            sh.pool.block_until_ready()  # the reply says: resident
            yield {"n": np.int64(sh.n)}
        return sink

    def _unary(self, sh: _Shard):
        def put(tree):
            self._store(sh, tree["x"])
            return {"seq": np.int64(sh.n - 1)}
        return put

    def _sync(self, sh: _Shard):
        def sync(tree):
            with sh.lock:
                sh.pool.block_until_ready()
                return {"n": np.int64(sh.n)}
        return sync

    # -- what the harness asks after the window ---------------------------------

    def sync(self) -> None:
        for sh in self.shards:
            with sh.lock:
                sh.pool.block_until_ready()

    def counts(self) -> list[int]:
        return [sh.n for sh in self.shards]

    def audit(self, sample: list[list[int]]):
        """``(facts, blobs)``: per connection the message count, the running
        fold and every slot's checksum as the device computes them, and the
        bytes of the sampled slots read back."""
        facts, blobs = [], []
        for c, sh in enumerate(self.shards):
            with sh.lock:
                sums = np.asarray(self._sums(sh.pool))
                facts.append({"n": sh.n, "acc": int(np.asarray(sh.acc)),
                              "slot_sums": sums.tolist()})
                for slot in sample[c]:
                    row = np.asarray(self._take(sh.pool, np.int32(slot)))
                    blobs.append(row.tobytes())
        return facts, blobs

    def free(self) -> None:
        for sh in self.shards:
            sh.pool.delete()
            sh.pool = sh.acc = sh.seq = None


def build(ctx) -> PoolSink:
    return PoolSink(ctx)
