"""pool_exchange: a tensor store that answers each block pushed with the
block it displaces.

The deployment behind ``tensor_pingpong_4m`` (PERF.md 1): a replay buffer an
actor pushes to and samples from, a KV offload tier that swaps one block out
and another in, a parameter server's push-gradient / pull-weights. The pool,
its geometry, its seeded initial words and its audit are ``pool_sink``'s; what
differs is the timed path: one jitted, donated program per message reads slot
``seq mod slots``, writes the message there, folds BOTH (what came in, what
goes out) into running per-connection accumulators on the device, and returns
the evicted tensor, which the behavior yields as the reply. The reply is an
output of the program that made the message resident, so it cannot leave
before the message is in its slot; it leaves through the program's own
outbound leg (``tpurpc.tpu.serialize.tree_from_device``).

After the window each client makes one small ``Report<c>`` call with what it
saw (every reply's two stamp words as received, which replies it kept whole,
how many of their bytes differ from the reference's); ``audit`` hands that
back among the facts, which is how the replies *as the client received them*
reach the comparison that decides ``correct`` (PERF.md 6, PR 28).
"""

from __future__ import annotations

import functools

import numpy as np

from benchmarks.handlers import pool_sink

#: ``pool_sink``'s four, each a guarantee on what the pool holds, and two on
#: the reply:
#:   stale_reply  the reply is the slot's NEW content (the message itself),
#:                not what the slot held before it
#:   reply_bf16   the reply rounded to bfloat16's precision ("approximated",
#:                in the outbound direction); the pool stays exact
FAULTS = pool_sink.FAULTS + ("stale_reply", "reply_bf16")


@functools.lru_cache(maxsize=None)
def swap_program(shape: tuple, dtype: str, slots: int,
                 fault: str | None = None):
    """``swap(pool, acc_in, acc_out, seq, x) -> (pool, acc_in, acc_out,
    seq + 1, evicted)`` for one shard geometry, jitted once per process, its
    first four arguments donated. The sequence number lives on the device
    (a scalar handed over from the host costs a transfer of its own)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    dt = jnp.dtype(dtype)
    words = int(np.prod(shape))
    zeros = (0,) * len(shape)

    def as_u32(x):
        return x if x.dtype == jnp.uint32 else lax.bitcast_convert_type(
            x, jnp.uint32)

    def flat_index():
        i, stride = jnp.uint32(0), 1
        for d in range(len(shape) - 1, -1, -1):
            i = i + lax.broadcasted_iota(jnp.uint32, shape, d) * jnp.uint32(
                stride)
            stride *= shape[d]
        return i

    def checksum(x):
        w = flat_index() * jnp.uint32(2) + jnp.uint32(1)
        return jnp.sum(as_u32(x) * w, dtype=jnp.uint32)

    def swap(pool, acc_in, acc_out, seq, x):
        slot = seq % jnp.uint32(slots)
        if fault == "reorder":
            slot = jnp.where(seq % 16 < 2, (seq ^ jnp.uint32(1))
                             % jnp.uint32(slots), slot)
        start = (slot.astype(jnp.int32),) + zeros
        old = lax.dynamic_slice(pool, start, (1,) + shape)[0]
        if fault == "approx_bf16":
            # not an astype round trip: the TPU compiler elides that
            x = lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        elif fault == "alter":
            hit = (flat_index() == words // 2) & (seq % 5 == 4)
            x = lax.bitcast_convert_type(
                as_u32(x) ^ jnp.where(hit, jnp.uint32(1 << 9), jnp.uint32(0)),
                dt)
        folded = acc_in * jnp.uint32(31) + checksum(x)
        if fault == "drop":  # acknowledged, counted, answered, not stored
            dropped = seq % 7 == 6
            x = jnp.where(dropped, old, x)
            folded = jnp.where(dropped, acc_in, folded)
        out = old
        if fault == "stale_reply":
            out = x
        elif fault == "reply_bf16":
            out = lax.reduce_precision(old, exponent_bits=8, mantissa_bits=7)
        left = acc_out * jnp.uint32(31) + checksum(out)
        return (lax.dynamic_update_slice(pool, x[None], start), folded, left,
                seq + jnp.uint32(1), out)

    return jax.jit(swap, donate_argnums=(0, 1, 2, 3))


class _Shard(pool_sink._Shard):
    __slots__ = ("acc_out", "report")

    def __init__(self, pool, acc, seq, acc_out):
        super().__init__(pool, acc, seq)
        self.acc_out, self.report = acc_out, None


class PoolExchange(pool_sink.PoolSink):
    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp

        if ctx.config["rpc"] != "stream_stream":
            raise ValueError("pool_exchange answers on a stream_stream "
                             f"method, not {ctx.config['rpc']}")
        if ctx.fault is not None and ctx.fault not in FAULTS:
            raise ValueError(f"no fault {ctx.fault!r}: {FAULTS}")
        super().__init__(ctx)
        self._swap = swap_program(self.shape, self.dtype, self.slots,
                                  self.fault)
        self.shards = [
            _Shard(sh.pool, sh.acc, sh.seq,
                   jax.device_put(jnp.uint32(0), self.device))
            for sh in self.shards]

    # -- the timed path -------------------------------------------------------

    def _exchange(self, sh: _Shard, x):
        """One message into its slot, what the slot held handed back."""
        jax = self._jax
        if not isinstance(x, jax.Array) or x.devices() != {self.device}:
            where = x.devices() if isinstance(x, jax.Array) else type(x)
            raise RuntimeError(f"device=True leaf is on {where}, not on "
                               f"{self.device}")
        with sh.lock, self._annot("bench.pool_put"):
            sh.pool, sh.acc, sh.acc_out, sh.seq, evicted = self._swap(
                sh.pool, sh.acc, sh.acc_out, sh.seq, x)
            sh.n += 1
        return evicted

    def register(self, server) -> None:
        from tpurpc.jaxshim import add_tensor_method

        for c, sh in enumerate(self.shards):
            add_tensor_method(server, f"Swap{c}", self._stream(sh),
                              kind="stream_stream", device=True)
            add_tensor_method(server, f"Report{c}", self._report(sh))

    def _stream(self, sh: _Shard):
        def exchange(trees):
            it = iter(trees)
            while True:
                with self._annot("bench.wait_next_message"):
                    tree = next(it, None)
                if tree is None:
                    return
                yield {"y": self._exchange(sh, tree["x"])}
        return exchange

    def _report(self, sh: _Shard):
        def report(tree):
            sh.report = {
                "first": int(np.ravel(tree["first"])[0]),
                "stamps": np.reshape(tree["stamps"], (-1, 2)).tolist(),
                "sampled": np.ravel(tree["sampled"]).tolist(),
                "sample_bytes_wrong": int(
                    np.ravel(tree["sample_bytes_wrong"])[0])}
            return {"ok": np.int32(1)}
        return report

    # -- what the harness asks after the window ---------------------------------

    def audit(self, sample: list[list[int]]):
        """``pool_sink``'s facts and blobs, and per connection the device's
        fold over everything that left (``acc_out``) and what the client
        reported of the replies it received (``client``; None if it never
        did)."""
        facts, blobs = super().audit(sample)
        for fact, sh in zip(facts, self.shards):
            with sh.lock:
                fact["acc_out"] = int(np.asarray(sh.acc_out))
                fact["client"] = sh.report
        return facts, blobs

    def free(self) -> None:
        super().free()
        for sh in self.shards:
            sh.acc_out = None


def build(ctx) -> PoolExchange:
    return PoolExchange(ctx)
