"""pool_batch: a receiver that takes its tensors a batch at a time.

The deployment behind ``tensor_fanin_batch_4m`` (PERF.md 1): a learner fed by
eight actors, a device-side shuffle or replay buffer with eight loaders, an
embedding or KV ingest with eight front ends. The consumer wants batches; the
senders do not know of each other. Every connection streams its messages to
its own method ``Put<c>``, registered ``add_tensor_method(device=True,
kind="stream_stream")``, so each lands in HBM by its connection's credit
window. The handler gives every landed message to ONE ``FanInBatcher`` shared
by all connections, as ONE row **with its leases**
(``DeviceRequests.take_leases`` -> ``FanInBatcher.submit(row, leases=...,
one_row=True)``: the gather gives it its leading axis, ``[1, *shape]``),
and goes on to land the connection's next message: landing, stacking and
consuming overlap, bounded by the credit a connection holds. The batcher
stacks ``max_rows`` rows on the device and hands the batch, not the message,
to the consumer: one jitted, donated program a batch that

* writes the batch into batch slot ``b mod slots`` of a resident pool
  ``dtype[slots, max_rows, *shape]`` (``b`` the batch's ordinal, kept on the
  device),
* folds every request row into its connection's running accumulator on the
  device (``acc[c] = acc[c] * 31 + checksum(row)``, ``pool_sink``'s fold; the
  connection and the sequence number are the row's own two stamp words, read
  on the device), which is how every message of the window, and not only the
  ones still resident at its end, reaches the comparison,
* appends the rows' stamps and the batch's row count to a device-side log.

One reply a stream, ``{n}``, after every row of the stream has been consumed
and ``block_until_ready``: that reply acknowledges the stream's messages.

A handler module gives the harness one function, ``build(ctx)``, and the
object it returns has ``register(server)``, ``sync()``, ``counts()``,
``audit(sample)`` and ``free()``. ``build`` ends the server with ``@fatal``
where the program's batcher has no non-blocking entry: a tree without it
would run this deployment in lock-step, which is another deployment.
"""

from __future__ import annotations

import functools
import json
import sys
import threading

import numpy as np

from benchmarks.handlers import pool_sink

#: what ``--fault`` may plant, each one a guarantee of the configuration
#: broken where the answer is produced:
#:   approx_bf16  every batch rounded to bfloat16's precision
#:   drop         every 7th message of a connection acknowledged and left
#:                out of its batch (never handed to the batcher)
#:   alter        one bit flipped in one word of every row whose sequence
#:                number is 4 mod 5
#:   reorder      messages 0 and 1 of every 16 of a connection trade places
#:                on their way to the batcher
#:   dup          every 11th message of a connection stacked twice
FAULTS = ("approx_bf16", "drop", "alter", "reorder", "dup")


@functools.lru_cache(maxsize=None)
def programs(shape: tuple, dtype: str, slots: int, max_rows: int, conns: int,
             log_batches: int, fault: str | None = None):
    """``(consume, facts, take)`` for one pool geometry, jitted once
    per process. ``shape`` is one message's; every payload dtype is 32 bits
    wide."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    dt = jnp.dtype(dtype)
    if dt.itemsize != 4:
        raise ValueError(f"pool_batch stores 32-bit elements, not {dt}")
    words = int(np.prod(shape))
    zeros = (0,) * len(shape)
    u32 = jnp.uint32

    def as_u32(x):
        return x if x.dtype == u32 else lax.bitcast_convert_type(x, u32)

    def flat_index():
        """Row-major index of every element of a message, built from iotas:
        no reshape, so no relayout."""
        i, stride = u32(0), 1
        for d in range(len(shape) - 1, -1, -1):
            i = i + lax.broadcasted_iota(u32, shape, d) * u32(stride)
            stride *= shape[d]
        return i

    def row_sums(x):
        """``payloads.checksum_np`` of every row of ``x[..., *shape]``."""
        w = flat_index() * u32(2) + u32(1)
        lead = x.ndim - len(shape)
        return jnp.sum(as_u32(x) * w[(None,) * lead],
                       axis=tuple(range(lead, x.ndim)), dtype=u32)

    def stamps_of(x):
        """The first two words of every row of ``x[n, *shape]``: the
        sequence number and the connection (``payloads.Bank``)."""
        first = lax.slice(x, (0,) * x.ndim,
                          (x.shape[0],) + (1,) * (len(shape) - 1) + (2,))
        return as_u32(first).reshape((x.shape[0], 2))

    def consume(pool, acc, log_stamps, log_rows, b, batch, rows):
        """One stacked batch into its slot. The ordinal lives on the device
        and comes back incremented; ``rows`` is the batcher's resident
        occupancy scalar: nothing crosses from the host per batch."""
        n = rows.astype(jnp.int32)
        stamps = stamps_of(batch)
        if fault == "approx_bf16":
            # not an astype round trip: the TPU compiler elides that
            batch = lax.reduce_precision(batch, exponent_bits=8,
                                         mantissa_bits=7)
        elif fault == "alter":
            hit = ((flat_index() == words // 2)[None]
                   & (stamps[:, 0] % 5 == 4).reshape(
                       (max_rows,) + (1,) * len(shape)))
            batch = lax.bitcast_convert_type(
                as_u32(batch) ^ jnp.where(hit, u32(1 << 9), u32(0)), dt)
        sums = row_sums(batch)
        for i in range(max_rows):
            c = jnp.minimum(stamps[i, 1], u32(conns - 1)).astype(jnp.int32)
            acc = acc.at[c].set(jnp.where(
                i < n, acc[c] * u32(31) + sums[i], acc[c]))
        slot = (b % u32(slots)).astype(jnp.int32)
        pool = lax.dynamic_update_slice(pool, batch[None], (slot, 0) + zeros)
        at = jnp.minimum(b, u32(log_batches - 1)).astype(jnp.int32)
        log_stamps = lax.dynamic_update_slice(log_stamps, stamps[None],
                                              (at, 0, 0))
        log_rows = lax.dynamic_update_slice(log_rows, n[None], (at,))
        return pool, acc, log_stamps, log_rows, b + u32(1)

    def facts(pool):
        """Every pool row's checksum, ``[slots, max_rows]``."""
        return row_sums(pool)

    def take(pool, slot):
        return lax.dynamic_slice(pool, (slot, 0) + zeros,
                                 (1, max_rows) + shape)[0]

    return (jax.jit(consume, donate_argnums=(0, 1, 2, 3, 4)),
            jax.jit(facts), jax.jit(take))


class _Conn:
    """One connection's side of the handler: what it has handed over and
    what has come back. ``done`` runs on the batcher's thread (or on the
    handler's, for a future that was already resolved)."""

    def __init__(self):
        self.n = 0          # messages received (what the reply states)
        self.sent = 0       # rows handed to the batcher
        self.back = 0       # ... whose batch the consumer has been given
        self.error: BaseException | None = None
        self.cond = threading.Condition()

    def done(self, fut) -> None:
        with self.cond:
            self.back += 1
            if self.error is None and not fut.cancelled():
                self.error = fut.exception()
            self.cond.notify_all()

    def settle(self) -> None:
        """Wait until every row handed over has been consumed."""
        with self.cond:
            while self.back < self.sent and self.error is None:
                self.cond.wait(1.0)
            if self.error is not None:
                raise self.error


class PoolBatch:
    def __init__(self, ctx):
        import jax
        from jax.profiler import TraceAnnotation

        from tpurpc.jaxshim import FanInBatcher

        cfg = ctx.config
        if cfg["rpc"] != "stream_stream":
            raise ValueError("pool_batch answers on a stream_stream method, "
                             f"not {cfg['rpc']}")
        if ctx.fault is not None and ctx.fault not in FAULTS:
            raise ValueError(f"no fault {ctx.fault!r}: {FAULTS}")
        if not callable(getattr(FanInBatcher, "submit", None)):
            raise NotImplementedError(
                "this tree's FanInBatcher has no submit(): rows cannot be "
                "handed over with their credit, and tensor_fanin_batch_4m "
                "is not run in lock-step")
        self.shape = tuple(cfg["message"]["shape"])
        self.dtype = cfg["message"]["dtype"]
        self.conns = int(ctx.traffic["connections"])
        self.max_rows = int(cfg["batch"]["max_rows"])
        self.log_batches = int(cfg["batch"]["log_batches"])
        nbytes = int(np.prod(self.shape)) * 4
        self.slots = int(cfg["pool"]["bytes"]) // (nbytes * self.max_rows)
        if self.slots < 1:
            raise ValueError("pool smaller than one batch")
        self.pool_bytes = self.slots * self.max_rows * nbytes
        self.device = ctx.device
        self.fault = ctx.fault
        self._jax, self._annot = jax, TraceAnnotation
        self._consume, self._facts, self._take = programs(
            self.shape, self.dtype, self.slots, self.max_rows, self.conns,
            self.log_batches, self.fault)
        # the seeded words of pool_sink's connection 0, by its own program
        self.pool = pool_sink.programs(
            (self.max_rows,) + self.shape, self.dtype, self.slots)[0](
            np.uint32(ctx.seed & 0xFFFFFFFF), np.uint32(0))
        put = functools.partial(jax.device_put, device=self.device)
        self.acc = put(np.zeros(self.conns, np.uint32))
        self.log_stamps = put(np.zeros((self.log_batches, self.max_rows, 2),
                                       np.uint32))
        self.log_rows = put(np.zeros(self.log_batches, np.int32))
        self.b = put(np.uint32(0))
        self.batches = 0    # host-side twin of `b`, for the log's bound
        self.lock = threading.Lock()
        self.per_conn = [_Conn() for _ in range(self.conns)]
        self.batcher = FanInBatcher(
            self._step, max_batch=self.max_rows,
            max_delay_s=float(cfg["batch"]["max_delay_ms"]) / 1e3,
            fixed_bucket=bool(cfg["batch"]["fixed_bucket"]), occupancy=True)

    # -- the timed path -------------------------------------------------------

    def _step(self, batch, rows):
        """One batch into the pool: the whole of what the consumer does.
        Runs on the batcher's thread, one batch at a time. Returns nothing:
        the batcher starts no read-back for it."""
        x = batch["x"]
        for leaf in (x, rows):
            if (not isinstance(leaf, self._jax.Array)
                    or leaf.devices() != {self.device}):
                where = (leaf.devices() if isinstance(leaf, self._jax.Array)
                         else type(leaf))
                raise RuntimeError(f"the batch is on {where}, not on "
                                   f"{self.device}")
        with self.lock, self._annot("bench.pool_put"):
            if self.batches >= self.log_batches:
                raise RuntimeError(
                    f"the batch log holds {self.log_batches} batches: raise "
                    "batch.log_batches for a run this long")
            (self.pool, self.acc, self.log_stamps, self.log_rows,
             self.b) = self._consume(self.pool, self.acc, self.log_stamps,
                                     self.log_rows, self.b, x, rows)
            self.batches += 1

    def _hand_over(self, cn: _Conn, x, leases) -> None:
        jax = self._jax
        if not isinstance(x, jax.Array) or x.devices() != {self.device}:
            where = x.devices() if isinstance(x, jax.Array) else type(x)
            for lease in leases:
                lease.release()
            raise RuntimeError(f"device=True leaf is on {where}, not on "
                               f"{self.device}")
        row = {"x": x}
        try:
            fut = self.batcher.submit(row, leases=leases, one_row=True)
        except BaseException:
            for lease in leases:
                lease.release()
            raise
        cn.sent += 1
        fut.add_done_callback(cn.done)
        if self.fault == "dup" and cn.n % 11 == 10:
            cn.sent += 1
            self.batcher.submit(row, one_row=True).add_done_callback(cn.done)

    def register(self, server) -> None:
        from tpurpc.jaxshim import add_tensor_method

        for c, cn in enumerate(self.per_conn):
            add_tensor_method(server, f"Put{c}", self._stream(cn),
                              kind="stream_stream", device=True)
            add_tensor_method(server, f"Sync{c}", self._sync)

    def _stream(self, cn: _Conn):
        def put(trees):
            held = None     # the `reorder` fault's message kept back
            while True:
                with self._annot("bench.wait_next_message"):
                    tree = next(trees, None)
                if tree is None:
                    break
                if cn.error is not None:
                    raise cn.error
                item = (tree["x"], trees.take_leases())
                if self.fault == "drop" and cn.n % 7 == 6:
                    for lease in item[1]:   # acknowledged, never batched
                        lease.release()
                elif self.fault == "reorder" and cn.n % 16 == 0:
                    held = item
                else:
                    self._hand_over(cn, *item)
                    if held is not None:
                        self._hand_over(cn, *held)
                        held = None
                cn.n += 1
            if held is not None:
                self._hand_over(cn, *held)
            cn.settle()
            with self.lock:
                self.pool.block_until_ready()  # the reply says: resident
            yield {"n": np.int64(cn.n)}
        return put

    def _sync(self, tree):
        self.sync()
        return {"n": np.int64(sum(cn.n for cn in self.per_conn))}

    # -- what the harness asks after the window ---------------------------------

    def sync(self) -> None:
        with self.lock:
            self.pool.block_until_ready()

    def counts(self) -> list[int]:
        """Per connection, the messages whose batch the consumer has been
        given: rows stacked, pad rows not."""
        return [cn.back for cn in self.per_conn]

    def audit(self, sample: list[int]):
        """``(facts, blobs)``: per connection the message count and the
        device's fold; the batch log (every batch's row count and its rows'
        two stamp words) and every pool row's checksum as the device holds
        them; the sampled batch slots read back whole."""
        with self.lock:
            b = int(np.asarray(self.b))
            kept = min(b, self.log_batches)
            facts = {
                "n": [cn.n for cn in self.per_conn],
                "acc": np.asarray(self.acc).tolist(),
                "batches": b,
                "log_rows": np.asarray(self.log_rows)[:kept].tolist(),
                "log_stamps": np.asarray(self.log_stamps)[:kept].tolist(),
                "row_sums": np.asarray(self._facts(self.pool)).tolist()}
            blobs = [np.asarray(self._take(self.pool, np.int32(s))).tobytes()
                     for s in sample]
        return facts, blobs

    def free(self) -> None:
        self.batcher.close()
        self.pool.delete()
        self.pool = self.acc = self.log_stamps = self.log_rows = self.b = None


def build(ctx) -> PoolBatch:
    try:
        return PoolBatch(ctx)
    except NotImplementedError as exc:
        # the server child's own way to end a run with no result
        sys.stdout.buffer.write(f"@fatal {json.dumps(str(exc))}\n".encode())
        sys.stdout.buffer.flush()
        sys.exit(3)
