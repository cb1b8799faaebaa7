"""pool_batch_exchange: a batching store that answers every request with
the tensor its row displaces.

The deployment behind ``tensor_fanin_exchange_4m`` (PERF.md 1): eight front
ends or workers, each with several requests outstanding, against one process
that owns the chip and batches across them, where every request gets a
tensor back: a block or embedding store that swaps (a row in, the row it
displaces out), workers that push gradients and pull weights through a
batched update, a served model whose answer is a tensor. It is
``pool_batch``'s ingest with ``pool_exchange``'s answer.

Every connection streams its requests to its own method ``Swap<c>``,
registered ``add_tensor_method(device=True, kind="stream_stream")``. The
handler gives each landed message, as ONE row with its leases
(``DeviceRequests.take_leases`` -> ``FanInBatcher.submit(row, leases=...,
one_row=True)``), to ONE ``FanInBatcher`` shared by all connections, **yields
the row's future** and goes on to land the connection's next request: the
server writes each stream's replies in the order they were yielded, as they
resolve. The batcher's parameters are the class's defaults but for
``max_batch``, ``fixed_bucket`` and ``occupancy``. Its consumer is one
jitted, donated program a batch that

* reads batch slot ``b mod slots`` of a resident pool ``dtype[slots,
  max_rows, *shape]`` (``b`` the batch's ordinal, kept on the device) and
  writes the stacked batch there,
* folds every request row into its connection's running accumulator and
  every row that leaves into a second one (``pool_sink``'s fold; the
  connection is the request row's own second stamp word, read on the
  device),
* appends the request rows' stamps and the batch's row count to a
  device-side log,
* **returns what the slot held**, ``dtype[max_rows, *shape]``: the batcher
  reads it back once a batch, and row ``r`` of it is the reply to the request
  stacked at row ``r``. It is an output of the program that made the
  request resident, so it cannot leave before.

After the window each client makes one small ``Report<c>`` call with what it
saw (every reply's two stamp words as received, which replies it kept whole,
their checksums and how many of their bytes differ from what their stamps
name); ``audit`` hands that back among the facts, which is how the replies
*as the clients received them* reach the comparison that decides
``correct``.

``build`` ends the server with ``@fatal`` where the program cannot take a
future for a reply: the cell is not run in lock-step (PR 32's deployment).
"""

from __future__ import annotations

import functools
import json
import sys
import threading

import numpy as np

from benchmarks.handlers import pool_sink

#: what ``--fault`` may plant. On the way in, ``pool_batch``'s five:
#:   approx_bf16  every batch rounded to bfloat16's precision
#:   drop         every 7th message of a connection answered (with zeros)
#:                and never handed to the batcher
#:   alter        one bit flipped in one word of every row whose sequence
#:                number is 4 mod 5
#:   reorder      messages 0 and 1 of every 16 of a connection trade places
#:                on their way to the batcher
#:   dup          every 11th message of a connection stacked twice
#: and on the way out:
#:   reply_swap   replies 0 and 1 of every batch trade places, after the
#:                device has folded them
#:   reply_stale  the reply is taken from the slot after the write (the
#:                request itself comes back)
#:   reply_bf16   every reply rounded to bfloat16's precision; the pool
#:                stays exact
FAULTS = ("approx_bf16", "drop", "alter", "reorder", "dup", "reply_swap",
          "reply_stale", "reply_bf16")


@functools.lru_cache(maxsize=None)
def programs(shape: tuple, dtype: str, slots: int, max_rows: int, conns: int,
             log_batches: int, fault: str | None = None):
    """``(swap, facts, take)`` for one pool geometry, jitted once per
    process. ``shape`` is one message's; every payload dtype is 32 bits
    wide."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    dt = jnp.dtype(dtype)
    if dt.itemsize != 4:
        raise ValueError(f"pool_batch_exchange stores 32-bit elements, "
                         f"not {dt}")
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

    def approx(x):
        # not an astype round trip: the TPU compiler elides that
        return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def swap(pool, acc, acc_out, log_stamps, log_rows, b, batch, rows):
        """One stacked batch into its slot, what the slot held handed back.
        The ordinal lives on the device and comes back incremented; ``rows``
        is the batcher's resident occupancy scalar: nothing crosses from the
        host per batch."""
        n = rows.astype(jnp.int32)
        stamps = stamps_of(batch)
        if fault == "approx_bf16":
            batch = approx(batch)
        elif fault == "alter":
            hit = ((flat_index() == words // 2)[None]
                   & (stamps[:, 0] % 5 == 4).reshape(
                       (max_rows,) + (1,) * len(shape)))
            batch = lax.bitcast_convert_type(
                as_u32(batch) ^ jnp.where(hit, u32(1 << 9), u32(0)), dt)
        slot = (b % u32(slots)).astype(jnp.int32)
        start = (slot, 0) + zeros
        out = lax.dynamic_slice(pool, start, (1, max_rows) + shape)[0]
        if fault == "reply_stale":
            out = batch
        elif fault == "reply_bf16":
            out = approx(out)
        sums, left = row_sums(batch), row_sums(out)
        for i in range(max_rows):
            c = jnp.minimum(stamps[i, 1], u32(conns - 1)).astype(jnp.int32)
            acc = acc.at[c].set(jnp.where(
                i < n, acc[c] * u32(31) + sums[i], acc[c]))
            acc_out = acc_out.at[c].set(jnp.where(
                i < n, acc_out[c] * u32(31) + left[i], acc_out[c]))
        if fault == "reply_swap":
            # as 32-bit words: a float concatenate is free to flush the
            # seeded rows' denormals and rewrite their NaNs (on a v5e it
            # did, in a thousandth of their words)
            bits = as_u32(out)
            out = lax.bitcast_convert_type(
                jnp.concatenate([bits[1:2], bits[0:1], bits[2:]]), dt)
        pool = lax.dynamic_update_slice(pool, batch[None], start)
        at = jnp.minimum(b, u32(log_batches - 1)).astype(jnp.int32)
        log_stamps = lax.dynamic_update_slice(log_stamps, stamps[None],
                                              (at, 0, 0))
        log_rows = lax.dynamic_update_slice(log_rows, n[None], (at,))
        return pool, acc, acc_out, log_stamps, log_rows, b + u32(1), out

    def facts(pool):
        """Every pool row's checksum, ``[slots, max_rows]``."""
        return row_sums(pool)

    def take(pool, slot):
        return lax.dynamic_slice(pool, (slot, 0) + zeros,
                                 (1, max_rows) + shape)[0]

    return (jax.jit(swap, donate_argnums=(0, 1, 2, 3, 4, 5)),
            jax.jit(facts), jax.jit(take))


class _Conn:
    """One connection's side of the handler."""

    __slots__ = ("n", "report")

    def __init__(self):
        self.n = 0          # requests received (and handed to the batcher)
        self.report = None  # what the client said of its replies


def takes_futures() -> bool:
    """Whether this tree's server can answer a stream with a future."""
    from tpurpc.jaxshim import FanInBatcher
    from tpurpc.rpc.server import RpcMethodHandler

    return (callable(getattr(FanInBatcher, "submit", None))
            and hasattr(RpcMethodHandler, "late_serializer"))


class PoolBatchExchange:
    def __init__(self, ctx):
        import jax
        from jax.profiler import TraceAnnotation

        from tpurpc.jaxshim import FanInBatcher

        cfg = ctx.config
        if cfg["rpc"] != "stream_stream":
            raise ValueError("pool_batch_exchange answers on a "
                             f"stream_stream method, not {cfg['rpc']}")
        if ctx.fault is not None and ctx.fault not in FAULTS:
            raise ValueError(f"no fault {ctx.fault!r}: {FAULTS}")
        if not takes_futures():
            raise NotImplementedError(
                "this tree's server cannot take a future for a stream's "
                "reply (rpc/server.py RpcMethodHandler.late_serializer): a "
                "handler that owes every request a tensor would wait for "
                "each before it lands the next, and "
                "tensor_fanin_exchange_4m is not run in lock-step")
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
        self._swap, self._facts, self._take = programs(
            self.shape, self.dtype, self.slots, self.max_rows, self.conns,
            self.log_batches, self.fault)
        # the seeded words of pool_sink's connection 0, by its own program
        self.pool = pool_sink.programs(
            (self.max_rows,) + self.shape, self.dtype, self.slots)[0](
            np.uint32(ctx.seed & 0xFFFFFFFF), np.uint32(0))
        put = functools.partial(jax.device_put, device=self.device)
        self.acc = put(np.zeros(self.conns, np.uint32))
        self.acc_out = put(np.zeros(self.conns, np.uint32))
        self.log_stamps = put(np.zeros((self.log_batches, self.max_rows, 2),
                                       np.uint32))
        self.log_rows = put(np.zeros(self.log_batches, np.int32))
        self.b = put(np.uint32(0))
        self.batches = 0    # host-side twin of `b`, for the log's bound
        self.lock = threading.Lock()
        self.per_conn = [_Conn() for _ in range(self.conns)]
        # every other parameter is the class's default: max_delay_s and
        # d2h_workers are judged as they ship
        self.batcher = FanInBatcher(
            self._step, max_batch=self.max_rows,
            fixed_bucket=bool(cfg["batch"]["fixed_bucket"]), occupancy=True)

    # -- the timed path -------------------------------------------------------

    def _step(self, batch, rows):
        """One batch into the pool, what its slot held out of it: the whole
        of what the consumer does. Runs on the batcher's thread, one batch
        at a time; the batcher reads the result back and splits it."""
        x = batch["x"]
        for leaf in (x, rows):
            if (not isinstance(leaf, self._jax.Array)
                    or leaf.devices() != {self.device}):
                where = (leaf.devices() if isinstance(leaf, self._jax.Array)
                         else type(leaf))
                raise RuntimeError(f"the batch is on {where}, not on "
                                   f"{self.device}")
        with self.lock, self._annot("bench.pool_swap"):
            if self.batches >= self.log_batches:
                raise RuntimeError(
                    f"the batch log holds {self.log_batches} batches: raise "
                    "batch.log_batches for a run this long")
            (self.pool, self.acc, self.acc_out, self.log_stamps,
             self.log_rows, self.b, evicted) = self._swap(
                self.pool, self.acc, self.acc_out, self.log_stamps,
                self.log_rows, self.b, x, rows)
            self.batches += 1
        return {"y": evicted}

    def _hand_over(self, x, leases):
        """The row and its credit to the batcher: the future of its reply."""
        jax = self._jax
        if not isinstance(x, jax.Array) or x.devices() != {self.device}:
            where = x.devices() if isinstance(x, jax.Array) else type(x)
            for lease in leases:
                lease.release()
            raise RuntimeError(f"device=True leaf is on {where}, not on "
                               f"{self.device}")
        try:
            return self.batcher.submit({"x": x}, leases=leases, one_row=True)
        except BaseException:
            for lease in leases:
                lease.release()
            raise

    def register(self, server) -> None:
        from tpurpc.jaxshim import add_tensor_method

        for c, cn in enumerate(self.per_conn):
            add_tensor_method(server, f"Swap{c}", self._stream(cn),
                              kind="stream_stream", device=True)
            add_tensor_method(server, f"Report{c}", self._report(cn))

    def _stream(self, cn: _Conn):
        def swap(trees):
            held = None     # the `reorder` fault's request kept back
            while True:
                with self._annot("bench.wait_next_message"):
                    tree = next(trees, None)
                if tree is None:
                    break
                item = (tree["x"], trees.take_leases())
                k, cn.n = cn.n, cn.n + 1
                if self.fault == "drop" and k % 7 == 6:
                    for lease in item[1]:   # answered, never batched
                        lease.release()
                    yield {"y": np.zeros(self.shape, self.dtype)}
                elif self.fault == "reorder" and k % 16 == 0:
                    held = item
                elif held is not None:
                    late = self._hand_over(*item)
                    yield self._hand_over(*held)
                    held = None
                    yield late
                else:
                    yield self._hand_over(*item)
                    if self.fault == "dup" and k % 11 == 10:
                        self.batcher.submit({"x": item[0]}, one_row=True)
            if held is not None:
                yield self._hand_over(*held)
        return swap

    def _report(self, cn: _Conn):
        def report(tree):
            cn.report = {
                "first": int(np.ravel(tree["first"])[0]),
                "stamps": np.reshape(tree["stamps"], (-1, 2)).tolist(),
                "sampled": np.ravel(tree["sampled"]).tolist(),
                "sample_sums": np.ravel(tree["sample_sums"]).tolist(),
                "sample_bytes_wrong": int(
                    np.ravel(tree["sample_bytes_wrong"])[0])}
            return {"ok": np.int32(1)}
        return report

    # -- what the harness asks after the window ---------------------------------

    def sync(self) -> None:
        with self.lock:
            self.pool.block_until_ready()

    def counts(self) -> list[int]:
        """Per connection, the requests landed and handed to the batcher."""
        return [cn.n for cn in self.per_conn]

    def audit(self, sample: list[int]):
        """``(facts, blobs)``: ``pool_batch``'s (per connection the message
        count and the device's fold; the batch log and every pool row's
        checksum as the device holds them; the sampled batch slots read back
        whole), and per connection the device's fold over every row that
        left for it (``acc_out``) and what its client reported of the
        replies it received (``client``; None if it never did)."""
        with self.lock:
            b = int(np.asarray(self.b))
            kept = min(b, self.log_batches)
            facts = {
                "n": [cn.n for cn in self.per_conn],
                "acc": np.asarray(self.acc).tolist(),
                "acc_out": np.asarray(self.acc_out).tolist(),
                "client": [cn.report for cn in self.per_conn],
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
        self.pool = self.acc = self.acc_out = None
        self.log_stamps = self.log_rows = self.b = None


def build(ctx) -> PoolBatchExchange:
    try:
        return PoolBatchExchange(ctx)
    except NotImplementedError as exc:
        # the server child's own way to end a run with no result
        sys.stdout.buffer.write(f"@fatal {json.dumps(str(exc))}\n".encode())
        sys.stdout.buffer.flush()
        sys.exit(3)
