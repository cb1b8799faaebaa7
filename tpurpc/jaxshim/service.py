"""Tensor services: serve jitted JAX callables over tpurpc.

The ``grpcio-jax`` surface from BASELINE.json:

* :func:`add_tensor_method` / :class:`TensorClient` — unary and
  server-streaming tensor RPCs (config #3: server-streaming
  ``float32[1024,1024]`` → ``jax.Array``).
* :class:`FanInBatcher` — cross-connection request batching (config #4:
  8-client fan-in → 1 TPU server): requests landing on independent
  connections are stacked into one leading batch axis and dispatched as a
  single jitted call, amortizing kernel launch + keeping the MXU fed.

The reference has no equivalent — its apps are byte-oriented greeters
(``examples/cpp/helloworld.benchmark``); batching here is the TPU-first
replacement for "more pollers": one big matmul beats eight small ones.
"""

from __future__ import annotations

import bisect
import functools
import itertools
import threading
import time
from collections import deque
from concurrent.futures import Future, InvalidStateError
from typing import Any, Callable, Iterator, List, Optional, Sequence

import numpy as np

from tpurpc.jaxshim import codec
from tpurpc.obs import flight as _flight
from tpurpc.obs import lens as _lens
from tpurpc.obs import metrics as _metrics
from tpurpc.obs import profiler as _profiler
from tpurpc.obs import tracing as _tracing
from tpurpc.rpc.server import (Server, stream_stream_rpc_method_handler,
                               unary_stream_rpc_method_handler,
                               unary_unary_rpc_method_handler)
from tpurpc.utils.trace import TraceFlag

trace_jax = TraceFlag("jaxshim")

# tpurpc-lens (ISSUE 8) sampling-profiler frame markers: batching control
# flow is `batcher`, running a gathered batch on the model/device (and the
# cross-shard merged dispatch) is `device-dispatch`
_LENS_STAGES = {
    "_loop": "batcher",
    "_split_compatible": "batcher",
    "_stack": "batcher",
    "_concat_pad": "batcher",
    "_complete_loop": "batcher",
    "_run": "device-dispatch",
    "_merge_loop": "batcher",
    "_dispatch_group": "device-dispatch",
    "_run_one": "device-dispatch",
}
_profiler.register_stages(__file__, _LENS_STAGES)

# tpurpc-scope (ISSUE 4): fan-in batching observability. One histogram
# record + one counter bump per DISPATCHED BATCH (amortized by design);
# the flush-reason counters say WHY batches went out — a serving stack
# stuck on "timer" is leaving latency on the table, one stuck on
# "drained" with tiny batches is the batch-of-one fixed point ISSUE 3
# fought (see FanInBatcher._drained_inflight).
_FANIN_BATCH = _metrics.histogram("fanin_batch")
_BATCHER_BATCHES = _metrics.counter("batcher_batches")
_BATCHER_ROWS = _metrics.counter("batcher_rows")
#: the hand-over (ISSUE 35): times the batcher's thread went to sleep, and
#: submits that found it asleep and woke it. 1 - wakes / rows is the share
#: of hand-overs that touched nothing but the queue
_BATCHER_PARKS = _metrics.counter("batcher_parks")
_BATCHER_WAKES = _metrics.counter("batcher_handoff_wakes")
#: batches whose rows may wait for the reaper thread (FanInBatcher._retire)
_SPENT_BATCHES = 4
_FLUSH_REASONS = {
    reason: _metrics.counter(f"batcher_flush_{reason}")
    for reason in ("size", "timer", "drained", "close")
}
#: tpurpc-blackbox (ISSUE 5): live batcher queue depth at sweep/scrape
#: time — the watchdog's "batcher-wait" stage evidence (the queue is a
#: deque: its len() is one step, and no lock is there to take)
_BATCHER_DEPTH = _metrics.fleet("batcher_queue_depth",
                                lambda b: len(b._queue))

#: calls to a ``device=True`` tensor method that arrived over a transport
#: with no device ring (anything but GRPC_PLATFORM_TYPE=RDMA_TPU — a TCP or
#: h2 client may reach any server) and were handed host views instead: the
#: degrade is deliberate, and this is where it shows
_DEVICE_DEGRADED = _metrics.counter("tensor_device_degraded")

TENSOR_SERVICE = "tpurpc.Tensor"


def _method_path(name: str) -> str:
    return f"/{TENSOR_SERVICE}/{name}"


def _device_decoder(ctx):
    """Per-call request decoder: device-ring placement when the transport is
    the TPU platform, host-aliasing decode otherwise.

    Returns ``(decode(buf) -> tree, finish(), take() -> leases)``. Credit
    discipline: each ``decode`` releases the PREVIOUS message's leases (the
    handler advancing the request iterator means it is done with that
    message — the rolling analog of the host ring's drain-then-credit,
    ``pair.cc:276-284``), and ``finish`` releases the last message's when the
    handler returns (SURVEY §7 hard-part #4: leases gate the ring's credit
    return). A handler that is NOT done with a message when it asks for the
    next (it gave the row to a batcher) calls ``take``: the leases of the
    message it was just handed become the taker's to release, and neither
    the rolling rule nor ``finish`` touches them."""
    ring = getattr(ctx, "device_ring", None)
    if ring is None:
        _DEVICE_DEGRADED.inc()
        return codec.tree_deserializer, lambda: None, list
    from tpurpc.tpu.endpoint import decode_tree_to_ring

    held = []

    def decode(buf):
        for lease in held:
            lease.release()
        held.clear()
        tree, leases = decode_tree_to_ring(ring, buf)
        held.extend(leases)
        return tree

    def finish():
        for lease in held:
            lease.release()
        held.clear()

    def take():
        leases = list(held)
        held.clear()
        return leases

    return decode, finish, take


class DeviceRequests:
    """The request iterator a ``device=True`` stream handler is given.

    Iterating it is what it always was: each ``next`` decodes one message
    into the connection's device ring and returns the credit of the message
    before. :meth:`take_leases` is for a handler that passes a message on
    (to a :class:`FanInBatcher`, with ``submit(row, leases=...)``) and goes
    on to the next while the first is still in use."""

    __slots__ = ("_raws", "_decode", "_take")

    def __init__(self, raws, decode, take):
        self._raws = iter(raws)
        self._decode = decode
        self._take = take

    def __iter__(self) -> "DeviceRequests":
        return self

    def __next__(self):
        return self._decode(next(self._raws))

    def take_leases(self) -> list:
        """The leases (:class:`tpurpc.tpu.hbm_ring.HbmLease`) of the message
        the last ``next`` returned: the caller's to release from now on,
        each exactly once; the iterator's advance and the end of the call no
        longer do. Empty where the message holds none (a call that arrived
        without a device ring, or a second take)."""
        return self._take()


def add_tensor_method(server: Server, name: str,
                      fn: Callable[..., Any],
                      kind: str = "unary_unary",
                      device: bool = False) -> None:
    """Register ``fn(tree) -> tree`` as a tensor-typed method.

    ``fn`` receives the decoded request pytree (numpy views over the receive
    buffer; pass through :func:`tpurpc.jaxshim.codec.to_jax` or let jit trace
    them — jax treats numpy zero-copy on CPU backends). Its return pytree is
    encoded the same way.

    With ``device=True`` and the TPU platform
    (``GRPC_PLATFORM_TYPE=RDMA_TPU``), request payloads are placed into the
    connection's HBM receive ring and ``fn`` gets lease-backed device arrays;
    the leases (ring credit) are released when ``fn`` returns; a
    ``stream_stream`` ``fn`` is handed a :class:`DeviceRequests`, each
    message's credit returned as it asks for the next unless it took the
    message's leases over (``take_leases``), and may yield a
    ``concurrent.futures.Future`` of a reply where it would yield the reply
    (what :meth:`FanInBatcher.submit` returns): the server writes the
    stream's replies in the order they were yielded, each once it and every
    earlier one are resolved, and ``fn``'s thread goes on to the next
    request meanwhile (``rpc/server.py`` ``_DeferredReplies``). Device arrays
    in what ``fn`` returns (or yields) leave through
    :func:`tpurpc.tpu.serialize.tree_from_device`: every leaf's transfer to
    the host is started before any is awaited, each billed ``dma_d2h`` once,
    the wait its own ``d2h`` stage, and the reply's gather list aliases the
    transfers' landing buffers all the way to the writer. A call that
    arrives over any other transport has no device ring: ``fn`` then gets the
    host-aliasing decode (numpy views), and the ``tensor_device_degraded``
    counter says how often — a handler that needs device arrays checks
    ``isinstance(leaf, jax.Array)`` or reads that counter.
    """
    if not device:
        if kind == "unary_unary":
            def behavior(req, ctx):
                return fn(req)
            handler = unary_unary_rpc_method_handler(
                behavior, codec.tree_deserializer, codec.tree_serializer)
        elif kind == "unary_stream":
            def behavior(req, ctx):
                yield from fn(req)
            handler = unary_stream_rpc_method_handler(
                behavior, codec.tree_deserializer, codec.tree_serializer)
        elif kind == "stream_stream":
            def behavior(req_iter, ctx):
                yield from fn(req_iter)
            handler = stream_stream_rpc_method_handler(
                behavior, codec.tree_deserializer, codec.tree_serializer)
        else:
            raise ValueError(f"unsupported tensor method kind {kind}")
        server.add_method(_method_path(name), handler)
        return

    # device mode: identity deserializer (raw message bytes reach the
    # behavior), decode inside where ctx exposes the connection's ring.
    # Responses are serialized INSIDE the behavior, before finish(): a
    # reply's read-back sits inside the request's lease window, so the
    # credit a message holds covers the whole call; the handler's
    # serializer is identity. tree_from_device is the one outbound leg: a
    # reply's device leaves are read back there (on a TPU into fresh host
    # buffers, so the bytes the writer places are the reply's own).
    from tpurpc.tpu.serialize import tree_from_device

    _ident = lambda b: b  # noqa: E731 — already-encoded bytes pass through
    if kind == "unary_unary":
        def behavior(raw, ctx):
            decode, finish, _ = _device_decoder(ctx)
            try:
                return tree_from_device(fn(decode(raw)))
            finally:
                finish()
        handler = unary_unary_rpc_method_handler(
            behavior, codec.raw_view, _ident)
    elif kind == "unary_stream":
        def behavior(raw, ctx):
            decode, finish, _ = _device_decoder(ctx)
            try:
                for item in fn(decode(raw)):
                    yield tree_from_device(item)
            finally:
                finish()
        handler = unary_stream_rpc_method_handler(
            behavior, codec.raw_view, _ident)
    elif kind == "stream_stream":
        def behavior(raw_iter, ctx):
            decode, finish, take = _device_decoder(ctx)
            try:
                for item in fn(DeviceRequests(raw_iter, decode, take)):
                    # a future (a row's share of a batcher's result) goes
                    # on as it is, and this thread back to the next request
                    yield (item if isinstance(item, Future)
                           else tree_from_device(item))
            finally:
                finish()
        handler = stream_stream_rpc_method_handler(
            behavior, codec.raw_view, _ident)
        # a future's result is a tree, serialized where it is written
        handler.late_serializer = tree_from_device
    else:
        raise ValueError(f"unsupported tensor method kind {kind}")
    server.add_method(_method_path(name), handler)


class TensorClient:
    """Client for tensor methods; wraps a :class:`tpurpc.rpc.channel.Channel`
    (or a :class:`tpurpc.rpc.native_client.NativeChannel` for ``call`` /
    ``call_async``).

    ``depth`` bounds the per-method in-flight window ``call_async`` uses —
    the serving pipeline's client half (ISSUE 3): one connection sustains
    ``depth`` outstanding unary calls, demuxed by stream id, which is what
    lets the server's :class:`FanInBatcher` see real batches instead of a
    lockstep of ones."""

    def __init__(self, channel, depth: int = 16):
        self._channel = channel
        self.depth = max(1, depth)
        self._pipelines: dict = {}
        self._pl_lock = threading.Lock()

    def call(self, name: str, tree: Any, timeout: Optional[float] = None) -> Any:
        mc = self._channel.unary_unary(
            _method_path(name), codec.tree_serializer, codec.tree_deserializer)
        return mc(tree, timeout=timeout)

    def pipeline(self, name: str, depth: Optional[int] = None):
        """A bounded multi-in-flight caller for ``name``: an object with
        ``call_async(tree, timeout=None) -> Future``. Works on both the
        Python channel (``Channel.unary_unary(...).pipeline()``) and the
        native channel (CQ futures / inline window)."""
        depth = self.depth if depth is None else max(1, depth)
        mc = self._channel.unary_unary(
            _method_path(name), codec.tree_serializer, codec.tree_deserializer)
        pl = getattr(mc, "pipeline", None)
        if pl is not None:  # Python channel: stream-id-demuxed window
            return pl(depth)
        # NativeChannel: its .future() is already pipelined (CQ on
        # reader-thread channels, bounded worker window on inline-read);
        # wrap it behind the same bounded-window surface.
        return _NativePipeline(mc.future, depth)

    def call_async(self, name: str, tree: Any,
                   timeout: Optional[float] = None):
        """Pipelined unary call: returns a Future of the response tree.
        At most ``depth`` calls per method are in flight; the next
        ``call_async`` blocks until a slot frees (window backpressure)."""
        with self._pl_lock:
            pl = self._pipelines.get(name)
            if pl is None:
                pl = self._pipelines[name] = self.pipeline(name)
        return pl.call_async(tree, timeout=timeout)

    def call_device(self, name: str, tree: Any,
                    timeout: Optional[float] = None):
        """Unary call whose RESPONSE decodes into the channel's device ring.

        Returns a :class:`tpurpc.tpu.endpoint.DeviceMessage` — use it as a
        context manager (or call ``.release()``) so the ring credit returns.
        Falls back to a plain host decode (still wrapped in DeviceMessage,
        with no leases) when the channel's transport isn't the TPU platform.
        """
        from tpurpc.tpu.endpoint import DeviceMessage, decode_tree_to_ring

        mc = self._channel.unary_unary(
            _method_path(name), codec.tree_serializer, codec.raw_view)
        raw, call = mc.with_call(tree, timeout=timeout)
        # The call's OWN connection: an LB re-pick here could land the
        # response in a different connection's ring (or fail a finished call).
        ring = call.device_ring()
        if ring is None:
            return DeviceMessage(codec.decode_tree(raw), [])
        out, leases = decode_tree_to_ring(ring, raw)
        return DeviceMessage(out, leases)

    def stream(self, name: str, tree: Any,
               timeout: Optional[float] = None) -> Iterator[Any]:
        mc = self._channel.unary_stream(
            _method_path(name), codec.tree_serializer, codec.tree_deserializer)
        return mc(tree, timeout=timeout)

    def duplex(self, name: str, trees: Iterator[Any],
               timeout: Optional[float] = None,
               native: bool = True) -> Iterator[Any]:
        """Bidi tensor stream. ``native=True`` (default) rides the
        libtpurpc loop on eligible channels — round 5's same-weather A/B
        measured it ~40% faster on 4 MiB tensor streams (1.20 vs 0.86
        GB/s vs the Python plane; earlier rounds measured the opposite,
        which turned out to be the since-fixed notify-token-stealing bug,
        ring_transport.h wait_event). Ineligible channels (TPU device-ring
        platform, TLS, compression, multi-address) degrade to the Python
        transport automatically; pass ``native=False`` to force the
        instrumented Python plane (copy-ledger measurement runs)."""
        mc = self._channel.stream_stream(
            _method_path(name), codec.tree_serializer,
            codec.tree_deserializer, tpurpc_native=native)
        return mc(trees, timeout=timeout)


class _NativePipeline:
    """Window-bounded wrapper over a native ``.future`` — the native side
    already pipelines (CQ or inline worker window); this adds the same
    caller-facing backpressure contract PipelinedUnary has, so bench and
    serving code can treat the two planes identically."""

    def __init__(self, future_fn, depth: int):
        self._future_fn = future_fn
        self._window = threading.BoundedSemaphore(max(1, depth))

    def call_async(self, tree: Any, timeout: Optional[float] = None):
        self._window.acquire()
        try:
            fut = self._future_fn(tree, timeout=timeout)
        except BaseException:
            self._window.release()
            raise
        fut.add_done_callback(lambda _f: self._window.release())
        return fut

    def close(self) -> None:
        pass


# ---------------------------------------------------------------------------
# Fan-in batching (BASELINE config #4)
# ---------------------------------------------------------------------------

#: the name the stack program has in a device trace (``jit_<name>``): the
#: benchmark's ``batch_stack_roofline`` finds its device time by it
STACK_PROGRAM = "tpurpc_batch_stack"


@functools.lru_cache(maxsize=None)
def _stack_program(lift: bool = False):
    """The one program that gathers a batch of device-resident rows:
    ``stack(*rows) -> batch``, every leaf concatenated along its leading
    axis (``lift``: stacked along a new one, for rows that came without).
    Its compiled shape is fixed by the shapes of its arguments, so a batcher
    that always hands it ``bucket`` one-row arguments (requests first,
    resident zero rows after) compiles it once whatever the occupancy.
    Nothing is donated: the batch is a new buffer and aliases no row."""
    import jax
    import jax.numpy as jnp

    gather = jnp.stack if lift else jnp.concatenate

    def stack(*rows):
        return jax.tree_util.tree_map(
            lambda *xs: gather(xs, axis=0), *rows)

    stack.__name__ = stack.__qualname__ = STACK_PROGRAM
    return jax.jit(stack)


#: the largest piece a batch's result is read back in. On a v5e a
#: device-to-host transfer of up to 16 MiB runs at 13 GB/s among others
#: started with it and one of 32 MiB or more at 2.4 to 3.6 GB/s, holding up
#: the landings queued behind it (PERF.md 6, PR 36: 1, 2, 4, 8, 16 MiB read
#: 11.3, 13.4, 13.4, 13.4, 13.0 GB/s; 32 and 64 MiB 3.6 and 3.0): half the
#: largest size that was seen to run fast
_D2H_PIECE_BYTES = 8 << 20
#: the name the program that cuts a result into such pieces has in a device
#: trace (``jit_<name>``)
CUT_PROGRAM = "tpurpc_batch_cut"


@functools.lru_cache(maxsize=None)
def _cut_program(cuts: tuple):
    """The one program that cuts a result leaf along its leading axis at
    ``cuts`` (row offsets, first 0, last the leaf's rows): ``cut(x) ->
    pieces``, one dispatch whatever their number, one compiled shape a
    batch shape."""
    import jax

    def cut(x):
        return tuple(x[a:b] for a, b in zip(cuts, cuts[1:]))

    cut.__name__ = cut.__qualname__ = CUT_PROGRAM
    return jax.jit(cut)


class _Cut:
    """A leaf of a batch's result that is read back in pieces: rows
    ``cuts[i] .. cuts[i + 1]`` are ``pieces[i]`` (device arrays on the
    batcher's thread, their host copies after the completion thread).
    Indexed like the leaf it stands for, by a row or a slice of rows: a view
    of one piece where the rows lie in one (a one-row request always does),
    a copy where a request's rows straddle two. No pytree node: jax's tree
    functions hand it on as a leaf."""

    __slots__ = ("pieces", "cuts")

    def __init__(self, pieces, cuts):
        self.pieces, self.cuts = tuple(pieces), cuts

    @property
    def nbytes(self) -> int:
        return sum(p.nbytes for p in self.pieces)

    def __getitem__(self, rows):
        one = not isinstance(rows, slice)
        a, b = (rows, rows + 1) if one else (rows.start, rows.stop)
        i = bisect.bisect_right(self.cuts, a) - 1
        parts = []
        while a < b:
            lo, hi = self.cuts[i], self.cuts[i + 1]
            parts.append(self.pieces[i][a - lo:min(b, hi) - lo])
            a, i = min(b, hi), i + 1
        got = parts[0] if len(parts) == 1 else np.concatenate(parts)
        return got[0] if one else got


def _nbytes(x) -> int:
    n = getattr(x, "nbytes", None)
    return int(n) if n is not None else np.asarray(x).nbytes


class _Pending:
    """One request on its way through a :class:`FanInBatcher`. Everything the
    batcher needs to know of the ROW is worked out here, by the thread that
    submits it: its signature (what it must share with its batch's first
    row to stack with it), its device, its rows and bytes. The batcher's one
    thread compares and adds; it flattens no request tree."""

    __slots__ = ("tree", "leases", "one_row", "future", "tctx", "t_enq",
                 "sig", "dev", "rows", "nbytes", "strays", "err")

    def __init__(self, tree, leases=(), one_row=False):
        self.tree = tree
        self.one_row = one_row
        #: ring credit the row holds (``HbmLease``-like: ``release()``);
        #: the batcher returns it once, when the row has been stacked or has
        #: failed (:meth:`FanInBatcher._release`)
        self.leases = tuple(leases)
        self.future: Future = Future()
        #: tpurpc-scope: the calling RPC's trace context (captured from the
        #: handler thread's ambient) — the batcher thread turns it and the
        #: enqueue stamp into "batch-wait"/"infer" spans per request; the
        #: stamp alone feeds the `batch_wait` hop, always
        self.tctx = _tracing.current() if _tracing.LIVE else None
        #: why the row can stack with no batch (a scalar, an empty tree,
        #: leaves on two devices): it fails alone, through its future, when
        #: the batcher's thread comes to it; ``submit`` does not raise it
        self.err: Optional[Exception] = None
        self.sig = self.dev = None
        self.rows = self.nbytes = self.strays = 0
        self.t_enq = time.monotonic_ns()
        try:
            self._sign()
        except Exception as exc:
            self.err = exc

    def _sign(self) -> None:
        import jax

        leaves, treedef = jax.tree_util.tree_flatten(self.tree)
        if not leaves:
            raise ValueError("empty request tree")
        for x in leaves:
            if np.ndim(x) < 1 and not self.one_row:
                raise ValueError(
                    "batched request leaves need a leading batch axis")
            if not isinstance(x, jax.Array):
                self.strays += 1  # a host leaf: landed with its batch
                continue
            here = x.devices()
            if len(here) != 1 or self.dev not in (None, here):
                raise ValueError(
                    "batched request leaves must sit on one device, not "
                    f"{here} beside {self.dev}")
            self.dev = here
        lead = 0 if self.one_row else 1
        #: (tree structure, each leaf's row shape and dtype, one_row)
        self.sig = (treedef, tuple((np.shape(x)[lead:], np.dtype(
            getattr(x, "dtype", None) or np.asarray(x).dtype))
            for x in leaves), self.one_row)
        self.rows = 1 if self.one_row else np.shape(leaves[0])[0]
        self.nbytes = sum(_nbytes(x) for x in leaves)

    def resolve(self, result) -> None:
        try:
            self.future.set_result(result)
        except InvalidStateError:
            pass  # the caller cancelled: nobody is waiting

    def fail(self, error: BaseException) -> None:
        try:
            self.future.set_exception(error)
        except InvalidStateError:
            pass


def _fail_all(batch: "Sequence[_Pending]", error: BaseException) -> None:
    for p in batch:
        p.fail(error)


class FanInBatcher:
    """Stack concurrent requests from many connections into one jitted call.

    ``fn`` must accept arrays with a leading batch axis and be
    shape-polymorphic only in that axis (pad-to-bucket keeps XLA's compile
    cache small: batch is padded up to the next power of two ≤ max_batch).
    Each request contributes leading-axis rows; replies are split back out.

    Dispatch fires when ``max_batch`` rows are waiting or ``max_delay_s``
    elapsed since the first queued request — the same latency/throughput dial
    as the reference's busy-poll timeout (``GRPC_RDMA_BUSY_POLLING_TIMEOUT_US``,
    README.md:17-25), applied at the request level instead of the byte level.

    One way in, :meth:`submit`: FIFO, returns a
    ``concurrent.futures.Future`` of the request's share of ``fn``'s result.
    ``batcher(tree)`` is ``submit(tree).result()``: a unary handler parks in
    it as it always did. A stream handler that must not park (its thread is
    what lands the connection's next message) submits the row **with its
    leases** and goes on; what bounds the rows it can have landed and not
    yet stacked is the connection's credit window, which the batcher gives
    back (below). Order is kept: batches are cut from the queue in arrival
    order, stacked and handed to ``fn`` one at a time by one thread, so row
    ``k`` of a producer is in the batch of its row ``k + 1`` or an earlier
    one, and before it within a batch.

    **The hand-over crosses no lock** (ISSUE 35). Many producers, ONE
    consumer (the batcher's thread), and nothing between them that can
    block: the queue is a ``collections.deque``, whose ``append`` and
    ``popleft`` are atomic under the interpreter and cannot give it up. A
    lock here, however short its critical sections, is handed by the OS to
    a thread that then stands in line for the interpreter with the lock in
    hand, and everybody who arrives meanwhile queues behind it: on a v5e
    with eight saturated producers a ``submit`` waited 4.2 ms for the one
    lock this class had, 99% of the hand-over, and the batcher's thread
    1.2 ms twice a batch (PERF.md 6, PR 35). So:

    * ``submit`` works out the row's signature on ITS thread
      (:class:`_Pending`: the one ``tree_flatten`` a row costs), appends,
      and looks at ``_parked``. The batcher's thread compares precomputed
      tuples and does per-batch work only.
    * **Park, then look once more.** Before it sleeps, the batcher's thread
      raises ``_parked`` to the queue length it is waiting for (1: a first
      row, which starts ``max_delay_s``; ``max_batch``: a full batch; with
      ``inflight_fn`` one more than it has seen, since the depth-aware flush
      looks at every arrival) and re-reads the queue: a row appended before
      the flag went up is found by the re-read, one appended after sees the
      flag and sets ``_wake`` (``core/ctrlring.py``'s discipline). A
      ``submit`` that finds the flag down, or the queue still short of it,
      touches nothing but the queue: a saturated batcher never parks, and a
      closed-loop caller pays one wake a batch (``batcher_parks``,
      ``batcher_handoff_wakes``).
    * **Spent rows die on a thread of their own.** Letting go of a device
      array gives the interpreter up (its buffer is freed outside it), and
      a thread that does so eight times a batch stands in line for the
      interpreter eight times: on a v5e that was 7 ms of the batcher's
      10 ms a batch, at the one place where the last reference to the
      rows of the batch before went (PERF.md 6, PR 35). Once a batch is
      stacked and its credit returned, its rows' trees go to ``_spent``
      and the reaper thread drops them (at most ``_SPENT_BATCHES`` wait
      there: a reaper that is behind hands the work back).
    * ``close()`` raises ``_closed`` and wakes. A ``submit`` that races it
      either raises with the leases still the caller's, or its row is
      served or failed and its leases released by the batcher, exactly
      once: it reads ``_closed`` again AFTER its append and, finding it up,
      takes its own row back out (``deque.remove``, atomic); if the row is
      gone the batcher's thread has it and will serve it. The thread ends
      only on an empty queue seen after ``_closed``, so a row whose
      ``submit`` saw the flag down is always found.

    Where the rows come from decides how they are gathered (hop
    ``batch_stack``), and nothing else differs:

    * **host leaves** (numpy views over the receive buffer: what every
      method registered without ``device=True`` hands over): concatenated
      and padded in numpy, shipped by ONE ``jax.device_put``;
    * **device leaves** (the lease-backed arrays of
      ``add_tensor_method(..., device=True)`` on ``RDMA_TPU``): ONE dispatch
      of the stack program (:data:`STACK_PROGRAM`) on the device the leaves
      are on, handed the requests' rows and then resident zero rows up to
      the bucket. Each payload byte moves on the device once (ledger
      ``dma_d2d``, the payload alone, once a batch); with one-row requests
      and ``fixed_bucket`` the program has one compiled shape for every
      occupancy, so nothing compiles after the first dispatch. On a v5e the
      program gathers eight rows of 4 MiB at 80% of the HBM roofline
      (``batch_stack_roofline.fanin``, PERF.md 6, PR 33);
    * **a mix** (a TCP client reaching a ``device=True`` method beside
      ``RDMA_TPU`` ones: ``tensor_device_degraded``): the host leaves are
      landed on the batch's device by one ``device_put`` (``dma_h2d``), then
      stacked there with the rest. Leaves on two different devices do not
      batch: the later request fails alone, as a mis-shaped one does.
      ``transfer_dtype`` applies to all-host batches only.

    **Credit returns when the stack has run.** A batch whose rows came with
    leases is awaited (``block_until_ready`` on the stacked batch, on the
    batcher's thread) and every lease of its rows is released then, in queue
    order, which is each ring's own order: a row's HBM is in use until the
    copy is done, and the batch aliases nothing of a released row. The wait
    comes AFTER ``fn`` has been dispatched on the batch: both of the
    batcher's dispatches are made while the producers that wait for this
    credit are still parked, and not in a queue for the interpreter behind
    the eight threads the release wakes (on a v5e ``fn``'s dispatch took
    6.7 ms after the release and takes 2.7 ms before it, PERF.md 6, PR 33).
    A lease is released exactly once on every path: a row that cannot stack
    fails alone and returns its credit at once, a stack or an ``fn`` that
    raises returns the whole batch's, and ``close()`` serves what is queued
    first. ``submit`` on a closed batcher raises and takes nothing: the
    leases stay the caller's. A batch with no lease is not awaited.

    **One row, no batch axis.** ``submit(tree, one_row=True)`` takes a
    request as it landed, ``dtype[*shape]``, and the gather gives it its
    leading axis (``jnp.stack`` where it would be ``jnp.concatenate``): a
    stream handler then pays no dispatch of its own to make a row of a
    message. Its share of ``fn``'s result comes back without the axis too.

    **The row count.** With ``occupancy=True`` the consumer learns how many
    leading rows of the padded batch are requests: ``fn(batch, rows)``,
    ``rows`` an ``int32`` scalar resident on the batch's device (one of a
    handful of constants placed there once, so nothing crosses from the host
    per batch and the consumer's own program takes it as an operand). A
    consumer that keeps state needs it: pad rows are zeros, not requests.

    **The reply.** ``fn``'s result is split along the leading axis, each
    request's rows to its future. Where the result has a ``jax.Array`` leaf
    the batch goes to a completion thread, which materializes it on the host
    (hop ``batch_d2h``; ledger ``dma_d2h`` once an output leaf) and splits
    replies as numpy views:

    * the read-back is asked for a batch, not a request (splitting device
      arrays per request would pay ``max_batch`` dispatches), and NOW, on
      the batcher's thread, before its wait for the stacked batch, so that
      it queues behind the consumer and not behind the next stack (asked
      for by the completion thread instead, ``fanex4m_c8`` read 1.36 and
      1.82 GB/s where this order reads 1.85 and 1.86);
    * a leaf of more than ``_D2H_PIECE_BYTES`` that the host cannot address
      is cut on the device first, by ONE dispatch (:data:`CUT_PROGRAM`), and
      comes back in pieces (:class:`_Cut`): on a v5e one transfer of 32 MiB
      runs at 2.4 GB/s and holds up every landing queued behind it, its
      four pieces of 8 MiB at 13 GB/s (``fanex4m_c8`` 1.30 -> 1.84 GB/s,
      PERF.md 6, PR 36). A request whose rows lie in one piece gets a view
      of it; the pieces of a batch stay alive until the last reply that
      views them has been written;
    * batch N+1's stacking and device dispatch overlap batch N's d2h
      (``_inflight``'s bounded depth, so backpressure still reaches the
      batcher's thread and, through the credit it holds, the senders);
    * ``d2h_workers`` completion threads take different batches side by
      side. Where a stream's replies are written by the thread that resolved
      them (``rpc/server.py`` ``_DeferredReplies``) they are also the
      writers: eight 4 MiB replies a batch are 17 to 28 ms of one thread.
      ``fanex4m_c8`` judged the default of 4 with whole 32 MiB read-backs
      (1, 2 and 4 workers: 1.3025, 1.3171, 1.3205 GB/s: with one the
      completion thread was the pace at 25 ms a batch, with four the
      batcher's): the transfer set the rate whatever their number, and
      more than one keeps the pace on the batcher's thread.

    Where it has none (``None``, or host leaves: an ingest consumer's count
    a batch) no read-back is started and no completion thread touches the
    batch: the futures resolve on the batcher's thread as soon as ``fn``
    returns, and what ``fn`` left running on the device is ``fn``'s to
    await.
    """

    #: lock map (lint rule `lock`) + shard contract (lint rule `shard`,
    #: tpurpc-manycore). ``None``: no lock, by design: the queue, the park
    #: flag and the close flag are touched by atomic steps alone (a deque's
    #: ``append`` / ``popleft`` / ``remove``, a constant stored in a flag),
    #: many producers and this batcher's ONE thread as the consumer. They
    #: are SHARD-LOCAL all the same: cross-shard access is confined to the
    #: device merger's declared ``_MERGE_BOUNDARY``. The two tallies have
    #: more than one writer (the completion threads) and a lock of their
    #: own, which no producer ever takes.
    _GUARDED_BY = {"_queue": None, "_parked": None, "_closed": None,
                   "batches_run": "_tally", "rows_run": "_tally"}

    def __init__(self, fn: Callable[..., Any], max_batch: int = 8,
                 max_delay_s: float = 0.002, pad_to_bucket: bool = True,
                 fixed_bucket: bool = False, d2h_workers: int = 4,
                 transfer_dtype=None,
                 inflight_fn: Optional[Callable[[], int]] = None,
                 occupancy: bool = False):
        #: depth-aware flush (ISSUE 3): a callable reporting how many
        #: requests are currently in flight at the transport (arrived or
        #: being read, response not yet finished — Server.inflight_requests).
        #: When every in-flight request is already queued here, no further
        #: arrival can happen until responses go out, so waiting out
        #: max_delay_s is pure latency: flush now. None = timer/size only.
        self._inflight_fn = inflight_fn
        #: recent dispatched batch sizes — the depth-aware flush's
        #: hysteresis floor is their max, so one small ramp-up batch can't
        #: drag the floor down while the occupancy the server recently
        #: proved it can fill keeps premature flushes suppressed
        self._recent_batches: "deque[int]" = deque(maxlen=8)
        #: cast host-side batches to this dtype before the h2d (e.g.
        #: ``jnp.bfloat16`` when the model computes in bf16 anyway): the
        #: transfer is usually the serving bottleneck and this halves it.
        #: None = ship requests in their wire dtype.
        self.transfer_dtype = transfer_dtype
        self._fn = fn
        self.occupancy = occupancy
        #: resident constants of the device path, placed once each and kept:
        #: zero rows by (device, signature, rows), occupancies by (device, n)
        self._pads: dict = {}
        self._occupancies: dict = {}
        self._ordinals = itertools.count(1)  # a batch's `call` in its spans
        self.max_batch = max_batch
        self.max_delay_s = max_delay_s
        self.pad_to_bucket = pad_to_bucket
        #: always pad to max_batch: ONE compiled shape for single-row
        #: requests, the right trade on accelerators where each new batch
        #: shape recompiles (XLA static shapes) — wasted pad rows cost far
        #: less than a mid-serving compile stall. NOTE: a dispatch whose
        #: requests total MORE than max_batch rows (multi-row requests) still
        #: pads to that larger total and compiles its shape; the one-shape
        #: guarantee assumes ≤1 row per request or callers sizing max_batch
        #: to the true row bound.
        self.fixed_bucket = fixed_bucket
        self._queue: "deque[_Pending]" = deque()
        #: 0 while the batcher's thread is awake; asleep, the queue length
        #: it wants to be woken at (see the class docstring)
        self._parked = 0
        self._wake = threading.Event()
        self._closed = False
        self._tally = threading.Lock()
        self.batches_run = 0
        self.rows_run = 0
        import queue as _queue

        #: in-flight (dispatched, not yet materialized) batches; the bound is
        #: the pipeline depth — blocking put() backpressures the batcher
        #: thread, and through it the callers, when the device falls behind
        self._inflight: "_queue.Queue" = _queue.Queue(maxsize=max(2, d2h_workers))
        self._reaped = False  # set by close() after the workers are gone
        #: stacked batches' rows, on their way to the thread that drops them
        self._spent: "_queue.SimpleQueue" = _queue.SimpleQueue()
        _BATCHER_DEPTH.track(self)
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="tpurpc-batcher")
        self._completers = [
            threading.Thread(target=self._complete_loop, daemon=True,
                             name=f"tpurpc-batcher-d2h-{i}")
            for i in range(max(1, d2h_workers))]
        self._reaper = threading.Thread(target=self._reap_loop, daemon=True,
                                        name="tpurpc-batcher-reap")
        self._thread.start()
        self._reaper.start()
        for c in self._completers:
            c.start()

    def queue_depth(self) -> int:
        """Requests parked behind the transport (queued here + dispatched
        batches not yet materialized) — the tpurpc-fleet load report's
        queue-depth field (Server.set_load_provider wiring in serve_jax):
        on a model server THIS is where overload actually accumulates."""
        return len(self._queue) + self._inflight.qsize()

    def close(self) -> None:
        import queue as _queue

        self._closed = True
        self._wake.set()
        self._thread.join(timeout=5)
        self._spent.put(None)
        self._reaper.join(timeout=5)
        for _ in self._completers:   # one sentinel per completion worker,
            try:                      # after the last dispatched batch.
                # Generous timeout: a merely-backlogged (healthy) queue
                # drains and takes the sentinel; only a truly wedged
                # consumer set makes us give up so close() stays bounded.
                self._inflight.put(None, timeout=10)
            except _queue.Full:
                break
        for c in self._completers:
            c.join(timeout=5)
        if any(c.is_alive() for c in self._completers):
            # Workers are wedged in device work (unrecoverable device
            # stall) but still hold the queue's consumer role — if they
            # ever unwedge they will drain remaining batches, so failing
            # those batches now would be both premature and racy. Leave
            # the daemon threads to their fate.
            return
        self._reaped = True  # a still-blocked dispatch put now fails its batch
        # Shutdown race sweep: if the batcher thread outlived its join
        # timeout its final batch can land after the workers exited on
        # sentinels — fail those callers instead of stranding them on
        # their futures forever. (A put racing this sweep is covered by the
        # _reaped check in the dispatch loop: either the sweep sees the
        # item, or the put times out and fails the batch itself.)
        while True:
            try:
                item = self._inflight.get_nowait()
            except _queue.Empty:
                break
            if item is not None:
                _fail_all(item[0], RuntimeError("batcher closed"))

    def submit(self, tree: Any, leases: Sequence = (),
               one_row: bool = False) -> Future:
        """Queue one request and return at once: a future of its rows of
        ``fn``'s result. ``leases`` is the ring credit the request's arrays
        hold (what :meth:`DeviceRequests.take_leases` returned): the
        batcher's from here on, released when the row has been stacked (see
        the class docstring). ``one_row``: the leaves are ONE row with no
        batch axis. Raises ``RuntimeError`` on a closed batcher, the leases
        then still the caller's."""
        if self._closed:
            raise RuntimeError("batcher closed")
        p = _Pending(tree, leases, one_row)
        queue = self._queue
        queue.append(p)
        if self._closed:
            # close() came between the look and the append. The row is the
            # caller's again if it is still there to take back; if not, the
            # batcher's thread has it and serves it
            try:
                queue.remove(p)
            except ValueError:
                return p.future
            raise RuntimeError("batcher closed")
        want = self._parked
        if want and len(queue) >= want:
            self._parked = 0  # one wake a park: the next arrival sees 0
            _BATCHER_WAKES.inc()
            self._wake.set()
        return p.future

    def __call__(self, tree: Any) -> Any:
        return self.submit(tree).result()

    # -- batcher thread ------------------------------------------------------

    def _park(self, want: int, timeout: Optional[float] = None) -> None:
        """Sleep until the queue holds ``want`` rows, ``close()``, or
        ``timeout``. The flag goes up first and the queue is read once more
        after it, so no arrival falls between the look that decided to
        sleep and the sleep."""
        self._parked = want
        if len(self._queue) < want and not self._closed:
            _BATCHER_PARKS.inc()
            self._wake.wait(timeout)
        self._parked = 0
        self._wake.clear()

    def _loop(self) -> None:
        queue = self._queue
        while True:
            while not queue:
                # `_closed` before the queue: a submit that saw the flag
                # down appended before it went up, so this look finds it
                if self._closed and not queue:
                    return
                self._park(1)
            deadline = time.monotonic() + self.max_delay_s
            reason = None
            while len(queue) < self.max_batch and not self._closed:
                seen = len(queue)
                if self._drained_inflight():
                    reason = "drained"  # nobody else is coming
                    break
                left = deadline - time.monotonic()
                if left <= 0:
                    reason = "timer"
                    break
                self._park(self.max_batch if self._inflight_fn is None
                           else seen + 1, left)
            if reason is None:
                reason = ("size" if len(queue) >= self.max_batch
                          else "close")
            batch = []
            try:
                while len(batch) < self.max_batch:
                    batch.append(queue.popleft())
            except IndexError:
                pass  # short: a timer, a drain, a close (or a submit that
                # lost to close() took its row back)
            if batch:
                self._recent_batches.append(len(batch))
                _FLUSH_REASONS[reason].inc()
                _FANIN_BATCH.record(len(batch))
                # flight: one event per DISPATCHED batch — the flush
                # decision (reason + size) a latency postmortem replays
                _flight.emit(_flight.BATCH_FLUSH, 0,
                             _flight.FLUSH_REASON_CODE[reason], len(batch))
                self._run(batch)

    def _drained_inflight(self) -> bool:
        """True when the transport says every arrived-and-unanswered
        request is already in our queue — the depth-aware flush signal
        (runs on the batcher's thread, between its looks at the queue).

        Hysteresis: the early flush also requires the queue to have
        reached the max RECENT batch size. "Every in-flight request is
        queued" is trivially true in the stagger gap of a closed-loop
        client set (responses written, next requests still on the wire) —
        flushing there degenerates to batches of one (measured: 5× QPS
        collapse under fixed_bucket, which pads every dispatch to
        max_batch). Demanding recently-proven occupancy first keeps
        steady-state batching intact; the max over a sliding window (not
        just the last batch) means one small ramp-up batch can't drag the
        floor into the sticky batch-of-one fixed point, while a genuinely
        quiet batcher decays to immediate flushes within a window."""
        if self._inflight_fn is None or not self._queue:
            return False
        try:
            pending = self._inflight_fn()
        except Exception:
            return False  # a broken probe degrades to the timer, never hangs
        q = len(self._queue)
        floor = min(self.max_batch, max(self._recent_batches, default=1))
        return q >= max(1, pending) and q >= floor

    def _reap_loop(self) -> None:
        """Drop what the batcher's thread is done with: each ``get`` lets
        go of the rows of one batch, here."""
        while self._spent.get() is not None:
            pass

    def _retire(self, batch: "Sequence[_Pending]") -> None:
        """The rows of a batch that has been stacked (or has failed) are
        nobody's any more: their last references go to the reaper, unless
        it is behind. Spent rows hold HBM whose credit has gone back, so
        what may wait for the reaper is bounded; beyond it the rows die
        here, on the batcher's thread, which is what slows the producers
        down."""
        trees = [p.tree for p in batch]
        for p in batch:
            p.tree = None
        if self._spent.qsize() < _SPENT_BATCHES:
            self._spent.put(trees)

    @staticmethod
    def _release(batch: "Sequence[_Pending]") -> None:
        """Return the credit of every row of ``batch``, in queue order. A
        row's leases are dropped from it as they go, so that no second path
        finds them."""
        for p in batch:
            leases, p.leases = p.leases, ()
            for lease in leases:
                lease.release()

    def _split_compatible(self, batch: List[_Pending]) -> List[_Pending]:
        """Fail (individually) requests whose pytree structure, leaf
        row-shape/dtype or device can't stack with the batch's first valid
        row — one bad request must not poison its siblings' futures. A row
        that fails here is never stacked: its credit goes back at once.
        Each row's signature was worked out by its ``submit``: this compares
        them."""
        good: List[_Pending] = []
        ref = ref_dev = None
        for p in batch:
            err = p.err
            if err is None and ref is not None and p.sig != ref:
                err = ValueError(
                    "request incompatible with batch: leaf shapes/dtypes "
                    f"{p.sig[1]} vs {ref[1]} (or differing tree structure, "
                    "or one with a batch axis and one without)")
            if err is None and None not in (p.dev, ref_dev) and (
                    p.dev != ref_dev):
                err = ValueError(
                    f"request incompatible with batch: its leaves are on "
                    f"{p.dev}, the batch's on {ref_dev}")
            if err is None:
                ref, ref_dev = ref or p.sig, ref_dev or p.dev
                good.append(p)
                continue
            self._release((p,))
            p.fail(err)
        return good

    def _bucket(self, n: int) -> int:
        if self.fixed_bucket:
            return self.max_batch
        if not self.pad_to_bucket:
            return n
        b = 1
        while b < n:
            b <<= 1
        return min(b, self.max_batch)

    def _run(self, batch: List[_Pending]) -> None:
        """Stage 1 (batcher thread): stack, dispatch ``fn``, return the
        rows' credit, and either resolve the futures (a result with no
        device leaf) or enqueue the batch in flight.

        Does NOT wait for ``fn``'s work on the device: ``self._fn`` on a
        jitted function returns after async dispatch, and materialization
        happens on the completion thread so the next batch's stacking
        overlaps this batch's device time + d2h. What it does wait for is
        the stacked batch of rows that hold credit, AFTER ``fn`` has been
        dispatched on it (see the class docstring)."""
        import jax

        batch = self._split_compatible(batch)
        if not batch:
            return
        ordinal = next(self._ordinals)
        t_disp = time.monotonic_ns()
        # enqueue → dispatch: one op of `batch_wait` a request (billed once
        # a batch), and its "batch-wait" span where the call is traced
        waited = len(batch) * t_disp
        for p in batch:
            waited -= p.t_enq
            if p.tctx is not None:
                _tracing.record("batch-wait", p.tctx, p.t_enq,
                                t_disp - p.t_enq)
        _lens.account("batch_wait", waited, ops=len(batch))
        leased = any(p.leases for p in batch)
        sizes = [p.rows for p in batch]
        total = sum(sizes)
        bucket = max(self._bucket(total), total)
        try:
            with _lens.stage("batch_stack", call=ordinal,
                             seq=total) as stacking:
                try:
                    stacked, stacking.nbytes = self._stack(batch, bucket)
                    stacking.copy = stacking.nbytes
                    running = _lens.stage("batch_run", call=ordinal,
                                          seq=total).begin()
                    try:
                        if self.occupancy:
                            out = self._fn(stacked, self._resident_rows(
                                stacked, total))
                        else:
                            out = self._fn(stacked)
                        out, away = self._ask_back(out)
                    finally:
                        stacking.exclude(running.end(), running.cpu_ns)
                        if leased:
                            # a row's HBM is in use until the copy is done:
                            # the thread's one wait for the device, a hop
                            # of its own inside batch_stack
                            with _lens.stage("batch_ready", stacking.nbytes,
                                             call=ordinal, seq=total):
                                jax.block_until_ready(stacked)
                finally:
                    stacked = None
                    self._release(batch)
                    self._retire(batch)
        except Exception as e:  # deliver failure to every caller in the batch
            _fail_all(batch, e)
            return
        if not away:
            # nothing to read back: no completion thread touches the batch
            self._deliver(batch, sizes, total, out, t_disp)
            return
        # Bounded-backpressure put that stays shutdown-safe: once close()
        # has reaped the completion workers (_reaped), nobody will ever
        # drain the queue — fail this batch's callers instead of parking
        # them behind a put that can no longer complete.
        import queue as _queue

        closed = RuntimeError("batcher closed")
        while True:
            if self._reaped:
                _fail_all(batch, closed)
                return
            try:
                self._inflight.put(
                    (batch, sizes, total, out, t_disp, ordinal), timeout=0.25)
                break
            except _queue.Full:
                continue
        if self._reaped:
            # Reaping raced our successful put and close()'s sweep may have
            # already drained: self-sweep so no batch is ever stranded.
            while True:
                try:
                    item = self._inflight.get_nowait()
                except _queue.Empty:
                    return
                if item is not None:
                    _fail_all(item[0], closed)

    @staticmethod
    def _ask_back(out):
        """``(out, away)``: ask for every device leaf of ``fn``'s result on
        the host NOW (enqueued behind the compute), so that the completion
        thread waits for one thing, the result on the host, and not for the
        compute and then for a transfer it has yet to ask for. ``away``:
        the leaves asked for. A leaf the host cannot address and that is
        larger than ``_D2H_PIECE_BYTES`` is cut on the device first, by one
        dispatch, and comes back in pieces (a :class:`_Cut` in its place in
        ``out``): a transfer that large is several times slower a byte."""
        import jax

        from tpurpc.tpu.serialize import _on_device

        away: list = []

        def ask(leaf):
            if not isinstance(leaf, jax.Array):
                return leaf
            rows = leaf.shape[0] if leaf.ndim else 0
            if (leaf.nbytes > _D2H_PIECE_BYTES and rows > 1
                    and _on_device(leaf)):
                step = max(1, _D2H_PIECE_BYTES // (leaf.nbytes // rows))
                cuts = tuple(range(0, rows, step)) + (rows,)
                leaf = _Cut(_cut_program(cuts)(leaf), cuts)
                for piece in leaf.pieces:
                    piece.copy_to_host_async()
            else:
                leaf.copy_to_host_async()
            away.append(leaf)
            return leaf

        return jax.tree_util.tree_map(ask, out), away

    def _complete_loop(self) -> None:
        """Stage 2: the batch's result awaited on the host (asked for by
        :meth:`_ask_back`), numpy reply split. Each output leaf is billed
        once, as ``tpu/serialize.py`` bills a reply's: ``dma_d2h`` where the
        host cannot address it, however many pieces carried it."""
        import jax

        from tpurpc.tpu import ledger
        from tpurpc.tpu.serialize import _on_device

        def to_host(leaf):
            if isinstance(leaf, _Cut):
                ledger.dma_d2h(leaf.nbytes)
                return _Cut([np.asarray(p) for p in leaf.pieces], leaf.cuts)
            if not isinstance(leaf, jax.Array):
                return leaf
            if _on_device(leaf):
                ledger.dma_d2h(leaf.nbytes)
            else:
                ledger.zero_copy(leaf.nbytes)
            return np.asarray(leaf)

        while True:
            item = self._inflight.get()
            if item is None:
                return
            batch, sizes, total, out, t_disp, ordinal = item
            try:
                # one read-back a batch (in pieces where a leaf is large);
                # per-request splits below are host views, free of device
                # round trips
                with _lens.stage("batch_d2h", call=ordinal, seq=total) as st:
                    host = jax.tree_util.tree_map(to_host, out)
                    st.nbytes = sum(_nbytes(x) for x in
                                    jax.tree_util.tree_leaves(host))
                self._deliver(batch, sizes, total, host, t_disp)
            except Exception as e:
                _fail_all(batch, e)

    def _deliver(self, batch: List[_Pending], sizes: List[int], total: int,
                 host: Any, t_disp: int) -> None:
        """Split a batch's host-side result along the leading axis, each
        request's rows to its future (a ``one_row`` request's without the
        axis)."""
        import jax

        try:
            t_done = time.monotonic_ns()
            for p in batch:
                if p.tctx is not None:
                    # dispatch → materialized: the "infer" span (jitted
                    # call + whole-batch d2h, shared by the batch)
                    _tracing.record("infer", p.tctx, t_disp,
                                    t_done - t_disp, rows=total)
            _BATCHER_BATCHES.inc()
            _BATCHER_ROWS.inc(total)
            with self._tally:
                self.batches_run += 1
                self.rows_run += total
            if not jax.tree_util.tree_leaves(host):
                for p in batch:  # nothing to split: an ingest consumer
                    p.resolve(host)
                return
            off = 0
            for p, n in zip(batch, sizes):
                s = off if p.one_row else slice(off, off + n)
                p.resolve(jax.tree_util.tree_map(lambda x: x[s], host))
                off += n
        except Exception as e:
            _fail_all(batch, e)

    def _stack(self, batch: "Sequence[_Pending]", bucket: int):
        """``(stacked, payload bytes)``: the requests' rows gathered along
        the leading axis (``one_row`` requests: along a new one) and padded
        with zero rows up to ``bucket``, on a device. See the class
        docstring for the three cases."""
        import jax

        from tpurpc.tpu import ledger

        lift = batch[0].one_row
        rows = [p.tree for p in batch]
        payload = sum(p.nbytes for p in batch)
        there = next((p.dev for p in batch if p.dev is not None), None)
        if there is None:
            return jax.tree_util.tree_map(
                lambda *xs: self._concat_pad(xs, bucket, lift), *rows), payload
        (device,) = there
        if any(p.strays for p in batch):
            leaves = [x for t in rows for x in jax.tree_util.tree_leaves(t)]
            strays = [i for i, x in enumerate(leaves)
                      if not isinstance(x, jax.Array)]
            landed = jax.device_put([np.asarray(leaves[i]) for i in strays],
                                    device)
            ledger.dma_h2d(sum(x.nbytes for x in landed))
            for i, x in zip(strays, landed):
                leaves[i] = x
            treedef = batch[0].sig[0]
            n = treedef.num_leaves
            rows = [treedef.unflatten(leaves[k:k + n])
                    for k in range(0, len(leaves), n)]
        # pad with resident zero rows shaped like the first request (so that
        # one-row requests always make `bucket` arguments of one shape) and
        # one shorter remainder where its row count does not divide the gap
        unit = batch[0].rows
        gap = bucket - sum(p.rows for p in batch)
        pads = [self._resident_zeros(rows[0], n, device, lift)
                for n in [unit] * (gap // unit) + [gap % unit] if n]
        stacked = _stack_program(lift)(*rows, *pads)
        ledger.dma_d2d(payload)
        return stacked, payload

    def _resident_zeros(self, like, n: int, device, lift: bool = False):
        """A tree shaped like request ``like`` with ``n`` zero rows (``lift``:
        one row, with no batch axis, as ``like`` has none), placed on
        ``device`` once and kept."""
        import jax

        leaves, treedef = jax.tree_util.tree_flatten(like)
        shapes = [x.shape if lift else (n,) + x.shape[1:] for x in leaves]
        key = (device, treedef, tuple(shapes),
               tuple(x.dtype for x in leaves))
        zeros = self._pads.get(key)
        if zeros is None:
            zeros = self._pads[key] = jax.device_put(treedef.unflatten(
                [np.zeros(s, x.dtype) for s, x in zip(shapes, leaves)]),
                device)
        return zeros

    def _resident_rows(self, batch, n: int):
        """``n`` as an ``int32`` scalar on the device ``batch`` is on: one
        of a handful of constants, each placed once and kept."""
        import jax

        (device,) = jax.tree_util.tree_leaves(batch)[0].devices()
        rows = self._occupancies.get((device, n))
        if rows is None:
            rows = self._occupancies[device, n] = jax.device_put(
                np.int32(n), device)
        return rows

    def _concat_pad(self, xs: Sequence, bucket: int, lift: bool = False):
        """One leaf of an all-host batch: concat (``lift``: stack) and pad
        in numpy, ship the batch in ONE h2d (an N-array device-side
        concatenate would turn one bulk transfer into N small ones plus a
        device launch)."""
        import jax

        gather = np.stack if lift else np.concatenate
        cat = gather([np.asarray(x) for x in xs], axis=0)
        if (self.transfer_dtype is not None
                and np.issubdtype(cat.dtype, np.floating)):
            cat = cat.astype(self.transfer_dtype)  # halve h2d bytes
        deficit = bucket - cat.shape[0]
        if deficit > 0:
            pad = [(0, deficit)] + [(0, 0)] * (cat.ndim - 1)
            cat = np.pad(cat, pad)
        return jax.device_put(cat)


# ---------------------------------------------------------------------------
# Device-boundary merge (tpurpc-manycore, ISSUE 7)
# ---------------------------------------------------------------------------

#: tpurpc-manycore: device-merger observability — how many sub-batches each
#: merged dispatch gathered (1 = nothing to merge), and how often a merged
#: dispatch had to fall back to per-sub isolation
_MERGE_SUBS = _metrics.histogram("merge_subbatches")
_MERGE_DISPATCH = _metrics.counter("merge_dispatches")
_MERGE_ISOLATED = _metrics.counter("merge_isolated_failures")


class _SubBatch:
    """One shard's stacked-and-padded batch, in flight across the merge
    boundary. The shard's batcher thread parks on ``done`` while the merger
    dispatches; ``result``/``error`` come back resolved."""

    __slots__ = ("stacked", "rows", "done", "out", "err")

    #: shard contract (lint rule `shard`): a sub-batch belongs to ITS shard;
    #: its out/err may only be written across the shard boundary inside
    #: the merger's declared ``_MERGE_BOUNDARY`` functions
    _GUARDED_BY = {"out": "done", "err": "done"}

    def __init__(self, stacked, rows: int):
        self.stacked = stacked
        self.rows = rows
        self.done = threading.Event()
        self.out = None
        self.err: Optional[Exception] = None


class DeviceMerger:
    """Gather compatible sub-batches from per-shard batchers into ONE
    device dispatch (tpurpc-manycore tentpole part 3).

    Shards batch independently — each :class:`FanInBatcher` keeps its own
    queue, thread and flush policy — and meet the single accelerator only
    here: sub-batches are published through a lock-free
    :class:`~tpurpc.core.handoff.HandoffRing` (no cross-shard mutex on the
    hot path), and the one merger thread gathers whatever the other shards
    already committed, concatenates shape-compatible sub-batches along the
    batch axis, and dispatches once. The device stays saturated without the
    transport serializing on one shared batcher.

    Failure isolation extends PR 3's poison semantics across the boundary:
    a merged dispatch that fails is retried per sub-batch, so a mis-shaped
    (or poisoned) sub-batch fails ALONE — its siblings' requests complete.
    Incompatible signatures never co-dispatch in the first place (grouped
    by pytree structure + row shape/dtype).

    Note the merge trades one compiled shape for throughput: merging two
    bucket-B sub-batches dispatches 2B rows, a new XLA shape. Callers who
    need the strict one-shape guarantee keep ``n_shards=1`` (plain
    FanInBatcher) or size buckets for the merged total.
    """

    #: the ONLY functions allowed to mutate another shard's `_GUARDED_BY`
    #: state (lint rule `shard`): the merge loop and its resolve/fail arms
    _MERGE_BOUNDARY = ("_merge_loop", "_dispatch_group", "_resolve_sub",
                       "_fail_sub")

    def __init__(self, fn: Callable[[Any], Any], capacity: int = 64,
                 max_merge_subs: int = 8, gather_window_s: float = 0.0005):
        from tpurpc.core.handoff import HandoffRing

        self._fn = fn
        self.max_merge_subs = max(1, max_merge_subs)
        self.gather_window_s = gather_window_s
        self._ring = HandoffRing(capacity)
        self._closed = False
        self.dispatches = 0
        self.subs_merged = 0
        self._thread = threading.Thread(target=self._merge_loop, daemon=True,
                                        name="tpurpc-merge")
        self._thread.start()

    # -- shard-facing ---------------------------------------------------------

    def entry(self) -> Callable[[Any], Any]:
        """An ``fn``-shaped callable for one shard's FanInBatcher: publishes
        the stacked sub-batch across the boundary and parks until the
        merger resolves it. Returns HOST-side results (the merger owns the
        d2h), so the shard's completion stage degrades to a no-op split."""

        def dispatch(stacked):
            import jax

            rows = jax.tree_util.tree_leaves(stacked)[0].shape[0]
            sub = _SubBatch(stacked, rows)
            if not self._ring.publish(sub):
                raise RuntimeError("device merger closed")
            sub.done.wait()
            if sub.err is not None:
                raise sub.err
            return sub.out

        return dispatch

    def close(self) -> None:
        self._closed = True
        self._ring.close()
        self._thread.join(timeout=5)

    # -- the merge boundary (single consumer thread) --------------------------

    def _merge_loop(self) -> None:
        import time as _time

        while True:
            first = self._ring.take(timeout=0.25)
            if first is None:
                if self._closed:
                    return
                continue
            group = [first]
            # gather pass: drain what the other shards ALREADY committed,
            # then one brief window for shards mid-publish — bounded so a
            # lone sub-batch never waits on shards with nothing to say
            deadline = _time.monotonic() + self.gather_window_s
            while len(group) < self.max_merge_subs:
                nxt = self._ring.take_ready()
                if nxt is not None:
                    group.append(nxt)
                    continue
                if _time.monotonic() >= deadline:
                    break
                _time.sleep(self.gather_window_s / 4)
            for sig_group in self._partition(group):
                self._dispatch_group(sig_group)

    @staticmethod
    def _signature(sub: _SubBatch):
        import jax
        import numpy as _np

        leaves, td = jax.tree_util.tree_flatten(sub.stacked)
        return (td, tuple((tuple(_np.shape(x)[1:]),
                           str(getattr(x, "dtype", type(x))))
                          for x in leaves))

    def _partition(self, group: List[_SubBatch]) -> List[List[_SubBatch]]:
        """Group sub-batches that can legally concatenate (same pytree
        structure, row shape, dtype); order-preserving within a group."""
        buckets: dict = {}
        order: List[List[_SubBatch]] = []
        for sub in group:
            try:
                sig = self._signature(sub)
            except Exception:
                sig = ("bad", id(sub))
            lst = buckets.get(sig)
            if lst is None:
                lst = buckets[sig] = []
                order.append(lst)
            lst.append(sub)
        return order

    def _dispatch_group(self, group: List[_SubBatch]) -> None:
        import jax

        _MERGE_DISPATCH.inc()
        _MERGE_SUBS.record(len(group))
        if len(group) == 1:
            sub = group[0]
            try:
                self._resolve_sub(sub, self._run_one(sub.stacked))
            except Exception as exc:
                self._fail_sub(sub, exc)
            return
        try:
            merged = jax.tree_util.tree_map(
                lambda *xs: self._concat(xs), *[s.stacked for s in group])
            host = self._run_one(merged)
            self.subs_merged += len(group)
            off = 0
            for sub in group:
                sl = slice(off, off + sub.rows)
                self._resolve_sub(
                    sub, jax.tree_util.tree_map(lambda x: x[sl], host))
                off += sub.rows
        except Exception:
            # merged dispatch failed: isolate — each sub-batch dispatches
            # alone so a poisoned shard cannot fail its siblings (PR 3's
            # poison-isolation contract, lifted across the merge boundary)
            _MERGE_ISOLATED.inc()
            for sub in group:
                try:
                    self._resolve_sub(sub, self._run_one(sub.stacked))
                except Exception as exc:
                    self._fail_sub(sub, exc)

    def _run_one(self, stacked):
        """Dispatch + materialize to host: ONE d2h for the merged batch;
        the shards' split stages see numpy and pay nothing further."""
        import jax

        return jax.device_get(self._fn(stacked))

    @staticmethod
    def _resolve_sub(sub: _SubBatch, result) -> None:
        sub.out = result
        sub.done.set()

    @staticmethod
    def _fail_sub(sub: _SubBatch, exc: Exception) -> None:
        sub.err = exc
        sub.done.set()

    @staticmethod
    def _concat(xs):
        import numpy as _np

        return _np.concatenate([_np.asarray(x) for x in xs], axis=0)


class ShardedFanIn:
    """N independent FanInBatcher shards merging at the device boundary.

    Callers are striped round-robin across shards (one GIL-atomic
    ``next()`` — no shared lock on the request path); each shard batches
    on its OWN queue and thread and publishes through the merger's handoff
    ring.
    Drop-in for FanInBatcher where serve_jax wires one (``__call__``,
    ``queue_depth``, ``batches_run``, ``close``)."""

    def __init__(self, fn: Callable[[Any], Any], n_shards: int = 2,
                 max_batch: int = 8, max_delay_s: float = 0.002,
                 inflight_fn: Optional[Callable[[], int]] = None, **kw):
        self.merger = DeviceMerger(fn, capacity=max(8, 4 * n_shards))
        self.shards = [
            FanInBatcher(self.merger.entry(), max_batch=max_batch,
                         max_delay_s=max_delay_s, inflight_fn=inflight_fn,
                         **kw)
            for _ in range(max(1, n_shards))]
        import itertools as _it

        self._rr = _it.count()

    def __call__(self, tree: Any) -> Any:
        return self.shards[next(self._rr) % len(self.shards)](tree)

    def queue_depth(self) -> int:
        return sum(s.queue_depth() for s in self.shards)

    @property
    def batches_run(self) -> int:
        return sum(s.batches_run for s in self.shards)

    @property
    def rows_run(self) -> int:
        return sum(s.rows_run for s in self.shards)

    def close(self) -> None:
        for s in self.shards:
            s.close()
        self.merger.close()


def serve_jax(fn: Callable[[Any], Any], address: str = "127.0.0.1:0", *,
              name: str = "Call", batching: bool = False, max_batch: int = 8,
              max_delay_s: float = 0.002, max_workers: int = 32,
              batch_shards: int = 1):
    """One-liner: stand up a tensor server around a (jitted) callable.

    Returns ``(server, port, batcher_or_None)``; the caller stops the server.

    With ``batching`` the FanInBatcher is wired to the server's in-flight
    request count (depth-aware flush): when every request the transport has
    admitted is already queued, the batch dispatches immediately instead of
    waiting out ``max_delay_s`` — pipelined clients (``TensorClient.
    call_async``) fill batches, lockstep clients stop paying the delay.

    ``batch_shards > 1`` (tpurpc-manycore) splits the batcher into that many
    independent shards merging only at the device boundary
    (:class:`ShardedFanIn`): callers stop queueing behind one batcher thread,
    the accelerator still sees merged dispatches.
    """
    srv = Server(max_workers=max_workers)
    batcher = None
    if batching:
        if batch_shards > 1:
            batcher = ShardedFanIn(fn, n_shards=batch_shards,
                                   max_batch=max_batch,
                                   max_delay_s=max_delay_s,
                                   inflight_fn=srv.inflight_requests)
        else:
            batcher = FanInBatcher(fn, max_batch=max_batch,
                                   max_delay_s=max_delay_s,
                                   inflight_fn=srv.inflight_requests)
        add_tensor_method(srv, name, batcher)
        # tpurpc-fleet: the batcher's queue depth rides the per-response
        # load report, so a least_loaded client sees model-side queueing
        # the transport-level inflight count alone would miss
        srv.set_load_provider(batcher.queue_depth)
    else:
        add_tensor_method(srv, name, fn)
    srv.start()
    port = srv.add_insecure_port(address)  # after start: returns the bound port
    return srv, port, batcher


def _claim_device(shard_id: int, workers: int) -> None:
    """A shard worker whose model is a JAX model takes its device NOW, at
    start-up, instead of at its first request: a chip belongs to one process,
    and a worker that cannot have one must fail the supervisor's start —
    loudly, naming the reason — not die later behind a port that still
    answers. A numpy model (jax never imported) claims nothing."""
    import sys

    if "jax" not in sys.modules:
        return
    import jax

    try:
        jax.devices()
    except RuntimeError as exc:
        raise RuntimeError(
            f"shard worker {shard_id} of {workers} could not initialise its "
            "JAX backend. A chip belongs to one process: workers that hold a "
            "device model must not outnumber the chips (one chip each); "
            "batch_shards scales the host side of one chip in-process. "
            f"Cause: {exc}") from exc


def serve_jax_sharded(build_fn: Callable[[], Callable[[Any], Any]],
                      address: str = "127.0.0.1:0", *,
                      workers: int = 2, name: str = "Call",
                      batching: bool = True, max_batch: int = 8,
                      max_delay_s: float = 0.002, max_workers: int = 32,
                      batch_shards: int = 1, listener: str = "reuseport",
                      handoff_policy: str = "round_robin"):
    """tpurpc-manycore serving: N per-core worker processes on ONE port.

    ``build_fn`` constructs the model callable and runs IN EACH WORKER
    (post-fork) — model/XLA state must never cross a fork, so each shard
    owns a replica built in its own process, and the supervisor must not
    have initialised JAX. A worker that holds a JAX model holds a device:
    such workers must not outnumber the chips (one chip each, claimed at
    start-up — a worker that gets none fails ``start`` with the reason);
    ``batch_shards`` is the in-process way to scale the host side of one
    chip. Each worker is a full
    :func:`serve_jax` stack: its own poller, rings, thread pool, and
    (per-shard, merged-at-the-device-boundary when ``batch_shards > 1``)
    batcher. Returns the started
    :class:`tpurpc.rpc.shard.ShardedServer`; ``.port`` is the serving
    port, ``.stop()`` tears the fleet down.
    """
    from tpurpc.rpc.shard import ShardedServer

    def build(shard_id: int):
        fn = build_fn()
        _claim_device(shard_id, workers)
        srv = Server(max_workers=max_workers)
        if batching:
            if batch_shards > 1:
                batcher = ShardedFanIn(fn, n_shards=batch_shards,
                                       max_batch=max_batch,
                                       max_delay_s=max_delay_s,
                                       inflight_fn=srv.inflight_requests)
            else:
                batcher = FanInBatcher(fn, max_batch=max_batch,
                                       max_delay_s=max_delay_s,
                                       inflight_fn=srv.inflight_requests)
            add_tensor_method(srv, name, batcher)
            srv.set_load_provider(batcher.queue_depth)
        else:
            add_tensor_method(srv, name, fn)
        return srv

    return ShardedServer(build, workers=workers, address=address,
                         listener=listener,
                         handoff_policy=handoff_policy).start()
