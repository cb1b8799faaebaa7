"""tpurpc-keystone: a paged KV cache in HBM, the decode side of a
disaggregated handoff.

In disaggregated serving (DeepSeek's separate prefill and decode pools,
Mooncake) a prefill worker streams a finished sequence's KV cache to the
decode instance that will generate from it, layer by layer as layer-wise
chunked prefill emits it. :class:`KvPageCache` is what receives it: ONE
device array ``[layers, pages, *page]`` that holds the pages' bits, and host
metadata over it: a free list (:class:`~tpurpc.serving.kv.KvBlockManager`'s
allocator), one block table per sequence shared by every layer (the layout
of vLLM and FlashMLA), the order in which sequences were acknowledged, and a
record of admissions, acknowledgements and evictions (:meth:`snapshot`).

The stream protocol, one ``stream_stream`` call of
``add_tensor_method(device=True)`` a prefill worker (:meth:`receiver`)::

    offer  {"offer": int32[2]}         (seq, n_tokens): the source's number
                                       for the sequence and its length, read
                                       on the host
    pages  {"pages": uint16[n, T, W]}  one layer's pages of one chunk, as the
                                       16-bit words of their bf16, chunk
                                       by chunk and layer by layer within a
                                       chunk: message k of a sequence of P'
                                       pages is layer k mod L of pages
                                       [cC, cC + n), c = k div L, n = min(C,
                                       P' - cC)
    ...                                (ceil(P'/C) x L page messages)
    ack    {"seq": int64, "pages": int32}  a Future the stream yields after
                                       the last message, resolved once
                                       every write of the sequence is done
                                       on the device

**A cache with state** (``state_layers``, for a hybrid model whose
linear-attention layers keep a fixed-size recurrent state a sequence beside
the paged KV of its full-attention layers) numbers the model's layers
``0 ... layers + S - 1``: the ``S`` in ``state_layers`` each keep a state of
the leaves ``state_leaves`` gives (``{name: (shape, dtype)}``, unsigned
words), the other ``layers`` keep pages. Each of ``slots`` slots holds one
sequence's state in every state layer, one store a leaf ``[S, slots,
*shape]``. A sequence's messages are then its page messages chunk by chunk
as above, except that the LAST chunk goes in model layer order: a state
layer as one state message ``{name: leaf, ...}`` (the state is final only
once prefill has run its last chunk), a page layer as its pages. So a
sequence of C chunks takes ``C x layers + S`` messages. Admission takes a
slot with the pages, eviction and a drop give both back, the state messages
are written by a second program on the same writing thread in the
sequence's order, and the acknowledgement waits for the last write of
either kind. A cache with no state layers is the page cache alone.

**Admission** (:meth:`admit`, the OFFER of ``serving/disagg.py``) allocates
all of a sequence's pages at once, and its slot, in the order offers came.
To make room it evicts the OLDEST ACKNOWLEDGED sequences (their decode is
over) and never one that is not acknowledged; short of room it waits. Since
every admitted sequence already holds all its pages and its slot, the wait
ends as soon as those ahead of it are acknowledged: it cannot deadlock. A
sequence with more pages than the cache fails its own stream, with an
error.

**The write** is ONE jitted, donated scatter a dispatch,
``jit_tpurpc_kv_pages``, one compiled shape a page count 1 ... C. A message
lands as the ``[n, T, W]`` array it was sent as, and any program that reads
it is compiled for that ``n``: so the scatter is, all C shapes built side by
side in :meth:`_warm`, and no page is padded on the wire or on the device.
A dispatch may carry a *run*: a message and the same sequence's next ones,
already queued behind it, of one kind, up to a kind's longest program. A
run of pages is full chunks (``n = C``, the count every chunk but a
sequence's last has) in consecutive layers of one chunk, a run of states
consecutive state layers; the run programs, one a length, are compiled
beside the others and keep their names, and a run of one is the message's
own program. Where the pages go comes from the sequence's state on the
device (its block table and a cursor of the messages written, put there
once a sequence): nothing crosses from the host a message beyond the
message itself. The
pages travel, land and are stored as their bits, unsigned integers, and no
program ever holds them as floats: on a TPU v5e even
``lax.bitcast_convert_type`` of a landed ``bfloat16`` array to ``uint16``
rewrites its NaNs and denormals (0.77% of all bit patterns; PERF.md 6), so a
page sent as ``bfloat16`` is refused, and a state sent as ``float32`` or
``bfloat16`` too: a state travels, lands and is stored as its 32-bit and
16-bit words (``jit_tpurpc_kv_state``, one shape, compiled beside the
scatter's; where it goes is the slot and a state cursor on the device).

**One writing thread** (``tpurpc-kv-write``) dispatches every write; a
stream's handler checks a message, hands it over with its ring credit
(``DeviceRequests.take_leases``) and goes on to land the next. The hand-over
is a ``queue.SimpleQueue``, whose ``put`` never blocks: no handler holds a
lock while a dispatch gives the interpreter up, which a lock around the
store made every handler queue behind (PERF.md 6). The credit comes back
once the write is dispatched, so what a connection can have waiting is its
ring. The thread takes what is queued when it looks, and a run pulls its
sequence's next messages forward past other sequences' (whose order
stands), never past a ``sync``, a give-up or the stop; it never waits for a
run to grow, so a writer that keeps up dispatches each message alone. Each
run costs one dispatch, and one give-up of the interpreter, where each of
its messages cost one (PERF.md 6). A stream that ends inside a sequence
hands over the sequence's release too, so its pages go back only behind
its own writes.

**The acknowledgement** is resolved by the cache's own thread
(``tpurpc-kv-ack``), never a handler's: it waits for the sequence's last
write to be ready on the device, marks the sequence acknowledged (evictable)
and resolves the Future, whose thread then writes the reply.

Stages (``obs/lens.py``): ``kv_admit`` (a wait for pages, one op an
admission that waited), ``kv_write`` (a dispatch of page messages on the
writing thread, its span carrying the handler's call and message of the
first, its layer, a message's page count and the messages, ``msgs``),
``kv_state_write`` (a dispatch of state messages on the same thread, the
same ids, the first's state layer, the slot and ``msgs``), ``kv_ack``
(counters only: last write dispatched to acknowledgement). Counters
``kv_messages_written`` (messages dispatched, of either kind: over the two
stages' ops, the messages a dispatch), ``kv_pages_written``,
``kv_seqs_admitted``, ``kv_seqs_acked``,
``kv_seqs_evicted``, ``kv_pages_evicted``; gauge ``kv_pages_free``. A cache
with state adds ``kv_states_written``, ``kv_state_bytes_written``,
``kv_slots_evicted``, ``kv_admit_waits_slots`` / ``kv_admit_waits_pages``
(admissions that waited, by the resource short when the wait began: its
own, else the one the offer at the head of the line waits for) and the
gauge ``kv_slots_free``.
"""

from __future__ import annotations

import collections
import functools
import itertools
import queue
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from tpurpc.obs import lens as _lens
from tpurpc.obs import metrics as _metrics
from tpurpc.serving.kv import ENTRY_BYTES, KvArenaFull, KvBlockManager

__all__ = ["KvPageCache", "KvSeq", "PROGRAM", "STATE_PROGRAM"]

#: the name the write program has in a device trace (``jit_<name>``)
PROGRAM = "tpurpc_kv_pages"
#: the name of the state write program (a cache with state)
STATE_PROGRAM = "tpurpc_kv_state"
#: a page whose words fill whole rows of this many is stored as such rows:
#: the chip's tiles are 128 wide, so ``[64, 576]`` would be padded to 640
_LANES = 128
#: events kept in the record (:meth:`KvPageCache.snapshot`); older ones go
#: first, and the record says how many went
_LOG_EVENTS = 1 << 16
#: the write programs' shapes compiled side by side: one compile holds a
#: host core for about 0.3 s, and the cache has one a page count
_COMPILERS = 8
#: the longest run of full-chunk page messages one dispatch takes: a
#: connection's default 16 MiB of ring credit holds three of a 64-page
#: chunk's 4.5 MiB messages, so a longer run never forms; each length is
#: one more program compiled
_PAGE_RUN = 4

_WRITTEN = _metrics.counter("kv_pages_written")
_MESSAGES = _metrics.counter("kv_messages_written")
_ADMITTED = _metrics.counter("kv_seqs_admitted")
_ACKED = _metrics.counter("kv_seqs_acked")
_EVICTED = _metrics.counter("kv_seqs_evicted")
_PAGES_EVICTED = _metrics.counter("kv_pages_evicted")
_FREE_G = _metrics.fleet("kv_pages_free", lambda c: c.free_pages())
_STATES_WRITTEN = _metrics.counter("kv_states_written")
_STATE_BYTES = _metrics.counter("kv_state_bytes_written")
_SLOTS_EVICTED = _metrics.counter("kv_slots_evicted")
_WAITS = {"slots": _metrics.counter("kv_admit_waits_slots"),
          "pages": _metrics.counter("kv_admit_waits_pages")}
_SLOTS_FREE_G = _metrics.fleet("kv_slots_free", lambda c: c.free_slots())


@functools.lru_cache(maxsize=None)
def _write_program(layers: int, chunk: int, page_shape: tuple):
    """``write(store, state, *xs) -> (store, state)``: messages
    ``state[0]``, ``state[0] + 1``, ... of a sequence into the store, one
    ``x`` each, and the cursor stepped by as many. ``state`` is ``[cursor,
    block table...]``; an ``x`` is the words of ``[n, T, W]`` pages. Both
    the store and the state are donated. One ``x`` is a message's own
    program, several a run's."""
    import jax
    from jax import lax

    def write(store, state, *xs):
        cursor = state[0]
        for i, x in enumerate(xs):
            n = x.shape[0]
            at = cursor + i if i else cursor
            layer = at % layers
            first = at // layers * chunk
            ids = lax.dynamic_slice(state, (first + 1,), (n,))
            # a table entry out of range is a page nobody owns: dropped
            store = store.at[layer, ids].set(x.reshape((n,) + page_shape),
                                             mode="drop")
        return store, state.at[0].add(len(xs))

    write.__name__ = write.__qualname__ = PROGRAM
    return jax.jit(write, donate_argnums=(0, 1))


@functools.lru_cache(maxsize=None)
def _state_program():
    """``write(stores, state, *xss) -> (stores, state)``: state messages,
    one tuple of arrays (a leaf each) an ``xs``, into the sequence's slot of
    each leaf's store at consecutive state layers from the one the cursor
    names, and the cursor stepped by as many. ``state`` ends ``[..., state
    cursor, slot]``. The stores and the state are donated."""
    import jax

    def write(stores, state, *xss):
        layer, slot = state[-2], state[-1]
        for i, xs in enumerate(xss):
            at = layer + i if i else layer
            # a slot out of range is one nobody owns: dropped
            stores = tuple(s.at[at, slot].set(x, mode="drop")
                           for s, x in zip(stores, xs))
        return stores, state.at[-2].add(len(xss))

    write.__name__ = write.__qualname__ = STATE_PROGRAM
    return jax.jit(write, donate_argnums=(0, 1))


class KvSeq:
    """One admitted sequence: its block table and how far it has come."""

    __slots__ = ("gid", "key", "ordinal", "tokens", "pages", "messages",
                 "slot", "sent", "done", "states_done", "state", "acked",
                 "future", "t_last", "error")

    def __init__(self, gid: int, key: int, ordinal: int, tokens: int,
                 pages: List[int], messages: int, slot: Optional[int] = None):
        self.gid = gid            # the cache's own number, in admission order
        self.key = key            # the stream's source (a prefill worker)
        self.ordinal = ordinal    # the source's own number for the sequence
        self.tokens = tokens
        self.pages = pages        # the block table, shared by every layer
        self.messages = messages  # messages the sequence takes
        self.slot = slot          # its state slot (a cache with state)
        self.sent = 0             # messages handed to the writer
        self.done = 0             # messages dispatched (writer's)
        self.states_done = 0      # of them, state messages
        self.state = None         # the device's copy of the table + cursor
        self.acked = False
        self.future: Optional[Future] = None
        self.t_last = 0           # last write's dispatch, monotonic ns
        self.error: Optional[BaseException] = None  # a dispatch that raised


class KvPageCache:
    """A paged KV cache of ``layers`` x ``pages`` pages of ``page_tokens`` x
    ``width`` 16-bit elements (bf16 latents) on ``device``, kept as their
    bits, ``uint16``; page messages carry up to ``chunk_pages`` pages
    (module docstring). With ``state_layers``, also ``slots`` state slots
    of the leaves ``state_leaves`` in each of those layers."""

    #: lint rule `lock`: the metadata is shared by every stream's handler
    #: thread, the writing thread and the acknowledging thread. ``None``: the
    #: hand-overs take no lock a producer can wait on. Not listed: the stores
    #: and the per-source counts, which only the writing thread changes once
    #: it runs (the snapshot copies the counts in one step of the
    #: interpreter)
    _GUARDED_BY = {"_resident": "_lock", "_acked": "_lock",
                   "_evictable": "_lock", "_log": "_lock",
                   "_events": "_lock", "_next": "_lock", "_turn": "_lock",
                   "_gids": "_lock", "_closed": "_lock",
                   "_free_slots": "_lock", "_short": "_lock", "_writes": None,
                   "_acks": None}

    def __init__(self, layers: int, pages: int, page_tokens: int,
                 width: int, *, chunk_pages: int = 64, device=None,
                 state_layers: Sequence[int] = (),
                 state_leaves: Optional[Mapping[str, Tuple[tuple, str]]]
                 = None, slots: int = 0):
        import jax.numpy as jnp

        if min(layers, pages, page_tokens, width, chunk_pages) < 1:
            raise ValueError("every size of a page cache is at least 1")
        self.layers, self.pages = int(layers), int(pages)
        self.page_tokens, self.width = int(page_tokens), int(width)
        self.chunk = int(chunk_pages)
        self.page_bytes = self.page_tokens * self.width * 2
        words = self.page_tokens * self.width
        self._page_shape = ((words // _LANES, _LANES) if words % _LANES == 0
                            else (self.page_tokens, self.width))
        self._init_state(state_layers, state_leaves, slots)
        if device is None:
            from tpurpc.utils.jaxenv import default_device

            device = default_device()
        self.device = device
        self._store = jnp.zeros((self.layers, self.pages) + self._page_shape,
                                jnp.uint16, device=device)
        self._states = tuple(
            jnp.zeros((len(self.state_layers), self.slots) + shape, dtype,
                      device=device)
            for shape, dtype in self.state_leaves.values())
        #: a sequence's table is read in whole chunks
        self._table_len = -(-self.pages // self.chunk) * self.chunk
        # the host allocator: one 16-byte record a page, which nothing
        # reads; the bytes are on the device
        self._arena = KvBlockManager(self.pages, ENTRY_BYTES,
                                     name="kv_pages")
        self._lock = threading.Lock()
        #: signalled whenever room may have appeared or the turn moved on
        self._moved = threading.Condition(self._lock)
        self._resident: Dict[int, KvSeq] = {}
        self._acked: "collections.deque[int]" = collections.deque()
        self._evictable = 0       # pages of acknowledged resident sequences
        self._free_slots: "collections.deque[int]" = collections.deque(
            range(self.slots))
        self._short = "pages"     # what the offer at the head waits for
        self._log: collections.deque = collections.deque(maxlen=_LOG_EVENTS)
        self._events = 0
        self._next = 0            # admission tickets handed out
        self._turn = 0            # the ticket whose turn it is
        self._gids = 0
        self._written: Dict[int, int] = collections.defaultdict(int)
        self._states_written: Dict[int, int] = collections.defaultdict(int)
        self._closed = False
        #: to the writing thread: ``(seq, x, leases, ids)`` a message (``x``
        #: a tuple of leaves for a state message), ``(seq, None, (), None)``
        #: a sequence given up, an Event to set once what is before it is
        #: dispatched, None to stop
        self._writes: queue.SimpleQueue = queue.SimpleQueue()
        #: to the acknowledging thread: sequences, None to stop
        self._acks: queue.SimpleQueue = queue.SimpleQueue()
        #: what the writing thread gathers for its next dispatch: ``(x,
        #: ids, marks, bytes)`` a message of one sequence and kind
        self._run: List[tuple] = []
        (self._programs, self._page_runs, self._state_write,
         self._state_runs) = self._warm()
        self._writer = threading.Thread(target=self._write_loop,
                                        name="tpurpc-kv-write", daemon=True)
        self._acker = threading.Thread(target=self._ack_loop,
                                       name="tpurpc-kv-ack", daemon=True)
        self._writer.start()
        self._acker.start()
        _FREE_G.track(self)
        if self.slots:
            _SLOTS_FREE_G.track(self)

    def _init_state(self, state_layers, state_leaves, slots) -> None:
        """The state layers, their leaves and the slots, checked; and which
        model layers of a sequence's last chunk are state messages."""
        self.state_layers = tuple(sorted(int(i) for i in state_layers))
        model = self.layers + len(self.state_layers)
        self.state_leaves: Dict[str, Tuple[tuple, np.dtype]] = {}
        self.slots = int(slots) if self.state_layers else 0
        if self.state_layers:
            if (len(set(self.state_layers)) != len(self.state_layers)
                    or not 0 <= self.state_layers[0] <= self.state_layers[-1]
                    < model):
                raise ValueError(f"state layers {self.state_layers} are not "
                                 f"distinct layers of a model of {model}")
            if self.slots < 1 or not state_leaves:
                raise ValueError("a cache with state layers has at least "
                                 "one slot and one leaf")
            for name, (shape, dtype) in state_leaves.items():
                dtype = np.dtype(dtype)
                if dtype.kind != "u":
                    # a program on the chip rewrites a float's NaNs and
                    # denormals: the words are what is kept (module doc)
                    raise ValueError(f"state leaf {name!r} is {dtype}: a "
                                     "state is kept as unsigned words")
                self.state_leaves[str(name)] = (tuple(int(d) for d in shape),
                                                dtype)
        #: one state message's bytes, and a sequence's in all its layers
        self.state_bytes = sum(int(np.prod(shape)) * dtype.itemsize
                               for shape, dtype in self.state_leaves.values())
        self.seq_state_bytes = len(self.state_layers) * self.state_bytes
        layers = set(self.state_layers)
        #: per model layer, whether the last chunk's message there is a state
        self._last_kinds = tuple(i in layers for i in range(model))
        #: the device's state of a sequence ends [state cursor, slot]
        self._tail = 2 if self.slots else 0
        #: the longest stretch of consecutive state layers: the longest run
        #: of state messages
        self._state_run = max((len(list(g)) for kind, g
                               in itertools.groupby(self._last_kinds) if kind),
                              default=0)

    def _warm(self):
        """The write programs, compiled side by side and each run once
        writing nothing (a table of out-of-range pages drops every page, a
        slot out of range every state): ``{n: compiled}`` of every page
        count; ``{k: compiled}`` of every run of k full-chunk page messages,
        k = 2 ... min(``_PAGE_RUN``, layers); the state program, or None;
        and ``{k: compiled}`` of every run of k states, k = 2 ... the
        longest stretch of state layers."""
        import jax
        from jax.sharding import SingleDeviceSharding

        write = _write_program(self.layers, self.chunk, self._page_shape)
        on = SingleDeviceSharding(self.device)

        def spec(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=on)

        store = spec(self._store.shape, self._store.dtype)
        state = spec((1 + self._table_len + self._tail,), np.int32)
        shape = (self.page_tokens, self.width)
        leaves = tuple(spec(shape, dtype) for shape, dtype
                       in self.state_leaves.values())

        def build(n, k=1):
            return write.lower(store, state,
                               *[spec((n,) + shape, np.uint16)] * k).compile()

        def build_state(k):
            return _state_program().lower(
                tuple(spec(s.shape, s.dtype) for s in self._states), state,
                *[leaves] * k).compile()

        counts = range(1, self.chunk + 1)
        runs = range(2, min(_PAGE_RUN, self.layers) + 1)
        state_runs = range(1, self._state_run + 1)
        pool = ThreadPoolExecutor(min(_COMPILERS, self.chunk),
                                  thread_name_prefix="tpurpc-kv-compile")
        with pool:
            state_writes = {k: pool.submit(build_state, k)
                            for k in state_runs}
            page_runs = {k: pool.submit(build, self.chunk, k) for k in runs}
            programs = dict(zip(counts, pool.map(build, counts)))
            page_runs = {k: f.result() for k, f in page_runs.items()}
            state_writes = {k: f.result() for k, f in state_writes.items()}
        empty = np.full(1 + self._table_len + self._tail, self.pages,
                        np.int32)
        empty[0] = 0
        for n, program in programs.items():
            x = jax.device_put(np.zeros((n,) + shape, np.uint16), self.device)
            self._store, _ = program(self._store,
                                     jax.device_put(empty, self.device), x)
        for k, program in page_runs.items():
            # x: the full chunk's, from the loop above
            self._store, _ = program(self._store,
                                     jax.device_put(empty, self.device),
                                     *[x] * k)
        self._store.block_until_ready()
        if state_writes:
            empty[-2:] = 0, self.slots
            xs = tuple(jax.device_put(np.zeros(shape, dtype), self.device)
                       for shape, dtype in self.state_leaves.values())
            for k, program in state_writes.items():
                self._states, _ = program(
                    self._states, jax.device_put(empty, self.device),
                    *[xs] * k)
            jax.block_until_ready(self._states)
        return (programs, page_runs, state_writes.pop(1, None),
                state_writes)

    # -- admission -----------------------------------------------------------

    def admit(self, key: int, ordinal: int, n_tokens: int) -> KvSeq:
        """Room for sequence ``ordinal`` of source ``key``, of ``n_tokens``,
        all its pages (and its slot) at once, in the order offers came:
        evicts the oldest acknowledged sequences, waits for
        acknowledgements where that is not enough. Raises
        :class:`~tpurpc.serving.kv.KvArenaFull` for a sequence larger than
        the cache, or one the cache was closed under."""
        import jax

        need = -(-int(n_tokens) // self.page_tokens)
        if not 0 < need <= self.pages:
            raise KvArenaFull(f"a sequence of {n_tokens} tokens takes {need} "
                              f"pages; the cache holds {self.pages}")
        with self._lock:
            ticket = self._next
            self._next += 1
            wait = None
            while not self._closed and (self._turn != ticket
                                        or self._short_locked(need)):
                short = self._short_locked(need)
                if self._turn == ticket:
                    self._short = short
                if wait is None:
                    wait = _lens.stage(
                        "kv_admit", need * self.layers * self.page_bytes
                        + self.seq_state_bytes)
                    wait.begin()
                    if self.slots:
                        _WAITS[short or self._short].inc()
                self._moved.wait()
            try:
                if self._closed:
                    raise KvArenaFull("the page cache is closed")
                while (self._arena.free_count() < need
                       or len(self._free_slots) < bool(self.slots)):
                    self._evict_locked(self._victim_locked())
                gid = self._gids
                self._gids += 1
                ids = self._arena.alloc_blocks(gid, need)
                slot = self._free_slots.popleft() if self.slots else None
                try:
                    seq = KvSeq(gid, key, int(ordinal), int(n_tokens), ids,
                                -(-need // self.chunk) * self.layers
                                + len(self.state_layers), slot)
                    self._resident[gid] = seq
                    self._record_locked("admit", gid, key, int(ordinal),
                                        int(n_tokens), tuple(ids),
                                        *(() if slot is None else (slot,)))
                except BaseException:
                    self._arena.free_blocks_raw(ids)
                    if slot is not None:
                        self._free_slots.appendleft(slot)
                    raise
            finally:
                self._turn += 1
                self._moved.notify_all()
                if wait is not None:
                    wait.end()
        _ADMITTED.inc()
        try:
            seq.state = jax.device_put(self._table(seq), self.device)
        except BaseException:
            self.drop(seq)
            raise
        return seq

    def _short_locked(self, need: int) -> Optional[str]:
        """What an admission of ``need`` pages lacks even once every
        acknowledged sequence is evicted: ``"slots"``, ``"pages"`` or
        None."""
        # contract: caller holds self._lock (the _locked suffix)
        if len(self._free_slots) + len(self._acked) < bool(self.slots):
            return "slots"
        if self._arena.free_count() + self._evictable < need:
            return "pages"
        return None

    def _table(self, seq: KvSeq) -> np.ndarray:
        """The sequence's state as the device keeps it: ``[cursor, block
        table padded with an out-of-range page]``, and in a cache with state
        ``[..., state cursor, slot]``."""
        state = np.full(1 + self._table_len + self._tail, self.pages,
                        np.int32)
        state[0] = 0
        state[1:1 + len(seq.pages)] = seq.pages
        if self.slots:
            state[-2:] = 0, seq.slot
        return state

    def _victim_locked(self) -> int:
        """The sequence to evict next: the oldest acknowledged."""
        return self._acked[0]

    def _evict_locked(self, gid: int) -> None:
        # contract: caller holds self._lock (the _locked suffix)
        seq = self._resident.pop(gid)  # tpr: allow(lock)
        if seq.acked:
            self._acked.remove(gid)  # tpr: allow(lock)
            self._evictable -= len(seq.pages)  # tpr: allow(lock)
        self._release_locked(seq)
        self._record_locked("evict", gid)
        _EVICTED.inc()
        _PAGES_EVICTED.inc(len(seq.pages))
        if seq.slot is not None:
            _SLOTS_EVICTED.inc()

    def _release_locked(self, seq: KvSeq) -> None:
        """The sequence's pages and slot back to the free lists."""
        # contract: caller holds self._lock (the _locked suffix)
        self._arena.free_blocks_raw(seq.pages)
        if seq.slot is not None:
            self._free_slots.append(seq.slot)  # tpr: allow(lock)

    def _record_locked(self, *event) -> None:
        # contract: caller holds self._lock (the _locked suffix)
        self._log.append(event)  # tpr: allow(lock)
        self._events += 1  # tpr: allow(lock)

    def drop(self, seq: KvSeq) -> None:
        """Give back the pages (and slot) of a sequence whose stream ended
        before its last message (its source is gone): it was never
        acknowledged, so no decode can want it. A sequence that was written
        whole is left to its acknowledgement. Called where none of its
        writes can still be waiting: a stream hands it to the writing thread
        (:meth:`give_up`)."""
        with self._lock:
            if (seq.future is not None
                    or self._resident.get(seq.gid) is not seq):
                return
            del self._resident[seq.gid]
            self._release_locked(seq)
            self._record_locked("drop", seq.gid)
            self._moved.notify_all()

    # -- the write -------------------------------------------------------------

    def submit(self, seq: KvSeq, x, take=None) -> bool:
        """Hand the sequence's next message ``x`` to the writing thread: a
        page message ``uint16[n, T, W]`` or, where the sequence's order has
        a state layer, a state message ``{leaf name: words}``; with the ring
        credit it holds: ``take()`` (``DeviceRequests.take_leases``) hands
        its leases over once the message is found sound, and they are
        released once it is dispatched. True where it was the sequence's
        last, whose acknowledgement is then ``seq.future``. Raises
        ValueError for a message of another kind, shape or dtype, or one
        past the last, having taken nothing."""
        import jax

        k = seq.sent
        if k >= seq.messages:
            raise ValueError(f"sequence {seq.ordinal} of source {seq.key} "
                             f"takes {seq.messages} messages; this is one "
                             "more")
        if self.is_state(seq, k):
            x = self._state_message(seq, k, x)
        else:
            first = self._chunk_of(seq, k) * self.chunk
            n = min(self.chunk, len(seq.pages) - first)
            want = (n, self.page_tokens, self.width)
            if (tuple(getattr(x, "shape", ())) != want
                    or getattr(x, "dtype", None) != np.uint16):
                got = (f"{x.dtype}{list(x.shape)}" if hasattr(x, "shape")
                       else type(x).__name__)
                raise ValueError(f"page message {k} of sequence "
                                 f"{seq.ordinal} of source {seq.key}: {got}"
                                 f", not uint16{list(want)} (the words of "
                                 "bf16 pages)")
            if not isinstance(x, jax.Array):
                # host leaves view the receive buffer, which the next message
                # reuses: the writer gets a copy of its own
                x = jax.device_put(np.array(x), self.device)
        leases = take() if take is not None else ()
        seq.sent = k + 1
        last = seq.sent == seq.messages
        if last:
            seq.future = Future()
        self._writes.put((seq, x, leases, _lens.current_ids()))
        return last

    def is_state(self, seq: KvSeq, k: int) -> bool:
        """Whether message ``k`` of the sequence is a state message: one of
        its last chunk's, at a state layer."""
        at = k - (-(-len(seq.pages) // self.chunk) - 1) * self.layers
        return at >= 0 and self._last_kinds[at]

    def _state_message(self, seq: KvSeq, k: int, x) -> tuple:
        """The leaves of state message ``k``, checked against the cache's,
        as device arrays in the leaves' order."""
        import jax

        want = {name: f"{dtype}{list(shape)}"
                for name, (shape, dtype) in self.state_leaves.items()}
        got = ({name: f"{np.dtype(v.dtype)}{list(v.shape)}"
                for name, v in x.items() if hasattr(v, "shape")}
               if isinstance(x, Mapping) else type(x).__name__)
        if got != want:
            raise ValueError(f"state message {k} of sequence {seq.ordinal} "
                             f"of source {seq.key}: {got}, not {want} (the "
                             "words of the state: a float never travels)")
        return tuple(
            x[name] if isinstance(x[name], jax.Array)
            # a host leaf views the receive buffer: copied, as a page is
            else jax.device_put(np.array(x[name]), self.device)
            for name in self.state_leaves)

    def give_up(self, seq: KvSeq) -> None:
        """The stream of ``seq`` ended before its last message: its pages
        and slot go back (:meth:`drop`) once the writes handed over before
        this are dispatched."""
        self._writes.put((seq, None, (), None))

    def _write_loop(self) -> None:
        """The writing thread: every message in the order it was handed
        over, which is each sequence's own order, a run a dispatch
        (:meth:`_run_of`); it waits only for the queue to be empty."""
        pending: collections.deque = collections.deque()
        while True:
            if not pending:
                pending.append(self._writes.get())
            try:
                while True:
                    pending.append(self._writes.get_nowait())
            except queue.Empty:
                pass
            item = pending.popleft()
            if item is None:
                return
            if isinstance(item, threading.Event):
                item.set()
                continue
            if item[1] is None:
                self.drop(item[0])
                continue
            self._write(item[0], self._run_of(item, pending))

    def _run_of(self, head, pending: collections.deque) -> list:
        """``head`` and the messages of its sequence that one dispatch takes
        with it, taken out of ``pending``: the sequence's next messages in
        its order, found before any barrier (an Event, a give-up, the stop),
        while each is of the head's kind and, for pages, of the head's chunk,
        up to the kind's longest program. Only full-chunk page messages and
        state messages make runs."""
        seq, x = head[0], head[1]
        state = isinstance(x, tuple)
        if state:
            limit = self._state_run
        elif x.shape[0] == self.chunk:
            limit = max(self._page_runs, default=1)
        else:
            limit = 1
        k = seq.done
        chunk = self._chunk_of(seq, k)
        run, i = [head], 0
        while len(run) < limit and i < len(pending):
            item = pending[i]
            if not isinstance(item, tuple) or item[1] is None:
                break
            if item[0] is not seq:
                i += 1
                continue
            k += 1
            if (self.is_state(seq, k) != state
                    or self._chunk_of(seq, k) != chunk):
                break
            run.append(item)
            del pending[i]
        return run

    def _chunk_of(self, seq: KvSeq, k: int) -> int:
        """The chunk message ``k`` of the sequence belongs to."""
        return min(k // self.layers, -(-len(seq.pages) // self.chunk) - 1)

    def _write(self, seq: KvSeq, run: list) -> None:
        """A run of the sequence's messages: each through its hook
        (:meth:`_dispatch`, :meth:`_dispatch_state`), then one program call
        for all (:meth:`_flush`), then their credit back."""
        first = seq.done
        self._run = []
        try:
            if seq.error is None:
                for _, x, _, ids in run:
                    if isinstance(x, tuple):
                        self._dispatch_state(seq, x, ids)
                    else:
                        self._dispatch(seq, x, ids)
                    seq.done += 1
                self._flush(seq)
        except Exception as exc:  # the sequence fails at its ack
            seq.error = exc
        finally:
            for _, _, leases, _ in run:
                for lease in leases:
                    lease.release()
        seq.done = first + len(run)
        if seq.done == seq.messages:
            seq.t_last = time.monotonic_ns()
            self._acks.put(seq)

    def _dispatch(self, seq: KvSeq, x, ids) -> None:
        """Page message ``seq.done - seq.states_done`` of the sequence into
        the run (the writing thread's)."""
        n = x.shape[0]
        self._run.append((x, ids, {"layer": (seq.done - seq.states_done)
                                   % self.layers, "pages": n},
                          n * self.page_bytes))
        self._written[seq.key] += n
        _WRITTEN.inc(n)

    def _dispatch_state(self, seq: KvSeq, xs: tuple, ids) -> None:
        """State message ``seq.states_done`` of the sequence into the run
        (the writing thread's)."""
        self._run.append((xs, ids, {"layer": seq.states_done,
                                    "slot": seq.slot}, self.state_bytes))
        seq.states_done += 1
        self._states_written[seq.key] += 1
        _STATES_WRITTEN.inc()
        _STATE_BYTES.inc(self.state_bytes)

    def _flush(self, seq: KvSeq) -> None:
        """One program call for the messages the hooks put in the run: the
        message's own program for one, the run's program for more."""
        if not self._run:
            return
        xs, k = [m[0] for m in self._run], len(self._run)
        _, ids, marks, _ = self._run[0]
        call, mseq = ids or (None, 0)
        marks = dict(marks, msgs=k)
        nbytes = sum(m[3] for m in self._run)
        if isinstance(xs[0], tuple):
            program = self._state_runs[k] if k > 1 else self._state_write
            with _lens.stage("kv_state_write", nbytes, call=call,
                             seq=mseq, marks=marks):
                self._states, seq.state = program(self._states, seq.state,
                                                  *xs)
        else:
            program = (self._page_runs[k] if k > 1
                       else self._programs[xs[0].shape[0]])
            with _lens.stage("kv_write", nbytes, call=call, seq=mseq,
                             marks=marks):
                self._store, seq.state = program(self._store, seq.state,
                                                 *xs)
        _MESSAGES.inc(k)

    # -- the acknowledgement -----------------------------------------------------

    def _ack_loop(self) -> None:
        """The acknowledging thread: sequences in the order their last
        writes were dispatched, which is the order the device runs them."""
        while True:
            seq = self._acks.get()
            if seq is None:
                return
            try:
                if seq.error is not None:
                    raise seq.error
                seq.state.block_until_ready()
            except Exception as exc:  # the device failed a write
                seq.future.set_exception(exc)
                continue
            with self._lock:
                # a sequence evicted under it stays unacknowledged
                if self._resident.get(seq.gid) is seq:
                    seq.acked = True
                    self._acked.append(seq.gid)
                    self._evictable += len(seq.pages)
                    self._record_locked("ack", seq.gid)
                    self._moved.notify_all()
            _ACKED.inc()
            _lens.account("kv_ack", time.monotonic_ns() - seq.t_last,
                          len(seq.pages) * self.layers * self.page_bytes
                          + self.seq_state_bytes)
            seq.future.set_result({"seq": np.int64(seq.ordinal),
                                   "pages": np.int32(len(seq.pages))})

    # -- the stream ---------------------------------------------------------------

    def receiver(self, key: int):
        """The behavior of one prefill worker's stream (module docstring),
        for ``add_tensor_method(server, name, cache.receiver(key),
        kind="stream_stream", device=True)``: ``key`` names the source in
        the cache's record."""
        def handoff(requests):
            take = getattr(requests, "take_leases", None)
            seq = None
            try:
                for tree in requests:
                    if seq is None:
                        if "offer" not in tree:
                            raise ValueError("a stream of KV pages opens "
                                             "each sequence with an offer "
                                             "{'offer': (seq, n_tokens)}")
                        ordinal, n_tokens = np.asarray(
                            tree["offer"]).reshape(-1)[:2].tolist()
                        seq = self.admit(key, ordinal, n_tokens)
                        continue
                    if "pages" in tree:
                        x = tree["pages"]
                    elif self.is_state(seq, seq.sent):
                        x = tree
                    else:
                        raise ValueError(
                            f"sequence {seq.ordinal} of source {key} still "
                            f"owes {seq.messages - seq.sent} messages")
                    if self.submit(seq, x, take):
                        done, seq = seq, None
                        yield done.future
            finally:
                if seq is not None:
                    self.give_up(seq)
        return handoff

    # -- what an operator or an audit reads ---------------------------------------

    def free_pages(self) -> int:
        return self._arena.free_count()

    def free_slots(self) -> int:
        return len(self._free_slots)

    @property
    def store(self):
        """The device array ``[layers, pages, *page]`` of the pages' bits,
        as of the last dispatched write (:meth:`sync` waits for every write
        handed over); the next write donates it, so it is for reading while
        no stream writes."""
        return self._store

    @property
    def states(self) -> tuple:
        """The state stores, one device array ``[state layers, slots,
        *shape]`` a leaf in the leaves' order, read as :attr:`store` is;
        empty in a cache without state."""
        return self._states

    def sync(self) -> None:
        """Wait until every write handed over so far is done on the
        device."""
        if self._writer.is_alive():
            reached = threading.Event()
            self._writes.put(reached)
            reached.wait()
        self._store.block_until_ready()
        for leaf in self._states:
            leaf.block_until_ready()

    def snapshot(self) -> dict:
        """The host metadata as of now: every resident sequence (``[gid,
        key, ordinal, tokens, acked, block table]``, and its slot last in a
        cache with state, in admission order), the acknowledged ones in the
        order they will be evicted, the free pages, the pages written per
        source, and the record: ``events`` in all, the last ``len(log)`` of
        them, each ``("admit", gid, key, ordinal, tokens, table)`` (and the
        slot), ``("ack", gid)``, ``("evict", gid)`` or ``("drop", gid)``. A
        cache with state adds the free slots and the state messages written
        per source."""
        tail = (lambda s: [s.slot]) if self.slots else (lambda s: [])
        with self._lock:
            resident = [[s.gid, s.key, s.ordinal, s.tokens, s.acked,
                         list(s.pages)] + tail(s)
                        for s in self._resident.values()]
            out = {"resident": resident, "acked": list(self._acked),
                   "free": self._arena.free_count(), "events": self._events,
                   "log": [list(e) for e in self._log]}
            if self.slots:
                out["free_slots"] = len(self._free_slots)
        out["written"] = dict(self._written)
        if self.slots:
            out["states_written"] = dict(self._states_written)
        return out

    def close(self) -> None:
        """Fail every admission still waiting, let the writing thread
        dispatch what it was handed and the acknowledging thread resolve
        what it holds, and let the stores go."""
        with self._lock:
            self._closed = True
            self._moved.notify_all()
        self._writes.put(None)
        self._writer.join(timeout=60)
        self._acks.put(None)
        self._acker.join(timeout=60)
        self._store.delete()
        for leaf in self._states:
            leaf.delete()
        self._arena.close()
