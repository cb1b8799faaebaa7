"""Server: listener → per-connection demux → handler dispatch on a thread pool.

Reference mapping:

* ``Server`` ≈ ``grpc_server`` (``src/core/lib/surface/server.cc``) + C++
  ``ServerBuilder`` (``src/cpp/server/server_builder.cc``): ports, registered
  methods, a thread pool standing in for the CQ/thread-manager machinery
  (``src/cpp/thread_manager/``).
* ``_ServerConnection`` ≈ one accepted chttp2 transport
  (``grpc_server_setup_transport``); its reader thread plays the role of the
  transport's read_action + stream demux.
* ``ServerContext`` mirrors grpcio's (``src/python/grpcio/grpc/_server.py``):
  invocation metadata, deadline, cancellation, ``abort``, trailing metadata.
* Method handlers reuse grpcio's four-shape taxonomy so generated service glue
  ports directly.
"""

from __future__ import annotations

import collections
import logging
import queue
import threading
import time
from concurrent.futures import CancelledError, Future, ThreadPoolExecutor
from typing import (Callable, Dict, Iterator, List, Optional,
                    Sequence, Tuple)

from tpurpc.core import ctrlring as _ctrl
from tpurpc.core import rendezvous as _rdv
from tpurpc.core.endpoint import (Endpoint, EndpointError, EndpointListener,
                                  passthru_endpoint_pair)
from tpurpc.obs import flight as _flight
from tpurpc.obs import lens as _lens
from tpurpc.obs import metrics as _obs_metrics
from tpurpc.obs import profiler as _obs_profiler
from tpurpc.obs import tracing as _tracing
from tpurpc.obs import watchdog as _watchdog
from tpurpc.rpc import frame as fr
from tpurpc.rpc.status import (AbortError, Deserializer, Metadata, Serializer,
                               StatusCode, deserialize as _deserialize,
                               identity_codec as _identity)
from tpurpc.utils.config import get_config
from tpurpc.utils.trace import TraceFlag

trace_server = TraceFlag("server")
_log = logging.getLogger("tpurpc.server")

# tpurpc-lens (ISSUE 8) sampling-profiler frame markers: handler dispatch
# on either execution path is the `dispatch` stage
_LENS_STAGES = {
    "_run_handler": "dispatch",
    "_run_handler_inner": "dispatch",
    "_run_inline": "dispatch",
}
_obs_profiler.register_stages(__file__, _LENS_STAGES)

#: tpurpc-scope (ISSUE 4): always-on server-side handler latency (one
#: perf_counter pair + one amortized histogram record per RPC — what
#: `tools.top` renders as serving percentiles)
_SRV_CALL_US = _obs_metrics.histogram("srv_call_us", kind="latency")
#: tpurpc-blackbox (ISSUE 5): per-method, per-status-code RED counters
#: (`srv_calls{method,code}` on /metrics); shared with the h2 plane
_SRV_CALLS = _obs_metrics.labeled_counter("srv_calls", ("method", "code"))
#: tpurpc-fleet (ISSUE 6): admission-control shed counter + the interned
#: flight tags for the emission sites below (pure-int plumbing — the
#: `flight` lint rule covers this module)
_SRV_SHED = _obs_metrics.counter("srv_admission_rejected")
#: ISSUE 36: responses a stream handler yielded as futures, and those of
#: them that were resolved while an earlier response of their stream was not
_SRV_DEFERRED = _obs_metrics.counter("srv_replies_deferred")
_SRV_OVERTAKEN = _obs_metrics.counter("srv_replies_overtaken")
_SRV_INLINE_TAG = _flight.tag_for("srv-inline")
_SRV_ADMIT_TAG = _flight.tag_for("srv-admission")
_SRV_DRAIN_TAG = _flight.tag_for("srv-drain")

#: trailing-metadata key carrying the ORCA-style per-response load report
#: (``"<inflight>,<queue_depth>,<p99_ms>"`` — see Server._load_md); the
#: client channel strips it and feeds the ``least_loaded`` LB policy
LOAD_KEY = "tpurpc-load"
#: trailing-metadata key on admission rejections: how long the client
#: should back off before retrying (milliseconds; RetryPolicy honors it)
PUSHBACK_KEY = "tpurpc-pushback-ms"


class AdmissionGate:
    """Server-side overload admission control (tpurpc-fleet, ISSUE 6).

    The gate sits at stream admission — BEFORE handler lookup, context
    construction, or any pool handoff — and sheds load while the server
    can still say so cheaply, instead of queueing toward collapse
    (RDMAvisor's shared-daemon lesson: a multiplexing service must bound
    what it accepts, arXiv:1802.01870). Two signals:

    * **queue depth** — admitted-but-unfinished RPCs. Below
      ``soft_limit`` everything is admitted; at ``max_inflight`` nothing
      is.
    * **rolling latency** — between the two limits, admission requires
      the stall watchdog's rolling p99 (PR 5's per-method duration
      windows) to be under ``latency_slo_ms``: rising latency at partial
      queue depth is the pre-collapse signature the hard limit alone
      would miss.

    Rejections carry ``UNAVAILABLE`` plus :data:`PUSHBACK_KEY` trailing
    metadata whose value grows with the excess — clients with a
    :class:`~tpurpc.rpc.channel.RetryPolicy` honor it as their backoff
    floor, so a shedding server is not immediately re-hammered. Health
    RPCs are exempt (the server dispatch layer skips the gate for
    ``/grpc.health.``-prefixed paths): an overloaded-but-alive backend
    must keep answering its probes.
    """

    def __init__(self, max_inflight: int, *,
                 soft_limit: Optional[int] = None,
                 latency_slo_ms: Optional[float] = None,
                 latency_ms_fn: "Optional[Callable[[], Optional[float]]]"
                 = None,
                 base_pushback_ms: int = 25,
                 max_pushback_ms: int = 1000):
        if max_inflight < 1:
            raise ValueError("max_inflight must be >= 1")
        self.max_inflight = int(max_inflight)
        self.soft_limit = (int(soft_limit) if soft_limit is not None
                           else max(1, (self.max_inflight * 3) // 4))
        if not 1 <= self.soft_limit <= self.max_inflight:
            raise ValueError("need 1 <= soft_limit <= max_inflight")
        self.latency_slo_ms = latency_slo_ms
        #: tpurpc-cadence (ISSUE 10): a workload-specific latency signal
        #: replacing the watchdog's RPC-level rolling p99. A decode server
        #: hands the scheduler's step-time p99 here: generate streams are
        #: SUPPOSED to be long-lived, so their RPC duration says nothing,
        #: while a rising step time is exactly the pre-collapse signature
        #: the between-limits band exists to catch. Returns ms or None
        #: (no signal yet = not slow).
        self.latency_ms_fn = latency_ms_fn
        self.base_pushback_ms = int(base_pushback_ms)
        self.max_pushback_ms = int(max_pushback_ms)
        self._inflight = 0
        self._lock = threading.Lock()
        self.rejected = 0

    def _latency_ms(self) -> "Optional[float]":
        if self.latency_ms_fn is not None:
            try:
                return self.latency_ms_fn()
            except Exception:
                return None  # a broken probe never blocks admission
        from tpurpc.obs import watchdog as _watchdog

        p99 = _watchdog.get().rolling_p99_ns()
        return None if p99 is None else p99 / 1e6

    def try_admit(self) -> Optional[int]:
        """None = admitted (the caller OWES a :meth:`release`); an int =
        rejected, with that many milliseconds of retry pushback."""
        with self._lock:
            n = self._inflight
            if n < self.soft_limit:
                self._inflight = n + 1
                return None
            slow = False
            if n < self.max_inflight:
                if self.latency_slo_ms is not None:
                    lat = self._latency_ms()
                    slow = (lat is not None
                            and lat > self.latency_slo_ms)
                if not slow:
                    self._inflight = n + 1
                    return None
            self.rejected += 1
            excess = max(1, n - self.soft_limit + 1)
            return min(self.max_pushback_ms,
                       self.base_pushback_ms * excess)

    def release(self) -> None:
        with self._lock:
            self._inflight = max(0, self._inflight - 1)

    def inflight(self) -> int:
        with self._lock:
            return self._inflight

    def connection_pushback_ms(self) -> Optional[int]:
        """Connection-level pressure probe for the accept path (ISSUE 16
        accept-storm hardening — ``EndpointListener`` consults this before
        spending any handshake work on a freshly accepted socket). Sheds
        new CONNECTIONS only at hard saturation (inflight at
        ``max_inflight``): between the limits, existing clients keep
        reconnecting and the per-RPC gate does the fine-grained shedding.
        Pure probe — admits nothing, so no :meth:`release` is owed."""
        with self._lock:
            n = self._inflight
            if n < self.max_inflight:
                return None
            excess = max(1, n - self.soft_limit + 1)
            return min(self.max_pushback_ms,
                       self.base_pushback_ms * excess)

    @classmethod
    def from_env(cls) -> "Optional[AdmissionGate]":
        """Gate configured by ``TPURPC_ADMISSION_MAX_INFLIGHT`` (+ optional
        ``TPURPC_ADMISSION_SLO_MS``), or None when unset — admission
        control is opt-in, like gRPC's resource quota."""
        import os

        raw = os.environ.get("TPURPC_ADMISSION_MAX_INFLIGHT", "")
        if not raw:
            return None
        try:
            max_inflight = int(raw)
        except ValueError:
            return None
        if max_inflight < 1:
            return None
        slo = None
        raw_slo = os.environ.get("TPURPC_ADMISSION_SLO_MS", "")
        if raw_slo:
            try:
                slo = float(raw_slo)
            except ValueError:
                slo = None
        return cls(max_inflight, latency_slo_ms=slo)


def _extract_trace(metadata) -> "Optional[_tracing.TraceContext]":
    """The tpurpc-trace context a client attached (sampled or tail-
    provisional), stripped from ``metadata`` IN PLACE — the context is
    transport-internal and must not surface to handlers (grpcio parity
    with te/content-type filtering; with tail capture on, EVERY call
    carries it)."""
    if not _tracing.LIVE:
        return None
    for i, (key, value) in enumerate(metadata):
        if key == _tracing.HEADER:
            del metadata[i]
            return _tracing.adopt(value)
    return None


class RpcMethodHandler:
    """One registered method: shape + behavior + codecs (grpcio taxonomy).

    ``inline=True`` (unary_unary only) runs the handler ON THE CONNECTION
    READER THREAD when the request completes — no thread-pool handoff, the
    low-latency reactor path (the native callback API's contract,
    ``native/include/tpurpc/server.h``; gRPC's inlineable callback methods
    are the upstream analog). The handler MUST NOT block: it stalls every
    stream on its connection.

    A response-streaming behavior may yield a ``concurrent.futures.Future``
    where it would yield a response (:class:`_DeferredReplies`): the
    response is the future's result, serialized where it is written by
    ``late_serializer`` (an attribute, None: ``response_serializer``; the
    tensor shim sets it for behaviors that serialize what they yield
    themselves).
    """

    __slots__ = ("kind", "behavior", "request_deserializer",
                 "response_serializer", "inline", "late_serializer")

    KINDS = ("unary_unary", "unary_stream", "stream_unary", "stream_stream")

    def __init__(self, kind: str, behavior: Callable,
                 request_deserializer: Deserializer = _identity,
                 response_serializer: Serializer = _identity,
                 inline: bool = False):
        if kind not in self.KINDS:
            raise ValueError(f"bad handler kind {kind}")
        if inline and kind != "unary_unary":
            raise ValueError("inline handlers are unary_unary only")
        self.inline = inline
        self.kind = kind
        self.behavior = behavior
        self.request_deserializer = request_deserializer
        self.response_serializer = response_serializer
        self.late_serializer: Optional[Serializer] = None

    @property
    def request_streaming(self) -> bool:
        return self.kind.startswith("stream")

    @property
    def response_streaming(self) -> bool:
        return self.kind.endswith("stream")


def unary_unary_rpc_method_handler(behavior, request_deserializer=_identity,
                                   response_serializer=_identity,
                                   inline: bool = False):
    return RpcMethodHandler("unary_unary", behavior, request_deserializer,
                            response_serializer, inline=inline)


def unary_stream_rpc_method_handler(behavior, request_deserializer=_identity,
                                    response_serializer=_identity):
    return RpcMethodHandler("unary_stream", behavior, request_deserializer,
                            response_serializer)


def stream_unary_rpc_method_handler(behavior, request_deserializer=_identity,
                                    response_serializer=_identity):
    return RpcMethodHandler("stream_unary", behavior, request_deserializer,
                            response_serializer)


def stream_stream_rpc_method_handler(behavior, request_deserializer=_identity,
                                     response_serializer=_identity):
    return RpcMethodHandler("stream_stream", behavior, request_deserializer,
                            response_serializer)


def method_handlers_generic_handler(service: str,
                                    method_handlers: Dict[str, RpcMethodHandler]):
    """grpcio-shaped: returns {path: handler} for Server.add_generic_handlers."""
    return {f"/{service}/{name}": h for name, h in method_handlers.items()}


class _HandlerCallDetails:
    """grpc.HandlerCallDetails shape for GenericRpcHandler.service()."""

    __slots__ = ("method", "invocation_metadata")

    def __init__(self, method: str, invocation_metadata=()):
        self.method = method
        self.invocation_metadata = tuple(invocation_metadata or ())


class ServerContext:
    """Handed to every handler; grpcio-compatible surface."""

    def __init__(self, conn: "_ServerConnection", stream: "_ServerStream",
                 metadata: List[Tuple[str, "str | bytes"]],
                 deadline: Optional[float]):
        self._conn = conn
        self._stream = stream
        self._metadata = metadata
        self._deadline = deadline
        self._trailing: Metadata = ()
        self._initial_sent = False
        self._cancelled = threading.Event()
        self._code: Optional[StatusCode] = None
        self._details = ""

    # grpcio surface ---------------------------------------------------------

    def invocation_metadata(self) -> Metadata:
        return list(self._metadata)

    def peer(self) -> str:
        return self._conn.endpoint.peer

    def auth_context(self) -> dict:
        """grpcio's ServerContext.auth_context: {} on plaintext,
        transport_security_type alone on certless TLS, plus the peer's
        x509 names under mTLS. Probed through the Endpoint seam (ring
        platforms keep the TLS socket as the pair's notify channel), and
        computed once per context — the cert can't change mid-call."""
        cached = getattr(self, "_auth_ctx", None)
        if cached is not None:
            return cached
        cert = self._conn.endpoint.peer_cert()
        if cert is None:  # non-TLS transport
            out: dict = {}
        elif not cert:  # TLS without a client certificate
            out = {"transport_security_type": [b"ssl"]}
        else:
            out = {"transport_security_type": [b"ssl"]}
            # every SAN kind counts as identity (URI carries SPIFFE ids)
            sans = [v.encode() if isinstance(v, str) else str(v).encode()
                    for _kind, v in cert.get("subjectAltName", ())]
            if sans:
                out["x509_subject_alternative_name"] = sans
            for rdn in cert.get("subject", ()):
                for key, val in rdn:
                    if key == "commonName":
                        out.setdefault("x509_common_name", []).append(
                            val.encode())
        self._auth_ctx = out
        return out

    def peer_identity_key(self) -> "Optional[str]":
        ac = self.auth_context()
        for key in ("x509_subject_alternative_name", "x509_common_name"):
            if key in ac:
                return key
        return None

    def peer_identities(self):
        key = self.peer_identity_key()
        return self.auth_context()[key] if key else None

    @property
    def device_ring(self):
        """The connection's device (HBM) receive ring, or None off-platform.

        Present only when the transport is a
        :class:`tpurpc.tpu.endpoint.TpuRingEndpoint`
        (``GRPC_PLATFORM_TYPE=TPU``); tensor handlers registered with
        ``device=True`` decode through it."""
        from tpurpc.core.endpoint import device_ring_of

        return device_ring_of(self._conn.endpoint)

    def deadline_remaining(self) -> Optional[float]:
        if self._deadline is None:
            return None
        return max(0.0, self._deadline - time.monotonic())

    time_remaining = deadline_remaining

    def is_active(self) -> bool:
        return not self._cancelled.is_set()

    def cancel(self) -> None:
        self._cancelled.set()

    def set_trailing_metadata(self, metadata: Metadata) -> None:
        self._trailing = metadata

    def set_code(self, code: StatusCode) -> None:
        self._code = code

    def set_details(self, details: str) -> None:
        self._details = details

    def abort(self, code: StatusCode, details: str = ""):
        if code is StatusCode.OK:
            raise ValueError("abort with OK is invalid")
        raise AbortError(code, details)

    def send_initial_metadata(self, metadata: Metadata) -> None:
        if self._initial_sent:
            raise RuntimeError("initial metadata already sent")
        self._initial_sent = True
        self._conn.writer.send(fr.HEADERS, 0, self._stream.stream_id,
                               fr.encode_metadata(list(metadata)))

    # internal ---------------------------------------------------------------

    def _deadline_exceeded(self) -> bool:
        return self._deadline is not None and time.monotonic() >= self._deadline


class _ServerStream:
    """Inbound half of one RPC: request frames → handler-visible iterator."""

    _END = object()
    _OVERSIZED = object()
    _BAD_COMPRESSION = object()

    def __init__(self, stream_id: int, queue_depth: int = 64,
                 recv_limit: Optional[int] = None):
        self.stream_id = stream_id
        #: the EFFECTIVE receive bound (server override or config), quoted in
        #: the RESOURCE_EXHAUSTED details so operators debug the right knob
        self.recv_limit = recv_limit
        self.requests: "queue.Queue[object]" = queue.Queue()
        #: fragment assembly — the FrameReader sink appends wire bytes here
        self.assembly = fr.Assembly()
        self.half_closed = False
        #: a request arrived FLAG_COMPRESSED: mirror the encoding on
        #: responses (the peer demonstrably speaks it)
        self.peer_compressed = False
        self.context: Optional[ServerContext] = None
        #: tpurpc-scope: the caller's trace context (None untraced) + the
        #: HEADERS-arrival stamp feeding the "dispatch" span
        self.trace_ctx = None
        self.trace_t0 = 0
        #: tpurpc-blackbox: the status this stream terminated with (set at
        #: every trailer-send site) — what srv_calls{method,code} records
        self.final_code: Optional[StatusCode] = None
        #: reactor-path pending invocation: (handler, ctx, path) set by
        #: _start_stream for inline unary handlers; consumed by the sink's
        #: commit when the request completes (runs on the reader thread)
        self.inline_call = None
        self.inline_timer = None  # deadline watchdog for the parked call
        #: tpurpc-lens (ISSUE 26): the call's stage bookkeeping, made by
        #: _run_handler on the handler thread before it takes a message
        self.stages: Optional[_lens.CallStages] = None
        #: monotonic_ns of each queued message's put, in queue order: the
        #: pop turns it into the `srv_queue` hop (sentinels carry none)
        self._queued: "collections.deque[int]" = collections.deque()
        #: Backpressure: at most queue_depth completed-but-unconsumed
        #: messages per stream. The connection READER blocks acquiring a
        #: credit, which stops draining the transport, which dries the
        #: ring's credits, which stalls the sender — memory stays bounded
        #: end to end. Control sentinels (_END/_OVERSIZED) bypass: they must
        #: never deadlock delivery. (resource_quota.cc's role, per-stream.)
        self._credits = threading.BoundedSemaphore(max(1, queue_depth))

    def _acquire_credit(self) -> bool:
        """Block until a queue slot frees; False if the stream/ctx died
        meanwhile (drop the message — nobody will read it)."""
        while not self._credits.acquire(timeout=0.25):
            ctx = self.context
            if ctx is not None and not ctx.is_active():
                return False
        return True

    def _release_credit(self) -> None:
        try:
            self._credits.release()
        except ValueError:
            pass  # sentinel consumption paths may over-release; cap holds

    def commit_message(self, more: bool, end_stream: bool,
                       no_message: bool = False,
                       oversized: bool = False,
                       compressed: bool = False) -> None:
        if oversized and not more:
            self.assembly.oversized = False
            self.requests.put(self._OVERSIZED)
        elif not no_message and not more:
            # take() detaches the storage (consumers may alias it); the
            # Assembly object itself is reusable for the next message.
            if self._acquire_credit():
                body = self.assembly.take()
                if compressed:
                    self.peer_compressed = True
                    try:
                        # limit on the POST-decompression size (bomb guard)
                        body = fr.decompress_message(body, self.recv_limit)
                    except fr.DecompressTooLarge:
                        self._release_credit()  # sentinels bypass credits
                        self.requests.put(self._OVERSIZED)
                        body = None
                    except fr.FrameError:
                        self._release_credit()
                        self.requests.put(self._BAD_COMPRESSION)
                        body = None
                if body is not None:
                    self._queued.append(time.monotonic_ns())
                    self.requests.put(body)
            else:
                self.assembly.take()  # stream dead: drop, free the bytes
        if end_stream:
            self.half_closed = True
            self.requests.put(self._END)

    def commit_external(self, body, end_stream: bool) -> None:
        """tpurpc-express: a rendezvous'd request payload — already whole,
        already in its final landing buffer (decode aliases it in place).
        Same per-stream credit backpressure as framed commits."""
        if self._acquire_credit():
            self._queued.append(time.monotonic_ns())
            self.requests.put(body)
        if end_stream:
            self.half_closed = True
            self.requests.put(self._END)

    def cancel(self) -> None:
        if self.context is not None:
            self.context.cancel()
        self.requests.put(self._END)

    def next_request(self, timeout: Optional[float] = None):
        """One queue item with its credit returned; queue.Empty on timeout.
        The wait is one `srv_recv` stage; what the item waited on the queue
        before this thread came for it is the `srv_queue` hop."""
        with self.stages.recv() as rx:
            item = self.requests.get(timeout=timeout)
            if item not in (self._END, self._OVERSIZED,
                            self._BAD_COMPRESSION):
                self._release_credit()
                rx.nbytes = len(item)
                _lens.account("srv_queue", time.monotonic_ns()
                              - self._queued.popleft(), rx.nbytes)
        return item

    def request_iterator(self, deserializer: Deserializer,
                         context: ServerContext) -> Iterator[object]:
        while True:
            item = self.next_request()
            if item is self._END:
                return
            if item is self._OVERSIZED:
                raise AbortError(
                    StatusCode.RESOURCE_EXHAUSTED,
                    "received message larger than max "
                    f"({self.recv_limit} bytes)")
            if item is self._BAD_COMPRESSION:
                raise AbortError(StatusCode.INTERNAL,
                                 "compressed message failed to decompress")
            if not context.is_active():
                return
            message = _deserialize(deserializer, item)
            # from the hand-over until the behavior asks for the next one
            # (or drops the iterator): what it does with this message
            self.stages.handle(len(item))
            try:
                yield message
            finally:
                self.stages.handled()


class _Reply:
    """One response of a stream that answers with futures, in yield order:
    the response, or (``deferred``) the future of it. ``t_done``: when it
    was ready to go (0: its future is still open)."""

    __slots__ = ("value", "deferred", "seq", "t_done")

    def __init__(self, value, seq: int):
        self.value, self.seq = value, seq
        self.deferred = isinstance(value, Future)
        self.t_done = 0


class _DeferredReplies:
    """The ordered writer of ONE stream whose behavior answers with futures
    (ISSUE 36).

    A response-streaming behavior hands its responses to the loop that
    runs it, which is also the only thread that can pull (and, under
    ``device=True``, land) the call's next request. A behavior that owes
    each request an answer it does not have yet (a row it gave to a
    :class:`tpurpc.jaxshim.FanInBatcher`) can therefore not wait for it
    without stopping its own intake, and cannot go on without leaving its
    answers unwritten until a client that is waiting for them sends again.
    It yields the answer's ``concurrent.futures.Future`` instead. From the
    first one on the call's responses go through this queue:

    * **order**: responses leave in the order they were yielded, each as
      soon as it and every earlier one of its stream are ready, whatever
      order (and on whatever threads) the futures resolve; a plain response
      yielded after a future queues behind it.
    * **who writes**: whoever made the head of the queue ready. One thread
      at a time holds ``_writing`` and writes ready responses from the head
      until it meets one that is not; a thread that finds the flag up
      leaves its response to the holder, who looks at the head again under
      the lock before it lets go. No thread of this class's own: a reply
      resolved by a batcher's completion thread is serialized
      (``RpcMethodHandler.late_serializer``) and placed on the wire by that
      thread, its bytes alive in ``_Reply.value`` until ``send`` returns.
    * **bound**: at most ``stream_queue_depth`` responses wait here (the
      bound a stream's requests have); the behavior's thread parks in
      ``push`` beyond it, which stops its intake as a blocking send did.
    * **failure**: a future that failed (or was cancelled) ends the call
      with its error, once: trailers from the thread that found it
      (``AbortError``: its status; else ``UNKNOWN``, as a behavior that
      raised), nothing of the queue written after, and the stream
      cancelled so that a handler thread parked in ``next(requests)``
      wakes and unwinds its generator (which returns what it holds).
    * **the end**: the handler thread closes the call. ``drain`` waits
      for the queue to empty, then it writes the trailers; before it ends
      the call for any other reason it calls ``stop``, after which no
      other thread writes to the stream. Cancellation and the deadline
      are looked at before every write and in every wait.

    Spans and counters: ``srv_send`` one op a response, on the thread that
    wrote it, carrying the call and the response's ordinal; hop
    ``srv_reply_wait`` (counters only) from a future's resolution to the
    start of its send; ``srv_replies_deferred``, ``srv_replies_overtaken``.
    """

    #: lock map (lint rule `lock`)
    _GUARDED_BY = {"_queue": "_lock", "_writing": "_lock", "_end": "_lock"}

    #: `_end` of a queue the handler thread stopped: it ends the call itself
    _STOPPED = "stopped"

    def __init__(self, conn: "_ServerConnection", handler: RpcMethodHandler,
                 st: "_ServerStream", ctx: ServerContext, path: str):
        self._conn, self._st, self._ctx, self._path = conn, st, ctx, path
        #: a plain response is serialized as on the plain path; a future's
        #: result by the handler's late serializer, where it has one
        self._serialize = handler.response_serializer
        self._serialize_late = (handler.late_serializer
                                or handler.response_serializer)
        self._depth = max(1, get_config().stream_queue_depth)
        self._owner = threading.get_ident()  # the call's handler thread
        self._lock = threading.Lock()
        #: signalled when the queue shortens and when a writer lets go
        self._moved = threading.Condition(self._lock)
        self._queue: "collections.deque[_Reply]" = collections.deque()
        self._writing = False
        #: None while responses flow; else what ended them: `_STOPPED`, or
        #: the error a writer met (that writer has ended the call)
        self._end: object = None
        self._seq = 0

    @property
    def failed(self) -> bool:
        """A writer met an error and has ended the call with it."""
        return self._end is not None and self._end is not self._STOPPED

    # -- the handler thread -----------------------------------------------------

    def push(self, response) -> None:
        """Queue the behavior's next response: a future, or a plain response
        that must not pass the futures before it."""
        ctx = self._ctx
        reply = _Reply(response, self._seq)
        self._seq += 1
        with self._lock:
            while (len(self._queue) >= self._depth and self._end is None
                   and ctx.is_active() and not ctx._deadline_exceeded()):
                self._moved.wait(0.25)
            self._queue.append(reply)
        if reply.deferred:
            _SRV_DEFERRED.inc()
            # runs here and now where the future is already resolved
            response.add_done_callback(
                lambda _f, reply=reply: self._resolved(reply))
        else:
            reply.t_done = time.monotonic_ns()
            self._pump()

    def drain(self) -> bool:
        """The behavior has yielded its last: wait until every response is
        on the wire. False where the wait ended another way (a failure, a
        cancel, the deadline): the caller looks which."""
        ctx = self._ctx
        with self._lock:
            while ((self._queue or self._writing) and self._end is None
                   and ctx.is_active() and not ctx._deadline_exceeded()):
                self._moved.wait(0.25)
            return (not self._queue and not self._writing
                    and self._end is None)

    def stop(self) -> bool:
        """The handler thread is about to end the call: no response is
        written after this returns. True where a writer has ended it
        already, with a reply's error (the caller then sends nothing)."""
        with self._lock:
            if self._end is None:
                self._end = self._STOPPED
            while self._writing:
                self._moved.wait()
            self._queue.clear()
        return self.failed

    # -- whoever made a response ready ------------------------------------------

    def _resolved(self, reply: _Reply) -> None:
        with self._lock:
            for earlier in self._queue:
                if earlier is reply:
                    break
                if not earlier.t_done:
                    _SRV_OVERTAKEN.inc()
                    break
        reply.t_done = time.monotonic_ns()
        self._pump()

    def _pump(self) -> None:
        """Write ready responses from the head of the queue, unless another
        thread is at it (it will see what this one made ready)."""
        with self._lock:
            if self._writing:
                return
            self._writing = True
        while True:
            with self._lock:
                head = self._queue[0] if self._queue else None
                if (self._end is not None or head is None
                        or not head.t_done):
                    self._writing = False
                    self._moved.notify_all()
                    return
            error = self._write(head)
            with self._lock:
                if error is None:
                    self._queue.popleft()
                    self._moved.notify_all()
                    continue
                if self._end is not None:
                    # the handler thread stopped the queue meanwhile: it
                    # ends the call itself
                    self._writing = False
                    self._moved.notify_all()
                    return
                self._end = error
            break
        try:
            self._end_call(error)
        finally:
            with self._lock:
                self._queue.clear()
                self._writing = False
                self._moved.notify_all()

    def _write(self, reply: _Reply) -> Optional[BaseException]:
        """Serialize and send the head of the queue; what went wrong, if
        anything did."""
        ctx, st = self._ctx, self._st
        if not ctx.is_active():
            return fr.FrameError("call cancelled")  # nobody to tell
        if ctx._deadline_exceeded():
            return _rdv.SendAbandoned("deadline exceeded")
        value, serialize = reply.value, self._serialize
        if reply.deferred:
            if value.cancelled():
                return CancelledError()
            error = value.exception()
            if error is not None:
                return error
            value, serialize = value.result(), self._serialize_late
            _lens.account("srv_reply_wait",
                          time.monotonic_ns() - reply.t_done)
        own = threading.get_ident() == self._owner
        tx = st.stages.send_begin(None if own else reply.seq)
        try:
            self._conn.writer.send(
                fr.MESSAGE,
                fr.FLAG_COMPRESSED if st.peer_compressed else 0,
                st.stream_id, serialize(value),
                deadline=ctx._deadline, should_stop=ctx._cancelled.is_set)
        except BaseException as exc:
            return exc
        finally:
            st.stages.send_end(tx, inside=own)
        return None

    def _end_call(self, error: BaseException) -> None:
        """A response could not be written: the call ends here, with its
        error, on this thread (``_writing`` is still up, so the handler
        thread's ``stop`` waits for the trailers)."""
        conn, st, ctx = self._conn, self._st, self._ctx
        if isinstance(error, (EndpointError, OSError)) or not ctx.is_active():
            pass  # the connection is gone, or the caller is
        elif isinstance(error, AbortError):
            conn._send_trailers(st, error.code, error.details, ctx._trailing)
        elif isinstance(error, _rdv.SendAbandoned):
            conn._send_trailers(st, StatusCode.DEADLINE_EXCEEDED,
                                "deadline exceeded", ctx._trailing)
        else:
            _log.error("a deferred response of %s failed", self._path,
                       exc_info=error)
            conn._send_trailers(st, StatusCode.UNKNOWN,
                                f"Exception calling application: {error}")
        st.cancel()


class _ServerSink(fr.MessageSink):
    """Routes request MESSAGE bytes into per-stream assembly buffers."""

    def __init__(self, conn: "_ServerConnection"):
        self._conn = conn
        self._discard = fr.Assembly()

    def buffer_for(self, stream_id: int) -> fr.Assembly:
        with self._conn._lock:
            st = self._conn._streams.get(stream_id)
        if st is None:
            self._discard.take()  # drop late bytes
            return self._discard
        return st.assembly

    def commit(self, stream_id: int, flags: int) -> None:
        with self._conn._lock:
            st = self._conn._streams.get(stream_id)
        if st is not None:
            st.commit_message(bool(flags & fr.FLAG_MORE),
                              bool(flags & fr.FLAG_END_STREAM),
                              bool(flags & fr.FLAG_NO_MESSAGE),
                              oversized=st.assembly.oversized,
                              compressed=bool(flags & fr.FLAG_COMPRESSED))
            if flags & fr.FLAG_END_STREAM:
                ic = self._conn._claim_inline(st)
                if ic is not None:
                    # reactor path: the whole request is in st.requests —
                    # run the handler ON THE READER THREAD (no pool
                    # handoff). The native callback API's exact contract
                    # (server.h), opt-in per handler; a blocking handler
                    # stalls this connection.
                    handler, ctx, path = ic
                    self._conn._run_inline(handler, st, ctx, path)


#: reentrancy guard for the inline dispatch path: set while a thread is
#: inside an inline handler. An inline handler that (transitively) completes
#: ANOTHER request on the same thread — inproc passthru endpoints and
#: loopback self-calls can do this synchronously — must not nest dispatches:
#: unbounded recursion, and a second handler's blocking would be invisible
#: to the first connection. Nested inline work reroutes to the pool.
_inline_tls = threading.local()


class _ServerConnection:
    def __init__(self, server: "Server", endpoint: Endpoint,
                 preface_consumed: bool = False):
        self.server = server
        self.endpoint = endpoint
        # coalesce=True: unary responses completing close together on this
        # connection (any mix of pool and inline handlers) flush as one
        # gathered writev — one client-side wakeup for N streams (ISSUE 3)
        self.writer = fr.FrameWriter(endpoint, coalesce=True)
        self.reader = fr.FrameReader(endpoint,
                                     expect_preface=not preface_consumed)
        self.reader.sink = _ServerSink(self)
        self.reader.sink.max_message_bytes = server.max_receive_message_length
        self._streams: Dict[int, _ServerStream] = {}
        self._lock = threading.Lock()
        self.alive = True
        self.draining = False  # GOAWAY sent; no new streams accepted
        self.streams_started = 0  # channelz SocketData counter
        self.last_frame = time.monotonic()  # any inbound frame refreshes
        # tpurpc-express: the rendezvous link (big requests land one-sided
        # in this side's pool; big responses go one-sided into the
        # client's). Created BEFORE the reader starts so the client's
        # capability hello can never race past an unarmed link.
        self.rdv = _rdv.link_for_endpoint(
            endpoint, "srv:" + getattr(endpoint, "peer", "?"),
            self._rdv_send_op, self._rdv_deliver,
            send_ops=self._rdv_send_ops)
        self.writer.rdv = self.rdv
        # tpurpc-pulse (ISSUE 13): the descriptor-ring control plane —
        # our receive ring rides the hello blob; the peer's arrives in its
        # hello and moves this link's control ops off frames entirely
        self._frames_dispatched = 0
        self.ctrl = None
        if self.rdv is not None and _ctrl.enabled():
            try:
                self.ctrl = _ctrl.CtrlPlane(
                    "srv:" + getattr(endpoint, "peer", "?"))
            except Exception:
                self.ctrl = None  # no shm: framed control forever
            if self.ctrl is not None:
                self.rdv.ctrl_post = self._rdv_ctrl_post
                self.rdv.ctrl_drain = self._ctrl_drain
                # per-stream order across the ring/framed split: control
                # ops posted before a sink-routed MESSAGE deliver first
                self.reader.pre_commit = self._ctrl_drain
        if self.rdv is not None:
            self.rdv.recv_limit = server.max_receive_message_length
            # ring planes negotiated at the pair bootstrap (Address.caps)
            pair = getattr(endpoint, "pair", None)
            if pair is not None and "rdv" in getattr(pair, "peer_caps",
                                                     ()):
                self.rdv.on_peer_hello()
            hello = _rdv.HELLO_PAYLOAD
            if self.ctrl is not None:
                hello += self.ctrl.hello_blob()
            try:
                self.writer.send(fr.PING, 0, 0, hello)
            except (EndpointError, OSError, fr.FrameError):
                pass  # connection dying; the read loop surfaces it
        self._thread = threading.Thread(target=self._read_loop, daemon=True,
                                        name="tpurpc-srv-reader")
        self._thread.start()
        self._start_age_timer()
        self._start_keepalive()

    def _start_keepalive(self) -> None:
        """Server-side keepalive (the same GRPC_ARG_KEEPALIVE_TIME_MS knob,
        symmetric with the client's): PING a quiet client, close the
        connection when nothing — not even the PONG — arrives within the
        timeout. Dead clients otherwise pin pooled pairs/rings forever."""
        cfg = get_config()
        if cfg.keepalive_time_ms <= 0:
            return
        interval = cfg.keepalive_time_ms / 1000.0
        timeout = max(0.001, cfg.keepalive_timeout_ms / 1000.0)
        from tpurpc.utils.timers import schedule

        from tpurpc.utils.timers import run_blocking

        state = {"ping_sent_at": None}  # monotonic ts of outstanding PING

        def tick():
            # Wheel-scheduled (no thread per connection; iomgr-timer style).
            if not self.alive:
                return
            with self._lock:
                busy = bool(self._streams)
            if busy:
                # In-flight streams: the reader may be deliberately
                # stalled on per-stream backpressure (stream_queue_depth)
                # with the client's PONGs sitting unread — reaping here
                # would kill live transfers. Peer death mid-stream is
                # caught by write errors / EOF; keepalive exists for the
                # IDLE-and-silent case (dead clients pinning pool state).
                state["ping_sent_at"] = None
                self._ka_handle = schedule(min(interval, 1.0), tick)
                return
            ping_sent_at = state["ping_sent_at"]
            if ping_sent_at is not None and self.last_frame >= ping_sent_at:
                ping_sent_at = state["ping_sent_at"] = None  # PING answered
            quiet = time.monotonic() - self.last_frame
            if quiet < interval:
                state["ping_sent_at"] = None  # frames flowed; window restarts
                self._ka_handle = schedule(min(interval - quiet, 1.0), tick)
                return
            if ping_sent_at is None:
                # Stamp BEFORE the send: on one core the reader can process
                # the loopback PONG before a stamp-after-send executes, and
                # the answered-check would then read the PING as ignored —
                # a healthy-but-quiet client reaped at the next tick.
                state["ping_sent_at"] = time.monotonic()

                def send_ping():  # endpoint write: never on the wheel
                    try:  # ONE ping per silence window (gRPC parity)
                        self.writer.send(fr.PING, 0, 0, b"srv-keepalive")
                    except (EndpointError, OSError, fr.FrameError):
                        self._shutdown()

                run_blocking(send_ping)
                self._ka_handle = schedule(min(timeout, 1.0), tick)
                return
            if time.monotonic() - ping_sent_at >= timeout:
                trace_server.log("keepalive: client silent %.1fs, closing",
                                 quiet)
                run_blocking(self._shutdown)
                return
            self._ka_handle = schedule(min(timeout, 1.0), tick)

        self._ka_handle = schedule(min(interval, 1.0), tick)

    def _start_age_timer(self) -> None:
        """max_age filter analog (GRPC_ARG_MAX_CONNECTION_AGE_MS, off by
        default): after the age, GOAWAY the client — it stops opening
        streams here and dials fresh — then close once in-flight streams
        drain. Bounds how long one connection monopolizes pooled pairs."""
        age_ms = get_config().max_connection_age_ms
        if age_ms <= 0:
            return

        def expire():
            with self._lock:
                if not self.alive or self.draining:
                    return
                self.draining = True
                empty = not self._streams
            try:
                self.writer.send(fr.GOAWAY, 0, 0, b"max_connection_age")
            except (EndpointError, OSError, fr.FrameError):
                return  # connection already dying
            if empty:
                self._linger_then_shutdown()

        from tpurpc.utils.timers import run_blocking, schedule

        # the GOAWAY is an endpoint write (can stall on a credit-wedged
        # transport): run it off the wheel thread
        self._age_timer = schedule(age_ms / 1000.0,
                                   lambda: run_blocking(expire))

    #: After GOAWAY, wait this long before closing the socket: a HEADERS
    #: frame already in flight from a client that hasn't processed the
    #: GOAWAY yet must be answered with RST "connection draining" (which
    #: clients retry transparently) — closing instantly turns that race
    #: into a visible UNAVAILABLE "server closed connection".
    _GOAWAY_LINGER_S = 1.0

    def _linger_then_shutdown(self) -> None:
        from tpurpc.utils.timers import run_blocking, schedule

        self._linger_timer = schedule(
            self._GOAWAY_LINGER_S, lambda: run_blocking(self._shutdown))

    def _read_loop(self) -> None:
        if self.rdv is not None:
            # a handler sending a big response on THIS thread (inline
            # dispatch) must never park waiting for a CLAIM this very
            # thread would have to deliver — such sends stay framed
            self.rdv.disallowed_thread = threading.get_ident()
        try:
            while True:
                f = self._read_frame_ctrl()
                if f is None:
                    break
                self.last_frame = time.monotonic()  # client is alive
                if f is fr.CONSUMED:  # MESSAGE already routed via the sink
                    self._frames_dispatched += 1
                    continue
                self._dispatch(f)
                self._frames_dispatched += 1
        except (EndpointError, fr.FrameError, OSError) as exc:
            trace_server.log("server connection error: %s", exc)
        finally:
            self._shutdown()

    # -- rendezvous plumbing (tpurpc-express) ---------------------------------

    def _rdv_send_op(self, op: int, stream_id: int, payload: bytes) -> None:
        self.writer.send(fr.RDV_FRAME_OF_OP[op], 0, stream_id, payload)

    def _rdv_send_ops(self, ops) -> None:
        """Cold-path coalescer flush: every queued control op in ONE
        gathered writev (tpurpc-pulse)."""
        self.writer.send_many([(fr.RDV_FRAME_OF_OP[op], 0, sid, payload)
                               for op, sid, payload in ops])

    # -- descriptor-ring control plane (tpurpc-pulse, ISSUE 13) ---------------

    def _rdv_ctrl_post(self, op: int, stream_id: int,
                       payload: bytes) -> bool:
        plane = self.ctrl
        if plane is None:
            return False
        return plane.post(op, stream_id, payload, self.writer.frames_sent,
                          self._ctrl_kick)

    def _ctrl_kick(self) -> None:
        try:
            self.writer.send(fr.CTRL_KICK, 0, 0, b"")
        except (EndpointError, OSError, fr.FrameError):
            pass  # connection dying; the read loop surfaces it

    def _frames_count(self) -> int:
        return self._frames_dispatched

    def _ctrl_drain(self) -> int:
        plane, rdv = self.ctrl, self.rdv
        if plane is None or rdv is None:
            return 0
        n = plane.drain(rdv.on_op, self._frames_count)
        if n:
            # ring records are client-liveness evidence exactly as frames
            # are: a pure-ring steady state must not read as "silent"
            self.last_frame = time.monotonic()
        return n

    def _read_frame_ctrl(self, timeout=None):
        plane = self.ctrl
        if plane is None or plane.rx is None:
            return self.reader.read_frame(timeout=timeout)
        return _ctrl.read_frame_polled(self.reader.read_frame,
                                       self._ctrl_drain, plane, timeout)

    def _rdv_deliver(self, stream_id: int, flags: int, body) -> None:
        """A completed rendezvous request payload: the stream's next
        message, zero-copy (the body aliases the landing region). Mirrors
        _ServerSink.commit — including the reactor claim when the message
        half-closes the stream."""
        with self._lock:
            st = self._streams.get(stream_id)
        if st is None:
            return
        st.commit_external(body, bool(flags & fr.FLAG_END_STREAM))
        if flags & fr.FLAG_END_STREAM:
            ic = self._claim_inline(st)
            if ic is not None:
                handler, ctx, path = ic
                self._run_inline(handler, st, ctx, path)

    def _dispatch(self, f: fr.Frame) -> None:
        if f.type == fr.PING:
            if (self.rdv is not None
                    and f.payload.startswith(_rdv.HELLO_PAYLOAD)):
                self.rdv.on_peer_hello(f.payload)
                if self.ctrl is not None:
                    self.ctrl.on_hello(
                        f.payload[len(_rdv.HELLO_PAYLOAD):])
            self.writer.send(fr.PONG, 0, 0, f.payload)
            return
        if f.type == fr.CTRL_KICK:
            return  # the wake itself was the delivery: the loop drains
        if f.type in fr.RDV_OP_OF_FRAME:
            if self.rdv is not None:
                self.rdv.on_op(fr.RDV_OP_OF_FRAME[f.type], f.stream_id,
                               f.payload)
            return
        if f.type == fr.PONG:
            return
        if f.type == fr.GOAWAY:
            raise EndpointError("client sent GOAWAY")
        with self._lock:
            st = self._streams.get(f.stream_id)
        if f.type == fr.HEADERS:
            if st is not None:
                raise fr.FrameError(f"duplicate HEADERS for stream {f.stream_id}")
            self._start_stream(f)
            return
        if st is None:
            return  # frame for a finished/cancelled stream
        if f.type == fr.MESSAGE:  # only without a sink (never in practice)
            st.assembly.append(f.payload)
            st.commit_message(bool(f.flags & fr.FLAG_MORE),
                              bool(f.flags & fr.FLAG_END_STREAM),
                              bool(f.flags & fr.FLAG_NO_MESSAGE),
                              compressed=bool(f.flags & fr.FLAG_COMPRESSED))
        elif f.type == fr.RST:
            st.cancel()
            self._finish_stream(st)
        else:
            raise fr.FrameError(f"unexpected frame {f!r}")

    def _start_stream(self, f: fr.Frame) -> None:
        path, timeout_us, metadata = fr.parse_headers(f.payload)
        st = _ServerStream(f.stream_id,
                           queue_depth=get_config().stream_queue_depth,
                           recv_limit=self.server.max_receive_message_length)
        #: health probes are admitted during drain and excluded from the
        #: drain's remaining-stream count (a held-open Watch must not make
        #: a clean drain report as missing its budget)
        st.is_probe = path.startswith("/grpc.health.")
        # Health RPCs are admitted even while draining: the drain contract
        # is that the health service ANSWERS NOT_SERVING — a refused probe
        # reads as death, not as leaving rotation.
        probe = st.is_probe
        with self._lock:
            # server._draining closes the adoption race: a connection
            # dialed into a draining server can dispatch HEADERS before
            # _sniff_and_serve marks it draining — the stream must still
            # be refused (zero-failed-RPC drain contract)
            if (self.draining or self.server._draining) and not probe:
                rejected = True  # raced the GOAWAY: client dials fresh
            else:
                rejected = False
                self._streams[f.stream_id] = st
                self.streams_started += 1
        if rejected:
            # FLAG_REFUSED is the contract ("no handler ran, replay is
            # safe"); the detail text is for humans only
            self.writer.send(fr.RST, fr.FLAG_REFUSED, f.stream_id,
                             fr.rst_payload(StatusCode.UNAVAILABLE,
                                            "connection draining (max_age)"))
            return
        # tpurpc-fleet admission control: shed BEFORE any handler work.
        # Health probes are exempt — an overloaded backend must keep
        # answering its LB's probes or shedding reads as death.
        gate = self.server.admission
        if gate is not None and not path.startswith("/grpc.health."):
            pushback_ms = gate.try_admit()
            if pushback_ms is not None:
                _SRV_SHED.inc()
                inflight_now = gate.inflight()
                _flight.emit(_flight.ADMIT_REJECT, _SRV_ADMIT_TAG,
                             inflight_now, pushback_ms)
                self._send_trailers(
                    st, StatusCode.UNAVAILABLE,
                    f"server overloaded: admission rejected "
                    f"({inflight_now} in flight); retry after "
                    f"{pushback_ms}ms",
                    [(PUSHBACK_KEY, str(pushback_ms))])
                self._finish_stream(st)
                return
            st._gate = gate  # released exactly once in _finish_stream
        deadline = (None if timeout_us is None
                    else time.monotonic() + timeout_us / 1e6)
        # tpurpc-scope: pick up a sampled caller's trace context; the
        # HEADERS→handler-start interval becomes the "dispatch" span
        st.trace_ctx = _extract_trace(metadata)
        st.trace_t0 = time.monotonic_ns() if st.trace_ctx is not None else 0
        handler = self.server._lookup_intercepted(path, metadata)
        if handler is None:
            self._send_trailers(st, StatusCode.UNIMPLEMENTED,
                                f"unknown method {path}")
            self._finish_stream(st)
            return
        ctx = ServerContext(self, st, metadata, deadline)
        st.context = ctx
        if getattr(handler, "inline", False):
            # reactor path: defer to the sink's commit (reader thread) when
            # the request message completes — zero pool handoffs. The
            # declared deadline still needs a watchdog: a client that opens
            # the stream but never sends the body would otherwise park the
            # call forever (and a non-empty _streams suppresses the
            # keepalive reaper) — non-inline handlers get this from
            # next_request(timeout=...).
            st.inline_call = (handler, ctx, path)
            if deadline is not None:
                # shared timer wheel, NOT threading.Timer: a thread spawn
                # per call was measured as a 25% RPC-rate regression. The
                # expiry itself sends trailers (endpoint write) — off-wheel.
                from tpurpc.utils.timers import run_blocking, schedule

                st.inline_timer = schedule(
                    max(0.0, deadline - time.monotonic()),
                    lambda: run_blocking(lambda: self._inline_deadline(st)))
            return
        try:
            self.server._pool.submit(self._run_handler, handler, st, ctx, path)
        except RuntimeError:  # pool shut down: server is stopping
            self._send_trailers(st, StatusCode.UNAVAILABLE, "server shutting down")
            self._finish_stream(st)
            # A server that cannot run handlers must not keep answering: kill
            # the connection so the client's subchannel redials (a fresh
            # server may own this port by now). Without this, a connection
            # adopted in the stop() race answers every call with this trailer
            # forever and the client — seeing healthy RPC replies — never
            # reconnects (observed: 597 failed attempts/60s in round-2 CI).
            self.close()

    def _claim_inline(self, st: _ServerStream):
        """Atomically take a parked inline call (the sink's commit and the
        deadline watchdog race for it; exactly one side runs)."""
        with self._lock:
            ic, st.inline_call = st.inline_call, None
        if ic is not None and st.inline_timer is not None:
            st.inline_timer.cancel()
            st.inline_timer = None
        return ic

    def _run_inline(self, handler: RpcMethodHandler, st: _ServerStream,
                    ctx: ServerContext, path: str) -> None:
        """Inline dispatch with the reentrancy guard: first level runs on
        the calling (reader) thread; a nested inline completion reroutes
        to the pool (see _inline_tls)."""
        if getattr(_inline_tls, "active", False):
            try:
                self.server._pool.submit(self._run_handler, handler, st,
                                         ctx, path)
            except RuntimeError:  # pool shut down: server is stopping
                self._send_trailers(st, StatusCode.UNAVAILABLE,
                                    "server shutting down")
                self._finish_stream(st)
                self.close()
            return
        _inline_tls.active = True
        try:
            self._run_handler(handler, st, ctx, path)
        finally:
            _inline_tls.active = False

    def _inline_deadline(self, st: _ServerStream) -> None:
        if self._claim_inline(st) is not None:
            _flight.emit(_flight.DEADLINE_EXPIRED,
                         _SRV_INLINE_TAG, st.stream_id)
            self._send_trailers(st, StatusCode.DEADLINE_EXCEEDED,
                                "deadline exceeded awaiting request")
            self._finish_stream(st)

    def _run_handler(self, handler: RpcMethodHandler, st: _ServerStream,
                     ctx: ServerContext, path: str) -> None:
        from tpurpc.utils import stats as _stats

        counters = self.server.call_counters
        counters.on_start()
        ok = False
        tctx = st.trace_ctx
        if tctx is not None and st.trace_t0:
            # HEADERS arrival → handler start: the queue/handoff interval
            _tracing.record("dispatch", tctx, st.trace_t0,
                            time.monotonic_ns() - st.trace_t0, method=path)
        # tpurpc-blackbox: in-flight registration — the stall watchdog
        # sweeps these and names the blocked stage for any call past its
        # method's rolling-p99 multiple
        wd_tok = _watchdog.call_started(
            path, tctx.trace_id if tctx is not None else 0)
        st.stages = _lens.CallStages(
            lambda: _watchdog.call_progress(wd_tok))
        t0 = time.perf_counter_ns()
        t0_mono = time.monotonic_ns()
        try:
            with _tracing.use(tctx) if tctx is not None \
                    else _tracing.NULL_CM:
                if _stats.profiling_on():  # GRPCProfiler span: handler exec
                    with _stats.profile("srv_handler"):
                        ok = self._run_handler_inner(handler, st, ctx, path)
                else:
                    ok = self._run_handler_inner(handler, st, ctx, path)
        finally:
            counters.on_finish(ok)
            _SRV_CALL_US.record((time.perf_counter_ns() - t0) // 1000)
            code = st.final_code if st.final_code is not None \
                else StatusCode.CANCELLED
            _SRV_CALLS.labels(path, int(code)).inc()
            _watchdog.call_finished(wd_tok, error=not ok)
            # tail capture: commit the provisional span tree iff this call
            # turned out pathological (slow for its method, or failed)
            _tracing.tail_decide(tctx, time.monotonic_ns() - t0_mono,
                                 error=not ok, method=path)
            st.stages.finish()

    def _run_handler_inner(self, handler: RpcMethodHandler, st: _ServerStream,
                           ctx: ServerContext, path: str) -> bool:
        try:
            if handler.request_streaming:
                request_in = st.request_iterator(handler.request_deserializer, ctx)
            else:
                try:
                    # Honor the declared deadline while waiting for the request
                    # body, or a silent client pins this pool worker until its
                    # connection dies.
                    item = st.next_request(timeout=ctx.deadline_remaining())
                except queue.Empty:
                    self._send_trailers(st, StatusCode.DEADLINE_EXCEEDED,
                                        "deadline exceeded awaiting request")
                    return
                if item is _ServerStream._OVERSIZED:
                    self._send_trailers(
                        st, StatusCode.RESOURCE_EXHAUSTED,
                        "received message larger than max "
                        f"({st.recv_limit} bytes)")
                    return
                if item is _ServerStream._BAD_COMPRESSION:
                    self._send_trailers(
                        st, StatusCode.INTERNAL,
                        "compressed message failed to decompress")
                    return
                if item is _ServerStream._END or not ctx.is_active():
                    if ctx.is_active():
                        self._send_trailers(
                            st, StatusCode.INVALID_ARGUMENT,
                            "client half-closed before sending a request")
                    return
                request_in = _deserialize(handler.request_deserializer, item)
                # one request: the behavior has it until the call ends (a
                # request stream's iterator opens one stage per message)
                st.stages.handle(len(item))

            result = handler.behavior(request_in, ctx)

            if handler.response_streaming:
                for response in result:
                    if isinstance(response, Future):
                        # an answer that is not ready: this and the rest of
                        # the call's responses go through an ordered queue
                        return self._answer_deferred(
                            handler, st, ctx, path, result, response)
                    if not ctx.is_active():
                        return
                    if ctx._deadline_exceeded():
                        self._send_trailers(st, StatusCode.DEADLINE_EXCEEDED,
                                            "deadline exceeded", ctx._trailing)
                        return
                    # Mirror the request's encoding, read PER SEND: for
                    # request-streaming shapes peer_compressed is only set
                    # once the lazy iterator has consumed a compressed
                    # frame — a value frozen before the generator ran
                    # would lose the mirror race.
                    tx = st.stages.send_begin()
                    try:
                        self.writer.send(
                            fr.MESSAGE,
                            fr.FLAG_COMPRESSED if st.peer_compressed else 0,
                            st.stream_id,
                            handler.response_serializer(response),
                            deadline=ctx._deadline,
                            should_stop=ctx._cancelled.is_set)
                    finally:
                        st.stages.send_end(tx)
                if ctx.is_active():
                    code = (ctx._code if ctx._code is not None
                            else StatusCode.OK)
                    self._send_trailers(st, code, ctx._details, ctx._trailing)
                    return code is StatusCode.OK
            elif ctx.is_active():
                # Unary response: MESSAGE + TRAILERS fused into one transport
                # write (one receiver wakeup instead of two). Serialization
                # + the gathered write are the trace timeline's "respond".
                code = ctx._code if ctx._code is not None else StatusCode.OK
                st.final_code = code
                tx = st.stages.send_begin()
                try:
                    with (_tracing.span("respond", st.trace_ctx)
                          if st.trace_ctx is not None else _tracing.NULL_CM):
                        self.writer.send_many([
                            (fr.MESSAGE,
                             # per-send mirror read (request fully consumed
                             # by now, so peer_compressed is settled)
                             fr.FLAG_COMPRESSED if st.peer_compressed else 0,
                             st.stream_id,
                             handler.response_serializer(result)),
                            (fr.TRAILERS, fr.FLAG_END_STREAM, st.stream_id,
                             fr.trailers_payload(
                                 code, ctx._details,
                                 list(ctx._trailing)
                                 + self.server._load_md())),
                        ])
                except fr.FrameError:
                    self._send_trailers(st, StatusCode.INTERNAL,
                                        "trailing metadata too large")
                    return False
                finally:
                    st.stages.send_end(tx)
                return code is StatusCode.OK
        except AbortError as exc:
            self._send_trailers(st, exc.code, exc.details, ctx._trailing)
        except _rdv.SendAbandoned:
            # a response's wait for rendezvous credit ended with the call:
            # cancelled (nobody to tell), or past its deadline
            if ctx.is_active():
                self._send_trailers(st, StatusCode.DEADLINE_EXCEEDED,
                                    "deadline exceeded", ctx._trailing)
        except (EndpointError, OSError):
            pass  # connection already gone
        except Exception as exc:  # handler bug → UNKNOWN, like grpcio
            _log.exception("handler for %s raised", path)
            self._send_trailers(st, StatusCode.UNKNOWN,
                                f"Exception calling application: {exc}")
        finally:
            self._finish_stream(st)
        return False

    def _answer_deferred(self, handler: RpcMethodHandler, st: _ServerStream,
                         ctx: ServerContext, path: str, result,
                         first: Future) -> bool:
        """The rest of a response stream whose behavior has yielded a
        future (:class:`_DeferredReplies`): the handler thread goes straight
        back to the generator after each response, so it pulls the call's
        next request while the answers to earlier ones are still open.
        Runs inside ``_run_handler_inner``'s ``try``: what is raised here is
        handled there, after ``stop`` has made this thread the stream's
        only writer again."""
        replies = _DeferredReplies(self, handler, st, ctx, path)
        try:
            replies.push(first)
            for response in result:
                if replies.failed or not ctx.is_active():
                    return False
                if ctx._deadline_exceeded():
                    break
                replies.push(response)
            if replies.drain():
                code = ctx._code if ctx._code is not None else StatusCode.OK
                self._send_trailers(st, code, ctx._details, ctx._trailing)
                return code is StatusCode.OK
        except BaseException:
            if replies.stop():
                return False  # the call has failed already, with a reply's error
            raise
        if replies.stop() or not ctx.is_active():
            return False
        self._send_trailers(st, StatusCode.DEADLINE_EXCEEDED,
                            "deadline exceeded", ctx._trailing)
        return False

    def _send_trailers(self, st: _ServerStream, code: StatusCode, details: str,
                       metadata: Metadata = ()) -> None:
        st.final_code = code
        if st.stages is not None:
            # the caller has the end of its stream once these trailers are
            # out: count the call before, not in _run_handler's finally
            st.stages.finish()
        # tpurpc-fleet: every terminal response piggybacks the (cached)
        # load report — the least_loaded policy's per-response feed
        md = list(metadata) + self.server._load_md()
        try:
            try:
                self.writer.send(fr.TRAILERS, fr.FLAG_END_STREAM, st.stream_id,
                                 fr.trailers_payload(code, details, md))
            except fr.FrameError:
                # User trailing metadata too large for one control frame: still
                # terminate the stream correctly, just without the metadata.
                self.writer.send(
                    fr.TRAILERS, fr.FLAG_END_STREAM, st.stream_id,
                    fr.trailers_payload(StatusCode.INTERNAL,
                                        "trailing metadata too large"))
        except (EndpointError, OSError):
            pass

    def _finish_stream(self, st: _ServerStream) -> None:
        with self._lock:
            self._streams.pop(st.stream_id, None)
            # admission release exactly once (the RST path and the handler
            # finally can both land here; the lock orders the take)
            gate = getattr(st, "_gate", None)
            if gate is not None:
                st._gate = None
            drained = self.draining and not self._streams and self.alive
        if gate is not None:
            gate.release()
        if drained and getattr(self, "_linger_timer", None) is None:
            # last in-flight stream after GOAWAY: close after the linger
            # (racing HEADERS still get a clean RST meanwhile)
            self._linger_then_shutdown()

    def _shutdown(self) -> None:
        with self._lock:
            if not self.alive:
                return
            self.alive = False
            streams = list(self._streams.values())
            self._streams.clear()
        for attr in ("_age_timer", "_ka_handle", "_linger_timer"):
            h = getattr(self, attr, None)
            if h is not None:
                h.cancel()  # wheel handles; ticks also re-check alive
        if self.rdv is not None:
            # peer gone mid-rendezvous: claimed landing regions release
            self.rdv.close()
        if self.ctrl is not None:
            # descriptor rings die with the connection (a straggler's late
            # slot store lands in the orphaned mapping — dead memory)
            self.ctrl.close()
        for st in streams:
            gate = getattr(st, "_gate", None)
            if gate is not None:
                st._gate = None
                gate.release()  # connection died with the stream admitted
            st.cancel()
        try:
            self.endpoint.close()
        except Exception:
            pass
        self.server._forget(self)

    def close(self) -> None:
        try:
            self.endpoint.close()  # unblocks the reader thread
        except Exception:
            pass


class Server:
    """Thread-pooled RPC server over any Endpoint source."""

    def __init__(self, max_workers: int = 32, interceptors: Sequence = (),
                 max_receive_message_length: Optional[int] = None,
                 native_dataplane: Optional[bool] = None,
                 admission: "Optional[AdmissionGate]" = None):
        #: tpurpc extension: None = auto (adopt ring connections onto the
        #: native shared-poller loop when eligible — the small-RPC latency
        #: plane); False = always the Python plane (fully instrumented —
        #: the copy ledger counts its passes; note it is ~40% slower on
        #: multi-MiB streams since round 5 fixed the native plane's
        #: notify-token-stealing bug — 1.20 vs 0.86 GB/s same-weather,
        #: bench.py sink A/B). True behaves like auto (the eligibility
        #: gates still apply; they are correctness gates).
        self._native_dataplane_opt = native_dataplane
        self._pool = ThreadPoolExecutor(max_workers=max_workers,
                                        thread_name_prefix="tpurpc-handler")
        self.interceptors = list(interceptors)
        #: per-message receive bound (None = config default; -1 = unlimited)
        self.max_receive_message_length = get_config().resolve_recv_limit(
            max_receive_message_length)
        from tpurpc.rpc import channelz as _channelz

        self.call_counters = _channelz.CallCounters()
        _channelz.register_server(self)
        self._methods: Dict[str, RpcMethodHandler] = {}
        self._generic_handlers: List = []  # grpcio GenericRpcHandler objects
        self._listeners: List[EndpointListener] = []
        self.bound_ports: List[int] = []
        self._connections: List[_ServerConnection] = []
        self._lock = threading.Lock()
        self._started = False
        self._stopping = False  # set under _lock before conns are torn down
        self._serving = threading.Event()
        self._stopped = threading.Event()
        # tpurpc-fleet (ISSUE 6): overload admission gate (explicit wins;
        # TPURPC_ADMISSION_MAX_INFLIGHT configures one from the env),
        # graceful-drain state, and the per-response load-report cache
        self.admission = (admission if admission is not None
                          else AdmissionGate.from_env())
        self._draining = False
        self._health_servicer = None  # set by HealthServicer.add_to_server
        import os as _os

        self._load_reports = _os.environ.get(
            "TPURPC_LOAD_REPORTS", "1").lower() not in ("0", "off", "false")
        self._load_extra: Optional[Callable[[], int]] = None
        self._load_cache: Tuple[float, Optional[list]] = (0.0, None)
        self._drain_hooks: List[Callable[[], None]] = []

    # -- registration --------------------------------------------------------

    def add_method(self, path: str, handler: RpcMethodHandler) -> None:
        self._methods[path] = handler

    def add_generic_handlers(self, handlers: Dict[str, RpcMethodHandler]) -> None:
        self._methods.update(handlers)

    # -- grpcio-generated-code compatibility ---------------------------------
    #
    # Modules generated by grpc_tools.protoc register services via
    # add_generic_rpc_handlers((generic_handler,)) and (grpcio>=1.60)
    # add_registered_method_handlers(service, {name: grpc.RpcMethodHandler}).
    # Accepting both — with grpcio's handler OBJECTS duck-adapted to ours —
    # makes `add_FooServicer_to_server(servicer, tpurpc_server)` run
    # unchanged: the mechanical-port claim for the server side.

    @staticmethod
    def _adapt_foreign_handler(h) -> Optional[RpcMethodHandler]:
        """grpc.RpcMethodHandler (any object with the grpcio attribute set)
        → our handler; None if it isn't one."""
        if isinstance(h, RpcMethodHandler):
            return h
        try:
            kind = (("stream" if h.request_streaming else "unary") + "_"
                    + ("stream" if h.response_streaming else "unary"))
            behavior = getattr(h, kind)
        except AttributeError:
            return None
        if behavior is None:
            return None
        return RpcMethodHandler(kind, behavior,
                                h.request_deserializer or _identity,
                                h.response_serializer or _identity)

    def add_generic_rpc_handlers(self, generic_handlers) -> None:
        """grpcio-shaped: a sequence of GenericRpcHandler objects whose
        ``.service(handler_call_details)`` resolves methods at call time."""
        self._generic_handlers.extend(generic_handlers)

    def add_registered_method_handlers(self, service: str,
                                       method_handlers) -> None:
        """grpcio-shaped (>=1.60): eager per-method registration."""
        for name, h in dict(method_handlers).items():
            adapted = self._adapt_foreign_handler(h)
            if adapted is not None:
                self._methods[f"/{service}/{name}"] = adapted

    def add_service(self, service: str,
                    method_handlers: Dict[str, RpcMethodHandler]) -> None:
        self.add_generic_handlers(
            method_handlers_generic_handler(service, method_handlers))

    def _lookup_intercepted(self, path: str,
                            metadata) -> Optional[RpcMethodHandler]:
        """Handler lookup through the server interceptor chain."""
        handler = self._lookup(path, metadata)
        if not self.interceptors:
            return handler
        from tpurpc.rpc.interceptors import apply_server_interceptors

        return apply_server_interceptors(handler, path, metadata,
                                         self.interceptors)

    def _lookup(self, path: str, metadata=()) -> Optional[RpcMethodHandler]:
        handler = self._methods.get(path)
        if handler is not None:
            return handler
        # grpcio-generic fallback: resolve through registered
        # GenericRpcHandler objects (duck-typed .service(details)), or plain
        # {path: handler} mappings (what tpurpc's own
        # method_handlers_generic_handler returns — pre-1.60-style generated
        # code passes those straight to add_generic_rpc_handlers).
        for gh in self._generic_handlers:
            getter = getattr(gh, "get", None)
            cacheable = getter is not None
            if cacheable:  # Mapping-shaped: metadata-independent by shape
                found = getter(path)
            else:
                try:
                    found = gh.service(_HandlerCallDetails(path, metadata))
                except Exception:
                    # a routing bug must not masquerade as UNIMPLEMENTED
                    _log.exception(
                        "generic handler %r raised resolving %s", gh, path)
                    continue
            if found is not None:
                adapted = self._adapt_foreign_handler(found)
                if adapted is not None and cacheable:
                    # hot-path cache; .service() results are NOT cached —
                    # a generic handler may route on metadata per call
                    self._methods[path] = adapted
                return adapted
        return None

    # -- ports / lifecycle ---------------------------------------------------

    def add_insecure_port(self, address: str, *,
                          reuseport: bool = False) -> int:
        """Bind now, return the real port (grpcio semantics: the port for
        ":0" must be known before start so clients can be pointed at it).

        ``reuseport=True`` is the tpurpc-manycore listener-sharding mode:
        shard workers bind the SAME port with ``SO_REUSEPORT`` and the
        kernel spreads accepts across them (see
        :class:`tpurpc.rpc.shard.ShardedServer`)."""
        host, _, port = address.rpartition(":")
        bound = self._open_port(host or "0.0.0.0", int(port),
                                reuseport=reuseport)
        self.bound_ports.append(bound)
        return bound

    def add_secure_port(self, address: str, server_credentials) -> int:
        """TLS port (grpcio-shaped): every connection handshakes before the
        protocol sniff, so native-framing, ring-bootstrap, and h2 traffic all
        ride the encrypted stream. Pass the result of
        :func:`tpurpc.rpc.credentials.ssl_server_credentials`."""
        host, _, port = address.rpartition(":")
        bound = self._open_port(host or "0.0.0.0", int(port),
                                ssl_context=server_credentials._context)
        self.bound_ports.append(bound)
        return bound

    def _open_port(self, host: str, port: int, ssl_context=None,
                   reuseport: bool = False) -> int:
        listener = EndpointListener(
            host, port, self.serve_endpoint, ready=self._serving,
            ssl_context=ssl_context,
            raw_hook=None if ssl_context is not None
            else self._try_native_adopt,
            reuseport=reuseport,
            admission=self._accept_pushback)
        self._listeners.append(listener)
        return listener.port

    def _accept_pushback(self) -> "Optional[int]":
        """Accept-path face of the admission gate (ISSUE 16): the
        listener sheds stormed connections before handshake work when the
        RPC plane is saturated."""
        gate = self.admission
        if gate is None:
            return None
        return gate.connection_pushback_ms()

    def adopt_socket(self, sock) -> None:
        """tpurpc-manycore handoff entry: serve a connection that was
        ACCEPTED ELSEWHERE (the shard supervisor's accept loop, delivered
        over SCM_RIGHTS) exactly as this server's own listener would —
        native-plane adoption probe first, then the platform endpoint
        factory, then the protocol sniff. Runs off the caller's thread: a
        ring bootstrap blocks, and the worker's control loop must not stall
        behind one silent client."""

        def _adopt():
            try:
                if self._try_native_adopt(sock):
                    return  # native data plane owns the socket now
            except Exception as exc:
                trace_server.log("handoff native probe failed (%s)", exc)
            try:
                peer = sock.getpeername()
                host = peer[0] if isinstance(peer, tuple) else str(peer)
                from tpurpc.core.endpoint import create_endpoint

                ep = create_endpoint(sock, is_server=True,
                                     pool_key=f"peer:{host}")
            except Exception as exc:
                trace_server.log("handoff bootstrap failed: %s", exc)
                try:
                    sock.close()
                except OSError:
                    pass
                return
            self.serve_endpoint(ep)

        threading.Thread(target=_adopt, daemon=True,
                         name="tpurpc-handoff").start()

    def start(self) -> "Server":
        if self._started:
            return self
        # Native data plane (rpc/native_server.py): eligible servers hand
        # accepted ring connections to libtpurpc's shared-poller loop with
        # Python handlers trampolined back — the grpcio architecture
        # (language surface over the C core). Built at start() so every
        # registered method exists; listeners only accept after _serving.
        self._native_dp = None
        try:
            from tpurpc.rpc.native_server import (NativeDataplane,
                                                  adoption_eligible)

            if adoption_eligible(self):
                self._native_dp = NativeDataplane(self)
        except Exception as exc:  # lib unbuildable etc.: Python plane
            trace_server.log("native dataplane unavailable: %s", exc)
        self._started = True
        # tpurpc-lens (ISSUE 8): continuous stage profiling starts with the
        # server (idempotent; no-op under TPURPC_LENS=0)
        try:
            _obs_profiler.ensure_started()
        except Exception:
            pass
        # tpurpc-argus (ISSUE 14): the ring tsdb samples this process's
        # registry from the moment it serves (idempotent; TPURPC_TSDB=0
        # off), any declared SLO objectives start evaluating, and
        # TPURPC_BUNDLE_DIR arms automatic evidence capture
        try:
            from tpurpc.obs import bundle as _obs_bundle
            from tpurpc.obs import slo as _obs_slo
            from tpurpc.obs import tsdb as _obs_tsdb

            _obs_tsdb.ensure_started()
            _obs_slo.ensure_started()
            _obs_bundle.maybe_enable_from_env()
        except Exception:
            pass
        self._serving.set()  # listeners begin accepting (bound since add_port)
        return self

    def _try_native_adopt(self, sock) -> bool:
        """Raw-socket listener hook: peek the protocol magic and hand RING
        connections (TRB1 bootstrap) to the native data plane. Peeking
        (MSG_PEEK) consumes nothing, so a False return leaves the socket
        exactly as accepted for the Python path."""
        import socket as _socket

        dp = getattr(self, "_native_dp", None)
        if dp is None:
            return False
        deadline = time.monotonic() + 30
        first = b""
        try:
            sock.settimeout(2)
            while len(first) < 4 and time.monotonic() < deadline:
                try:
                    first = sock.recv(4, _socket.MSG_PEEK)
                except (TimeoutError, _socket.timeout):
                    continue
                if not first:
                    return False  # peer closed before the preface
                if len(first) < 4:
                    time.sleep(0.002)
        except OSError:
            return False
        finally:
            # EVERY False return hands the socket to the Python plane, which
            # expects it exactly as accepted (blocking); a leaked 2s timeout
            # would surface as spurious socket.timeout on slow valid reads.
            try:
                sock.settimeout(None)
            except OSError:
                pass  # already closed/reset: the caller's read will see it
        if first != b"TRB1":
            return False
        return dp.adopt(sock)

    def serve_endpoint(self, endpoint: Endpoint) -> None:
        """Adopt an already-connected endpoint, sniffing the protocol.

        The first 8 bytes decide: the TPURPC magic routes to the native
        framing; ``PRI * HT`` (the h2 connection preface) routes to the gRPC
        wire-compat path — one port serves stock gRPC clients and tpurpc
        clients simultaneously (the reference needs no sniff because it IS
        gRPC; we speak both).

        Runs the sniff on its own thread: callers (accept bootstrap, inproc
        tests) may invoke this before the client has written a byte.
        """
        threading.Thread(target=self._sniff_and_serve, args=(endpoint,),
                         daemon=True, name="tpurpc-sniff").start()

    def _sniff_and_serve(self, endpoint: Endpoint) -> None:
        first = bytearray(8)
        got = 0
        try:
            while got < 8:
                n = endpoint.read_into(memoryview(first)[got:], timeout=30)
                if n == 0:
                    endpoint.close()
                    return
                got += n
        except (EndpointError, TimeoutError):
            endpoint.close()
            return
        try:
            if bytes(first) == fr.MAGIC:
                conn = _ServerConnection(self, endpoint,
                                         preface_consumed=True)
            elif bytes(first) == b"PRI * HT":
                from tpurpc.wire.grpc_h2 import GrpcH2Connection

                conn = GrpcH2Connection(self, endpoint, preface_consumed=8)
            elif (bytes(first[:4]) == b"GET "
                  or bytes(first[:5]) == b"HEAD "):
                # tpurpc-scope introspection plane (ISSUE 4): the SAME
                # serving port answers plain-HTTP scrapes — /metrics
                # (Prometheus text), /traces (chrome trace JSON),
                # /channelz, /healthz. One request per connection, served
                # on this sniff thread, then closed. TPURPC_SCRAPE=0 off.
                from tpurpc.obs import scrape as _scrape

                if _scrape.scrape_enabled():
                    _scrape.handle_http(endpoint, bytes(first))
                else:
                    endpoint.close()
                return
            else:
                trace_server.log("unknown protocol preface %r; dropping",
                                 bytes(first))
                endpoint.close()
                return
        except (EndpointError, OSError) as exc:
            # The peer vanished mid-adoption (e.g. junk preface + close —
            # the h2 path writes SETTINGS during construction): contain it
            # to this connection instead of dying as an unhandled thread
            # exception.
            trace_server.log("peer gone during adoption: %s", exc)
            endpoint.close()
            return
        # Registration must be atomic against stop(): this sniff thread may
        # have been waiting on the preface for seconds, during which stop()
        # closed every *registered* connection and shut the pool. Adopting a
        # connection now would strand the client on a server that answers
        # every call "server shutting down" and never dies (the round-2
        # reconnect bug: client saw healthy trailers, so it never redialed).
        with self._lock:
            adopted = not self._stopping
            drain_new = self._draining
            if adopted:
                self._connections.append(conn)
        if not adopted:
            conn.close()
        elif drain_new:
            # tpurpc-fleet: a connection dialed INTO a draining server (a
            # stale resolver, or a subchannel racing the drain) is told
            # immediately — streams that race the GOAWAY get the refused
            # RST, which clients replay on another backend
            writer = getattr(conn, "writer", None)
            if writer is not None:
                with conn._lock:
                    conn.draining = True
                try:
                    writer.send(fr.GOAWAY, 0, 0, b"server drain")
                except (EndpointError, OSError, fr.FrameError):
                    pass
                conn._linger_then_shutdown()

    def _forget(self, conn: _ServerConnection) -> None:
        with self._lock:
            try:
                self._connections.remove(conn)
            except ValueError:
                pass

    def stop(self, grace: Optional[float] = None) -> threading.Event:
        for listener in self._listeners:
            listener.close()
        self._listeners.clear()
        with self._lock:
            self._stopping = True  # gate _sniff_and_serve adoptions first
            conns = list(self._connections)
        if grace:
            # Graceful semantics (grpcio parity): announce shutdown — every
            # frame-protocol connection gets a GOAWAY so clients stop
            # opening streams here (in-flight calls keep running through
            # the grace window). h2 connections have no GOAWAY sender yet;
            # they still get the drain wait below and close() after it.
            from tpurpc.wire import h2 as _h2

            for conn in conns:
                writer = getattr(conn, "writer", None)
                if writer is None:
                    # h2-protocol connection: speak h2's own GOAWAY
                    try:
                        conn._write(_h2.pack_goaway(0, 0, b"server shutdown"))
                    except Exception:
                        pass  # connection already dying
                    continue
                with conn._lock:
                    conn.draining = True
                try:
                    writer.send(fr.GOAWAY, 0, 0, b"server shutdown")
                except (EndpointError, OSError, fr.FrameError):
                    pass  # connection already dying
            deadline = time.monotonic() + grace
            while time.monotonic() < deadline:
                with self._lock:
                    busy = any(c._streams for c in self._connections)
                if not busy:
                    break
                time.sleep(0.01)
        for conn in conns:
            conn.close()
        dp = getattr(self, "_native_dp", None)
        if dp is not None:
            self._native_dp = None
            try:
                dp.close()  # tears down adopted connections + native pollers
            except Exception:
                pass
        self._pool.shutdown(wait=False)
        self._stopped.set()
        return self._stopped

    def wait_for_termination(self, timeout: Optional[float] = None) -> bool:
        return self._stopped.wait(timeout)

    def inflight_requests(self) -> int:
        """Number of currently open inbound streams across connections —
        requests admitted (HEADERS seen) whose response hasn't finished.
        The FanInBatcher's depth-aware flush probe (serve_jax wiring): when
        its queue holds this many, no further arrival can happen until
        responses go out, so it flushes instead of waiting out max_delay_s.
        A snapshot, not a fence — callers must tolerate staleness."""
        with self._lock:
            conns = list(self._connections)
        return sum(len(getattr(c, "_streams", ())) for c in conns)

    # -- fleet front door (tpurpc-fleet, ISSUE 6) -----------------------------

    def set_load_provider(self, fn: Optional[Callable[[], int]]) -> None:
        """Register an extra queue-depth signal for the load report —
        serve_jax wires the FanInBatcher's queue depth here, so the
        ``least_loaded`` policy sees requests parked BEHIND the transport
        (the batcher is where overload actually queues on a model server).
        tpurpc-keystone wires ``DecodeScheduler.load_depth`` (waiting AND
        swapped) — queue depth alone made a server holding preempted work
        look idle."""
        self._load_extra = fn

    def add_drain_hook(self, fn: Callable[[], None]) -> None:
        """Register a callback the FIRST :meth:`drain` runs after the
        GOAWAY round, before waiting out in-flight streams — the seam
        stateful serving uses to MIGRATE live sequences to a peer instead
        of merely finishing them (tpurpc-keystone: the zero-failed-RPC
        drain contract extended to generation state). Hooks run on the
        draining thread; exceptions are swallowed (a failed hook degrades
        to a plain drain, never a stuck one)."""
        self._drain_hooks.append(fn)

    def _load_md(self) -> list:
        """The ORCA-style piggyback: ``[(LOAD_KEY, "i,q,p99ms")]`` appended
        to every terminal response's trailing metadata, or ``[]`` when
        disabled (``TPURPC_LOAD_REPORTS=0``).

        Cached ~20 ms so the per-response cost is one monotonic read plus a
        list concat — load is a trend, not a fence, and the client-side
        EWMA smooths staleness anyway. Inflight comes from the admission
        gate's own counter when one is installed (no lock sweep), else from
        :meth:`inflight_requests`."""
        if not self._load_reports:
            return []
        now = time.monotonic()
        stamp, cached = self._load_cache
        if cached is not None and now - stamp < 0.02:
            return cached
        gate = self.admission
        inflight = (gate.inflight() if gate is not None
                    else self.inflight_requests())
        qdepth = 0
        extra = self._load_extra
        if extra is not None:
            try:
                qdepth = int(extra())
            except Exception:
                qdepth = 0
        p99_ms = 0.0
        try:
            from tpurpc.obs import watchdog as _watchdog

            p99 = _watchdog.get().rolling_p99_ns()
            if p99:
                p99_ms = p99 / 1e6
        except Exception:
            pass
        md = [(LOAD_KEY, f"{inflight},{qdepth},{p99_ms:.1f}")]
        self._load_cache = (now, md)
        return md

    @property
    def draining(self) -> bool:
        """True between :meth:`drain` and :meth:`stop` — /healthz reports
        ``draining`` and the health service answers NOT_SERVING. A stopped
        server is not draining (it is gone): /healthz on a process whose
        old server object lingers must not keep reporting the drain."""
        return self._draining and not self._stopped.is_set()

    def drain(self, linger: float = 5.0) -> bool:
        """Server-wide graceful drain: announce, bleed, never fail a call.

        Generalizes the per-connection ``max_connection_age`` path to the
        whole server: (1) the attached health servicer (if any) flips every
        service to NOT_SERVING so LBs stop routing here; (2) every live
        connection gets a GOAWAY — clients stop opening streams on it and
        dial elsewhere; streams that race the GOAWAY are refused with
        FLAG_REFUSED, which clients replay on another subchannel
        (zero failed RPCs); (3) in-flight streams run to completion under
        the ``linger`` budget. Connections opened DURING the drain are
        GOAWAY'd at adoption, so a stale resolver can't keep feeding this
        backend.

        The server object stays alive (listeners answer /healthz scrapes
        and health RPCs — orchestrators need the probe plane up while
        connections bleed); call :meth:`stop` once traffic has moved.
        Returns True iff every in-flight stream finished within the budget.
        Idempotent: a second call just re-waits the remaining streams."""
        with self._lock:
            first = not self._draining
            self._draining = True
            conns = list(self._connections)
        n_conns = len(conns)
        if first:
            _flight.emit(_flight.DRAIN_BEGIN, _SRV_DRAIN_TAG, n_conns)
            hs = self._health_servicer
            if hs is not None:
                from tpurpc.rpc.health import ServingStatus

                hs.set_all(ServingStatus.NOT_SERVING)
            from tpurpc.wire import h2 as _h2

            for conn in conns:
                writer = getattr(conn, "writer", None)
                if writer is None:
                    # h2-protocol connection: speak h2's own GOAWAY
                    try:
                        conn._write(_h2.pack_goaway(0, 0, b"server drain"))
                    except Exception:
                        pass  # connection already dying
                    continue
                with conn._lock:
                    if not conn.alive or conn.draining:
                        continue
                    conn.draining = True
                    empty = not conn._streams
                try:
                    writer.send(fr.GOAWAY, 0, 0, b"server drain")
                except (EndpointError, OSError, fr.FrameError):
                    continue  # connection already dying
                if empty:
                    # no in-flight streams: close after the refused-HEADERS
                    # linger (the max_age path's exact contract)
                    conn._linger_then_shutdown()
            # stateful-serving seam: migrate live sequences BEFORE the
            # in-flight wait, so streams end with re-attach records (and
            # stop counting against the linger) instead of running out
            # their full generations here
            for hook in list(self._drain_hooks):
                try:
                    hook()
                except Exception:
                    pass  # a failed hook degrades to a plain drain
        deadline = time.monotonic() + max(0.0, linger)
        while True:
            with self._lock:
                # health probes (Check + held-open Watch streams) are
                # admitted during drain and must not count against it
                remaining = sum(
                    1
                    for c in self._connections
                    for st in list(getattr(c, "_streams", {}).values())
                    if not getattr(st, "is_probe", False))
            if remaining == 0 or time.monotonic() >= deadline:
                break
            time.sleep(0.01)
        if first:
            _flight.emit(_flight.DRAIN_END, _SRV_DRAIN_TAG, remaining)
        return remaining == 0


def server(thread_pool=None, handlers=None, interceptors=None, options=None,
           maximum_concurrent_rpcs=None, compression=None, *,
           max_workers: int = 32) -> Server:
    """grpcio-shaped constructor — accepts the stock call
    ``grpc.server(ThreadPoolExecutor(max_workers=N), options=[...])``
    verbatim: a passed executor contributes its worker count (the Server
    keeps its own pool), handlers/interceptors register directly, the
    recognized channel-arg options map onto Server parameters, and the
    remaining stock kwargs are accepted-and-advisory
    (maximum_concurrent_rpcs — concurrency is bounded by the worker pool
    and per-stream credits instead; compression is negotiated per wire).
    A bare int first argument keeps the historical server(N) meaning."""
    if isinstance(thread_pool, int):  # legacy positional max_workers
        max_workers = thread_pool
    elif thread_pool is not None:
        workers = getattr(thread_pool, "_max_workers", None)
        if workers:
            max_workers = workers
    max_recv = None
    if options:
        max_recv = dict(options).get("grpc.max_receive_message_length")
    srv = Server(max_workers=max_workers, interceptors=interceptors or (),
                 max_receive_message_length=max_recv)
    if handlers:
        srv.add_generic_rpc_handlers(handlers)
    return srv


def inproc_channel(srv: Server):
    """In-process channel↔server wiring over a passthru endpoint pair — the
    reference's inproc transport (``src/core/ext/transport/inproc/``) as a seam."""
    from tpurpc.rpc.channel import Channel

    def factory():
        a, b = passthru_endpoint_pair()
        srv.serve_endpoint(b)
        return a

    return Channel(endpoint_factory=factory)
