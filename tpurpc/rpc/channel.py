"""Client channel: multiplexed calls over one endpoint, with reconnect-on-UNAVAILABLE.

Reference mapping (SURVEY.md §3.2/§3.3):

* ``Channel`` ≈ ``grpc_channel`` + the client_channel filter
  (``ext/filters/client_channel/client_channel.cc``): it owns subchannel
  (re)connection with exponential backoff (``lib/backoff/``), hands calls to a live
  transport, and maps transport failure to ``UNAVAILABLE`` so callers may retry
  (``rdma_bp_posix.cc:86-96`` annotation rule).
* ``_Connection`` ≈ one chttp2 transport instance: a reader thread demuxing frames
  to per-stream state (``chttp2_transport.cc`` read_action_locked), a write path
  serialized by ``FrameWriter`` (write_action), odd client stream ids as in h2.
* The four ``*MultiCallable`` shapes mirror grpcio's public API
  (``src/python/grpcio/grpc/_channel.py``) so porting an app is mechanical.
"""

from __future__ import annotations

import os
import queue
import random
import threading
import time
from typing import (Callable, Iterable, Iterator, List, Optional, Sequence,
                    Tuple, Union)

from tpurpc.analysis.locks import make_condition, make_lock
from tpurpc.core import ctrlring as _ctrl
from tpurpc.core import rendezvous as _rdv
from tpurpc.core.endpoint import Endpoint, EndpointError, connect_endpoint
from tpurpc.obs import flight as _flight
from tpurpc.obs import metrics as _obs_metrics
from tpurpc.obs import tracing as _tracing
from tpurpc.rpc import frame as fr
from tpurpc.rpc.status import (ChannelConnectivity, Deserializer, Metadata,
                               RpcError, Serializer, StatusCode,
                               deserialize as _deserialize,
                               identity_codec as _identity)
from tpurpc.utils.trace import TraceFlag

trace_channel = TraceFlag("channel")

# tpurpc-scope (ISSUE 4): pipelined-client observability. In-flight depth
# is a scrape-time fleet gauge over live PipelinedUnary windows; the two
# latency histograms record once per pipelined call (microseconds) —
# call_us is send→future-resolved, demux_us is the reader-thread hop from
# terminal delivery to future resolution.
_PIPELINES_INFLIGHT = _obs_metrics.fleet("pipeline_inflight",
                                         lambda pl: pl._inflight)
_PIPE_CALL_US = _obs_metrics.histogram("pipeline_call_us", kind="latency")
_PIPE_DEMUX_US = _obs_metrics.histogram("pipeline_demux_us", kind="latency")
#: tpurpc-blackbox (ISSUE 5): per-method client-observed deadline expiries
#: (PipelinedUnary's timer wheel + the blocking unary path both feed it)
_DEADLINE_EXCEEDED = _obs_metrics.labeled_counter("deadline_exceeded",
                                                  ("method",))
# tpurpc-fleet (ISSUE 6): hedging counters + the interned flight tag for
# the hedge emission sites (pure-int plumbing; the `flight` lint rule
# covers this module). The metadata keys mirror tpurpc.rpc.server's
# LOAD_KEY/PUSHBACK_KEY — duplicated literals rather than a server import
# in the client module (test_fleet pins them equal).
_HEDGES_FIRED = _obs_metrics.counter("hedges_fired")
_HEDGES_WON = _obs_metrics.counter("hedges_won")
_HEDGE_TAG = _flight.tag_for("hedge")
_LOAD_KEY = "tpurpc-load"
_PUSHBACK_KEY = "tpurpc-pushback-ms"


def _pushback_s(exc) -> "Optional[float]":
    """Server retry-pushback (``tpurpc-pushback-ms`` trailing metadata on
    an admission rejection) in seconds, or None when absent/junk."""
    try:
        md = exc.trailing_metadata() or ()
    except Exception:
        return None
    for key, value in md:
        if key == _PUSHBACK_KEY:
            try:
                return max(0.0, float(value) / 1000.0)
            except (TypeError, ValueError):
                return None
    return None


class _ClientStream:
    """Per-call state the reader thread feeds and the caller thread drains."""

    def __init__(self, stream_id: int, queue_depth: int = 64):
        self.stream_id = stream_id
        self.events: "queue.Queue[tuple]" = queue.Queue()
        self.initial_metadata: Optional[List[Tuple[str, "str | bytes"]]] = None
        #: fragment assembly — the FrameReader sink appends wire bytes here
        #: directly (single receive-side copy; no per-fragment bytes + join)
        self.assembly = fr.Assembly()
        self.done = False  # trailers or failure delivered
        self.refused = False  # RST|FLAG_REFUSED: admission refusal, replayable
        #: tpurpc-scope: open "wire" span of a traced call (closed at the
        #: terminal event) + the terminal-delivery stamp for demux latency
        self._wire_span = None
        self._t_terminal = 0
        #: pipelined-call completion hook: invoked (on the delivering thread)
        #: AFTER the terminal event is queued — PipelinedUnary resolves its
        #: future here instead of parking a thread on the event queue
        self.on_terminal: Optional[Callable[[], None]] = None
        #: backpressure: bounded count of completed-but-unconsumed response
        #: messages (see _ServerStream._credits for the full rationale);
        #: trailers/failure events bypass — they must never deadlock
        self._credits = threading.BoundedSemaphore(max(1, queue_depth))

    def _acquire_credit(self) -> bool:
        while not self._credits.acquire(timeout=0.25):
            if self.done:
                return False
        return True

    def release_credit(self) -> None:
        try:
            self._credits.release()
        except ValueError:
            pass

    def commit_message(self, more: bool, oversized: bool = False,
                       compressed: bool = False,
                       recv_limit: "Optional[int]" = None,
                       ) -> "Optional[Tuple[StatusCode, str]]":
        """Returns (code, details) when THIS SIDE failed the stream (bad or
        oversized payload) — the caller owes the server an RST so it stops
        streaming into a stream we've already finished locally."""
        if more:
            return None
        if oversized:
            self.assembly.oversized = False
            code, details = (StatusCode.RESOURCE_EXHAUSTED,
                             "received message larger than "
                             "max_receive_message_length")
            self.deliver_failure(code, details)
            return (code, details)
        # take() detaches the storage (consumers may alias it); the Assembly
        # object itself is reusable for the next message.
        if self._acquire_credit():
            body = self.assembly.take()
            if compressed:
                try:
                    # limit enforced POST-decompression (gzip-bomb guard)
                    body = fr.decompress_message(body, recv_limit)
                except fr.DecompressTooLarge as exc:
                    self.deliver_failure(StatusCode.RESOURCE_EXHAUSTED,
                                         str(exc))
                    return (StatusCode.RESOURCE_EXHAUSTED, str(exc))
                except fr.FrameError as exc:
                    self.deliver_failure(StatusCode.INTERNAL, str(exc))
                    return (StatusCode.INTERNAL, str(exc))
            self.events.put(("message", body))
        else:
            self.assembly.take()  # stream already finished: drop
        return None

    def commit_external(self, body) -> None:
        """tpurpc-express: a rendezvous'd response payload — already whole,
        already in its final resting buffer (the landing region the decode
        will alias). Same credit backpressure as framed commits."""
        if self._acquire_credit():
            self.events.put(("message", body))

    def deliver_trailers(self, code: StatusCode, details: str, md) -> None:
        self.done = True
        self.events.put(("trailers", code, details, md))
        self._fire_terminal()

    def deliver_failure(self, code: StatusCode, details: str) -> None:
        self.done = True
        self.events.put(("trailers", code, details, []))
        self._fire_terminal()

    def _fire_terminal(self) -> None:
        sp = self._wire_span
        if sp is not None:
            self._wire_span = None
            _tracing.finish(sp)
        self._t_terminal = time.perf_counter_ns()
        cb = self.on_terminal
        if cb is not None:
            try:
                cb()
            except Exception:  # a completion hook bug must not kill the
                pass           # reader thread (every stream rides it)


class _ChannelSink(fr.MessageSink):
    """Routes MESSAGE payload bytes into per-stream assembly buffers."""

    def __init__(self, conn: "_Connection"):
        self._conn = conn
        self._discard = fr.Assembly()  # sink for late frames of dead streams

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
            failed = st.commit_message(
                bool(flags & fr.FLAG_MORE),
                oversized=st.assembly.oversized,
                compressed=bool(flags & fr.FLAG_COMPRESSED),
                recv_limit=self.max_message_bytes)
            if failed is not None:
                # Stream finished locally (undecodable/oversized payload):
                # RST so the server stops streaming into it, and drop the
                # local stream entry so late frames go to the discard sink.
                code, details = failed
                try:
                    self._conn.writer.send(fr.RST, 0, stream_id,
                                           fr.rst_payload(code, details))
                except (EndpointError, OSError):
                    pass
                self._conn.close_stream(st)


class _Connection:
    """One live transport: endpoint + reader thread + muxed writer."""

    def __init__(self, endpoint: Endpoint, on_dead: Callable[["_Connection"], None],
                 max_recv_bytes: "Optional[int]" = None):
        self.endpoint = endpoint
        self.writer = fr.FrameWriter(endpoint)
        self.reader = fr.FrameReader(endpoint)
        self.reader.sink = _ChannelSink(self)
        self.reader.sink.max_message_bytes = max_recv_bytes
        self._streams: dict[int, _ClientStream] = {}
        self._lock = make_lock("_Connection._lock")
        self._next_stream_id = 1  # odd ids, client-initiated (h2 convention)
        self._pong_waiters: List[threading.Event] = []
        self.pong_count = 0  # keepalive verdict ticks compare against this
        self.alive = True
        self.draining = False        # GOAWAY received: no new streams
        self.last_activity = time.monotonic()
        self._on_dead = on_dead
        #: tpurpc-fleet: sink for server load reports stripped from
        #: trailing metadata (bound per pick by Channel._connection when
        #: the LB policy consumes them; None otherwise)
        self.on_load = None
        #: tpurpc-blackbox: connection lifecycle in the flight ring — the
        #: disconnect→reconnect→first-OK sequence a postmortem replays
        self._ftag = _flight.tag_for("conn:" + getattr(endpoint, "peer",
                                                       "?"))
        self._flight_first_ok = False
        _flight.emit(_flight.CONN_CONNECT, self._ftag)
        self.writer.send_preface()
        # tpurpc-express: arm the rendezvous link and say hello. The hello
        # is a PING any peer (native C plane, older builds) safely echoes;
        # only a rendezvous-capable peer recognizes it and replies with its
        # own, which flips `negotiated` — until then every payload frames.
        # tpurpc-pulse (ISSUE 13): the hello also carries this side's
        # descriptor-ring blob; a peer that opens it (same host, shm) moves
        # the whole control plane off frames.
        self.rdv = _rdv.link_for_endpoint(
            endpoint, "chan:" + getattr(endpoint, "peer", "?"),
            self._rdv_send_op, self._rdv_deliver,
            send_ops=self._rdv_send_ops)
        self.writer.rdv = self.rdv
        self._frames_dispatched = 0
        self.ctrl = None
        if self.rdv is not None and _ctrl.enabled():
            try:
                self.ctrl = _ctrl.CtrlPlane(
                    "chan:" + getattr(endpoint, "peer", "?"))
            except Exception:
                self.ctrl = None  # no shm: framed control forever
            if self.ctrl is not None:
                self.rdv.ctrl_post = self._rdv_ctrl_post
                self.rdv.ctrl_drain = self._ctrl_drain
                # per-stream order across the ring/framed split: control
                # ops posted before a sink-routed MESSAGE deliver first
                self.reader.pre_commit = self._ctrl_drain
        if self.rdv is not None:
            self.rdv.recv_limit = max_recv_bytes
            # ring planes negotiated at the PAIR BOOTSTRAP (Address.caps
            # "rdv"): arm immediately — no hello round trip for the first
            # bulk payload to race
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
                pass  # connection dying; normal paths surface it
        # Inline-pump discipline (the reference's pollset_work model,
        # SURVEY §3.4; the Python analog of TPURPC_NATIVE_INLINE_READ):
        # on ring platforms the WAITING CALLER pumps the transport itself,
        # eliminating the reader-thread→caller wakeup from every RTT — on
        # the 1-core bench host those 2 extra context switches per round
        # trip were why the Python ring path LOST to TCP (VERDICT r3 weak
        # #4). TPURPC_INLINE_PUMP=auto (default) enables it for ring
        # endpoints; =1 forces it for every endpoint; =0 keeps the
        # dedicated reader thread everywhere.
        self._pump_mode = self._pump_enabled(endpoint)
        self._pumping = False
        self._pump_cond = make_condition("_Connection._pump_cond", self._lock)
        if self.rdv is not None and self._pump_mode:
            # inline-pump transports: a sender waiting for a CLAIM must
            # drive the reader itself (nobody else will) — hand the link
            # the pump-wait primitive instead of its condition fallback
            self.rdv._pump = self._pump_wait
        if self._pump_mode:
            self._start_backup_pump()
        else:
            self._thread = threading.Thread(target=self._read_loop,
                                            daemon=True,
                                            name="tpurpc-chan-reader")
            self._thread.start()
        self._start_keepalive()
        self._start_idle_monitor()

    @staticmethod
    def _pump_enabled(endpoint: Endpoint) -> bool:
        mode = os.environ.get("TPURPC_INLINE_PUMP", "auto").lower()
        if mode in ("0", "off", "false"):
            return False
        if mode in ("1", "on", "true"):
            return True
        # auto: ring endpoints only (a Pair-backed byte pipe — the path the
        # discipline was built for; TCP keeps the blocking reader thread)
        return hasattr(endpoint, "pair")

    def _start_keepalive(self) -> None:
        """Client keepalive (GRPC_ARG_KEEPALIVE_TIME_MS family, off by
        default like gRPC): PING on an idle cadence; a missed PONG within
        keepalive_timeout kills the connection so the channel's reconnect
        machinery takes over instead of calls hanging on a dead peer.

        Runs on the shared timer wheel, event-style (the reference drives
        keepalive from iomgr timers the same way): one tick sends the PING
        and schedules a verdict tick that compares pong_count — no blocking
        ping() on the wheel thread, and no dedicated thread per connection
        (a thread per connection was 2x128 threads at the reference's
        128-client scale)."""
        from tpurpc.utils.config import get_config
        from tpurpc.utils.timers import schedule

        cfg = get_config()
        if cfg.keepalive_time_ms <= 0:
            return
        interval = cfg.keepalive_time_ms / 1000.0
        timeout = max(0.001, cfg.keepalive_timeout_ms / 1000.0)

        from tpurpc.utils.timers import run_blocking

        def tick():
            if not self.alive:
                return
            # Ping only a genuinely idle connection (gRPC pings after
            # keepalive_time of *inactivity*; the server loop skips
            # in-flight streams for the same reason): with streams open,
            # the single reader thread can be parked in credit-acquire or
            # a long message burst, leaving the PONG unread past the
            # timeout — and the keepalive would then kill a healthy
            # connection, failing every in-flight call UNAVAILABLE.
            with self._lock:
                busy = (bool(self._streams)
                        or time.monotonic() - self.last_activity < interval)
                before = self.pong_count
            if busy:
                self._ka_handle = schedule(interval, tick)
                return
            sent_at = time.monotonic()

            def send_ping():  # endpoint write: never on the wheel thread
                try:
                    self.writer.send(fr.PING, 0, 0, b"tpurpc-keepalive")
                except (EndpointError, OSError, fr.FrameError):
                    self._die("keepalive ping send failed")

            run_blocking(send_ping)

            def check():
                # Sliced verdict: answered → next PING an INTERVAL after
                # this one (the configured cadence; waiting the full
                # timeout first would stretch it to interval+timeout);
                # unanswered past timeout → reap, off-wheel (teardown
                # closes fds / fails streams).
                if not self.alive:
                    return
                elapsed = time.monotonic() - sent_at
                with self._lock:
                    ponged = self.pong_count > before
                if ponged:
                    self._ka_handle = schedule(max(0.05, interval - elapsed),
                                               tick)
                elif elapsed >= timeout:
                    run_blocking(
                        lambda: self._die("keepalive ping timed out"))
                else:
                    self._ka_handle = schedule(
                        min(1.0, max(0.05, timeout - elapsed)), check)

            self._ka_handle = schedule(min(1.0, timeout), check)

        self._ka_handle = schedule(interval, tick)

    def _start_idle_monitor(self) -> None:
        """client_idle filter analog (GRPC_ARG_CLIENT_IDLE_TIMEOUT_MS, off
        by default): a connection with no streams and no activity for the
        idle window is closed; the next call dials fresh. Frees server-side
        per-connection state (pairs, rings) held by forgotten channels.
        Wheel-scheduled checks — no per-connection thread."""
        from tpurpc.utils.config import get_config
        from tpurpc.utils.timers import schedule

        cfg = get_config()
        if cfg.client_idle_timeout_ms <= 0:
            return
        window = cfg.client_idle_timeout_ms / 1000.0

        def tick():
            if not self.alive:
                return
            with self._lock:
                remain = window - (time.monotonic() - self.last_activity)
                busy = bool(self._streams)
                idle = not busy and remain <= 0
                if idle:
                    # Gate BEFORE releasing the lock: open_stream checks
                    # draining under this same lock, so a call racing
                    # the idle close gets "draining" (transparently
                    # re-dialed) instead of a spurious UNAVAILABLE
                    # after its HEADERS hit a dying connection.
                    self.draining = True
                # streams in flight: re-check a full window from now;
                # otherwise wake exactly when the idle window would lapse
                delay = window if busy else max(0.05, remain)
            if idle:
                from tpurpc.utils.timers import run_blocking

                run_blocking(lambda: self._die("client idle timeout"))
                return
            self._idle_handle = schedule(delay, tick)

        self._idle_handle = schedule(window, tick)

    def open_stream(self) -> _ClientStream:
        with self._lock:
            if not self.alive:
                raise EndpointError("connection closed")
            if self.draining:
                raise EndpointError("connection draining (GOAWAY)")
            sid = self._next_stream_id
            self._next_stream_id += 2
            from tpurpc.utils.config import get_config

            st = _ClientStream(sid,
                               queue_depth=get_config().stream_queue_depth)
            self._streams[sid] = st
            self.last_activity = time.monotonic()
            return st

    def close_stream(self, st: _ClientStream) -> None:
        finish_drain = False
        with self._lock:
            self._streams.pop(st.stream_id, None)
            self.last_activity = time.monotonic()
            finish_drain = self.draining and not self._streams
        if finish_drain:
            # last in-flight call on a GOAWAY'd connection finished: the
            # graceful close completes (max_connection_age contract)
            self._die("drained after GOAWAY")

    def _read_loop(self) -> None:
        try:
            while True:
                f = self._read_frame_ctrl()
                if f is None:
                    self._die("server closed connection")
                    return
                if f is fr.CONSUMED:  # MESSAGE already routed via the sink
                    self._frames_dispatched += 1
                    continue
                self._dispatch(f)
                self._frames_dispatched += 1
        except (EndpointError, fr.FrameError, OSError) as exc:
            self._die(str(exc))

    # -- inline pump (pump-mode connections only) -----------------------------

    def _pump_wait(self, pred: Callable[[], bool],
                   deadline: Optional[float]) -> bool:
        """Wait for ``pred`` by PUMPING the transport from this thread.

        One pumper at a time owns the FrameReader (it is not thread-safe);
        others park on the condition and are notified after every dispatched
        frame, so a parked waiter whose pred was satisfied by the owner's
        pumping wakes immediately — the owner keeps pumping only until its
        OWN pred holds (native analog: tpurpc_client.cc pump_until).

        Returns True when pred() holds or the connection died (the caller
        decodes the terminal state from its event queue); False only when
        ``deadline`` (a time.monotonic() instant) passed."""
        while True:
            with self._pump_cond:
                while True:
                    if pred() or not self.alive:
                        return True
                    remaining = (None if deadline is None
                                 else deadline - time.monotonic())
                    if remaining is not None and remaining <= 0:
                        return False
                    if not self._pumping:
                        self._pumping = True
                        break  # this thread owns the pump now
                    self._pump_cond.wait(remaining)
            try:
                self._pump(pred, deadline)
            finally:
                with self._pump_cond:
                    self._pumping = False
                    self._pump_cond.notify_all()
            # loop: re-evaluate pred/deadline under the lock (the pump may
            # have returned because the connection died mid-frame)

    def _pump(self, pred: Callable[[], bool],
              deadline: Optional[float]) -> None:
        """Drain frames until pred/deadline/death. Runs WITHOUT the
        connection lock (the credit-backpressure path inside sink.commit
        may block until a consumer drains its queue; consumers must be able
        to run), owning the reader exclusively via ``_pumping``."""
        while True:
            with self._lock:
                if pred() or not self.alive:
                    return
            remaining = (None if deadline is None
                         else deadline - time.monotonic())
            if remaining is not None and remaining <= 0:
                return

            def _stop() -> bool:
                # a ctrl-ring drain inside the polled read may satisfy the
                # pred with no frame ever arriving — bail back to the
                # outer loop instead of blocking out the deadline
                with self._lock:
                    return pred() or not self.alive

            try:
                f = self._read_frame_ctrl(remaining, should_stop=_stop)
            except TimeoutError:
                return  # deadline/pred: outer loop re-checks
            except (EndpointError, fr.FrameError, OSError) as exc:
                self._die(str(exc))
                return
            if f is None:
                self._die("server closed connection")
                return
            if f is not fr.CONSUMED:
                self._dispatch(f)
            self._frames_dispatched += 1
            # every frame (CONSUMED commits included) may satisfy a PARKED
            # waiter's pred — hand them the wakeup now, not at pump release
            with self._pump_cond:
                self._pump_cond.notify_all()

    def _start_backup_pump(self) -> None:
        """Idle servicing for pump-mode connections: with no caller waiting
        (no RPC in flight), nobody pumps — server PINGs, GOAWAYs, and
        keepalive PONGs would sit unread. A timer-wheel tick takes the pump
        when it is free and drains whatever is already buffered. This is
        the backup-poller role gRPC's client runs for the same reason."""
        from tpurpc.utils.config import get_config
        from tpurpc.utils.timers import run_blocking, schedule

        # The backup pump is the only transport reader on an IDLE pump-mode
        # connection, so its cadence must beat the keepalive verdict: a
        # PONG that sits unread past keepalive_timeout would reap a
        # healthy connection. A third of the timeout guarantees >=2 pump
        # chances inside any verdict window.
        cfg = get_config()
        INTERVAL = 0.5
        if cfg.keepalive_time_ms > 0:
            INTERVAL = min(INTERVAL,
                           max(0.05, cfg.keepalive_timeout_ms / 1000.0 / 3))

        def service():
            if not self.alive:
                return
            with self._pump_cond:
                grab = not self._pumping and self.alive
                if grab:
                    self._pumping = True
            if grab:
                try:
                    while True:
                        self._ctrl_drain()
                        try:
                            f = self.reader.read_frame(timeout=0.005)
                        except TimeoutError:
                            break
                        except (EndpointError, fr.FrameError, OSError) as exc:
                            self._die(str(exc))
                            return
                        if f is None:
                            self._die("server closed connection")
                            return
                        if f is not fr.CONSUMED:
                            self._ctrl_drain()  # ring ops sent before f
                            self._dispatch(f)
                        self._frames_dispatched += 1
                finally:
                    with self._pump_cond:
                        self._pumping = False
                        self._pump_cond.notify_all()
            if self.alive:
                self._backup_handle = schedule(INTERVAL, tick)

        def tick():
            run_blocking(service)

        self._backup_handle = schedule(INTERVAL, tick)

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
            pass  # connection dying; the framed paths surface it

    def _frames_count(self) -> int:
        return self._frames_dispatched

    def _ctrl_drain(self) -> int:
        plane, rdv = self.ctrl, self.rdv
        if plane is None or rdv is None:
            return 0
        n = plane.drain(rdv.on_op, self._frames_count)
        if n and self._pump_mode:
            # a drained record may satisfy a PARKED pump waiter's pred —
            # same handoff the frame path performs after each dispatch
            with self._pump_cond:
                self._pump_cond.notify_all()
        return n

    def _read_frame_ctrl(self, timeout=None, should_stop=None):
        plane = self.ctrl
        if plane is None or plane.rx is None:
            return self.reader.read_frame(timeout=timeout)
        return _ctrl.read_frame_polled(self.reader.read_frame,
                                       self._ctrl_drain, plane, timeout,
                                       should_stop)

    def _rdv_deliver(self, stream_id: int, flags: int, body) -> None:
        """A completed rendezvous payload IS the stream's next message —
        delivered in frame-arrival order, zero-copy (the body aliases the
        landing region; credits/backpressure identical to framed commits)."""
        with self._lock:
            st = self._streams.get(stream_id)
        if st is not None:
            st.commit_external(body)

    def _dispatch(self, f: fr.Frame) -> None:
        if f.type == fr.PING:
            if (self.rdv is not None
                    and f.payload.startswith(_rdv.HELLO_PAYLOAD)):
                # capability hello: the peer speaks rendezvous (both sides
                # send one proactively at connection start, so no echo).
                # tpurpc-pulse: the tail of the payload is the peer's
                # descriptor-ring blob — adopting it moves this link's
                # control plane off frames entirely.
                self.rdv.on_peer_hello(f.payload)
                if self.ctrl is not None:
                    self.ctrl.on_hello(
                        f.payload[len(_rdv.HELLO_PAYLOAD):])
            self.writer.send(fr.PONG, 0, 0, f.payload)
            return
        if f.type == fr.CTRL_KICK:
            return  # the wake itself was the delivery: read loops drain
        if f.type in fr.RDV_OP_OF_FRAME:
            if self.rdv is not None:
                self.rdv.on_op(fr.RDV_OP_OF_FRAME[f.type], f.stream_id,
                               f.payload)
            return
        if f.type == fr.PONG:
            with self._lock:
                self.pong_count += 1
                waiters, self._pong_waiters = self._pong_waiters, []
            for ev in waiters:
                ev.set()
            return
        if f.type == fr.GOAWAY:
            # Graceful drain (gRPC GOAWAY semantics / max_age filter): stop
            # opening new streams here — the subchannel dials fresh for the
            # next call — but let in-flight calls run to completion. Close
            # when the last one finishes (or now, if none are in flight).
            with self._lock:
                self.draining = True
                empty = not self._streams
            if empty:
                self._die("server sent GOAWAY")
            return
        with self._lock:
            st = self._streams.get(f.stream_id)
        if st is None:
            return  # late frame for a cancelled/finished stream
        if f.type == fr.MESSAGE:  # only without a sink (never in practice)
            st.assembly.append(f.payload)
            st.commit_message(
                bool(f.flags & fr.FLAG_MORE),
                compressed=bool(f.flags & fr.FLAG_COMPRESSED))
        elif f.type == fr.HEADERS:
            md, _ = fr.decode_metadata(f.payload)
            st.initial_metadata = md
            st.events.put(("initial_metadata", md))
        elif f.type in (fr.TRAILERS, fr.RST):
            code, details, md = fr.parse_trailers(f.payload)
            if md:
                # tpurpc-fleet: the server's piggybacked load report is
                # transport-internal — strip it before metadata surfaces
                # to the app, feed it to the LB policy's sink
                for i, (key, value) in enumerate(md):
                    if key == _LOAD_KEY:
                        del md[i]
                        cb = self.on_load
                        if cb is not None:
                            try:
                                cb(value)
                            except Exception:
                                pass  # a policy bug must not kill the reader
                        break
            if f.type == fr.RST and f.flags & fr.FLAG_REFUSED:
                # admission refusal: the server certifies no handler ran
                # (set BEFORE the event lands; the queue orders the read)
                st.refused = True
            # Terminal frame: nothing further arrives for this stream — drop it
            # now so abandoned Call objects don't leak connection state.
            self.close_stream(st)
            st.deliver_trailers(code, details, md)
        else:
            raise fr.FrameError(f"unexpected frame {f!r}")

    def ping(self, timeout: float) -> float:
        """Round-trip one PING/PONG; returns seconds or raises on no reply."""
        ev = threading.Event()
        with self._lock:
            if not self.alive:
                raise EndpointError("connection closed")
            self._pong_waiters.append(ev)
            before = self.pong_count
        t0 = time.perf_counter()
        self.writer.send(fr.PING, 0, 0, b"tpurpc-ping")
        if self._pump_mode:
            ok = self._pump_wait(lambda: self.pong_count > before,
                                 time.monotonic() + timeout)
            if not ok:
                raise TimeoutError("ping timed out")
        elif not ev.wait(timeout):
            raise TimeoutError("ping timed out")
        if not self.alive:  # waiters are released on death too
            raise EndpointError("connection died during ping")
        return time.perf_counter() - t0

    def _die(self, why: str) -> None:
        with self._lock:
            if not self.alive:
                return
            self.alive = False
            streams = list(self._streams.values())
            self._streams.clear()
            waiters, self._pong_waiters = self._pong_waiters, []
        for ev in waiters:
            ev.set()  # ping() observes !alive via the raced send/raise below
        for attr in ("_ka_handle", "_idle_handle", "_backup_handle"):
            h = getattr(self, attr, None)
            if h is not None:
                h.cancel()  # wheel ticks also re-check alive themselves
        graceful = "GOAWAY" in why or "closed" in why or "idle" in why
        _flight.emit(_flight.CONN_DEAD, self._ftag, 1 if graceful else 0)
        if self.rdv is not None:
            # peer gone mid-rendezvous: every claimed landing region is
            # released (the modeled peer-death invariant) and any sender
            # parked on a claim wakes to fall back/fail with the transport
            self.rdv.close()
        if self.ctrl is not None:
            # descriptor rings die with the connection: our rx region is
            # released (a straggling peer's late slot store lands in the
            # orphaned mapping — dead memory, never a re-advertised ring)
            self.ctrl.close()
        trace_channel.log("connection dead: %s", why)
        for st in streams:
            st.deliver_failure(StatusCode.UNAVAILABLE, f"transport failed: {why}")
        try:
            self.endpoint.close()
        except Exception:
            pass
        self._on_dead(self)

    def close(self) -> None:
        self._die("channel closed")


class _Subchannel:
    """One address's connection + exponential reconnect backoff
    (≈ Subchannel in client_channel + lib/backoff, SURVEY.md §3.2)."""

    def __init__(self, factory: Callable[[], Endpoint], channel: "Channel"):
        self._factory = factory
        self._channel = channel
        self._conn: Optional[_Connection] = None
        # guards _conn/backoff state
        self._lock = make_lock("_Subchannel._lock")
        # serializes dial attempts only
        self._connect_lock = make_lock("_Subchannel._connect_lock")
        self._backoff = Channel._BACKOFF_INITIAL
        self._next_attempt = 0.0
        #: tpurpc-blackbox: a previous connection died — the NEXT
        #: successful dial is a reconnect (flight-recorder event)
        self._lost_conn = False

    def get(self, fail_fast: bool = False) -> _Connection:
        """The live connection, dialing if needed. ``fail_fast=True`` (the
        multi-subchannel LB walk) raises UNAVAILABLE immediately while the
        subchannel is in connect backoff instead of sleeping it out —
        sleeping through backoff INSIDE the dial lock convoys every walker
        behind one dead backend (observed: hedged fleet traffic serializing
        2 s per caller on a killed server), and with other backends in the
        walk there is nothing worth waiting for. Single-subchannel channels
        keep the sleep: there, waiting out the backoff IS the reconnect
        contract."""
        with self._lock:
            if (self._conn is not None and self._conn.alive
                    and not self._conn.draining):
                return self._conn
            if fail_fast and self._next_attempt > time.monotonic():
                raise RpcError(StatusCode.UNAVAILABLE,
                               "subchannel in connect backoff")
        # Dial outside self._lock: a blackholed connect must not freeze close()
        # or concurrent calls for the whole connect timeout.
        with self._connect_lock:
            with self._lock:
                if (self._conn is not None and self._conn.alive
                        and not self._conn.draining):
                    return self._conn
                wait = self._next_attempt - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            if self._channel._is_closed():
                raise RpcError(StatusCode.UNAVAILABLE, "channel closed")
            try:
                ep = self._factory()
                conn = _Connection(
                    ep, self._on_conn_dead,
                    max_recv_bytes=self._channel.max_receive_message_length)
            except (OSError, EndpointError) as exc:
                with self._lock:
                    self._next_attempt = (
                        time.monotonic()
                        + self._backoff * (1 + 0.2 * random.random()))
                    self._backoff = min(self._backoff * Channel._BACKOFF_MULT,
                                        Channel._BACKOFF_MAX)
                raise RpcError(StatusCode.UNAVAILABLE,
                               f"connect failed: {exc}") from exc
            with self._lock:
                if self._channel._is_closed():
                    conn.close()
                    raise RpcError(StatusCode.UNAVAILABLE, "channel closed")
                self._backoff = Channel._BACKOFF_INITIAL
                self._conn = conn
                was_lost, self._lost_conn = self._lost_conn, False
            if was_lost:
                _flight.emit(_flight.RECONNECT, conn._ftag)
            return conn

    def _on_conn_dead(self, conn: _Connection) -> None:
        with self._lock:
            if self._conn is conn:
                self._conn = None
            self._lost_conn = True

    def close(self) -> None:
        with self._lock:
            conn, self._conn = self._conn, None
        if conn is not None:
            conn.close()


class Channel:
    """A lazily-(re)connecting client channel.

    ``target`` is ``"host:port"``; tests may instead inject ``endpoint_factory``
    (e.g. one half of :func:`tpurpc.core.endpoint.passthru_endpoint_pair` — the
    moral equivalent of the reference's inproc transport).

    ``lb_policy`` is a policy name (``"pick_first"``, ``"round_robin"``,
    ``"ring_hash"``) or a composition-tree dict spec (``priority`` /
    ``weighted_target`` over subchannel index subsets) — see
    :func:`tpurpc.rpc.resolver.make_policy` for the grammar.
    """

    #: reconnect backoff, mirroring lib/backoff defaults (initial 1s would be
    #: sluggish for tests; we start at 50ms, cap 2s, jitter 20%).
    _BACKOFF_INITIAL = 0.05
    _BACKOFF_MAX = 2.0
    _BACKOFF_MULT = 1.6

    def __init__(self, target: Optional[str] = None, *,
                 endpoint_factory: Optional[Callable[[], Endpoint]] = None,
                 connect_timeout: float = 30.0,
                 lb_policy: "Union[str, dict]" = "pick_first",
                 credentials=None,
                 max_receive_message_length: Optional[int] = None,
                 retry_policy: "Optional[RetryPolicy]" = None,
                 hedging_policy: "Optional[HedgingPolicy]" = None,
                 compression=None,
                 options=None):
        # grpcio channel options: [("grpc.arg_name", value), ...]. The
        # recognized args map onto this constructor's own parameters (an
        # explicit parameter wins); unrecognized ones are ignored the way
        # grpcio ignores unknown channel args.
        if options:
            opt = dict(options)
            if max_receive_message_length is None:
                max_receive_message_length = opt.get(
                    "grpc.max_receive_message_length")
            if lb_policy == "pick_first" and "grpc.lb_policy_name" in opt:
                lb_policy = opt["grpc.lb_policy_name"]
            if compression is None:
                compression = opt.get("grpc.default_compression_algorithm")
            #: grpcio's service-config channel arg: a JSON FALLBACK used
            #: only when the resolver delivers no config (gRPC documents
            #: GRPC_ARG_SERVICE_CONFIG as ignored when the resolver
            #: returns one; the resolver wins)
            self._svc_cfg_fallback = opt.get("grpc.service_config")
        else:
            self._svc_cfg_fallback = None
        # Message compression on the tpurpc framing (FLAG_COMPRESSED; the
        # h2 wire negotiates grpc-encoding separately): requests compress,
        # tpurpc servers mirror on responses. The framing's one codec is
        # gzip, so grpcio's Compression.Deflate (1) — which a drop-in call
        # site may legitimately pass — is honored as "compress my
        # messages" using that codec rather than rejected at construction.
        # Unknown values degrade to identity with a warning (grpcio
        # tolerates unknown channel args; a constructor ValueError would
        # break drop-in compatibility).
        if compression in (None, 0, "identity", False):
            self._compress_flag = 0
        elif (compression in ("gzip", "deflate", 1, 2)
              or str(compression).endswith(("Gzip", "Deflate"))):
            self._compress_flag = fr.FLAG_COMPRESSED
        else:
            import warnings
            warnings.warn(
                f"unsupported compression {compression!r}: the tpurpc "
                "framing speaks gzip only — using identity", stacklevel=2)
            self._compress_flag = 0
        #: channel-level retry policy for unary-request calls (None = off,
        #: matching gRPC's default of retries disabled without service
        #: config). An explicit policy here WINS over any service config the
        #: resolver delivers (explicit code beats delivered config).
        self.retry_policy = retry_policy
        #: channel-level hedging policy (tpurpc-fleet, gRFC A6): staggered
        #: parallel attempts on distinct subchannels, first response wins.
        #: Retry wins when both are configured (a call runs ONE strategy);
        #: same explicit-beats-config precedence as retry_policy.
        self.hedging_policy = hedging_policy
        #: parsed resolver-delivered service config (per-method timeout /
        #: retryPolicy / retryThrottling — service_config.cc analog); swapped
        #: whole by update_service_config, consulted per call via
        #: _policy_for/_effective_timeout
        self._service_config = None
        from tpurpc.rpc.resolver import make_policy, resolve_target_full
        from tpurpc.utils.config import get_config

        self.max_receive_message_length = get_config().resolve_recv_limit(
            max_receive_message_length)

        ssl_ctx = getattr(credentials, "_context", None)
        override = getattr(credentials, "_override_hostname", None)
        self._lb_spec = lb_policy
        self._conn_kw = dict(timeout=connect_timeout, ssl_context=ssl_ctx,
                             server_hostname=override)
        if endpoint_factory is None:
            if target is None:
                raise ValueError("need target or endpoint_factory")
            resolution = resolve_target_full(target)
            addrs = resolution.addresses
            self._addrs: "Optional[list]" = list(addrs)
            factories = [self._addr_factory(h, p) for h, p in addrs]
            if resolution.service_config is not None:
                self.update_service_config(resolution.service_config)
        else:
            self._addrs = None  # injected factory: membership is fixed
            factories = [endpoint_factory]
        if self._service_config is None and self._svc_cfg_fallback is not None:
            self.update_service_config(self._svc_cfg_fallback)
        self._subchannels = [_Subchannel(f, self) for f in factories]
        self._policy = make_policy(lb_policy, len(self._subchannels))
        self._lock = make_lock("Channel._lock")  # guards _closed
        self._closed = False
        self._kicker: Optional[threading.Thread] = None  # get_state dialer
        # Native unary fast path (lazy; see _native_fast): the reference's
        # defining property is that EVERY binding rides the fast pipe
        # because the hot loop lives in the C core under a thin language
        # surface (grpcio → core, SURVEY §2.4). _native_ch is the cached
        # NativeChannel; _native_retry_at throttles re-dial attempts after
        # a failure so an absent/down native path costs one probe per 5 s.
        self._native_lock = make_lock("Channel._native_lock")
        self._native_ch = None
        self._native_retry_at = 0.0
        from tpurpc.rpc import channelz as _channelz

        #: channelz ChannelData counters (started/succeeded/failed)
        self.call_counters = _channelz.CallCounters()
        _channelz.register_channel(self)

    # -- connection management ----------------------------------------------

    def _addr_factory(self, h: str, p: int):
        kw = self._conn_kw
        return lambda: connect_endpoint(h, p, timeout=kw["timeout"],
                                        ssl_context=kw["ssl_context"],
                                        server_hostname=kw["server_hostname"])

    def update_service_config(self, cfg) -> None:
        """Apply a resolver-delivered JSON service config (dict or JSON
        text): per-method timeouts, retry policies, and channel-wide retry
        throttling take effect for SUBSEQUENT calls without touching call
        sites — the reference's service_config.cc/retry_service_config.cc
        behavior. A malformed config raises and the previous one stays
        (reject-whole, keep-last-good). Retry-throttle DRAIN state carries
        across updates (retry_throttle.cc): a re-resolution re-delivering
        the same config must not refill the bucket and resume a suppressed
        retry storm."""
        from tpurpc.rpc.service_config import ServiceConfig

        new = ServiceConfig.from_json(cfg)
        prev = self._service_config
        if new.retry_throttle is not None:
            new.retry_throttle.carry_from(
                prev.retry_throttle if prev else None)
        self._service_config = new

    def _call_plan(self, method: str, timeout: "Optional[float]",
                   wait_for_ready: bool = False):
        """ONE consistent per-call snapshot of the service-config-derived
        values: ``(retry_policy, timeout, throttle, wait_for_ready,
        hedging_policy)``. Derived from a single read of
        ``_service_config`` so a concurrent resolver update can never pair
        one config's retry policy with another's throttle or timeout.
        Rules: explicit constructor policy wins; config timeout can only
        TIGHTEN the call's (min rule); waitForReady is or-ed with the
        per-call kwarg (gRFC A2: the config enables it, a call-site value
        may also enable it); a method runs ONE execution strategy — when
        both retry and hedging resolve, retry wins (the config layer
        already rejects both in one entry, gRFC A6)."""
        sc = self._service_config
        mc = sc.for_method(method) if sc is not None else None
        policy = self.retry_policy
        if policy is None and mc is not None:
            policy = mc.retry_policy
        hedging = self.hedging_policy
        if hedging is None and mc is not None:
            hedging = mc.hedging_policy
        if policy is not None:
            hedging = None
        if mc is not None and mc.timeout is not None:
            timeout = (mc.timeout if timeout is None
                       else min(timeout, mc.timeout))
        return (policy, timeout,
                sc.retry_throttle if sc is not None else None,
                bool(wait_for_ready) or bool(mc and mc.wait_for_ready),
                hedging)

    def update_addresses(self, addrs) -> None:
        """Replace the channel's backend set (re-resolution / look-aside
        balancing — the grpclb ServerList update, ``grpclb.cc``). Addresses
        present in both old and new sets KEEP their live subchannel (and
        its connection); removed ones are closed; the LB policy is rebuilt
        over the new membership with the channel's original spec.

        ``addrs``: iterable of ``(host, port)`` or ``"host:port"`` strings.
        In-flight calls on kept subchannels are unaffected; calls racing
        the swap may still land on a closing backend once and retry per
        the normal UNAVAILABLE path.
        """
        from tpurpc.rpc.resolver import make_policy, resolve_target

        parsed: list = []
        for a in addrs:
            if isinstance(a, tuple):
                parsed.append(a)
            else:
                # resolve strings the same way the constructor did — the
                # keep-live matching below compares against RESOLVED
                # addresses, so "localhost:p" must normalize to the same
                # keys or a no-op update would tear down live connections
                parsed.extend(resolve_target(a))
        if not parsed:
            raise ValueError("update_addresses needs at least one address")
        # Composite dict specs pin absolute subchannel indices — they can't
        # survive a membership size change. Balanced sets get round_robin,
        # exactly what grpclb runs over its server lists (grpclb.cc).
        spec = (self._lb_spec if isinstance(self._lb_spec, str)
                else "round_robin")
        # Dynamic membership (re-resolution / grpclb server lists) is
        # routing the Python transport owns: the single-address native
        # fast path would pin traffic to the original backend. Disable it
        # for this channel permanently.
        with self._native_lock:
            nch, self._native_ch = self._native_ch, None
            self._native_retry_at = float("inf")
        if nch is not None:
            try:
                nch.close()
            except Exception:
                pass
        with self._lock:
            if self._closed:
                raise RpcError(StatusCode.UNAVAILABLE, "channel closed")
            if self._addrs is None:
                raise RuntimeError(
                    "channel built from endpoint_factory has fixed membership")
            old = {}
            for a, sc in zip(self._addrs, self._subchannels):
                old.setdefault(a, []).append(sc)
            new_subs = []
            for a in parsed:
                bucket = old.get(a)
                if bucket:
                    new_subs.append(bucket.pop(0))  # keep the live conn
                else:
                    new_subs.append(_Subchannel(self._addr_factory(*a), self))
            removed = [sc for bucket in old.values() for sc in bucket]
            policy = make_policy(spec, len(new_subs))
            # atomic swap: _connection() snapshots both attributes
            self._subchannels = new_subs
            self._policy = policy
            self._addrs = list(parsed)
        for sc in removed:
            sc.close()

    def batch_calls(self):
        """tpurpc-pulse (ISSUE 13): batch the fused unary sends THIS
        thread issues inside the block into ONE gathered writev — the
        coalesced control path for bursts of small control RPCs (a
        migration drain's N sequence handoffs flush as one transport
        write instead of one frame pair each).  Pipelined ``call_async``
        inside the block composes naturally: the sends queue, the
        responses demux as usual.  Best-effort: on a channel with no
        dialable connection the block simply runs unbatched (the calls
        themselves will surface the dial failure)."""
        import contextlib

        try:
            conn = self._connection()
        except Exception:
            return contextlib.nullcontext()
        return conn.writer.batch()

    def _connection(self, exclude=None, picked=None) -> _Connection:
        """LB pick: walk subchannels in policy order, first READY/dialable
        wins (client_channel resolver→LB→subchannel flow, SURVEY.md §3.2).

        ``exclude`` (a set of :class:`_Subchannel` objects) deprioritizes
        backends this logical call already used — hedged attempts prefer
        distinct subchannels, and a drain-refused replay migrates instead
        of re-hitting the drainer. Excluded subchannels are appended LAST,
        not dropped: landing on a busy backend beats failing the call when
        nothing else is dialable. ``picked`` (a list, out-param) receives
        the chosen subchannel."""
        with self._lock:
            if self._closed:
                raise RpcError(StatusCode.UNAVAILABLE, "channel closed")
            # snapshot: update_addresses swaps both under this lock, so a
            # pick never mixes one generation's policy with another's subs
            policy, subs = self._policy, self._subchannels
        last_exc: Optional[Exception] = None
        order = list(policy.order())
        if exclude:
            order = ([i for i in order if subs[i] not in exclude]
                     + [i for i in order if subs[i] in exclude])
        fail_fast = len(subs) > 1  # walkers skip backing-off members
        for idx in order:
            sc = subs[idx]
            try:
                conn = sc.get(fail_fast=fail_fast)
            except RpcError as exc:
                policy.failed(idx)
                last_exc = exc
                continue
            policy.connected(idx)
            # tpurpc-fleet: bind the connection's load-report sink to this
            # pick's (policy, index) — rebound every pick so a policy
            # rebuilt by update_addresses never receives stale indices
            if hasattr(policy, "load_report"):
                conn.on_load = (lambda raw, _p=policy, _i=idx:
                                _p.load_report(_i, raw))
            if picked is not None:
                picked.append(sc)
            return conn
        raise last_exc if last_exc is not None else RpcError(
            StatusCode.UNAVAILABLE, "no subchannels")

    def device_ring(self):
        """The live connection's device (HBM) receive ring, or None when the
        transport isn't :class:`tpurpc.tpu.endpoint.TpuRingEndpoint`
        (``GRPC_PLATFORM_TYPE=TPU``). NOTE: this dials/picks a connection;
        to decode a response already in hand, prefer
        :meth:`Call.device_ring`, which is pinned to the connection the
        response arrived on."""
        from tpurpc.core.endpoint import device_ring_of

        return device_ring_of(self._connection().endpoint)

    def ping(self, timeout: float = 5.0) -> float:
        """Round-trip a PING; returns seconds.  Liveness probe (the reference's
        analog: rate-limited ``ibv_query_qp``, ``pair.cc:349-375``)."""
        conn = self._connection()
        try:
            return conn.ping(timeout)
        except TimeoutError as exc:
            raise RpcError(StatusCode.DEADLINE_EXCEEDED, str(exc)) from exc
        except (EndpointError, OSError) as exc:
            raise RpcError(StatusCode.UNAVAILABLE, str(exc)) from exc

    def _is_closed(self) -> bool:
        with self._lock:
            return self._closed

    def get_state(self, try_to_connect: bool = False):
        """grpcio's ``Channel.get_state``: the channel-level connectivity
        summary (connectivity_state.h semantics folded over subchannels).

        READY if any subchannel holds a live connection; CONNECTING while
        a kicked dial is in flight; TRANSIENT_FAILURE if none are live but
        some subchannel is in connect backoff; else IDLE.
        ``try_to_connect=True`` on an idle channel kicks ONE background
        dial sweep over the subchannels (the way grpcio's flag kicks the
        channel, not a fixed address) — repeated polls while it runs keep
        reporting CONNECTING instead of stacking threads."""
        CC = ChannelConnectivity
        with self._lock:
            if self._closed:
                return CC.SHUTDOWN
        with self._native_lock:
            nch = self._native_ch
        if nch is not None and nch._ch:
            # calls are flowing through the native fast path: the channel
            # is READY even though no Python-transport connection exists
            return CC.READY
        now = time.monotonic()
        backing_off = False
        for sc in self._subchannels:
            with sc._lock:
                conn = sc._conn
                if conn is not None and conn.alive and not conn.draining:
                    return CC.READY
                if sc._next_attempt > now:
                    backing_off = True
        with self._lock:
            kicker = self._kicker
            if kicker is not None and kicker.is_alive():
                return CC.CONNECTING  # one dial sweep at a time
            if try_to_connect and self._subchannels:
                self._kicker = threading.Thread(
                    target=self._kick_connect, daemon=True,
                    name="tpurpc-try-connect")
                self._kicker.start()
                return CC.CONNECTING
        return CC.TRANSIENT_FAILURE if backing_off else CC.IDLE

    def _kick_connect(self) -> None:
        # Dial every subchannel until one answers: a dead first address
        # must not mask a live second one (the LB policy would reach it).
        for sc in self._subchannels:
            if self._is_closed():
                return
            try:
                sc.get()
                return
            except RpcError:
                continue  # backoff state answers TRANSIENT_FAILURE

    def wait_for_state_change(self, last_observed_state,
                              timeout: Optional[float] = None) -> bool:
        """Block until ``get_state()`` differs from ``last_observed_state``
        (grpcio's experimental channel-watch shape, polled — this channel
        has no state-subscription machinery to hook)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while self.get_state() == last_observed_state:
            if deadline is not None and time.monotonic() >= deadline:
                return False
            time.sleep(0.02)
        return True

    def close(self) -> None:
        with self._lock:
            self._closed = True
        with self._native_lock:
            nch, self._native_ch = self._native_ch, None
            self._native_retry_at = float("inf")  # closed: never re-dial
        if nch is not None:
            try:
                nch.close()
            except Exception:
                pass
        for sc in self._subchannels:
            sc.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- native unary fast path -----------------------------------------------

    def _native_fast(self):
        """The channel's NativeChannel (calls run inside libtpurpc.so's
        inline-read loop — BASELINE.md: 5.42 µs ring RTT vs ~95 µs for the
        pure-Python path on the same host), or None when ineligible.

        Eligibility is the common drop-in case, checked once: plain single
        address, pick_first with static membership, no TLS, no
        compression, a shm-ring platform (where the pure-Python loop
        measurably loses to kernel TCP — VERDICT r3 weak #4; plain-TCP
        channels keep the Python transport, whose kernel-socket path is
        already competitive and fully introspectable), lib present.
        TPURPC_NATIVE_FAST_UNARY=1 forces it for TCP too; =0 opts out
        entirely; TPURPC_NATIVE=0 disables all native paths. Everything
        else silently stays on the Python transport — same wire, same
        server."""
        with self._native_lock:
            if self._native_ch is not None:
                return self._native_ch
            now = time.monotonic()
            if now < self._native_retry_at or self._closed:
                return None
            self._native_retry_at = now + 5.0  # throttle failed probes
            mode = os.environ.get("TPURPC_NATIVE_FAST_UNARY",
                                  "auto").lower()
            if mode in ("0", "off", "false"):
                self._native_retry_at = float("inf")
                return None
            if (self._compress_flag or self._addrs is None
                    or len(self._addrs) != 1 or self._lb_spec != "pick_first"
                    or self._conn_kw.get("ssl_context") is not None):
                self._native_retry_at = float("inf")
                return None
            from tpurpc.utils.config import get_config

            cfg = get_config()
            ring_ok = (cfg.platform.is_ring and cfg.platform.name != "TPU"
                       and cfg.ring_domain == "shm")
            if not (ring_ok or (mode in ("1", "on", "true")
                                and not cfg.platform.is_ring)):
                self._native_retry_at = float("inf")
                return None
            try:
                from tpurpc.rpc.native_client import NativeChannel

                host, port = self._addrs[0]
                # inline_read: the fast path only issues BLOCKING entries
                # (unary calls + NativeCall streams — the .future() CQ
                # path is never used here), so it takes the lowest-latency
                # discipline: callers pump the ring, no reader-thread
                # wakeup per RTT (the 5.65 vs 7.63 µs rows in BASELINE.md)
                self._native_ch = NativeChannel(
                    host, port, connect_timeout=self._conn_kw["timeout"],
                    inline_read=True)
            except Exception:
                return None  # lib absent/unbuildable or server down: retry in 5s
            return self._native_ch

    def _native_invalidate(self, nch) -> None:
        """Drop a dead fast-path channel; the next eligible call re-dials."""
        with self._native_lock:
            if self._native_ch is nch:
                self._native_ch = None
        try:
            nch.close()
        except Exception:
            pass

    # -- call surface (grpcio-shaped) ----------------------------------------

    # Factories accept (and ignore) the extra kwargs grpcio-generated stubs
    # pass (_registered_method=True since grpcio 1.60) and treat None codecs
    # as identity, grpcio-style — so a stock *_pb2_grpc.FooStub(channel)
    # built against THIS channel works unchanged (mechanical-port claim).

    def unary_unary(self, method: str, request_serializer: Serializer = _identity,
                    response_deserializer: Deserializer = _identity,
                    **_grpcio_kwargs) -> "UnaryUnary":
        return UnaryUnary(self, method, request_serializer or _identity,
                          response_deserializer or _identity,
                          allow_native=_grpcio_kwargs.pop(
                              "tpurpc_native", True))

    def unary_stream(self, method: str, request_serializer: Serializer = _identity,
                     response_deserializer: Deserializer = _identity,
                     **_grpcio_kwargs) -> "UnaryStream":
        return UnaryStream(self, method, request_serializer or _identity,
                           response_deserializer or _identity,
                           allow_native=_grpcio_kwargs.pop(
                               "tpurpc_native", True))

    def stream_unary(self, method: str, request_serializer: Serializer = _identity,
                     response_deserializer: Deserializer = _identity,
                     **_grpcio_kwargs) -> "StreamUnary":
        return StreamUnary(self, method, request_serializer or _identity,
                           response_deserializer or _identity,
                           allow_native=_grpcio_kwargs.pop(
                               "tpurpc_native", True))

    def stream_stream(self, method: str, request_serializer: Serializer = _identity,
                      response_deserializer: Deserializer = _identity,
                      **_grpcio_kwargs) -> "StreamStream":
        return StreamStream(self, method, request_serializer or _identity,
                            response_deserializer or _identity,
                            allow_native=_grpcio_kwargs.pop(
                                "tpurpc_native", True))


class Call:
    """In-flight call handle: response iteration, cancel, metadata accessors."""

    def __init__(self, conn: _Connection, st: _ClientStream,
                 deserializer: Deserializer, deadline: Optional[float],
                 counters=None, channel: "Optional[Channel]" = None):
        self._conn = conn
        self._st = st
        self._deser = deserializer
        self._deadline = deadline
        self._trailing: Optional[Metadata] = None
        self._code: Optional[StatusCode] = None
        self._details = ""
        self._cancelled = False
        self._counters = counters  # channelz ChannelData (counted once)
        self._channel = channel  # for compression degrade on UNIMPLEMENTED

    # -- metadata/status ------------------------------------------------------

    def initial_metadata(self):
        return self._st.initial_metadata or []

    def trailing_metadata(self):
        return self._trailing

    def code(self) -> Optional[StatusCode]:
        return self._code

    def details(self) -> str:
        return self._details

    def cancel(self) -> None:
        if self._code is not None or self._cancelled:
            return
        self._cancelled = True
        try:
            self._conn.writer.send(fr.RST, 0, self._st.stream_id,
                                   fr.rst_payload(StatusCode.CANCELLED,
                                                  "cancelled by client"))
        except (EndpointError, OSError):
            pass
        self._st.deliver_failure(StatusCode.CANCELLED, "cancelled by client")

    def __del__(self):
        # An ABANDONED streaming call (iterator dropped mid-stream without
        # cancel) must not wedge the connection: the server keeps streaming,
        # the stream's credit bound fills, and the reader thread would block
        # in _acquire_credit with nobody left to set `done`. GC-time cancel
        # RSTs the server and delivers the failure that unblocks the reader
        # (grpcio's core does the equivalent via call refcounts).
        try:
            self.cancel()
        except Exception:
            pass  # interpreter teardown: modules may be half-dead

    def time_remaining(self) -> Optional[float]:
        if self._deadline is None:
            return None
        return max(0.0, self._deadline - time.monotonic())

    def device_ring(self):
        """Device ring of the connection THIS call ran on (or None off the
        TPU platform) — unlike :meth:`Channel.device_ring`, never dials, so
        it can't pick a different subchannel than the one that carried the
        response."""
        from tpurpc.core.endpoint import device_ring_of

        return device_ring_of(self._conn.endpoint)

    # -- response consumption -------------------------------------------------

    def _next_event(self):
        if self._conn._pump_mode:
            # Inline pump: THIS thread drains the transport until its
            # stream has an event — no reader-thread wakeup in the RTT.
            got = self._conn._pump_wait(
                lambda: not self._st.events.empty(), self._deadline)
            if got:
                try:
                    return self._st.events.get_nowait()
                except queue.Empty:
                    # pred held via `not alive`: the death path delivers the
                    # failure event right after flipping alive — wait for it
                    try:
                        return self._st.events.get(timeout=5)
                    except queue.Empty:
                        raise RpcError(
                            StatusCode.UNAVAILABLE,
                            "connection died without delivering status",
                        ) from None
            self._expire()
            raise RpcError(StatusCode.DEADLINE_EXCEEDED,
                           "deadline exceeded awaiting response") from None
        timeout = self.time_remaining()
        try:
            return self._st.events.get(timeout=timeout)
        except queue.Empty:
            self._expire()
            raise RpcError(StatusCode.DEADLINE_EXCEEDED,
                           "deadline exceeded awaiting response") from None

    def _tail_decide(self, error: bool) -> None:
        """tpurpc-blackbox: the client half of the tail-capture decision —
        commit this call's provisional span tree iff it was slow or
        failed (either endpoint committing promotes the shared trace)."""
        stash = getattr(self._st, "_tail", None)
        if stash is None:
            return
        self._st._tail = None  # decide once per stream
        tctx, t0, method = stash
        _tracing.tail_decide(tctx, time.monotonic_ns() - t0,
                             error=error, method=method)

    def _expire(self) -> None:
        if self._counters is not None:  # counters reconcile: expiry = failed
            self._counters.on_finish(False)
            self._counters = None
        self._code = StatusCode.DEADLINE_EXCEEDED
        self._details = "deadline exceeded"
        stash = getattr(self._st, "_tail", None)
        if stash is not None and stash[2]:
            _DEADLINE_EXCEEDED.labels(stash[2]).inc()
        _flight.emit(_flight.DEADLINE_EXPIRED, self._conn._ftag,
                     self._st.stream_id)
        self._tail_decide(error=True)
        try:
            self._conn.writer.send(fr.RST, 0, self._st.stream_id,
                                   fr.rst_payload(StatusCode.DEADLINE_EXCEEDED,
                                                  "deadline exceeded"))
        except (EndpointError, OSError):
            pass
        self._conn.close_stream(self._st)

    def _finish(self, code: StatusCode, details: str, md) -> None:
        if self._counters is not None:
            self._counters.on_finish(code is StatusCode.OK)
            self._counters = None  # retries/dup events must not double-count
        self._code = code
        self._details = details
        self._trailing = md
        self._tail_decide(error=code is not StatusCode.OK)
        if code is StatusCode.OK and not self._conn._flight_first_ok:
            self._conn._flight_first_ok = True
            _flight.emit(_flight.CALL_FIRST_OK, self._conn._ftag)
        if (self._channel is not None and self._channel._compress_flag
                and code is StatusCode.UNIMPLEMENTED
                and fr.COMPRESSED_UNSUPPORTED_SENTINEL in details):
            # Peer can't decompress: degrade the channel to identity so
            # SUBSEQUENT calls (all four shapes) succeed. The unary path
            # additionally replays this one transparently (_with_call_impl).
            self._channel._compress_flag = 0
        self._conn.close_stream(self._st)

    def messages(self) -> Iterator[object]:
        """Yield deserialized responses until trailers; raise on non-OK."""
        while True:
            ev = self._next_event()
            if ev[0] == "initial_metadata":
                continue
            if ev[0] == "message":
                self._st.release_credit()  # slot freed: reader may refill
                yield _deserialize(self._deser, ev[1])
                continue
            _, code, details, md = ev
            self._finish(code, details, md)
            if code is not StatusCode.OK:
                exc = RpcError(code, details, md)
                if getattr(self._st, "refused", False):
                    exc._tpurpc_refused = True  # replay-safe: FLAG_REFUSED
                raise exc
            return

    def __iter__(self):
        return self.messages()


_NO_REQUEST = object()
#: "no sampling decision was made upstream" sentinel for _start's
#: trace_ctx parameter (None means DECIDED-unsampled — don't redraw)
_TRACE_UNSET = object()


def _status_of(exc: RpcError) -> StatusCode:
    """RpcError's grpcio-style ``code()`` method, tolerant of plain attrs."""
    return exc.code() if callable(exc.code) else exc.code


class RetryPolicy:
    """Client retry policy — the reference inherits gRPC's service-config
    retries (retryPolicy: maxAttempts/backoff/retryableStatusCodes, applied
    in the client_channel filter). tpurpc applies it to unary-request calls
    (the full request is in hand to replay); calls that already delivered a
    response message are never retried, matching the gRPC retry contract.

    >>> ch = Channel(target, retry_policy=RetryPolicy(max_attempts=4))
    """

    __slots__ = ("max_attempts", "initial_backoff", "max_backoff",
                 "backoff_multiplier", "retryable_codes")

    def __init__(self, max_attempts: int = 3, initial_backoff: float = 0.05,
                 max_backoff: float = 1.0, backoff_multiplier: float = 2.0,
                 retryable_codes: Sequence[StatusCode] = (
                     StatusCode.UNAVAILABLE,)):
        if max_attempts < 1:
            raise ValueError("max_attempts must be >= 1")
        self.max_attempts = max_attempts
        self.initial_backoff = initial_backoff
        self.max_backoff = max_backoff
        self.backoff_multiplier = backoff_multiplier
        self.retryable_codes = tuple(retryable_codes)

    def next_sleep(self, backoff: float,
                   deadline: Optional[float]) -> Optional[float]:
        """The jittered (±20%, lib/backoff style) clamped sleep for the next
        retry, or None when it would outlive the call deadline."""
        sleep = min(backoff, self.max_backoff)
        sleep *= 1.0 + random.uniform(-0.2, 0.2)
        if deadline is not None and time.monotonic() + sleep >= deadline:
            return None
        return sleep

    def run(self, deadline: Optional[float], attempt_fn, throttle=None):
        """Drive attempt_fn() under this policy. ``throttle`` is the
        channel-wide :class:`~tpurpc.rpc.service_config.RetryThrottle`
        (gRFC A6): retryable failures drain it, successes refill it, and a
        drained bucket suppresses the retry (the failure surfaces) so a
        collapsing backend is not hammered by retry storms."""
        backoff = self.initial_backoff
        attempt = 0
        while True:
            try:
                result = attempt_fn()
            except RpcError as exc:
                attempt += 1
                code = _status_of(exc)
                retryable = code in self.retryable_codes
                if throttle is not None and retryable:
                    throttle.record_failure()
                if (attempt >= self.max_attempts
                        or not retryable
                        or getattr(exc, "_tpurpc_committed", False)
                        or (throttle is not None
                            and not throttle.allow_retry())):
                    raise
                sleep = self.next_sleep(backoff, deadline)
                # tpurpc-fleet: an admission-shedding server names its own
                # backoff (tpurpc-pushback-ms) — honor it as the FLOOR of
                # the retry sleep so a shedding backend isn't re-hammered
                # on the client's (possibly tiny) early-attempt backoff
                pushback = _pushback_s(exc)
                if pushback is not None:
                    sleep = pushback if sleep is None else max(sleep,
                                                               pushback)
                    if (deadline is not None
                            and time.monotonic() + sleep >= deadline):
                        sleep = None
                if sleep is None:
                    raise
                time.sleep(sleep)
                backoff *= self.backoff_multiplier
            else:
                if throttle is not None:
                    throttle.record_success()
                return result


class HedgingPolicy:
    """gRFC A6 hedging: up to ``max_attempts`` copies of one unary call in
    flight, staggered ``hedging_delay`` apart, each preferring a subchannel
    the call hasn't used yet. The first usable response wins and the losers
    are cancelled (RST on their streams); a failure with a status in
    ``non_fatal_codes`` fires the next hedge IMMEDIATELY instead of waiting
    out the delay; any other failure is fatal and resolves the call.

    Hedging trades duplicate work for tail latency — the method must be
    idempotent (two servers may both execute it; that is the contract, not
    a bug). All attempts share ONE deadline budget (the caller's timeout,
    anchored once), the channel-wide :class:`RetryThrottle` gates every
    hedge beyond the first (a collapsing fleet stops receiving hedges the
    same way it stops receiving retries), and a server's admission
    pushback stops further hedging outright.

    >>> ch = Channel(target, lb_policy="round_robin",
    ...              hedging_policy=HedgingPolicy(max_attempts=3,
    ...                                           hedging_delay=0.01))
    """

    __slots__ = ("max_attempts", "hedging_delay", "non_fatal_codes")

    def __init__(self, max_attempts: int = 2, hedging_delay: float = 0.05,
                 non_fatal_codes: Sequence[StatusCode] = (
                     StatusCode.UNAVAILABLE,)):
        if max_attempts < 2:
            raise ValueError("max_attempts must be >= 2")
        if hedging_delay < 0:
            raise ValueError("hedging_delay must be >= 0")
        self.max_attempts = int(max_attempts)
        self.hedging_delay = float(hedging_delay)
        self.non_fatal_codes = tuple(non_fatal_codes)


class _MultiCallable:
    def __init__(self, channel: Channel, method: str,
                 serializer: Serializer, deserializer: Deserializer,
                 allow_native: bool = True):
        self._channel = channel
        self._method = method
        self._ser = serializer
        self._deser = deserializer
        #: tpurpc extension (tpurpc_native=False at the factory): opt a
        #: method out of the native fast paths — e.g. to keep a bulk
        #: stream on the fully instrumented Python plane (copy-ledger
        #: runs). Historical note: rounds 3-4 measured the Python plane
        #: FASTER on multi-MiB payloads (0.43 vs 0.86 GB/s) — that gap
        #: was the notify-token-stealing bug fixed in round 5
        #: (ring_transport.h wait_event); the same A/B now measures the
        #: native loop ~40% ahead (1.20 vs 0.86 GB/s), and it wins
        #: small-RPC latency as before.
        self._allow_native = allow_native

    def _dial(self, wait_for_ready: bool,
              deadline: Optional[float],
              exclude=None, picked=None) -> _Connection:
        """One LB-picked connection. With ``wait_for_ready`` (the grpcio
        per-call flag), a channel in TRANSIENT_FAILURE QUEUES the call —
        keep redialing until the deadline — instead of failing it fast
        (gRPC's wait-for-ready semantics; fail-fast is the default)."""
        if not wait_for_ready:
            return self._channel._connection(exclude=exclude, picked=picked)
        while True:
            try:
                return self._channel._connection(exclude=exclude,
                                                 picked=picked)
            except RpcError as exc:
                if (self._channel._is_closed()
                        or _status_of(exc) is not StatusCode.UNAVAILABLE):
                    raise
                if (deadline is not None
                        and time.monotonic() >= deadline):
                    raise RpcError(
                        StatusCode.DEADLINE_EXCEEDED,
                        "deadline exceeded waiting for channel readiness",
                    ) from exc
                # Subchannel.get already sleeps through its backoff window;
                # this small sleep only paces the no-deadline case. Known
                # bound: the deadline is checked BETWEEN attempts, so one
                # in-flight connect to a blackholed (SYN-dropped) address
                # can overshoot by up to the channel connect_timeout — the
                # dial itself is not interruptible.
                time.sleep(0.05)

    def _start(self, metadata: Optional[Metadata],
               timeout: Optional[float],
               first_request=_NO_REQUEST,
               wait_for_ready: bool = False,
               trace_ctx=_TRACE_UNSET,
               exclude=None, picked=None,
               ) -> Tuple[_Connection, _ClientStream, Call]:
        """Open a stream and send HEADERS — fused with the first (only)
        MESSAGE when the request is known upfront, so a unary call costs one
        transport write/notify instead of two.

        A connection that turned draining (max_age GOAWAY) between the LB
        pick and open_stream is retried transparently on a fresh dial —
        gRPC's "transparent retry" for streams the application never saw on
        the wire; without it every age expiry has a window of spurious
        UNAVAILABLE."""
        # ONE deadline for the whole call, anchored before the dial: time
        # spent queuing in wait_for_ready counts against the caller's
        # timeout (grpcio semantics) — re-anchoring after the dial would
        # let a late-appearing server nearly double the budget.
        deadline = None if timeout is None else time.monotonic() + timeout
        for _ in range(3):
            conn = self._dial(wait_for_ready, deadline,
                              exclude=exclude, picked=picked)
            try:
                st = conn.open_stream()
                break
            except EndpointError:
                if not conn.draining:
                    raise RpcError(StatusCode.UNAVAILABLE,
                                   "connection closed while starting call")
        else:
            raise RpcError(StatusCode.UNAVAILABLE,
                           "no non-draining connection after 3 dials")
        # tpurpc-scope trace propagation (ISSUE 4): a sampled call carries
        # its context in ordinary metadata; the send interval is the
        # "client-send" span, and the open "wire" span rides the stream
        # until the terminal event closes it on the delivering thread.
        # Callers that already drew the sampling decision (UnaryUnary's
        # native-path gate) pass it via trace_ctx; _TRACE_UNSET means
        # decide here.
        if trace_ctx is _TRACE_UNSET:
            tctx = _tracing.maybe_sample() if _tracing.LIVE else None
        else:
            tctx = trace_ctx
        send_sp = None
        if tctx is not None:
            tctx = tctx.child()  # this call's own span id
            metadata = list(metadata or ())
            metadata.append((_tracing.HEADER, tctx.encode()))
            send_sp = _tracing.begin("client-send", tctx)
            # Open the wire span BEFORE the write: on a loopback transport
            # the server can be parsing HEADERS before send_many returns,
            # and the wire interval must enclose every server-side span.
            st._wire_span = _tracing.begin("wire", tctx)
        # tpurpc-blackbox: what Call needs to make the client-side tail
        # decision (and to label deadline expiries) at terminal time
        st._tail = (tctx, time.monotonic_ns(), self._method)
        try:
            remaining = (None if deadline is None
                         else max(0.0, deadline - time.monotonic()))
            timeout_us = (None if remaining is None
                          else max(0, int(remaining * 1e6)))
            hdr_payload = fr.headers_payload(self._method, metadata or (),
                                             timeout_us)
            with _tracing.use(tctx) if tctx is not None \
                    else _tracing.NULL_CM:
                if first_request is _NO_REQUEST:
                    conn.writer.send(fr.HEADERS, 0, st.stream_id, hdr_payload)
                else:
                    conn.writer.send_many([
                        (fr.HEADERS, 0, st.stream_id, hdr_payload),
                        (fr.MESSAGE,
                         fr.FLAG_END_STREAM | self._channel._compress_flag,
                         st.stream_id, self._ser(first_request)),
                    ])
            if tctx is not None:
                _tracing.finish(send_sp)
                send_sp = None
        except fr.FrameError as exc:
            conn.close_stream(st)
            raise RpcError(StatusCode.RESOURCE_EXHAUSTED, str(exc)) from exc
        except (EndpointError, OSError) as exc:
            raise RpcError(StatusCode.UNAVAILABLE,
                           f"transport failed: {exc}") from exc
        self._channel.call_counters.on_start()
        return conn, st, Call(conn, st, self._deser, deadline,
                              counters=self._channel.call_counters,
                              channel=self._channel)

    def _send_one(self, conn: _Connection, st: _ClientStream, request,
                  end_stream: bool,
                  deadline: Optional[float] = None) -> None:
        try:
            flags = ((fr.FLAG_END_STREAM if end_stream else 0)
                     | self._channel._compress_flag)
            conn.writer.send(fr.MESSAGE, flags, st.stream_id,
                             self._ser(request), deadline=deadline,
                             should_stop=lambda: st.done)
        except (EndpointError, OSError) as exc:
            raise RpcError(StatusCode.UNAVAILABLE,
                           f"transport failed: {exc}") from exc

    @staticmethod
    def _instruments_live() -> bool:
        """Measurement honesty, one definition for every call shape: an
        open copy-ledger window or live profiling spans are measuring the
        INSTRUMENTED Python data plane — don't route around the
        instruments."""
        from tpurpc.tpu import ledger as _ledger
        from tpurpc.utils import stats as _stats

        return _ledger.tracking() or _stats.profiling_on()

    def _try_native_stream(self, request_iterator: Iterable,
                           timeout: Optional[float],
                           pre_serialized: bool = False):
        """Shared native-stream entry for the three streaming shapes:
        an eager :class:`_NativeStreamCall` through the channel's fast
        path, or None to use the Python transport (ineligible channel,
        live measurement windows, or a dead cached fast channel — which
        is invalidated so the next call re-dials; nothing was sent, so
        the Python replay is unconditionally safe)."""
        if self._instruments_live():
            return None
        nch = self._channel._native_fast()
        if nch is None:
            return None
        # Native-plane trace propagation (ISSUE 4): a sampled stream call
        # carries its context through tpr_call_start's metadata array —
        # same wire key, same server-side extraction as the Python plane.
        md = None
        if _tracing.LIVE:
            tctx = _tracing.maybe_sample()
            if tctx is not None:
                md = [(_tracing.HEADER, tctx.child().encode())]
        try:
            nc = nch.start_call(self._method, timeout, metadata=md)
        except RpcError:
            self._channel._native_invalidate(nch)
            return None
        ser = (lambda x: x) if pre_serialized else self._ser
        return _NativeStreamCall(self._channel, nc, ser, self._deser,
                                 request_iterator, timeout)

    def _send_stream(self, conn: _Connection, st: _ClientStream,
                     request_iterator: Iterable, call: Call) -> None:
        try:
            for request in request_iterator:
                if st.done:
                    return  # server already terminated the call
                self._send_one(conn, st, request, end_stream=False,
                               deadline=call._deadline)
            # Pure half-close marker, NOT an empty message (FLAG_NO_MESSAGE).
            conn.writer.send(fr.MESSAGE,
                             fr.FLAG_END_STREAM | fr.FLAG_NO_MESSAGE,
                             st.stream_id, b"")
        except (RpcError, EndpointError, OSError):
            pass  # reader thread surfaces the transport failure with a status
        except _rdv.SendAbandoned:
            # the call ended (terminated by the server, or past its
            # deadline) while a message waited for rendezvous credit:
            # whoever reads the call gets that status; nothing more to send
            pass
        except Exception as exc:
            # The *user's* request iterator (or serializer) raised: terminate the
            # stream both ways or the call would hang until its deadline and the
            # server handler would block forever on requests.get().
            try:
                conn.writer.send(fr.RST, 0, st.stream_id,
                                 fr.rst_payload(StatusCode.CANCELLED,
                                                f"request iterator raised: {exc}"))
            except (EndpointError, OSError, fr.FrameError):
                pass
            conn.close_stream(st)
            st.deliver_failure(StatusCode.CANCELLED,
                               f"request iterator raised: {exc!r}")


def _reject_call_credentials(grpcio_kw: dict) -> None:
    """grpcio callers may pass credentials/wait_for_ready/compression per
    call. wait_for_ready is honored (queue instead of fail-fast, see
    _MultiCallable._dial); per-call compression is advisory (use the
    CHANNEL-level compression= knob — FLAG_COMPRESSED on the framing);
    per-call CREDENTIALS are a security feature we must not silently
    drop."""
    if grpcio_kw.get("credentials") is not None:
        raise NotImplementedError(
            "per-call credentials are not supported; use channel credentials")


class UnaryUnary(_MultiCallable):
    #: (NativeChannel, native multicallable) cache — rebuilt when the
    #: channel re-dials its fast path after a failure
    _native_mc: "Optional[tuple]" = None

    def __call__(self, request, timeout: Optional[float] = None,
                 metadata: Optional[Metadata] = None, **grpcio_kw):
        _reject_call_credentials(grpcio_kw)
        # Native fast path (the grpcio shape: Python surface, C-core hot
        # loop): plain response-only unary calls with no per-call extras
        # run inside libtpurpc.so's inline-read loop. with_call (needs a
        # Call with trailing metadata), metadata, and wait_for_ready —
        # whether per-call or via the service config — stay on the Python
        # transport (the queue-until-ready dial loop lives there).
        # Sampled (traced) calls stay on the Python transport: the unary
        # native entry has no metadata channel to carry the trace context
        # (NativeCall STREAMS do — _try_native_stream threads it through
        # tpr_call_start). Sampling defaults off, so the common path pays
        # one global load. TAIL-provisional contexts do NOT force the
        # Python path — the 5 µs native loop must not pay the 95 µs plane
        # for a trace that is overwhelmingly about to be dropped; instead
        # _native_call synthesizes a post-hoc span iff the call turns out
        # pathological (client-side-only tree, documented trade).
        tctx = _tracing.maybe_sample() if _tracing.LIVE else None
        plan = self._channel._call_plan(self._method, None)
        if ((tctx is None or getattr(tctx, "provisional", False))
                and self._allow_native and not metadata
                and not grpcio_kw.get("wait_for_ready")
                and not plan[3]
                # hedged calls stay on the Python transport: hedging wants
                # N streams on distinct subchannels + cross-thread cancel,
                # none of which the single-pipe native loop can express
                and plan[4] is None
                and not self._instruments_live()):
            nch = self._channel._native_fast()
            if nch is not None:
                done, resp = self._native_call(nch, request, timeout, tctx)
                if done:
                    return resp
        # the sampling decision rides DOWN the call explicitly (not via
        # ambient TLS): re-deriving it in _start would cost a second
        # sampler draw per call even when tracing never fires
        response, _ = self.with_call(request, timeout=timeout,
                                     metadata=metadata,
                                     _trace_ctx=tctx, **grpcio_kw)
        return response

    def _native_call(self, nch, request, timeout: Optional[float],
                     tctx=None):
        """One unary call inside the native loop. Returns ``(True, resp)``
        or ``(False, None)`` — fall back to the Python transport, allowed
        only for failures that PROVE no handler ran (refused/connect-time),
        so a fallback can never re-execute a committed call.

        ``tctx`` is a tail-capture provisional context: nothing is recorded
        on the fast path; iff the call turns out slow or errored, the trace
        commits and a post-hoc ``native-unary`` span materializes — the
        native plane's bounded-cost tail story."""
        cached = self._native_mc
        if cached is None or cached[0] is not nch:
            cached = (nch, nch.unary_unary(self._method))
            self._native_mc = cached
        mc = cached[1]
        counters = self._channel.call_counters
        policy, timeout, throttle, _, _hedging = self._channel._call_plan(
            self._method, timeout)
        deadline = None if timeout is None else time.monotonic() + timeout

        recv_limit = self._channel.max_receive_message_length

        def attempt():
            remaining = (None if deadline is None
                         else max(0.0, deadline - time.monotonic()))
            counters.on_start()
            try:
                body = mc(self._ser(request), timeout=remaining)
                if recv_limit is not None and len(body) > recv_limit:
                    # max_receive_message_length parity: the native loop
                    # doesn't enforce it, so the contract holds here (the
                    # bytes crossed the wire, the app never sees them —
                    # grpcio's client behaves the same at this layer)
                    raise RpcError(
                        StatusCode.RESOURCE_EXHAUSTED,
                        "received message larger than "
                        "max_receive_message_length")
            except RpcError:
                counters.on_finish(False)
                raise
            counters.on_finish(True)
            return _deserialize(self._deser, body)

        t0 = time.monotonic_ns() if tctx is not None else 0

        def _tail(error: bool) -> None:
            if tctx is None:
                return
            dur = time.monotonic_ns() - t0
            if _tracing.tail_decide(tctx, dur, error=error,
                                    method=self._method):
                _tracing.record("native-unary", tctx, t0, dur,
                                method=self._method)

        try:
            if policy is None:
                result = attempt()
            else:
                result = policy.run(deadline, attempt, throttle=throttle)
            _tail(error=False)
            return True, result
        except RpcError as exc:
            _tail(error=True)
            if _status_of(exc) is StatusCode.UNAVAILABLE:
                # dead fast-path connection: drop it so the next call
                # re-dials. Fall back to the Python transport (its
                # reconnect machinery) only when the failure provably
                # happened before any handler could run.
                self._channel._native_invalidate(nch)
                # Pre-execution failures only: the native side reports the
                # verdict machine-readably (_tpurpc_preexec, set from
                # tpr_unary_call_ex's preexec out-param or by the ctypes
                # wrapper's own admission refusals) — True means the server
                # never saw a complete request, so the Python transport may
                # safely re-dial and replay. Post-send deaths ("connection
                # lost", tpurpc_client.cc die()) carry False — the handler
                # may have executed and replaying would double-execute; they
                # surface to the caller exactly as the Python transport's
                # mid-call death does. Never match on details wording: the
                # human-readable text is not a contract (ADVICE r4 #2). One
                # compat exception, mirroring the transparent-retry gate
                # below: a pre-round-5 SERVER sends its max_age refusal RST
                # without FLAG_REFUSED, so the wording is the only signal.
                if (getattr(exc, "_tpurpc_preexec", False)
                        or "connection draining" in (exc.details() or "")):
                    return False, None
            raise

    def with_call(self, request, timeout: Optional[float] = None,
                  metadata: Optional[Metadata] = None,
                  _trace_ctx=_TRACE_UNSET, **grpcio_kw):
        from tpurpc.utils import stats as _stats

        if _stats.profiling_on():  # GRPCProfiler span: whole unary call
            with _stats.profile("cli_unary"):
                return self._with_call_impl(request, timeout, metadata,
                                            _trace_ctx=_trace_ctx,
                                            **grpcio_kw)
        return self._with_call_impl(request, timeout, metadata,
                                    _trace_ctx=_trace_ctx, **grpcio_kw)

    def _with_call_impl(self, request, timeout: Optional[float] = None,
                        metadata: Optional[Metadata] = None,
                        _trace_ctx=_TRACE_UNSET, **grpcio_kw):
        _reject_call_credentials(grpcio_kw)
        policy, timeout, throttle, eff_wfr, hedging = \
            self._channel._call_plan(
                self._method, timeout, bool(grpcio_kw.get("wait_for_ready")))
        deadline = None if timeout is None else time.monotonic() + timeout
        if policy is None and hedging is not None:
            return self._hedged_call(request, deadline, metadata, eff_wfr,
                                     hedging, throttle, _trace_ctx)
        #: subchannels that REFUSED this logical call (drain/max-age): the
        #: replay deprioritizes them, so a draining backend's traffic
        #: deterministically migrates instead of re-racing the same GOAWAY
        refused_subs: set = set()

        def attempt():
            # Transparent retry (distinct from RetryPolicy): a stream the
            # server REFUSED at admission — RST "connection draining" from a
            # max_age GOAWAY race — never reached a handler, so replaying it
            # on a fresh connection is always safe (gRPC does the same for
            # GOAWAY-refused streams). Each replay re-derives its budget from
            # the OUTER deadline — a per-attempt re-anchor would extend the
            # caller's wall-clock deadline by up to 3 refused attempts.
            def remaining():
                return (None if deadline is None
                        else max(0.0, deadline - time.monotonic()))

            wfr = eff_wfr
            for _ in range(3):
                try:
                    return self._call_once(request, remaining(), metadata,
                                           wfr, trace_ctx=_trace_ctx,
                                           exclude=refused_subs or None)
                except RpcError as exc:
                    committed = getattr(exc, "_tpurpc_committed", False)
                    # FLAG_REFUSED is the contract; the "connection draining"
                    # wording stays as compat with pre-round-5 servers that
                    # sent the RST without the flag
                    refused = ((getattr(exc, "_tpurpc_refused", False)
                                or (_status_of(exc) is StatusCode.UNAVAILABLE
                                    and "connection draining"
                                    in exc.details()))
                               and not committed)
                    # Compression negotiation by probe: a peer that can't
                    # decompress (the native server/client) rejects the
                    # stream with UNIMPLEMENTED before any handler runs, so
                    # degrading the CHANNEL to identity and replaying is
                    # safe — the grpcio equivalent of the server dropping
                    # the codec from grpc-accept-encoding.
                    # (Call._finish already cleared the channel flag when it
                    # saw this trailer, so don't gate on it still being set.)
                    if (not committed and not refused
                            and _status_of(exc) is StatusCode.UNIMPLEMENTED
                            and fr.COMPRESSED_UNSUPPORTED_SENTINEL
                            in exc.details()):
                        self._channel._compress_flag = 0
                        refused = True
                    if not refused:
                        raise
                    sub = getattr(exc, "_tpurpc_sub", None)
                    if sub is not None:
                        refused_subs.add(sub)
            return self._call_once(request, remaining(), metadata, wfr,
                                   trace_ctx=_trace_ctx,
                                   exclude=refused_subs or None)

        if policy is None:
            return attempt()
        return policy.run(deadline, attempt, throttle=throttle)

    def _hedged_call(self, request, deadline: Optional[float],
                     metadata: Optional[Metadata], wait_for_ready: bool,
                     hp: "HedgingPolicy", throttle, trace_ctx):
        """The gRFC A6 hedging state machine (tpurpc-fleet, ISSUE 6).

        One orchestrating thread (the caller's) drives N attempt threads:

        * attempt 0 launches immediately; attempt k+1 launches when the
          hedging delay lapses with nothing resolved, OR immediately when
          an attempt fails with a non-fatal status;
        * every launch beyond the first consults the channel-wide
          RetryThrottle — a drained bucket stops hedging, so hedges can
          never amplify into the retry storm the throttle exists to stop;
        * admission pushback from any attempt stops further hedging
          outright (the fleet said "back off");
        * the first OK response wins: the losers' streams are RST and
          their Calls observe CANCELLED. A fatal (non-retryable) failure
          resolves the call the same way.

        All attempts share the ONE deadline anchored by the caller; each
        attempt thread carries its own remaining-budget snapshot, so every
        outstanding attempt self-resolves by the deadline and the
        orchestrator's final wait cannot hang."""
        def remaining():
            return (None if deadline is None
                    else max(0.0, deadline - time.monotonic()))

        results: "queue.Queue[tuple]" = queue.Queue()
        lock = make_lock("HedgeOrchestrator._lock")
        calls: dict = {}       # attempt idx -> live Call (for cancellation)
        used_subs: set = set()  # prefer-distinct exclusion, cross-attempt
        done = [False]

        def on_call_for(idx):
            def on_call(call, sub):
                cancel_now = False
                with lock:
                    calls[idx] = call
                    if sub is not None:
                        used_subs.add(sub)
                    if done[0]:
                        cancel_now = True  # raced the winner: die quietly
                if cancel_now:
                    call.cancel()
            return on_call

        def run_attempt(idx):
            refused_local: set = set()
            last_exc = None
            for _ in range(3):  # transparent refused-replay, per attempt
                with lock:
                    excl = set(used_subs) | refused_local
                try:
                    resp, call = self._call_once(
                        request, remaining(), metadata, wait_for_ready,
                        trace_ctx=trace_ctx, exclude=excl or None,
                        on_call=on_call_for(idx))
                    results.put((idx, (resp, call), None))
                    return
                except RpcError as exc:
                    last_exc = exc
                    if (getattr(exc, "_tpurpc_refused", False)
                            and not getattr(exc, "_tpurpc_committed",
                                            False)):
                        sub = getattr(exc, "_tpurpc_sub", None)
                        if sub is not None:
                            refused_local.add(sub)
                        continue
                    results.put((idx, None, exc))
                    return
                except BaseException as exc:  # serializer bug etc.
                    results.put((idx, None, exc))
                    return
            results.put((idx, None, last_exc))

        launched = 0
        outstanding = 0
        stop_hedging = False  # flipped by admission pushback

        def may_hedge():
            return (launched < hp.max_attempts and not stop_hedging
                    and (throttle is None or throttle.allow_retry()))

        def launch():
            nonlocal launched, outstanding
            idx = launched
            launched += 1
            outstanding += 1
            if idx > 0:
                _HEDGES_FIRED.inc()
                _flight.emit(_flight.HEDGE_FIRED, _HEDGE_TAG, idx)
            threading.Thread(target=run_attempt, args=(idx,), daemon=True,
                             name="tpurpc-hedge").start()

        def finish(win_idx=None):
            with lock:
                done[0] = True
                losers = [(i, c) for i, c in calls.items() if i != win_idx]
            for i, call in losers:
                try:
                    call.cancel()
                except Exception:
                    pass
                if win_idx is not None:
                    _flight.emit(_flight.HEDGE_CANCELLED, _HEDGE_TAG, i)

        launch()
        last_failure = None
        while True:
            wait = hp.hedging_delay if may_hedge() else None
            rem = remaining()
            if rem is not None and (wait is None or rem < wait):
                # bound the wait by the budget + slack: outstanding
                # attempts self-expire at the deadline and deliver here
                wait = rem + 1.0
            try:
                idx, ok, exc = results.get(timeout=wait)
            except queue.Empty:
                if may_hedge():
                    launch()  # the delay lapsed unresolved: hedge
                    continue
                if outstanding > 0:
                    continue  # just wait: attempts carry their own deadline
                # nothing in flight, nothing launchable
                finish()
                raise last_failure if last_failure is not None else RpcError(
                    StatusCode.DEADLINE_EXCEEDED,
                    "deadline exceeded before any hedged attempt resolved")
            outstanding -= 1
            if exc is None:
                resp, call = ok
                if idx > 0:
                    _HEDGES_WON.inc()
                _flight.emit(_flight.HEDGE_WON, _HEDGE_TAG, idx)
                finish(win_idx=idx)
                if throttle is not None:
                    throttle.record_success()
                return resp, call
            if done[0]:
                continue  # a cancelled loser reporting in: ignore
            if isinstance(exc, RpcError):
                code = _status_of(exc)
                retryable = (code in hp.non_fatal_codes
                             and not getattr(exc, "_tpurpc_committed",
                                             False))
                if throttle is not None and retryable:
                    throttle.record_failure()
                if _pushback_s(exc) is not None:
                    stop_hedging = True  # the fleet is shedding: no more
                if retryable:
                    last_failure = exc
                    if may_hedge():
                        launch()  # gRFC A6: non-fatal fires the next
                        continue  # hedge immediately
                    if outstanding > 0:
                        continue
                    finish()
                    raise exc
            # fatal failure (or a non-RpcError bug): resolve now
            finish()
            raise exc

    def _call_once(self, request, timeout: Optional[float],
                   metadata: Optional[Metadata], wait_for_ready: bool = False,
                   trace_ctx=_TRACE_UNSET, exclude=None, on_call=None):
        """One wire attempt. ``exclude`` deprioritizes subchannels this
        logical call already touched (drain migration / hedge spread);
        ``on_call(call, subchannel)`` fires as soon as the stream is open —
        the hedged driver registers the Call for cross-attempt
        cancellation there. A failure carries the subchannel it ran on as
        ``_tpurpc_sub`` so callers can extend their exclusion set."""
        picked: list = []
        conn, st, call = self._start(metadata, timeout, first_request=request,
                                     wait_for_ready=wait_for_ready,
                                     trace_ctx=trace_ctx,
                                     exclude=exclude, picked=picked)
        if on_call is not None:
            on_call(call, picked[-1] if picked else None)
        response = None
        got = False
        try:
            for msg in call.messages():
                if got:
                    raise RpcError(StatusCode.INTERNAL,
                                   "unary call received multiple responses")
                response, got = msg, True
        except RpcError as exc:
            if got:
                # A response message was already delivered: the call is
                # committed — replaying it would re-execute the handler
                # (gRPC's retry contract forbids this too).
                exc._tpurpc_committed = True
            if picked:
                exc._tpurpc_sub = picked[-1]
            raise
        if not got:
            raise RpcError(StatusCode.INTERNAL, "unary call received no response")
        return response, call

    def future(self, request, timeout: Optional[float] = None,
               metadata: Optional[Metadata] = None):
        """Minimal future: runs the call on a daemon thread. The caller's
        ring_hash key (a thread-local) is captured NOW and re-installed in
        the worker thread, so keyed routing survives the thread hop."""
        import concurrent.futures

        from tpurpc.rpc import resolver as _resolver

        key = getattr(_resolver._call_key, "key", None)
        fut: "concurrent.futures.Future" = concurrent.futures.Future()

        def run():
            if not fut.set_running_or_notify_cancel():
                return
            try:
                if key is not None:
                    with _resolver.ring_hash_key(key):
                        fut.set_result(self(request, timeout, metadata))
                else:
                    fut.set_result(self(request, timeout, metadata))
            except BaseException as exc:
                fut.set_exception(exc)

        threading.Thread(target=run, daemon=True,
                         name="tpurpc-unary-future").start()
        return fut

    def pipeline(self, depth: int = 16) -> "PipelinedUnary":
        """A bounded-window pipelined caller for this method: many unary
        calls in flight on ONE connection, demuxed by stream id — no
        thread per call (contrast :meth:`future`, which spawns one)."""
        return PipelinedUnary(self, depth=depth)


class PipelinedUnary:
    """Multi-in-flight unary calls over one connection (the serving
    pipeline's client half, ISSUE 3).

    ``call_async`` sends the fused HEADERS+MESSAGE immediately and returns
    a ``concurrent.futures.Future``; the connection's reader (or inline
    pump) thread demuxes completions by stream id and resolves each future
    in place, so N in-flight calls cost N streams — not N parked threads.
    The bounded window (``depth``) backpressures callers: the depth+1'th
    ``call_async`` blocks until a completion frees a slot, which is what
    keeps a fast client from ballooning server-side queues.

    Completion (including response deserialization) runs on the delivering
    thread — keep deserializers cheap (the tensor codec's zero-copy decode
    qualifies). Out-of-order completion across streams is the point: a
    slow call does not head-of-line-block its siblings' futures.
    """

    def __init__(self, mc: "UnaryUnary", depth: int = 16):
        import concurrent.futures

        self._Future = concurrent.futures.Future
        self._mc = mc
        self.depth = max(1, int(depth))
        self._window = threading.BoundedSemaphore(self.depth)
        self._lock = make_lock("PipelinedUnary._lock")
        self._inflight = 0
        self._closed = False
        self._pump_threads: dict = {}  # conn id -> Thread (pump-mode only)
        _PIPELINES_INFLIGHT.track(self)

    def call_async(self, request, timeout: Optional[float] = None,
                   metadata: Optional[Metadata] = None):
        """One pipelined call; returns a Future of the deserialized
        response. Blocks only for a window slot (backpressure), never for
        the response.

        tpurpc-fleet: a REFUSED terminal (drain / max-age GOAWAY race —
        the server certifies no handler ran) replays transparently on
        another subchannel instead of failing the future, up to 3 times
        under the original deadline — the pipelined half of the
        zero-failed-RPC drain contract. The replay's dial runs off the
        delivering reader thread (timer-wheel blocking pool)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        if not self._window.acquire(
                timeout=None if timeout is None else timeout):
            raise RpcError(StatusCode.DEADLINE_EXCEEDED,
                           "deadline exceeded waiting for pipeline window")
        t_start = time.perf_counter_ns()
        fut = self._Future()
        state = {"claimed": False, "timer": None, "replays": 0,
                 "exclude": set(), "cur": None}
        # tpurpc-blackbox: register with the stall watchdog — a pipelined
        # call has NO thread parked on it, so the sweeper is the only
        # observer that can notice it wedged and name the stage
        from tpurpc.obs import watchdog as _watchdog

        def claim() -> bool:
            with self._lock:
                if state["claimed"]:
                    return False
                state["claimed"] = True
                self._inflight -= 1
            self._window.release()
            return True

        def start_attempt():
            remaining = (None if deadline is None
                         else max(0.0, deadline - time.monotonic()))
            picked: list = []
            conn, st, call = self._mc._start(
                metadata, remaining, first_request=request,
                exclude=state["exclude"] or None, picked=picked)
            stash = getattr(st, "_tail", None)
            wd_tok = _watchdog.call_started(
                self._mc._method,
                stash[0].trace_id if stash and stash[0] is not None else 0,
                kind="client")
            cur = {"st": st, "call": call, "wd": wd_tok, "fired": False,
                   "sub": picked[-1] if picked else None}
            state["cur"] = cur

            def complete():
                with self._lock:
                    if cur["fired"]:
                        return  # hook + done-fallback both ran: once only
                    cur["fired"] = True
                msgs = []
                code, details, md = None, "", []
                while True:
                    try:
                        ev = st.events.get_nowait()
                    except queue.Empty:
                        break
                    if ev[0] == "message":
                        st.release_credit()
                        msgs.append(ev[1])
                    elif ev[0] == "trailers":
                        _, code, details, md = ev
                if code is None:  # terminal hook without a queued trailer
                    code, details = (StatusCode.INTERNAL,
                                     "terminal without status")
                refused = (code is not StatusCode.OK and not msgs
                           and getattr(st, "refused", False))
                if refused and state["replays"] < 3 and not state["claimed"]:
                    # migrate: the refusing subchannel is deprioritized and
                    # the attempt replays — off this (reader) thread, which
                    # must not block in a dial
                    state["replays"] += 1
                    if cur["sub"] is not None:
                        state["exclude"].add(cur["sub"])
                    call._finish(code, details, md)
                    _watchdog.call_finished(wd_tok, error=True)
                    from tpurpc.utils.timers import run_blocking

                    def replay():
                        if state["claimed"]:
                            return  # expired while queued
                        try:
                            start_attempt()
                        except BaseException as exc:
                            if claim():
                                timer = state.get("timer")
                                if timer is not None:
                                    timer.cancel()
                                if fut.set_running_or_notify_cancel():
                                    fut.set_exception(exc)

                    run_blocking(replay)
                    return
                if not claim():
                    return
                timer = state.get("timer")
                if timer is not None:
                    timer.cancel()
                call._finish(code, details, md)
                _watchdog.call_finished(wd_tok,
                                        error=code is not StatusCode.OK)
                if not fut.set_running_or_notify_cancel():
                    return  # caller cancelled the future; drop the result
                if code is not StatusCode.OK:
                    exc = RpcError(code, details, md)
                    if refused:
                        exc._tpurpc_refused = True
                    fut.set_exception(exc)
                elif len(msgs) != 1:
                    fut.set_exception(RpcError(
                        StatusCode.INTERNAL,
                        "unary call received no response" if not msgs
                        else "unary call received multiple responses"))
                else:
                    try:
                        fut.set_result(
                            _deserialize(self._mc._deser, msgs[0]))
                    except BaseException as exc:  # a raising deserializer
                        fut.set_exception(exc)    # fails, never hangs
                now = time.perf_counter_ns()
                _PIPE_CALL_US.record((now - t_start) // 1000)
                if st._t_terminal:
                    _PIPE_DEMUX_US.record((now - st._t_terminal) // 1000)

            # Hook AFTER the send: the terminal may already have been
            # delivered (fast server + slow caller), in which case st.done
            # is set and the hook will never fire — complete from here
            # instead. cur["fired"] makes the two funnels once-only.
            st.on_terminal = complete
            if st.done:
                complete()
            self._ensure_pump(conn)

        with self._lock:
            self._inflight += 1
        try:
            start_attempt()
        except BaseException:
            with self._lock:
                self._inflight -= 1
            self._window.release()
            raise
        if deadline is not None:
            # No thread waits on this call, so the deadline needs its own
            # watchdog: expire RSTs the CURRENT attempt's stream (endpoint
            # write — off the wheel thread) and fails the future. One
            # absolute deadline covers every replay.
            from tpurpc.utils.timers import run_blocking, schedule

            def expire():
                if not claim():
                    return
                cur = state["cur"]
                if cur is not None:
                    cur["call"]._expire()
                    _watchdog.call_finished(cur["wd"], error=True)
                if fut.set_running_or_notify_cancel():
                    fut.set_exception(RpcError(
                        StatusCode.DEADLINE_EXCEEDED,
                        "deadline exceeded awaiting pipelined response"))

            state["timer"] = schedule(
                max(0.0, deadline - time.monotonic()),
                lambda: run_blocking(expire))
        return fut

    # -- pump-mode servicing --------------------------------------------------

    def _ensure_pump(self, conn: _Connection) -> None:
        """Pump-mode connections have no reader thread: with every caller
        detached (futures, nobody blocking in _pump_wait), the transport
        would never be drained. One servicing thread per live connection
        pumps while this pipeline has calls in flight."""
        if not conn._pump_mode:
            return
        key = id(conn)
        with self._lock:
            t = self._pump_threads.get(key)
            if t is not None and t.is_alive():
                return
            t = threading.Thread(target=self._pump_loop, args=(conn, key),
                                 daemon=True, name="tpurpc-pipeline-pump")
            self._pump_threads[key] = t
        t.start()

    def _pump_loop(self, conn: _Connection, key: int) -> None:
        try:
            while True:
                conn._pump_wait(
                    lambda: self._idle() or not conn.alive, None)
                with self._lock:
                    if self._idle() or not conn.alive:
                        self._pump_threads.pop(key, None)
                        return
        except Exception:
            with self._lock:
                self._pump_threads.pop(key, None)

    def _idle(self) -> bool:
        return self._inflight == 0 or self._closed

    def close(self) -> None:
        """Stop servicing. Outstanding futures still resolve off the
        reader thread; pump-mode servicing threads wind down."""
        with self._lock:
            self._closed = True

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class _RetryingStreamCall:
    """Call-shaped wrapper retrying a server-streaming RPC that failed
    BEFORE its first response message (gRPC's retry rule for streams: once
    anything was delivered the call is committed). The request is unary,
    so replay is always possible. Start failures (dial, admission) consume
    retry attempts exactly like stream failures; one attempt/backoff
    budget spans the whole call. Cancellation during a backoff sleep stops
    further replays."""

    def __init__(self, mc: "UnaryStream", request, timeout, metadata,
                 policy: "RetryPolicy", wait_for_ready: bool = False,
                 throttle=None):
        self._inner: Optional[Call] = None  # first: __getattr__ recursion guard
        self._mc = mc
        self._request = request
        self._deadline = (None if timeout is None
                          else time.monotonic() + timeout)
        self._metadata = metadata
        self._policy = policy
        self._throttle = throttle  # channel-wide gRFC A6 token bucket
        self._wait_for_ready = wait_for_ready
        self._attempt = 0
        self._backoff = policy.initial_backoff
        self._cancelled = False
        self._start_with_retry()  # eager start, grpcio semantics

    def _handle_failure(self, exc: RpcError, committed: bool) -> None:
        """Count the attempt; sleep for the backoff; or re-raise."""
        self._attempt += 1
        retryable = _status_of(exc) in self._policy.retryable_codes
        if self._throttle is not None and retryable:
            self._throttle.record_failure()
        if (self._cancelled or committed
                or self._attempt >= self._policy.max_attempts
                or not retryable
                or (self._throttle is not None
                    and not self._throttle.allow_retry())):
            raise exc
        sleep = self._policy.next_sleep(self._backoff, self._deadline)
        pushback = _pushback_s(exc)  # admission shed: server-named floor
        if pushback is not None:
            sleep = pushback if sleep is None else max(sleep, pushback)
            if (self._deadline is not None
                    and time.monotonic() + sleep >= self._deadline):
                sleep = None
        if sleep is None:
            raise exc
        time.sleep(sleep)
        self._backoff *= self._policy.backoff_multiplier
        if self._cancelled:  # cancelled while we slept: stop replaying
            raise exc

    def _start_with_retry(self) -> None:
        while True:
            try:
                remaining = (None if self._deadline is None
                             else max(0.0, self._deadline - time.monotonic()))
                _, _, self._inner = self._mc._start(
                    self._metadata, remaining, first_request=self._request,
                    wait_for_ready=self._wait_for_ready)
                return
            except RpcError as exc:
                self._handle_failure(exc, committed=False)

    def messages(self) -> Iterator[object]:
        while True:
            delivered = False
            try:
                for msg in self._inner.messages():
                    delivered = True
                    yield msg
                if self._throttle is not None:
                    self._throttle.record_success()
                return
            except RpcError as exc:
                self._handle_failure(exc, committed=delivered)
                self._start_with_retry()

    def __iter__(self):
        return self.messages()

    def cancel(self):
        self._cancelled = True
        if self._inner is not None:
            self._inner.cancel()

    def __getattr__(self, name):
        # full Call-surface delegation (time_remaining, device_ring, ...)
        # to the CURRENT attempt's call
        return getattr(self._inner, name)


def _drain_single_response(messages) -> object:
    """The exactly-one-response rule, shared by both transports (identical
    status details either way)."""
    response = None
    got = False
    for msg in messages:
        if got:
            raise RpcError(StatusCode.INTERNAL,
                           "unary call received multiple responses")
        response, got = msg, True
    if not got:
        raise RpcError(StatusCode.INTERNAL, "unary response missing")
    return response


class UnaryStream(_MultiCallable):
    def __call__(self, request, timeout: Optional[float] = None,
                 metadata: Optional[Metadata] = None, **grpcio_kw):
        _reject_call_credentials(grpcio_kw)
        policy, timeout, throttle, wfr, _hedging = self._channel._call_plan(
            self._method, timeout, bool(grpcio_kw.get("wait_for_ready")))
        # Native fast path (same eligibility as the other shapes; retrying
        # and wait-for-ready calls stay on the Python transport —
        # _RetryingStreamCall's first-response rule and the queue-until-
        # ready dial loop are built on its Call internals)
        if (policy is None and self._allow_native and not metadata
                and not wfr
                # cheap eligibility FIRST (same gates _try_native_stream
                # re-checks): when the call is headed for the Python path
                # anyway, don't serialize here only to have _start
                # re-serialize the same request (ADVICE r4 #3)
                and not self._instruments_live()
                and self._channel._native_fast() is not None):
            # serialize EAGERLY: the Python path raises serializer errors
            # at call time (_start serializes first_request inline), and
            # the native path must not defer them to first iteration
            raw = self._ser(request)
            nsc = self._try_native_stream(iter([raw]), timeout,
                                          pre_serialized=True)
            if nsc is not None:
                return nsc
        if policy is None:
            conn, st, call = self._start(
                metadata, timeout, first_request=request,
                wait_for_ready=wfr)
            return call
        return _RetryingStreamCall(self, request, timeout, metadata, policy,
                                   wfr, throttle=throttle)


class StreamUnary(_MultiCallable):
    def __call__(self, request_iterator: Iterable,
                 timeout: Optional[float] = None,
                 metadata: Optional[Metadata] = None, **grpcio_kw):
        _reject_call_credentials(grpcio_kw)
        _, timeout, _, wfr, _hedging = self._channel._call_plan(
            self._method, timeout, bool(grpcio_kw.get("wait_for_ready")))
        if self._allow_native and not metadata and not wfr:
            nsc = self._try_native_stream(request_iterator, timeout)
            if nsc is not None:
                return _drain_single_response(nsc)
        conn, st, call = self._start(
            metadata, timeout, wait_for_ready=wfr)
        sender = threading.Thread(
            target=self._send_stream, args=(conn, st, request_iterator, call),
            daemon=True)
        sender.start()
        response = _drain_single_response(call.messages())
        sender.join(timeout=5)
        return response


class _NativeStreamCall:
    """Call-shaped bidi stream over a native ``NativeCall``. The RPC starts
    EAGERLY (the Python transport's semantics: requests flow before the
    first response is consumed), cancel() is cross-thread-safe (a plain C
    call, unlike closing a running generator), responses honor the
    channel's receive limit, and completions feed the channel's call
    counters — the parity points the native unary path already carries."""

    def __init__(self, channel: "Channel", nc, serializer, deserializer,
                 request_iterator, timeout: Optional[float]):
        self._nc = nc
        self._deser = deserializer
        self._code: Optional[StatusCode] = None
        self._details = ""
        self._deadline = (None if timeout is None
                          else time.monotonic() + timeout)
        self._recv_limit = channel.max_receive_message_length
        self._counters = channel.call_counters
        self._counters.on_start()
        self._finished = False
        self._finish_lock = make_lock("_NativeStreamCall._finish_lock")
        self._callbacks: list = []
        self._app_exc: list = []
        self._sender = threading.Thread(
            target=self._pump_requests, args=(request_iterator, serializer),
            daemon=True)
        self._sender.start()

    def _pump_requests(self, request_iterator, serializer) -> None:
        try:
            for item in request_iterator:
                self._nc.write(serializer(item))
            self._nc.writes_done()
        except RpcError:
            pass  # the read side surfaces the status
        except BaseException as exc:  # the app's iterator/serializer raised
            self._app_exc.append(exc)
            self._nc.cancel()  # both sides unblock; reader sees CANCELLED

    def _finish(self) -> None:
        with self._finish_lock:
            if self._finished:
                return
            self._finished = True
        if self._sender.is_alive():
            # early consumer exit with requests still flowing: RST first
            # so the blocked writer fails fast, THEN join (destroying the
            # call under a live writer is a native use-after-free)
            self._nc.cancel()
        self._sender.join()
        code, details = self._nc.finish()
        self._code, self._details = code, details
        self._nc.close()
        self._counters.on_finish(code is StatusCode.OK)
        for cb in self._callbacks:
            try:
                cb()
            except Exception:
                pass

    def __iter__(self):
        return self

    def __next__(self):
        msg = self._nc.read()
        if msg is None:
            self._finish()
            if self._app_exc:
                raise self._app_exc[0]
            if self._code is not StatusCode.OK:
                raise RpcError(self._code, self._details)
            raise StopIteration
        if self._recv_limit is not None and len(msg) > self._recv_limit:
            self._nc.cancel()
            self._finish()
            self._code = StatusCode.RESOURCE_EXHAUSTED
            self._details = ("received message larger than "
                            "max_receive_message_length")
            raise RpcError(self._code, self._details)
        return _deserialize(self._deser, msg)

    def __del__(self):
        # abandoned stream: RST + teardown so the server stops producing
        try:
            if not self._finished:
                self._nc.cancel()
                self._finish()
        except Exception:
            pass

    # -- grpc Call surface ---------------------------------------------------

    def cancel(self) -> None:
        self._nc.cancel()  # thread-safe: plain C call, reader unblocks

    def code(self) -> Optional[StatusCode]:
        return self._code

    def details(self) -> str:
        return self._details

    def is_active(self) -> bool:
        return not self._finished

    def time_remaining(self) -> Optional[float]:
        if self._deadline is None:
            return None
        return max(0.0, self._deadline - time.monotonic())

    def add_callback(self, callback) -> bool:
        with self._finish_lock:
            if not self._finished:
                self._callbacks.append(callback)
                return True
        return False

    def initial_metadata(self):
        return []

    def trailing_metadata(self):
        return []

    def messages(self) -> Iterator[object]:
        """Call-surface parity: response iteration (UnaryStream callers
        use this name; on this wrapper it IS the iterator)."""
        return self

    def device_ring(self):
        """Call-surface parity: the native loop has no device-ring seam
        (the TPU platform is never fast-path eligible), so callers get
        the documented off-platform answer and fall back to host decode."""
        return None


class StreamStream(_MultiCallable):
    def __call__(self, request_iterator: Iterable,
                 timeout: Optional[float] = None,
                 metadata: Optional[Metadata] = None, **grpcio_kw):
        _reject_call_credentials(grpcio_kw)
        _, timeout, _, wfr, _hedging = self._channel._call_plan(
            self._method, timeout, bool(grpcio_kw.get("wait_for_ready")))
        # Native bidi fast path, same eligibility story as UnaryUnary:
        # plain calls on eligible channels stream through libtpurpc's
        # loop (the duplex/tensor hot path). Callers needing per-call
        # metadata (or queue-until-ready) stay on the Python transport.
        if self._allow_native and not metadata and not wfr:
            nsc = self._try_native_stream(request_iterator, timeout)
            if nsc is not None:
                return nsc
        conn, st, call = self._start(
            metadata, timeout, wait_for_ready=wfr)
        sender = threading.Thread(
            target=self._send_stream, args=(conn, st, request_iterator, call),
            daemon=True)
        sender.start()
        return call


def channel_ready_future(channel: "Channel"):
    """grpc.channel_ready_future analog: a Future resolving (with None)
    once the channel reports READY; get_state(try_to_connect=True) drives
    the dial. Cancel the future to stop waiting early — an abandoned,
    uncancelled future keeps watching only while the channel object stays
    alive (the watcher holds a weakref, so it can't pin the Channel from
    GC or outlive a dropped one)."""
    import concurrent.futures
    import weakref

    fut: "concurrent.futures.Future" = concurrent.futures.Future()
    chref = weakref.ref(channel)

    def watch():
        while not fut.cancelled():
            ch = chref()
            if ch is None:
                return  # channel was dropped; nobody can ever see READY
            state = ch.get_state(try_to_connect=True)
            del ch  # don't pin the channel across the sleep
            if state is ChannelConnectivity.READY:
                if fut.set_running_or_notify_cancel():
                    fut.set_result(None)
                return
            if state is ChannelConnectivity.SHUTDOWN:
                if fut.set_running_or_notify_cancel():
                    fut.set_exception(
                        RpcError(StatusCode.UNAVAILABLE, "channel closed"))
                return
            time.sleep(0.02)

    threading.Thread(target=watch, daemon=True,
                     name="tpurpc-channel-ready").start()
    return fut


def insecure_channel(target: str, **kwargs) -> Channel:
    """grpcio-shaped constructor."""
    return Channel(target, **kwargs)


def secure_channel(target: str, credentials, **kwargs) -> Channel:
    """grpcio-shaped constructor: pass the result of
    :func:`tpurpc.rpc.credentials.ssl_channel_credentials`."""
    return Channel(target, credentials=credentials, **kwargs)
