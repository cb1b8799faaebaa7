"""tpurpc-manycore: shard the server data plane into per-core workers.

Every number the repo produced through PR 6 was single-core physics — PR 3
measured the serving core at 0% idle at depth 1, so 935 QPS was a one-core
ceiling, not a transport ceiling. The scale-out unit here is a worker
PROCESS, which buys three things at once:

* **one poller + ring set per worker, no cross-shard locking, by
  construction** — each worker owns its :class:`~tpurpc.core.poller.Poller`,
  pair pool, rings, thread pool, and batcher in its own address space (the
  RDMAbox lesson, arXiv:2104.12197: per-core queue/MR placement dominates
  throughput for memory-intensive RPC);
* **real core scaling** — CPython's GIL caps what N threads in one process
  can do to the Python framing path; N processes scale with the host;
* **honest failure units** — a shard that crashes takes exactly its own
  connections (clients see UNAVAILABLE and redial onto a live shard) and
  its telemetry VANISHES from the aggregated scrape instead of freezing.

Listener sharding comes in two flavors (the tentpole's part 1):

* ``listener="reuseport"`` (default) — every worker binds the serving port
  with ``SO_REUSEPORT``; the kernel spreads accepted connections across the
  listening workers with no supervisor in the accept path (RDMAvisor's
  shared-daemon multiplexing, arXiv:1802.01870, done by the kernel).
* ``listener="handoff"`` — the supervisor owns the listen socket and passes
  each accepted fd to a worker over its ``SOCK_SEQPACKET`` control channel
  (``SCM_RIGHTS``), round-robin or least-loaded on the workers' streamed
  load reports (the PR 6 load signals: transport in-flight + batcher
  depth). For platforms/hosts where REUSEPORT spread is unavailable or the
  operator wants load-aware placement.

Workers are forked, not spawned: the build callable (with its registered
handlers, model builders, closures) runs post-fork in the child, so
arbitrary servers shard without an import-path contract. The price is
post-fork hygiene — :func:`_postfork_worker_init` rebuilds every process
singleton the child inherited (poller, pair pool, timer wheel, metrics
registry with fresh locks and fleet membership, flight ring, watchdog,
channelz) so the worker starts with ITS truth, not the supervisor's.

Ring sizing is per-shard cache-resident (tentpole part 2): round 5 measured
*smaller* rings running *faster* (the working-set effect), so unless the
operator pins ``TPURPC_SHARD_RING_BUFFER_SIZE_KB``, each worker scales the
configured ring size down by the shard count — N shards share the LLC the
one big ring used to monopolize.

Observability: each worker runs a loopback scrape listener; the supervisor
broadcasts the peer map, and :mod:`tpurpc.obs.shard` makes any worker
answer ``GET /metrics`` (flight, stalls, healthz) with the AGGREGATED,
shard-tagged view. See ARCHITECTURE.md §16.
"""

from __future__ import annotations

import atexit
import itertools
import json
import os
import signal
import socket
import sys
import threading
import time
from typing import Callable, Dict, List, Optional

from tpurpc.analysis.locks import make_lock
from tpurpc.obs import flight as _flight
from tpurpc.utils.trace import TraceFlag

trace_shard = TraceFlag("shard")

_SUP_TAG = _flight.tag_for("shard-supervisor")

#: control-channel message cap (SOCK_SEQPACKET: one recv = one message)
_CTRL_MSG_BYTES = 65536


# ---------------------------------------------------------------------------
# post-fork hygiene
# ---------------------------------------------------------------------------

def _postfork_worker_init(shard_id: int, n_shards: int) -> None:
    """Rebuild inherited process singletons in a freshly forked worker.

    Threads do not survive a fork, but their objects and (worst case) their
    held locks do: every singleton below is REPLACED — fresh lock objects,
    fresh state — rather than reset through machinery that might block on a
    lock a dead thread still holds. Order matters only for config (the ring
    sizing must land before anything reads it)."""
    import weakref

    # 1. per-shard cache-resident rings (round-5 working-set effect): N
    # workers share the LLC one ring used to own — scale the configured
    # size down by the shard count unless the operator pinned one.
    from tpurpc.utils import config as _cfg
    from tpurpc.utils.config import env_lookup

    pinned = env_lookup("TPURPC_SHARD_RING_BUFFER_SIZE_KB")[1]
    if pinned is not None:
        os.environ["TPURPC_RING_BUFFER_SIZE_KB"] = pinned
    elif n_shards > 1:
        base = _cfg.Config.from_env().ring_buffer_size_kb
        os.environ["TPURPC_RING_BUFFER_SIZE_KB"] = str(
            max(256, base // n_shards))
    _cfg.set_config(None)

    # 2. transport singletons: fresh locks, no inherited instances
    from tpurpc.core.poller import PairPool, Poller

    Poller._instance_lock = make_lock("Poller._instance_lock")
    Poller._instance = None
    PairPool._instance_lock = make_lock("PairPool._instance_lock")
    PairPool._instance = None

    from tpurpc.utils import timers as _timers

    _timers.TimerWheel._instance_lock = make_lock(
        "TimerWheel._instance_lock")
    _timers.TimerWheel._instance = None

    # 3. telemetry: this worker's registry must describe THIS worker.
    # Counters zero; fleet gauges drop the supervisor's (inert, forked)
    # objects — the weakref-death contract, enforced at the fork boundary.
    from tpurpc.obs import metrics as _metrics

    reg = _metrics.registry()
    reg._lock = make_lock("MetricsRegistry._lock")
    for m in reg.metrics().values():
        if isinstance(m, _metrics.FleetGauge):
            m._lock = make_lock("FleetGauge._lock")
            m._refs = weakref.WeakSet()
            continue
        if hasattr(m, "_lock"):
            m._lock = make_lock("Metric._lock")
        m.reset()

    from tpurpc.obs import profiler as _profiler
    from tpurpc.obs import shard as _obs_shard
    from tpurpc.obs import watchdog as _watchdog

    _flight.postfork_restart()
    _watchdog.postfork_reset()
    _profiler.postfork_reset()  # tpurpc-lens: supervisor samples are not ours
    # tpurpc-argus: the inherited tsdb rings hold the SUPERVISOR's history
    # and the slo evaluator thread died in the fork — fresh instances
    # (Server.start in the worker's build restarts both)
    try:
        from tpurpc.obs import slo as _slo
        from tpurpc.obs import tsdb as _tsdb

        _tsdb.postfork_reset()
        _slo.postfork_reset()
    except Exception:
        pass
    # tpurpc-odyssey: the inherited sequence ledgers are the supervisor's
    try:
        from tpurpc.obs import odyssey as _ody

        _ody.postfork_reset()
    except Exception:
        pass
    _obs_shard.set_identity(shard_id, n_shards)

    from tpurpc.rpc import channelz as _channelz

    _channelz._lock = make_lock("channelz._lock")
    _channelz._servers = weakref.WeakSet()
    _channelz._channels = weakref.WeakSet()

    try:  # tracing buffers: supervisor spans are not this worker's
        from tpurpc.obs import tracing as _tracing

        _tracing._lock = make_lock("tracing._lock")
        _tracing._pending = {}
        _tracing._spans.clear()
    except Exception:
        pass


# ---------------------------------------------------------------------------
# worker main (runs in the forked child, never returns)
# ---------------------------------------------------------------------------

def _ctrl_send(ctrl: socket.socket, obj: dict) -> None:
    try:
        ctrl.send(json.dumps(obj).encode())
    except OSError:
        pass  # supervisor gone; the worker lives until told otherwise


def _worker_main(ctrl: socket.socket, shard_id: int, n_shards: int,
                 build: Callable[[int], object], mode: str,
                 host: str, port: int) -> None:
    _postfork_worker_init(shard_id, n_shards)
    try:
        srv = build(shard_id)
        srv.start()
        bound = None
        if mode == "reuseport":
            bound = srv.add_insecure_port(f"{host}:{port}", reuseport=True)
        from tpurpc.obs import scrape as _scrape

        _http, scrape_port = _scrape.start_http_server()
    except Exception as exc:
        _ctrl_send(ctrl, {"fatal": repr(exc)})
        os._exit(1)
    _flight.emit(_flight.SHARD_START, 0, shard_id, n_shards)
    _ctrl_send(ctrl, {"ready": shard_id, "scrape_port": scrape_port,
                      "port": bound, "pid": os.getpid()})

    def _load() -> int:
        n = srv.inflight_requests()
        extra = getattr(srv, "_load_extra", None)
        if extra is not None:
            try:
                n += int(extra())
            except Exception:
                pass
        return n

    ctrl.settimeout(0.05)
    last_load = -1
    while True:
        try:
            data, fds, _flags, _addr = socket.recv_fds(
                ctrl, _CTRL_MSG_BYTES, 4)
        except (TimeoutError, socket.timeout):
            # idle tick: stream the load signal (the handoff picker's feed;
            # only deltas, so an idle worker costs one int compare)
            load = _load()
            if load != last_load:
                last_load = load
                _ctrl_send(ctrl, {"load": load})
            continue
        except OSError:
            data, fds = b"", []
        if not data:
            # supervisor died: a headless worker must not linger holding
            # the port — exit and let clients re-dial whatever replaces us
            _flight.emit(_flight.SHARD_EXIT, 0, shard_id)
            os._exit(0)
        try:
            msg = json.loads(data)
        except ValueError:
            msg = {}
        if msg.get("handoff") and fds:
            for fd in fds:
                try:
                    srv.adopt_socket(socket.socket(
                        socket.AF_INET, socket.SOCK_STREAM, fileno=fd))
                except OSError:
                    try:
                        os.close(fd)
                    except OSError:
                        pass
        elif "peers" in msg:
            from tpurpc.obs import shard as _obs_shard

            _obs_shard.set_peers(
                {int(k): int(v) for k, v in msg["peers"].items()})
        elif "drain" in msg:
            linger = float(msg["drain"])

            def _drain():
                ok = srv.drain(linger)
                _ctrl_send(ctrl, {"drained": shard_id, "clean": bool(ok)})

            threading.Thread(target=_drain, daemon=True,
                             name="tpurpc-shard-drain").start()
        elif "stop" in msg:
            grace = msg.get("stop")
            try:
                srv.stop(grace if isinstance(grace, (int, float)) else None)
            except Exception:
                pass
            _flight.emit(_flight.SHARD_EXIT, 0, shard_id)
            _ctrl_send(ctrl, {"bye": shard_id})
            os._exit(0)


# ---------------------------------------------------------------------------
# the supervisor
# ---------------------------------------------------------------------------

class _Worker:
    __slots__ = ("shard_id", "pid", "ctrl", "alive", "scrape_port",
                 "load", "stopping", "drained")

    def __init__(self, shard_id: int, pid: int, ctrl: socket.socket):
        self.shard_id = shard_id
        self.pid = pid
        self.ctrl = ctrl
        self.alive = True
        self.scrape_port: Optional[int] = None
        self.load = 0
        self.stopping = False
        self.drained = False


class ShardedServer:
    """Supervisor for N per-core worker processes serving ONE port.

    ``build(shard_id) -> Server`` runs IN THE WORKER after the fork: it
    constructs and registers (but does not start) the shard's server —
    handlers, batchers, admission gates, anything. The supervisor itself
    stays thin: bind, fork, broadcast the peer map, monitor, and (handoff
    mode) spread accepted fds.

    Lifecycle: :meth:`start` → traffic → optional :meth:`drain` →
    :meth:`stop`. :meth:`kill_worker` is the chaos-test face (SIGKILL one
    shard; survivors keep serving and the aggregated scrape drops the dead
    shard's series).
    """

    def __init__(self, build: Callable[[int], object], workers: int = 2,
                 address: str = "127.0.0.1:0", *,
                 listener: str = "reuseport",
                 handoff_policy: str = "round_robin"):
        if listener not in ("reuseport", "handoff"):
            raise ValueError(f"unknown listener mode {listener!r}")
        if handoff_policy not in ("round_robin", "least_loaded"):
            raise ValueError(f"unknown handoff policy {handoff_policy!r}")
        self.build = build
        self.n_workers = max(1, int(workers))
        host, _, port = address.rpartition(":")
        self.host = host or "127.0.0.1"
        self._want_port = int(port)
        self.listener = listener
        self.handoff_policy = handoff_policy
        self.port: Optional[int] = None
        self._workers: List[_Worker] = []
        self._lock = make_lock("ShardedServer._lock")
        self._stopping = False
        self._started = False
        self._reserve: Optional[socket.socket] = None
        self._listen: Optional[socket.socket] = None
        self._accept_thread: Optional[threading.Thread] = None
        self._rr = itertools.count()
        self._fatal: Optional[str] = None

    # -- lifecycle ------------------------------------------------------------

    def start(self, ready_timeout: float = 60.0) -> "ShardedServer":
        if self._started:
            return self
        self._started = True
        if self.listener == "reuseport":
            # reserve the port number before forking: a bound-not-listening
            # REUSEPORT socket pins the port (the kernel only routes among
            # LISTENING sockets, so it never receives a connection)
            self._reserve = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            self._reserve.setsockopt(socket.SOL_SOCKET,
                                     socket.SO_REUSEPORT, 1)
            self._reserve.bind((self.host, self._want_port))
            self.port = self._reserve.getsockname()[1]
        else:
            self._listen = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            self._listen.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            self._listen.bind((self.host, self._want_port))
            self._listen.listen(128)
            self.port = self._listen.getsockname()[1]
        for i in range(self.n_workers):
            self._spawn(i)
        atexit.register(self._atexit_kill)
        self._await_ready(ready_timeout)
        self._broadcast_peers()
        if self.listener == "handoff":
            self._accept_thread = threading.Thread(
                target=self._accept_loop, daemon=True,
                name="tpurpc-shard-accept")
            self._accept_thread.start()
        return self

    def _spawn(self, shard_id: int) -> None:
        # SEQPACKET: every control message (and every SCM_RIGHTS handoff)
        # arrives whole — no framing layer, no fd/payload pairing races
        parent_end, child_end = socket.socketpair(socket.AF_UNIX,
                                                  socket.SOCK_SEQPACKET)
        sys.stdout.flush()
        sys.stderr.flush()
        pid = os.fork()
        if pid == 0:
            # -- child: never returns, never runs the parent's atexit --
            try:
                parent_end.close()
                for s in (self._reserve, self._listen):
                    if s is not None:
                        s.close()
                for w in self._workers:  # siblings' control fds
                    try:
                        w.ctrl.close()
                    except OSError:
                        pass
                _worker_main(child_end, shard_id, self.n_workers, self.build,
                             self.listener, self.host, self.port)
            except BaseException:
                pass
            finally:
                os._exit(1)
        child_end.close()
        w = _Worker(shard_id, pid, parent_end)
        with self._lock:
            self._workers.append(w)
        threading.Thread(target=self._monitor, args=(w,), daemon=True,
                         name=f"tpurpc-shard-mon-{shard_id}").start()

    def _await_ready(self, timeout: float) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self._fatal is not None:
                self.stop()
                raise RuntimeError(f"shard worker failed: {self._fatal}")
            with self._lock:
                ready = [w for w in self._workers
                         if w.scrape_port is not None]
                if len(ready) == self.n_workers:
                    return
            time.sleep(0.01)
        self.stop()
        raise TimeoutError("shard workers did not report ready")

    def _monitor(self, w: _Worker) -> None:
        """One blocking reader per worker control socket: loads, acks, and
        — on EOF — the death path."""
        while True:
            try:
                data = w.ctrl.recv(_CTRL_MSG_BYTES)
            except OSError:
                data = b""
            if not data:
                break
            try:
                msg = json.loads(data)
            except ValueError:
                continue
            if "ready" in msg:
                w.scrape_port = int(msg["scrape_port"])
            elif "load" in msg:
                w.load = int(msg["load"])
            elif "fatal" in msg:
                self._fatal = str(msg["fatal"])
            elif "drained" in msg:
                w.drained = True
            # "bye" needs no action: the stop() path reaps by pid
        self._reap(w)

    def _reap(self, w: _Worker) -> None:
        status = 0
        try:
            _pid, status = os.waitpid(w.pid, 0)
        except ChildProcessError:
            pass
        died = False
        with self._lock:
            if w.alive:
                w.alive = False
                died = not w.stopping and not self._stopping
        if died and w.scrape_port is None and self._fatal is None:
            # never reported ready: start() must fail now, with the reason,
            # not after its ready timeout (an abort inside a native runtime
            # sends no "fatal" message)
            self._fatal = (f"shard {w.shard_id} (pid {w.pid}) died during "
                           f"start-up, wait status {status}")
        if died:
            # tpurpc-manycore death contract: the shard's connections are
            # gone (clients got UNAVAILABLE and re-dial onto live shards —
            # in reuseport mode the kernel stopped routing to the closed
            # socket the instant the process died); telemetry-wise the
            # shard must DROP OUT, so survivors get a peer map without it.
            _flight.emit(_flight.SHARD_DEATH, _SUP_TAG, w.shard_id, status)
            trace_shard.log("shard %d died (status %d)", w.shard_id, status)
            self._broadcast_peers()

    # -- peer map -------------------------------------------------------------

    def scrape_ports(self) -> Dict[int, int]:
        with self._lock:
            return {w.shard_id: w.scrape_port for w in self._workers
                    if w.alive and w.scrape_port is not None}

    def _broadcast_peers(self) -> None:
        peers = self.scrape_ports()
        payload = {"peers": peers}
        with self._lock:
            targets = [w for w in self._workers if w.alive]
        for w in targets:
            _ctrl_send(w.ctrl, payload)

    # -- handoff accept spread ------------------------------------------------

    def _pick_worker(self) -> Optional[_Worker]:
        with self._lock:
            alive = [w for w in self._workers if w.alive]
        if not alive:
            return None
        if self.handoff_policy == "least_loaded":
            # PR 6 load signals, streamed over the control channel: place
            # the connection where the least work is queued (ties rotate)
            best = min(w.load for w in alive)
            alive = [w for w in alive if w.load == best]
        return alive[next(self._rr) % len(alive)]

    def _accept_loop(self) -> None:
        self._listen.settimeout(0.2)
        while not self._stopping:
            try:
                sock, _addr = self._listen.accept()
            except (TimeoutError, socket.timeout):
                continue
            except OSError:
                if self._stopping:
                    return
                time.sleep(0.05)
                continue
            handed = False
            for _attempt in range(self.n_workers):
                w = self._pick_worker()
                if w is None:
                    break
                try:
                    socket.send_fds(w.ctrl, [b'{"handoff": 1}'],
                                    [sock.fileno()])
                    _flight.emit(_flight.CONN_HANDOFF, _SUP_TAG, w.shard_id)
                    handed = True
                    break
                except OSError:
                    continue  # racing a worker death: try another
            sock.close()  # worker holds its own duplicate (or nobody: RST)
            if not handed:
                trace_shard.log("handoff: no live worker for connection")

    # -- operator face --------------------------------------------------------

    def alive_workers(self) -> List[int]:
        with self._lock:
            return [w.shard_id for w in self._workers if w.alive]

    def worker_pid(self, shard_id: int) -> Optional[int]:
        with self._lock:
            for w in self._workers:
                if w.shard_id == shard_id:
                    return w.pid
        return None

    def kill_worker(self, shard_id: int, sig: int = signal.SIGKILL) -> bool:
        """Chaos face: kill one shard. Returns False if it wasn't running."""
        with self._lock:
            target = next((w for w in self._workers
                           if w.shard_id == shard_id and w.alive), None)
        if target is None:
            return False
        try:
            os.kill(target.pid, sig)
        except ProcessLookupError:
            return False
        return True

    def drain(self, linger: float = 5.0) -> None:
        """Broadcast a graceful drain (PR 6 semantics, per worker)."""
        with self._lock:
            targets = [w for w in self._workers if w.alive]
        for w in targets:
            _ctrl_send(w.ctrl, {"drain": linger})

    def stop(self, grace: Optional[float] = None,
             timeout: float = 10.0) -> None:
        self._stopping = True
        with self._lock:
            targets = list(self._workers)
        for w in targets:
            w.stopping = True
            _ctrl_send(w.ctrl, {"stop": grace})
        deadline = time.monotonic() + timeout
        for w in targets:
            while w.alive and time.monotonic() < deadline:
                time.sleep(0.01)
            if w.alive:
                try:
                    os.kill(w.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
            try:
                w.ctrl.close()
            except OSError:
                pass
        for s in (self._reserve, self._listen):
            if s is not None:
                try:
                    s.close()
                except OSError:
                    pass
        self._reserve = self._listen = None

    def _atexit_kill(self) -> None:
        """Last-resort reaper: a crashed test/supervisor must not leak
        worker processes holding the port."""
        with self._lock:
            targets = [w for w in self._workers if w.alive]
        for w in targets:
            try:
                os.kill(w.pid, signal.SIGKILL)
            except (ProcessLookupError, OSError):
                pass
