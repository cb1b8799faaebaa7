"""tpurpc-scope: the unified telemetry subsystem (ISSUE 4).

Three faces over one always-on core:

* :mod:`tpurpc.obs.metrics` — the process-wide metrics registry. Counters
  are plain-int, GIL-atomic bumps (branch-free on the hot path); batch/
  latency histograms amortize one lock per *batch*; state gauges are
  evaluated at SCRAPE time over weakly-referenced live objects (fleet
  gauges), so idle-state observability costs the hot path nothing.
* :mod:`tpurpc.obs.tracing` — per-RPC span timelines with a trace context
  (trace_id / span_id / sampled bit) carried in call metadata
  client→server→batcher→device on both the Python and native planes.
  Sampling defaults OFF; the whole plane is behind one module-global gate.
* :mod:`tpurpc.obs.scrape` — the introspection plane: a Prometheus-text
  endpoint served in-process on every :class:`tpurpc.rpc.server.Server`
  port (the protocol sniff answers plain ``GET /metrics``), feeding the
  registry, the copy ledger, and channelz; ``python -m tpurpc.tools.top``
  renders it live.

tpurpc-blackbox (ISSUE 5) adds the POSTMORTEM faces on top:

* :mod:`tpurpc.obs.flight` — an always-on, fixed-size binary ring of
  structured transport events (stall/starvation edges, lease lifecycle,
  poller mode flips, window exhaustion, deadline expiry, peer death) with
  a preallocated lock-free encoder; dump via ``GET /debug/flight``,
  ``SIGUSR2``, or automatically on watchdog trip.
* :mod:`tpurpc.obs.watchdog` — a stall sweeper over the in-flight-RPC
  registry that names the blocked STAGE (credit starvation / poller wake /
  h2 flow control / batcher wait / device infer / peer-not-reading) from
  the flight tail + fleet gauges; served at ``GET /debug/stalls`` and
  reflected in ``/healthz``.
* tail-based trace capture (in :mod:`tpurpc.obs.tracing`) — every RPC gets
  a provisional span buffer regardless of sample rate, committed iff the
  call was slow, errored, or watchdog-flagged: ``TPURPC_TRACE_SAMPLE=0``
  still yields a full span tree for every pathological call.

tpurpc-lens (ISSUE 8) adds the PERFORMANCE-ATTRIBUTION faces:

* :mod:`tpurpc.obs.profiler` — a continuous stage-tagged sampling
  profiler: thread stacks sampled at ~50 Hz and mapped to pipeline stages
  via a static frame-marker registry; per-stage shares + collapsed stacks
  at ``GET /debug/profile``.
* :mod:`tpurpc.obs.lens` — the byte-flow waterfall: per-hop (device →
  send ring → wire → peer ring → decode → hbm → jax.Array) bytes/busy-ns
  counters whose scrape-time ratio is each hop's effective GB/s; the
  argmin names the bottleneck. ``GET /debug/waterfall``.
* ``python -m tpurpc.tools.timeline`` — one Perfetto trace for a whole
  deployment: spans + flight edges + CPU samples from every shard/fleet
  member, aligned on per-process monotonic↔wall clock anchors.

tpurpc-argus (ISSUE 14) adds the TIME and FLEET dimensions:

* :mod:`tpurpc.obs.tsdb` — a bounded in-process ring time-series store:
  a background sampler snapshots the registry into preallocated
  two-tier rings (~1 s grain for minutes, ~15 s for the hour);
  ``rate()`` / ``quantile_over_time()`` / ``window()`` queries at
  ``GET /debug/history``.
* :mod:`tpurpc.obs.slo` — declared availability/latency objectives
  evaluated as multi-window multi-burn-rate alerts over the tsdb
  (pending→firing→resolved; admission sheds burn a separate budget);
  ``GET /debug/slo``, flight fire/resolve events, watchdog bridge,
  degraded ``/healthz``.
* :mod:`tpurpc.obs.collector` — a standalone fleet collector polling
  every member's existing routes and serving merged, member-labeled
  ``/fleet/metrics`` + ``/fleet/slo`` + ``/fleet/timeline`` (stale
  members' series vanish; counter resets clamped).
* :mod:`tpurpc.obs.bundle` — automatic evidence capture: a firing alert
  or watchdog trip writes a rate-limited, size-capped postmortem bundle
  (flight dump, tail traces, profile, waterfall, tsdb window) that
  ``python -m tpurpc.analysis protocol --flight`` replays unmodified.

The reference fork's whole debugging story was trace flags plus a
shutdown-time profiler table (SURVEY.md §5, ``stats_time.cc``); tpurpc-scope
replaces post-hoc printf with always-on, near-free telemetry, tpurpc-blackbox
makes the rare-event failures it samples away recoverable after the fact,
tpurpc-lens says where the cycles and bytes actually go, and tpurpc-argus
answers over time and across members — then writes the postmortem itself.
"""

from tpurpc.obs import flight, lens, metrics, profiler, tracing  # noqa: F401
from tpurpc.obs import native_obs  # noqa: F401  (registers its collector)

__all__ = ["flight", "lens", "metrics", "profiler", "tracing"]
