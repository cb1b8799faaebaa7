"""tpurpc-top: a terminal dashboard over the introspection plane.

Polls a tpurpc process's Prometheus endpoint (any serving port answers
``GET /metrics`` — see tpurpc/obs/scrape.py) and renders live QPS, handler
latency percentiles, ring occupancy/credits, pipelined-window depth, the
fan-in batcher's batch-size/flush-reason profile, and — tpurpc-blackbox
(ISSUE 5) — a stalls/anomalies pane fed by ``/debug/stalls`` (active
watchdog diagnoses with their attributed stage, plus the trip counters),
and — tpurpc-odyssey (ISSUE 15) — a ``seq`` pane fed by ``/debug/seq``
(top sequences by device step-ms and KV byte-seconds, per-account cost
rollup), and — tpurpc-xray (ISSUE 19) — a ``natv`` pane from the
``native_*`` series the scrape mirrors out of the C core's shm metrics
table (rdv ledger, ctrl drain cadence, fallbacks, pin/delivery pressure),
and — tpurpc-oracle (ISSUE 20) — a ``diag`` pane fed by
``/debug/diagnose``: when a symptom is active, the top ranked cause with
confidence and the suggested action.

    python -m tpurpc.tools.top HOST:PORT [--interval 1.0] [--once]

``--once`` prints a single snapshot (no screen clearing) — what the CI
metrics smoke and scripts use. When stdout is not a TTY (CI logs, pipes),
one-shot mode is the automatic default: no ANSI clears in captured logs.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
import time
import urllib.request
from typing import Dict, Optional, Tuple

_LINE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>[^}]*)\})?\s+(?P<value>[-+0-9.eE]+|NaN)$")


def parse_prometheus(text: str) -> Dict[Tuple[str, str], float]:
    """{(name, labels): value} for every sample line (types ignored)."""
    out: Dict[Tuple[str, str], float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        m = _LINE.match(line)
        if m is None:
            continue
        try:
            out[(m.group("name"), m.group("labels") or "")] = float(
                m.group("value"))
        except ValueError:
            continue
    return out


def fetch(target: str, timeout: float = 5.0) -> Dict[Tuple[str, str], float]:
    url = f"http://{target}/metrics"
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return parse_prometheus(resp.read().decode("utf-8", "replace"))


def fetch_stalls(target: str, timeout: float = 5.0) -> Optional[dict]:
    """The watchdog's /debug/stalls snapshot, or None when unreachable /
    pre-blackbox server (the dashboard degrades to 'n/a', never dies)."""
    try:
        with urllib.request.urlopen(f"http://{target}/debug/stalls",
                                    timeout=timeout) as resp:
            return json.loads(resp.read().decode("utf-8", "replace"))
    except Exception:
        return None


def fetch_waterfall(target: str, timeout: float = 5.0) -> Optional[dict]:
    """tpurpc-lens /debug/waterfall (per-hop effective GB/s), or None when
    unreachable / pre-lens server."""
    try:
        with urllib.request.urlopen(f"http://{target}/debug/waterfall",
                                    timeout=timeout) as resp:
            return json.loads(resp.read().decode("utf-8", "replace"))
    except Exception:
        return None


def fetch_slo(target: str, timeout: float = 5.0) -> Optional[dict]:
    """tpurpc-argus /debug/slo (objectives + burn-rate alert states), or
    None when unreachable / pre-argus server."""
    try:
        with urllib.request.urlopen(f"http://{target}/debug/slo",
                                    timeout=timeout) as resp:
            return json.loads(resp.read().decode("utf-8", "replace"))
    except Exception:
        return None


def fetch_seq(target: str, timeout: float = 5.0) -> Optional[dict]:
    """tpurpc-odyssey /debug/seq (per-sequence cost ledgers + account
    rollup), or None when unreachable / pre-odyssey server."""
    try:
        with urllib.request.urlopen(f"http://{target}/debug/seq",
                                    timeout=timeout) as resp:
            return json.loads(resp.read().decode("utf-8", "replace"))
    except Exception:
        return None


def fetch_diagnose(target: str, timeout: float = 5.0) -> Optional[dict]:
    """tpurpc-oracle /debug/diagnose (ranked causal hypotheses for the
    active symptom), or None when unreachable / pre-oracle server."""
    try:
        with urllib.request.urlopen(f"http://{target}/debug/diagnose",
                                    timeout=timeout) as resp:
            return json.loads(resp.read().decode("utf-8", "replace"))
    except Exception:
        return None


def _val(m: Dict, name: str, labels: str = "") -> float:
    return m.get((name, labels), 0.0)


def _sum_label(m: Dict, name: str, needle: str = "") -> float:
    return sum(v for (n, lab), v in m.items()
               if n == name and needle in lab)


def _fmt_us(us: float) -> str:
    if us >= 1e6:
        return f"{us / 1e6:.2f}s"
    if us >= 1e3:
        return f"{us / 1e3:.1f}ms"
    return f"{us:.0f}us"


def render(cur: Dict, prev: Optional[Dict], dt: float,
           target: str, stalls: Optional[dict] = None,
           waterfall: Optional[dict] = None,
           slo: Optional[dict] = None,
           seq: Optional[dict] = None,
           diagnose: Optional[dict] = None) -> str:
    P = "tpurpc_"
    Q50 = 'quantile="0.5"'
    Q99 = 'quantile="0.99"'
    lines = []
    lines.append(f"tpurpc-top — {target} — {time.strftime('%H:%M:%S')}")
    lines.append("=" * 64)

    def rate(name: str, labels: str = "") -> float:
        if prev is None or dt <= 0:
            return 0.0
        return max(0.0, (_val(cur, name, labels)
                         - _val(prev, name, labels))) / dt

    # QPS from channelz call counters (sum across entities)
    def crate(kind: str) -> float:
        if prev is None or dt <= 0:
            return 0.0
        name = P + "channelz_calls"
        now = sum(v for (n, lab), v in cur.items()
                  if n == name and f'kind="{kind}"' in lab)
        was = sum(v for (n, lab), v in (prev or {}).items()
                  if n == name and f'kind="{kind}"' in lab)
        return max(0.0, now - was) / dt

    lines.append(f"rpc   qps {crate('started'):8.1f}   "
                 f"ok/s {crate('succeeded'):8.1f}   "
                 f"fail/s {crate('failed'):6.1f}   "
                 f"streams {int(_sum_label(cur, P + 'channelz_streams')):4d}")
    lines.append(
        f"lat   srv p50 {_fmt_us(_val(cur, P + 'srv_call_us', Q50)):>8}  "
        f"p99 {_fmt_us(_val(cur, P + 'srv_call_us', Q99)):>8}   "
        f"pipe p50 {_fmt_us(_val(cur, P + 'pipeline_call_us', Q50)):>8}  "
        f"p99 {_fmt_us(_val(cur, P + 'pipeline_call_us', Q99)):>8}")
    lines.append(
        f"ring  in-flight {int(_val(cur, P + 'ring_in_flight_bytes')):>10}B  "
        f"unpub-credit {int(_val(cur, P + 'ring_credit_unpublished_bytes')):>8}B  "
        f"msgs/s in {rate(P + 'ring_msgs_read'):8.0f} "
        f"out {rate(P + 'ring_msgs_written'):8.0f}")
    lines.append(
        f"pipe  in-flight {int(_val(cur, P + 'pipeline_inflight')):>4} over "
        f"{int(_val(cur, P + 'pipeline_inflight_objects')):>3} windows   "
        f"pairs {int(_val(cur, P + 'pairs_connected')):>3} "
        f"(stalled {int(_val(cur, P + 'pairs_write_stalled'))})")
    lines.append(
        f"wake  spin-hit/s {rate(P + 'wait_spin_hit'):7.0f}  "
        f"spin-miss/s {rate(P + 'wait_spin_miss'):7.0f}  "
        f"sleep/s {rate(P + 'wait_sleep'):7.0f}")
    lines.append(
        f"batch fanin p50 {int(_val(cur, P + 'fanin_batch', Q50)):>3}  "
        f"p99 {int(_val(cur, P + 'fanin_batch', Q99)):>3}  "
        f"rows/s {rate(P + 'batcher_rows'):8.0f}  "
        "flush size/timer/drained "
        f"{int(_val(cur, P + 'batcher_flush_size'))}/"
        f"{int(_val(cur, P + 'batcher_flush_timer'))}/"
        f"{int(_val(cur, P + 'batcher_flush_drained'))}")
    lines.append(
        f"coal  resp p50 {int(_val(cur, P + 'resp_coalesce', Q50)):>3}  "
        f"h2-data p50 {int(_val(cur, P + 'h2_data_coalesce', Q50)):>3}   "
        f"drain p50 {int(_val(cur, P + 'ring_drain', Q50)):>3} "
        f"msgs/wakeup")
    led = {k[1]: v for k, v in cur.items() if k[0] == P + "ledger_bytes"}
    if led:
        hc = led.get('kind="host_copy"', 0)
        zc = led.get('kind="zero_copy"', 0)
        lines.append(f"copy  host {int(hc):>12}B   zero-copy {int(zc):>12}B")
    # tpurpc-xray native-plane pane (ISSUE 19): the native_* series the
    # scrape mirrors out of the C core's shm metrics table — rdv ledger,
    # ctrl-ring drain cadence, fallbacks, pin/delivery pressure. Absent
    # (emitted == 0) on python-plane-only processes.
    if _val(cur, P + "native_emitted") > 0:
        lines.append(
            f"natv  rdv sent "
            f"{int(_val(cur, P + 'native_rdv_send_bytes')):>12}B  recv "
            f"{int(_val(cur, P + 'native_rdv_recv_bytes')):>12}B  "
            f"waits {int(_val(cur, P + 'native_rdv_waits'))}  "
            f"fallbacks {int(_val(cur, P + 'native_rdv_fallbacks'))}")
        lines.append(
            f"      ctrl drains/s {rate(P + 'native_ctrl_drain_batches'):7.0f} "
            f"({rate(P + 'native_ctrl_drain_records'):8.0f} rec/s)  "
            f"posts/s {rate(P + 'native_ctrl_posts'):7.0f}  "
            f"kicks/s {rate(P + 'native_ctrl_kicks'):5.0f}  "
            f"frames {int(_val(cur, P + 'native_ctrl_frames'))}")
        lines.append(
            f"      pin-waits {int(_val(cur, P + 'native_pin_waits'))} "
            f"({_fmt_us(_val(cur, P + 'native_pin_wait_ns') / 1e3):>7})  "
            f"dlv depth {int(_val(cur, P + 'native_dlv_depth')):>4} "
            f"stalls {int(_val(cur, P + 'native_dlv_stalls'))}  conns "
            f"{int(_val(cur, P + 'native_conn_up') - _val(cur, P + 'native_conn_down'))}")
    # tpurpc-blackbox stalls/anomalies pane (/debug/stalls + trip counters)
    trips = int(_val(cur, P + "watchdog_trips"))
    errs = int(_sum_label(cur, P + "deadline_exceeded"))
    if stalls is None:
        lines.append(f"stall n/a (no /debug/stalls)   trips {trips}   "
                     f"deadline-exceeded {errs}")
    else:
        active = stalls.get("active", [])
        lines.append(
            f"stall active {len(active)}   in-flight "
            f"{stalls.get('inflight', 0)}   trips {trips}   "
            f"deadline-exceeded {errs}")
        for d in active[:3]:
            lines.append(
                f"  !! {d.get('kind', '?'):>6} {d.get('method', '?'):<28} "
                f"{d.get('age_s', 0):>7.2f}s  {d.get('stage', '?')}")
    # tpurpc-lens byte-flow waterfall pane (/debug/waterfall): per-hop
    # effective GB/s, slowest hop flagged — the streaming-gap instrument
    if waterfall is not None:
        hops = [r for r in waterfall.get("hops", ()) if r.get("bytes")]
        slow = waterfall.get("slowest_hop")
        if hops:
            cells = "  ".join(
                f"{r['hop']} {r['gbps']:.2f}" + ("*" if r["hop"] == slow
                                                 else "")
                for r in hops)
            lines.append(f"flow  GB/s by hop: {cells}")
            # the second clock: of a hop's busy time, what its threads
            # spent on a core (the rest: a wait, or the line for the
            # interpreter; lens.py says which by kind of stage)
            lines.append("      cpu/busy ms: " + "  ".join(
                f"{r['hop']} {r.get('cpu_ms', 0.0):.0f}/{r['busy_ms']:.0f}"
                for r in hops))
            if slow:
                lines.append(f"      slowest hop: {slow} "
                             "(* = the hop to attack)")
        obs = waterfall.get("observers")
        if obs:  # what the obs/ loops' own threads take (lens.waterfall)
            lines.append(
                f"      observers: {obs['cpu_ms']:.0f} ms cpu in "
                f"{obs['ticks']} ticks ({obs['us_a_tick']:.0f} us a tick)")
    # tpurpc-argus SLO alerts pane (/debug/slo): objective/track states
    # with burn rates — the page an operator would get, rendered live
    if slo is not None:
        objs = slo.get("objectives", ())
        if objs:
            n_fire = len(slo.get("firing", ()))
            lines.append(f"slo   objectives {len(objs)}   firing {n_fire}")
            for obj in objs:
                for track, st in sorted((obj.get("tracks") or {}).items()):
                    state = st.get("state", "ok")
                    if state == "ok" and not st.get("fired"):
                        continue
                    mark = "!!" if state == "firing" else \
                        " !" if state == "pending" else "  "
                    lines.append(
                        f"  {mark} {obj.get('name', '?'):<20} "
                        f"{track:<8} {state:<8} "
                        f"burn {st.get('burn_fast', 0):>6.1f}x fast "
                        f"{st.get('burn_slow', 0):>6.1f}x slow  "
                        f"fired {st.get('fired', 0)}")
    # tpurpc-odyssey sequence pane (/debug/seq): top sequences by device
    # step-ms and KV byte-seconds, plus the per-account cost rollup — the
    # "whose sequences own the device" view
    if seq is not None and seq.get("enabled"):
        live = seq.get("live", ())
        att = seq.get("attributed_pct")
        lines.append(
            f"seq   live {seq.get('live_total', len(live))}   "
            f"step-time attributed "
            f"{att if att is not None else 'n/a'}%")
        rows = sorted(list(live) + list(seq.get("recent", ()))[:8],
                      key=lambda r: r.get("step_us", 0), reverse=True)
        for r in rows[:4]:
            lines.append(
                f"   #{r.get('sid', '?'):<5} {r.get('account', '?'):<14} "
                f"{r.get('state', '?'):<9} tok {r.get('tokens', 0):>4}  "
                f"step {r.get('step_us', 0) / 1e3:>8.1f}ms  "
                f"kv {r.get('kv_byte_s', 0):>8.1f}B·s  "
                f"swap {r.get('swap_byte_s', 0):>6.1f}B·s")
        accounts = seq.get("accounts") or {}
        for name in sorted(accounts,
                           key=lambda a: -accounts[a].get("step_us", 0))[:4]:
            b = accounts[name]
            lines.append(
                f"   @{name:<14} seqs {int(b.get('seqs', 0)):>4}  "
                f"tok {int(b.get('tokens', 0)):>6}  "
                f"step {b.get('step_us', 0) / 1e3:>8.1f}ms  "
                f"kv {b.get('kv_byte_s', 0):>8.1f}B·s  "
                f"preempt {int(b.get('preempts', 0))}  "
                f"mig {int(b.get('migrations', 0))}")
    # tpurpc-oracle diagnosis pane (/debug/diagnose): when any symptom is
    # active, the top ranked cause with its confidence and the action
    # hint — the "why", one line under all the "what" panes above
    if diagnose is not None and diagnose.get("enabled"):
        sym = diagnose.get("symptom") or {}
        hyps = diagnose.get("hypotheses") or []
        if sym.get("stage") and hyps:
            top = hyps[0]
            lines.append(
                f"diag  symptom {sym.get('stage', '?'):<22} "
                f"-> {top.get('cause', '?'):<22} "
                f"conf {top.get('confidence', 0):.2f}  "
                f"({len(top.get('evidence', ()))} evidence, "
                f"{len(hyps)} hypotheses)")
            act = top.get("actionable")
            if act:
                lines.append(f"      action: {act}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="tpurpc.tools.top")
    ap.add_argument("target", help="HOST:PORT of any tpurpc serving port")
    ap.add_argument("--interval", type=float, default=1.0)
    ap.add_argument("--once", action="store_true",
                    help="print one snapshot and exit (automatic when "
                         "stdout is not a TTY — CI/pipe safe)")
    args = ap.parse_args(argv)
    if not args.once and not sys.stdout.isatty():
        args.once = True  # non-TTY: never emit ANSI clears into a log

    prev: Optional[Dict] = None
    t_prev = time.monotonic()
    while True:
        try:
            cur = fetch(args.target)
        except OSError as exc:
            print(f"tpurpc-top: {args.target} unreachable: {exc}",
                  file=sys.stderr)
            return 1
        stalls = fetch_stalls(args.target)
        wf = fetch_waterfall(args.target)
        slo = fetch_slo(args.target)
        seq = fetch_seq(args.target)
        diag = fetch_diagnose(args.target)
        now = time.monotonic()
        out = render(cur, prev, now - t_prev, args.target, stalls=stalls,
                     waterfall=wf, slo=slo, seq=seq, diagnose=diag)
        if args.once:
            print(out)
            return 0
        sys.stdout.write("\x1b[2J\x1b[H" + out + "\n")
        sys.stdout.flush()
        prev, t_prev = cur, now
        time.sleep(args.interval)


if __name__ == "__main__":
    raise SystemExit(main())
