#!/usr/bin/env python3
"""tpurpc-oracle bench diff (ISSUE 20): compare two ``bench.py`` result
snapshots (``{"parsed": {...}}``) and flag regressions, with waterfall-hop
attribution.

    python tools/bench_diff.py old.json new.json
    python tools/bench_diff.py old.json new.json --threshold 5 --json

Every numeric series in ``parsed`` is compared direction-aware:
``value`` / ``*_qps`` / ``*_gbps`` / ``*_mfu`` are higher-better;
``*_pct`` / ``*_us`` / ``*_ns`` are lower-better (gate constants
``*_gate_pct`` and booleans are skipped). A move of more than the
threshold (default 10%) in the bad direction on a **gated** series — one
that carries a ``*_gate_pct`` acceptance gate, plus the headline
throughput/serving series — is a REGRESSION and the tool exits 1, so it
slots straight into CI. When the regressed series is a throughput and
both snapshots carry ``waterfall_gbps_by_hop``, the diff names the hop
whose relative drop is worst — the same attribution the live lens
waterfall gives, applied to the delta ("the regression lives in the
scatter hop"), instead of a bare "0.68 → 0.55 GB/s".

Snapshots whose ``parsed`` is null (a crashed run) still diff: every series in the other file reports as
added/removed rather than crashing the tool.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Optional, Tuple

# Headline series that count as gated even without a *_gate_pct twin:
# the numbers the README tracks release over release.
_HEADLINE = frozenset({
    "value", "serving_qps", "device_infer_qps", "serving_mfu",
    "device_mfu",
})

_SKIP_SUFFIXES = ("_gate_pct", "_pass", "_error")
_SKIP_KEYS = frozenset({
    "n", "rc", "metric", "unit", "calibration",
    "device_kind", "jax_platform", "serving_model",
    "peak_flops", "peak_flops_assumed", "peak_flops_source",
    "model_flops_per_inference", "serving_requests",
    "serving_client_depth", "serving_client_mode", "host_load",
})


def _higher_better(name: str) -> Optional[bool]:
    """True = higher is better, False = lower is better, None = unknown
    (unknown series are reported but never flagged)."""
    if name in ("value",) or name.endswith(("_qps", "_gbps", "_mfu")):
        return True
    if name.endswith(("_pct", "_us", "_ns", "_ms")):
        return False
    return None


def _numeric_series(doc: dict) -> Dict[str, float]:
    parsed = doc.get("parsed") or {}
    out: Dict[str, float] = {}
    for k, v in parsed.items():
        if k in _SKIP_KEYS or k.endswith(_SKIP_SUFFIXES):
            continue
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            continue
        out[k] = float(v)
    return out


def _gated_names(doc: dict) -> frozenset:
    parsed = doc.get("parsed") or {}
    gated = {k[:-len("_gate_pct")] + "_pct" for k in parsed
             if k.endswith("_gate_pct")}
    return frozenset(gated | _HEADLINE)


def _hop_attribution(old: dict, new: dict) -> Optional[dict]:
    """Worst relative per-hop drop between the two waterfall snapshots."""
    oh = (old.get("parsed") or {}).get("waterfall_gbps_by_hop") or {}
    nh = (new.get("parsed") or {}).get("waterfall_gbps_by_hop") or {}
    worst: Optional[Tuple[str, float, float, float]] = None
    for hop in oh:
        if hop not in nh:
            continue
        try:
            o, n = float(oh[hop]), float(nh[hop])
        except (TypeError, ValueError):
            continue
        if o <= 0:
            continue
        drop_pct = (o - n) / o * 100.0
        if worst is None or drop_pct > worst[3]:
            worst = (hop, o, n, drop_pct)
    if worst is None:
        return None
    hop, o, n, drop = worst
    return {"hop": hop, "old_gbps": round(o, 3), "new_gbps": round(n, 3),
            "drop_pct": round(drop, 1)}


def diff_docs(old: dict, new: dict, threshold_pct: float = 10.0) -> dict:
    """The machine-readable diff: per-series rows, flagged regressions,
    and (when a throughput regressed) the waterfall hop to blame."""
    a, b = _numeric_series(old), _numeric_series(new)
    gated = _gated_names(old) | _gated_names(new)
    rows: List[dict] = []
    regressions: List[dict] = []
    for name in sorted(set(a) | set(b)):
        if name == "waterfall_gbps_by_hop":
            continue
        if name not in a:
            rows.append({"series": name, "old": None, "new": b[name],
                         "status": "added"})
            continue
        if name not in b:
            rows.append({"series": name, "old": a[name], "new": None,
                         "status": "removed"})
            continue
        o, n = a[name], b[name]
        delta_pct = ((n - o) / abs(o) * 100.0) if o else 0.0
        hb = _higher_better(name)
        if hb is None:
            status = "unscored"
        else:
            bad = -delta_pct if hb else delta_pct
            if bad > threshold_pct and name in gated:
                status = "REGRESSED"
            elif bad > threshold_pct:
                status = "worse"       # >threshold but not a gated series
            elif -bad > threshold_pct:
                status = "improved"
            else:
                status = "ok"
        row = {"series": name, "old": o, "new": n,
               "delta_pct": round(delta_pct, 1),
               "direction": ("higher-better" if hb
                             else "lower-better" if hb is False
                             else "unknown"),
               "status": status, "gated": name in gated}
        rows.append(row)
        if status == "REGRESSED":
            reg = dict(row)
            if hb and (name == "value" or name.endswith(("_qps", "_gbps"))):
                attr = _hop_attribution(old, new)
                if attr:
                    reg["slowest_hop"] = attr
            regressions.append(reg)
    return {"threshold_pct": threshold_pct, "rows": rows,
            "regressions": regressions,
            "ok": not regressions}


def render(doc: dict, old_name: str, new_name: str) -> str:
    out = [f"bench diff: {old_name} -> {new_name} "
           f"(threshold {doc['threshold_pct']:g}%)"]
    width = max((len(r["series"]) for r in doc["rows"]), default=10)
    for r in doc["rows"]:
        if r["status"] in ("added", "removed"):
            out.append(f"  {r['series']:<{width}}  {r['status']}")
            continue
        mark = {"REGRESSED": "!!", "worse": " -", "improved": " +",
                "ok": "  ", "unscored": " ?"}[r["status"]]
        out.append(
            f"{mark}{r['series']:<{width}}  {r['old']:>12.4g} -> "
            f"{r['new']:>12.4g}  {r['delta_pct']:+7.1f}%  {r['status']}")
    for reg in doc["regressions"]:
        line = (f"REGRESSION: {reg['series']} "
                f"{reg['old']:g} -> {reg['new']:g} "
                f"({reg['delta_pct']:+.1f}%, {reg['direction']})")
        hop = reg.get("slowest_hop")
        if hop:
            line += (f" — worst hop: {hop['hop']} "
                     f"{hop['old_gbps']:g} -> {hop['new_gbps']:g} GB/s "
                     f"({hop['drop_pct']:g}% drop)")
        out.append(line)
    if doc["ok"]:
        out.append("no gated regressions")
    return "\n".join(out) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python tools/bench_diff.py",
        description="diff two BENCH_r*.json snapshots, flag >threshold "
                    "regressions on gated series, attribute to the "
                    "slowest waterfall hop")
    ap.add_argument("old")
    ap.add_argument("new")
    ap.add_argument("--threshold", type=float, default=10.0,
                    help="regression threshold in percent (default 10)")
    ap.add_argument("--json", action="store_true",
                    help="print the machine-readable diff")
    args = ap.parse_args(argv)
    try:
        with open(args.old, encoding="utf-8") as f:
            old = json.load(f)
        with open(args.new, encoding="utf-8") as f:
            new = json.load(f)
    except (OSError, ValueError) as exc:
        print(f"bench_diff: {exc}", file=sys.stderr)
        return 2
    doc = diff_docs(old, new, threshold_pct=args.threshold)
    if args.json:
        print(json.dumps(doc, indent=1))
    else:
        sys.stdout.write(render(doc, args.old, args.new))
    return 0 if doc["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
