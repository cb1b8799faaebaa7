"""From a ``jax.profiler`` trace to busy time, idle gaps and op times.

What a v5e trace looks like (looked at by hand, PR 25): the plane
``/device:TPU:0`` has the lines ``XLA Modules`` (one event per jitted program
run, named ``jit_update(<hash>)``), ``XLA Ops`` (one per HLO op, named by its
whole HLO text) and ``Async XLA Ops``; the plane ``/host:CPU`` has one line per
host thread, and ``TraceAnnotation`` spans show on the line of the thread that
wrote them. All planes share one clock, in nanoseconds. Host-to-device
transfers are host-side events and are not device operations.

The arithmetic is in plain functions over ``(start, end)`` pairs so that the
tests can hold it to made-up traces; ``reduce_dir`` is the one function that
touches ``jax.profiler.ProfileData`` (and so runs in the server child, the
only process that may import jax).
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OP_LINES = ("XLA Ops",)
MODULE_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
UNATTRIBUTED = "host.unattributed"


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, merged copy of ``intervals`` (pairs ``(start, end)``)."""
    out: list[tuple[float, float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(merged) -> float:
    return sum(b - a for a, b in merged)


def gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of ``[lo, hi]`` that ``merged`` (a union) leaves open."""
    out, at = [], lo
    for a, b in merged:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


class _Cover:
    """Covered length of a union up to a point, for overlap queries."""

    def __init__(self, merged):
        self.starts = [a for a, _ in merged]
        self.ends = [b for _, b in merged]
        self.before = [0.0]
        for a, b in merged:
            self.before.append(self.before[-1] + (b - a))

    def upto(self, t: float) -> float:
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        return self.before[i - 1] + min(t, self.ends[i - 1]) - self.starts[i - 1]

    def overlap(self, lo: float, hi: float) -> float:
        return self.upto(hi) - self.upto(lo)


def attribute_gaps(idle, spans: dict) -> dict[str, float]:
    """Idle time by what the host was doing: each gap's length is shared out
    over the named host spans that overlap it (scaled down where spans of
    several threads overlap each other), and what no span covers is
    ``host.unattributed``."""
    covers = {name: _Cover(union(iv)) for name, iv in spans.items()}
    out = {UNATTRIBUTED: 0.0}
    for lo, hi in idle:
        length = hi - lo
        parts = {n: c.overlap(lo, hi) for n, c in covers.items()}
        covered = sum(parts.values())
        scale = min(1.0, length / covered) if covered > 0 else 0.0
        for n, v in parts.items():
            if v > 0:
                out[n] = out.get(n, 0.0) + v * scale
        out[UNATTRIBUTED] += max(0.0, length - covered * scale)
    return out


def short_name(name: str) -> str:
    """``jit_update(123)`` -> ``jit_update``; an HLO op's text -> its
    ``%name`` and opcode."""
    m = re.match(r"^(%[\w.\-]+) = \S+ ([\w\-]+)\(", name)
    if m:
        return f"{m.group(1)} {m.group(2)}"
    return re.sub(r"\(\d+\)$", "", name)[:80]


def reduce(device_ops, device_modules, host_spans: dict, window) -> dict:
    """``device_ops`` / ``device_modules``: lists of ``(name, start, end)`` on
    ONE device; ``host_spans``: name -> list of ``(start, end)``; ``window``:
    ``(start, end)`` of the traced window. Times in seconds, one clock."""
    lo, hi = window
    events = device_ops or device_modules
    busy = union(clip([(a, b) for _, a, b in events], lo, hi))
    idle = gaps(busy, lo, hi)
    by_name: dict[str, float] = {}
    for name, a, b in list(device_modules) + list(device_ops):
        a, b = max(a, lo), min(b, hi)
        if b > a:
            key = short_name(name)
            by_name[key] = by_name.get(key, 0.0) + (b - a)
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    idle_by = attribute_gaps(idle, host_spans)
    return {
        "busy_s": total(busy), "window_s": hi - lo,
        "longest_gap_s": max((b - a for a, b in idle), default=0.0),
        "device_ops": [[n, s] for n, s in top],
        "idle_gaps": [[n, s] for n, s in sorted(
            idle_by.items(), key=lambda kv: -kv[1])[:10] if s > 0],
    }


def reduce_dir(trace_dir: str, chips: int = 1,
               require_device: bool = True) -> dict:
    """Reads the newest ``.xplane.pb`` under ``trace_dir``. ``busy_s`` is
    averaged over the ``chips`` device planes used. A trace in which no
    operation ran on a device is an error (``require_device=False``, the CPU
    rehearsal, gets ``busy_s`` 0 and no device metric instead)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    per_device, spans, window = [], {}, None
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, modules = [], []
            for line in plane.lines:
                into = (ops if line.name in OP_LINES
                        else modules if line.name == MODULE_LINE else None)
                if into is not None:
                    into.extend((e.name, e.start_ns / 1e9,
                                 (e.start_ns + e.duration_ns) / 1e9)
                                for e in line.events)
            per_device.append((ops, modules))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if not e.name.startswith(SPAN_PREFIX):
                        continue
                    iv = (e.start_ns / 1e9,
                          (e.start_ns + e.duration_ns) / 1e9)
                    if e.name == WINDOW_SPAN:
                        window = iv
                    else:
                        spans.setdefault(e.name, []).append(iv)
    if window is None:
        raise ValueError(f"the trace has no {WINDOW_SPAN} span")
    per_device = [d for d in per_device if d[0] or d[1]][:chips]
    if not per_device:
        if require_device:
            raise ValueError(
                "no operation ran on a device in the traced window")
        per_device = [([], [])]
    reduced = [reduce(ops, mods, spans, window) for ops, mods in per_device]
    out = max(reduced, key=lambda r: r["busy_s"])
    out["busy_s"] = sum(r["busy_s"] for r in reduced) / len(reduced)
    out["trace_bytes"] = os.path.getsize(paths[-1])
    return out
