"""``BENCHMARK.json`` against the driver's contract, before anything runs.

PR 22 was refused on one non-ASCII character in a ``source``; this is the
check that would have said so. ``problems(manifest, root)`` returns every
breach as a line of text; an empty list means the driver's manifest check
has nothing to refuse (as far as the contract's text says what it checks).
A test helper only: a run does not execute it, the driver refuses at run time.
"""

from __future__ import annotations

import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
MAX_RUN_SECONDS = 51
CHECK_SECONDS = 43200


def _line(s, limit: int = 200) -> bool:
    """1..limit printable ASCII characters on one line, no tab."""
    return (isinstance(s, str) and 1 <= len(s) <= limit
            and all(32 <= ord(ch) < 127 for ch in s))


def _under(path: str, paths) -> bool:
    return any(path == p or path.startswith(p.rstrip("/") + "/")
               for p in paths)


def problems(m: dict, root: str | None = None) -> list[str]:
    out: list[str] = []
    say = out.append
    if set(m) != TOP_KEYS:
        say(f"top-level keys {sorted(m)} != {sorted(TOP_KEYS)}")
        return out
    paths, cmd = m["paths"], m["command"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16
            and all(isinstance(p, str) and PATH.match(p)
                    and not p.startswith("/") and ".." not in p.split("/")
                    for p in paths)):
        say(f"paths {paths!r}: 1 to 16 relative directories")
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32
            and all(_line(w) for w in cmd)):
        say("command: a list of 1 to 32 words of 1 to 200 characters")
    else:
        for w in cmd:
            if w.startswith("/") or ".." in w.split("/"):
                say(f"command word {w!r} leaves the checkout")
            elif (root and os.path.exists(os.path.join(root, w))
                  and not _under(w, paths)):
                say(f"command names {w!r}, a file outside paths")
    rs = m["run_seconds"]
    if not (isinstance(rs, int) and not isinstance(rs, bool)
            and 1 <= rs <= MAX_RUN_SECONDS):
        say(f"run_seconds {rs!r}: a whole number from 1 to {MAX_RUN_SECONDS}")
    elif (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 > CHECK_SECONDS:
        say(f"run_seconds {rs}: a full check of 24 cells would not fit")

    cfg_names, files = set(), set()
    if not 1 <= len(m["configs"]) <= 24:
        say("configs: 1 to 24")
    for c in m["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            say(f"config {c.get('name')}: keys {sorted(c)}")
            continue
        if not NAME.match(c["name"]) or c["name"] in cfg_names:
            say(f"config name {c['name']!r}: bad or repeated")
        cfg_names.add(c["name"])
        for key in ("source", "why"):
            if not _line(c[key]):
                say(f"config {c['name']}: {key} must be 1 to 200 printable "
                    "ASCII characters on one line")
        if (not PATH.match(c["file"]) or not _under(c["file"], paths)
                or c["file"] in files):
            say(f"config {c['name']}: file {c['file']!r} not under paths, "
                "or another configuration's")
        files.add(c["file"])
        if root and not os.path.isfile(os.path.join(root, c["file"])):
            say(f"config {c['name']}: file {c['file']} does not exist")
        if not (isinstance(c["reduced"], list) and len(c["reduced"]) <= 16
                and all(isinstance(k, str) and NAME.match(k)
                        for k in c["reduced"])):
            say(f"config {c['name']}: reduced")

    cells, pairs, used = {}, set(), set()
    if not 1 <= len(m["workloads"]) <= 24:
        say("workloads: 1 to 24")
    for w in m["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            say(f"workload {w.get('name')}: keys {sorted(w)}")
            continue
        if not NAME.match(w["name"]) or w["name"] in cells:
            say(f"workload name {w['name']!r}: bad or repeated")
        cells[w["name"]] = w
        if w["config"] not in cfg_names:
            say(f"workload {w['name']}: unknown config {w['config']!r}")
        used.add(w["config"])
        if not NAME.match(w["traffic"]):
            say(f"workload {w['name']}: traffic name {w['traffic']!r}")
        if (w["config"], w["traffic"]) in pairs:
            say(f"workload {w['name']}: pair appears twice")
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4):
            say(f"workload {w['name']}: chips {w['chips']!r}")
        if not _line(w["why"]):
            say(f"workload {w['name']}: why must be 1 to 200 printable "
                f"ASCII characters ({len(w['why'])})")
    for name in cfg_names - used:
        say(f"config {name}: used by no cell")
    four = sum(1 for w in cells.values() if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        say(f"{four} of {len(cells)} cells ask for 4 chips")

    metric_names: set[str] = set()

    def metric(e: dict, keys: set, kind: str) -> list[str] | None:
        """Checks one metric; returns the cells it is reported in."""
        if not (keys <= set(e) <= keys | {"workloads"}):
            say(f"{kind} {e.get('name')}: keys {sorted(e)}")
            return None
        if not NAME.match(e["name"]) or e["name"] in metric_names:
            say(f"{kind} name {e['name']!r}: bad or repeated")
        metric_names.add(e["name"])
        if not UNIT.match(e["unit"]):
            say(f"{kind} {e['name']}: unit {e['unit']!r}")
        if e["better"] not in ("lower", "higher"):
            say(f"{kind} {e['name']}: better {e['better']!r}")
        if e["source"] not in SOURCES:
            say(f"{kind} {e['name']}: source {e['source']!r}")
        listed = e.get("workloads", list(cells))
        for c in listed:
            if c not in cells:
                say(f"{kind} {e['name']}: unknown cell {c!r}")
        return listed

    e2e: dict[str, list[str]] = {}
    if not 1 <= len(m["end_to_end"]) <= 16:
        say("end_to_end: 1 to 16")
    for e in m["end_to_end"]:
        listed = metric(e, {"name", "unit", "better", "bound", "source"},
                        "end_to_end")
        if listed is None:
            continue
        e2e[e["name"]] = listed
        if e["source"] not in ("host_clock", "device_trace"):
            say(f"end_to_end {e['name']}: source {e['source']!r}")
        b = e["bound"]
        if not (isinstance(b, (int, float)) and not isinstance(b, bool)
                and 0.01 <= b <= 0.1):
            say(f"end_to_end {e['name']}: bound {b!r} outside 0.01..0.1")
    if "setup_s" not in e2e:
        say("end_to_end: setup_s is missing")
    elif set(e2e["setup_s"]) != set(cells):
        say("setup_s must be reported in every cell")

    per_cell_layer = {c: 0 for c in cells}
    if not 1 <= len(m["per_layer"]) <= 128:
        say("per_layer: 1 to 128")
    for e in m["per_layer"]:
        listed = metric(e, {"name", "unit", "better", "source", "layer",
                            "moves"}, "per_layer")
        if listed is None:
            continue
        if not _line(e["layer"]):
            say(f"per_layer {e['name']}: layer")
        if e["moves"] not in e2e:
            say(f"per_layer {e['name']}: moves unknown metric "
                f"{e['moves']!r}")
            continue
        if "workloads" not in e:
            listed = e2e[e["moves"]]
        for c in listed:
            if c in cells and c not in e2e[e["moves"]]:
                say(f"per_layer {e['name']}: cell {c} does not report "
                    f"{e['moves']}")
            if c in per_cell_layer:
                per_cell_layer[c] += 1
        if (("_roofline" in e["name"] or "mfu" in e["name"])
                and e["unit"] != "%"):
            say(f"per_layer {e['name']}: a share of a peak has the unit %")
    for c in cells:
        others = [n for n, cs in e2e.items() if c in cs and n != "setup_s"]
        if not others:
            say(f"cell {c}: no end-to-end metric besides setup_s")
        if not per_cell_layer[c]:
            say(f"cell {c}: no per-layer metric")
    if len(json.dumps(m)) > 64 << 10:
        say("the file is over 64 KiB")
    return out
