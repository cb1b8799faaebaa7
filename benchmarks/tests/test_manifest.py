"""BENCHMARK.json against the contract: the check PR 22 died without."""

import copy
import json
import os
import sys

import pytest

from benchmarks.tests import manifest_check as manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
from run import cell_metrics  # noqa: E402  (benchmarks/run.py, as it is run)


@pytest.fixture(scope="module")
def m():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_committed_manifest_has_no_problem(m):
    assert manifest.problems(m, ROOT) == []


def test_every_data_file_a_cell_names_exists_and_parses(m):
    for w in m["workloads"]:
        with open(os.path.join(ROOT, "benchmarks", "traffic",
                               w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "traffic_kinds", traffic["kind"] + ".py"))
    for c in m["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        for key in ("handler", "reference"):
            sub = "handlers" if key == "handler" else "configs"
            assert os.path.isfile(os.path.join(
                ROOT, "benchmarks", sub, cfg[key] + ".py"))
        assert cfg["guarantees"], "the guarantees are part of the result"
    for e in m["per_layer"]:
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "layer_metrics", e["name"] + ".py"))


def test_pool_is_the_reckoned_share_of_the_chip(m):
    with open(os.path.join(ROOT, "benchmarks", "peaks.json")) as f:
        hbm = json.load(f)["TPU v5 lite"]["hbm_bytes"]
    for c in m["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["pool"]["bytes"] / hbm == 0.375
        assert (cfg["pool"]["slots_in_all"] * cfg["message"]["bytes"]
                == cfg["pool"]["bytes"])


def _broken(m, edit):
    bad = copy.deepcopy(m)
    edit(bad)
    return manifest.problems(bad, ROOT)


@pytest.mark.parametrize("what,edit", [
    ("non-ascii source", lambda b: b["configs"][0].update(
        source="4 MiB → HBM")),
    ("source too long", lambda b: b["configs"][0].update(source="x" * 201)),
    ("unit with a space", lambda b: b["end_to_end"][0].update(
        unit="GB per s")),
    ("name with a slash", lambda b: b["per_layer"][0].update(name="a/b")),
    ("moves a metric its cell lacks", lambda b: (
        b["end_to_end"].insert(0, dict(b["end_to_end"][0], name="other",
                                       workloads=["stream4m_c1"])),
        b["per_layer"][0].update(moves="other"))),
    ("metric lists a cell that is gone", lambda b: b["end_to_end"][0].update(
        workloads=["stream4m_c1", "unary64k_c8"])),
    ("config without a cell", lambda b: b["configs"].append(dict(
        b["configs"][0], name="orphan", file="benchmarks/configs/o.json"))),
    ("bound over a tenth", lambda b: b["end_to_end"][0].update(bound=0.2)),
    ("bound under one percent", lambda b: b["end_to_end"][0].update(
        bound=0.001)),
    ("stray key on a metric", lambda b: b["per_layer"][0].update(why="x")),
    ("no setup_s", lambda b: b["end_to_end"].pop()),
    ("file outside paths", lambda b: b["configs"][0].update(
        file="tests/x.json")),
    ("four chips in every cell", lambda b: [w.update(chips=4)
                                            for w in b["workloads"]]),
    ("run_seconds too long", lambda b: b.update(run_seconds=52)),
    ("same pair twice", lambda b: b["workloads"].append(dict(
        b["workloads"][0], name="again"))),
    ("roofline not in percent", lambda b: [
        e.update(unit="B/B") for e in b["per_layer"]
        if "_roofline" in e["name"]]),
])
def test_validator_refuses(m, what, edit):
    assert _broken(m, edit), what


def test_cell_metrics_follow_the_workloads_keys(m):
    b = copy.deepcopy(m)
    b["end_to_end"].append({"name": "calls_s", "unit": "calls/s",
                            "better": "higher", "bound": 0.1,
                            "source": "host_clock", "workloads": ["later"]})
    b["per_layer"] += [
        {"name": "idle.later", "moves": "calls_s", "workloads": ["later"]},
        {"name": "follows_what_it_moves", "moves": "calls_s"}]
    names = lambda cell, group: {
        e["name"] for e in cell_metrics(b, cell, group)}
    assert names("stream4m_c1", "end_to_end") == {"hbm_gbytes_s", "setup_s"}
    assert names("later", "end_to_end") == {"calls_s", "setup_s"}
    assert names("later", "per_layer") == {"idle.later",
                                           "follows_what_it_moves"}
    assert not names("stream4m_c8", "per_layer") & {
        "idle.later", "follows_what_it_moves"}
