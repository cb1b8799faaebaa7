"""``run.py`` end to end at KiB sizes on the CPU (``--rehearsal-cpu``): the
whole of a run except the look for a chip. A sound run compares clean; with
the timed path broken underneath, ``correct`` comes out false. Also: the
harness takes a new configuration, traffic mix, handler, generator and
per-layer metric as new files plus new entries. One such addition is a unary
cell (``unary_fixture_c8``): the 64 KiB unary cell of ISSUE 25 could not be
admitted (PERF.md 7), and the ``unary_closed`` generator, the handler's unary
method and the percentiles stay proven here until a cell can hold them."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.tests import manifest_check

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
LABEL = "[REHEARSAL on cpu - not a chip result]"


def copy_of_the_benchmark(root):
    """A checkout-like copy under ``root`` that a test may add files to."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for name in ("tpurpc", "native"):
        os.symlink(os.path.join(ROOT, name), os.path.join(root, name))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return os.path.join(root, "benchmarks"), json.load(f)


@pytest.fixture(scope="module")
def unary_root(tmp_path_factory):
    """The benchmark plus a unary cell, added as data: a configuration, a
    traffic mix of the kind ``unary_closed``, three end-to-end entries and a
    per-layer reader."""
    root = str(tmp_path_factory.mktemp("unary"))
    bench, m = copy_of_the_benchmark(root)
    with open(os.path.join(bench, "configs", "tensor_stream_4m.json")) as f:
        cfg = json.load(f)
    cfg.update(name="tensor_unary_fixture", rpc="unary_unary",
               bank_messages=64)
    cfg["rehearsal_cpu"].update(
        message={"dtype": "float32", "shape": [256], "bytes": 1024},
        pool={"bytes": 262144, "slots_in_all": 256})
    with open(os.path.join(bench, "configs", "tensor_unary_fixture.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "unary_closed_c8.json"),
              "w") as f:
        json.dump({"name": "unary_closed_c8", "kind": "unary_closed",
                   "connections": 8, "warmup_messages": 6}, f)
    with open(os.path.join(bench, "layer_metrics",
                           "landing_ops_per_call.unary.py"), "w") as f:
        f.write("def read(run):\n"
                "    led = run['server_ledger']\n"
                "    return ((led.get('dma_h2d_ops', 0)"
                " + led.get('dma_d2d_ops', 0)) / run['messages'])\n")
    m["configs"].append({"name": "tensor_unary_fixture", "source": "a test",
                         "file": "benchmarks/configs/tensor_unary_fixture.json",
                         "reduced": [], "why": "a test"})
    m["workloads"].append({"name": "unary_fixture_c8",
                           "config": "tensor_unary_fixture",
                           "traffic": "unary_closed_c8", "chips": 1,
                           "why": "a test"})
    for name, unit, better in (("calls_s", "calls/s", "higher"),
                               ("call_p50_ms", "ms", "lower"),
                               ("call_p95_ms", "ms", "lower")):
        m["end_to_end"].insert(0, {
            "name": name, "unit": unit, "better": better, "bound": 0.1,
            "source": "host_clock", "workloads": ["unary_fixture_c8"]})
    m["per_layer"].append({
        "name": "landing_ops_per_call.unary", "unit": "ops/call",
        "better": "lower", "source": "program_counter",
        "layer": "landing: h2d, place, view", "moves": "call_p50_ms",
        "workloads": ["unary_fixture_c8"]})
    assert manifest_check.problems(m, root) == []
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    return root


def rehearse(root, workload, *extra, seconds="1.5", trace="0"):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"),
         "--workload", workload, "--seed", "3000000007", "--seconds", seconds,
         "--trace", trace, "--rehearsal-cpu", *extra],
        capture_output=True, text=True, timeout=300, env=env, cwd=root)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    return json.loads(lines[-1]), lines[:-1], proc.stderr


@pytest.mark.parametrize("workload,trace", [
    ("stream4m_c1", "1"), ("unary_fixture_c8", "1"), ("stream4m_c8", "0")])
def test_rehearsal_compares_clean_and_can_not_pass_for_a_result(
        workload, trace, unary_root):
    root = unary_root if workload.startswith("unary") else ROOT
    last, lines, err = rehearse(root, workload, trace=trace)
    assert all(line.startswith(LABEL) for line in lines)
    # the server child runs with the environment its configuration states
    assert any("server_env: {'TPU_PREMAPPED_BUFFER_SIZE': '268435456'}"
               in line for line in lines), lines[:3]
    assert last["rehearsal_cpu"] is True and last["correct"] is False
    assert "metrics" not in last and "device" not in last
    assert last["would_be_correct"] is True, last
    assert last["attempted"] > 0 and last["failed"] == 0
    assert list(last)[-1] == "compared"
    for name, pair in last["compared"].items():
        assert pair["value"] <= pair["limit"], name
        assert f"compared {name} = {pair['value']} (limit" in err


@pytest.mark.parametrize("workload,fault,fails", [
    # the control: a guarantee of the configuration broken ("approximated")
    ("stream4m_c1", "approx_bf16", "sample_bytes_wrong"),
    ("unary_fixture_c8", "approx_bf16", "slots_wrong"),
    # a step that returns its state unchanged
    ("stream4m_c1", "drop", "slots_wrong"),
    # an answer altered where it is produced
    ("stream4m_c1", "alter", "folds_wrong"),
    ("unary_fixture_c8", "alter", "folds_wrong"),
    # per-connection order not kept
    ("stream4m_c8", "reorder", "slots_wrong"),
])
def test_a_broken_timed_path_reads_not_correct(workload, fault, fails,
                                               unary_root):
    root = unary_root if workload.startswith("unary") else ROOT
    last, _, _ = rehearse(root, workload, "--fault", fault)
    assert last["would_be_correct"] is False
    pair = last["compared"][fails]
    assert pair["value"] > pair["limit"], last["compared"]


def test_new_cell_is_new_files_plus_new_entries(tmp_path):
    """A dummy configuration, traffic mix, handler, generator and per-layer
    metric, added to a copy of the benchmark without editing a file of it."""
    root = str(tmp_path)
    bench, m = copy_of_the_benchmark(root)
    before = {os.path.join(dp, p): open(os.path.join(dp, p), "rb").read()
              for dp, _, fs in os.walk(bench) for p in fs}
    with open(os.path.join(bench, "configs", "tensor_stream_4m.json")) as f:
        cfg = json.load(f)
    cfg.update(name="dummy_cfg", handler="dummy_sink")
    with open(os.path.join(bench, "configs", "dummy_cfg.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "dummy_c2.json"), "w") as f:
        json.dump({"name": "dummy_c2", "kind": "dummy_kind",
                   "connections": 2, "warmup_messages": 2}, f)
    with open(os.path.join(bench, "handlers", "dummy_sink.py"), "w") as f:
        f.write("from benchmarks.handlers.pool_sink import PoolSink\n\n"
                "class Dummy(PoolSink):\n    pass\n\n"
                "def build(ctx):\n    return Dummy(ctx)\n")
    with open(os.path.join(bench, "traffic_kinds", "dummy_kind.py"),
              "w") as f:
        f.write("from benchmarks.traffic_kinds.stream import run, warm\n")
    with open(os.path.join(bench, "layer_metrics", "dummy_metric.py"),
              "w") as f:
        f.write("def read(run):\n    return float(run['messages'])\n")
    m["configs"].append({"name": "dummy_cfg", "source": "a test",
                         "file": "benchmarks/configs/dummy_cfg.json",
                         "reduced": [], "why": "a test"})
    m["workloads"].append({"name": "dummy_cell", "config": "dummy_cfg",
                           "traffic": "dummy_c2", "chips": 1,
                           "why": "a test"})
    for e in m["end_to_end"]:
        if e["name"] == "hbm_gbytes_s":
            e["workloads"].append("dummy_cell")
    m["per_layer"].append({"name": "dummy_metric", "unit": "msgs",
                           "better": "higher", "source": "program_counter",
                           "layer": "a test", "moves": "hbm_gbytes_s",
                           "workloads": ["dummy_cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    last, lines, _ = rehearse(root, "dummy_cell", trace="1")
    assert last["would_be_correct"] is True and last["attempted"] > 0
    assert any("dummy_metric" in line for line in lines)
    for path, data in before.items():  # nothing that was there was edited
        assert open(path, "rb").read() == data, path


def test_alone_in_a_directory_it_exits_non_zero_and_prints_no_result(
        tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"),
         "--workload", "stream4m_c1", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=120,
        env=env, cwd=root)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_without_a_tpu_it_exits_non_zero_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "stream4m_c1", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT)
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
