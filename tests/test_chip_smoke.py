"""chip_smoke.py from the CPU side: it must FAIL where there is no TPU, its
CPU rehearsal must pass end to end without ever reading as a chip result, and
the compile cache must go where it is told and stop growing."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(args, env_extra, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("XLA_FLAGS", None)  # one CPU device, as a user's shell has
    return subprocess.run([sys.executable, SMOKE, *args], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=timeout)


def test_no_tpu_exits_nonzero_and_names_the_device(tmp_path):
    """No argument, no TPU: non-zero exit, the missing device named, no
    result line. With g++ off PATH as well, the Python data plane is
    announced loudly (and nothing is compiled, which keeps this fast)."""
    p = _run([], {"PATH": str(tmp_path)}, timeout=120)
    assert p.returncode != 0
    out = p.stdout + p.stderr
    assert "no tpu device" in out and "does not fall back" in out
    assert "DATA PLANE: PYTHON" in p.stdout and "g++" in p.stdout
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_peak_flops_table_rejects_unknown_kind():
    sys.path.insert(0, ROOT)
    try:
        import bench
    finally:
        sys.path.remove(ROOT)
    peak, source = bench._peak_flops("TPU v5 lite")
    assert peak == 197e12 and "v5e" in source
    with pytest.raises(KeyError, match="no published peak"):
        bench._peak_flops("TPU v9 imaginary")
    with pytest.raises(KeyError):
        bench._peak_flops("cpu")  # a host has no published peak either


@pytest.mark.slow
def test_rehearsal_passes_twice_and_the_cache_stops_growing(tmp_path):
    """The CPU dress rehearsal end to end, twice from one checkout, with
    the compile cache placed from outside: entries appear THERE, the second
    run adds none, and no line can be mistaken for a chip result."""
    cache = tmp_path / "cache"
    counts = []
    for _ in range(2):
        p = _run(["--rehearsal-cpu"],
                 {"JAX_COMPILATION_CACHE_DIR": str(cache)}, timeout=600)
        assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
        lines = p.stdout.strip().splitlines()
        assert all(ln.startswith("[REHEARSAL on cpu") for ln in lines[:-1])
        # the last line: exactly the keys the driver's check reads
        last = json.loads(lines[-1])
        assert set(last) == {"ok", "device"} and last["ok"] is False
        assert set(last["device"]) == {"platform", "kind", "count"}
        assert last["device"]["platform"] == "cpu"
        assert isinstance(last["device"]["kind"], str)
        assert type(last["device"]["count"]) is int
        # the line before it: the counts of every leg
        head, _, body = lines[-2].partition(" summary ")
        assert head.startswith("[REHEARSAL on cpu") and body.endswith(
            '"claim": null}')
        result = json.loads(body)
        assert result["rehearsal_passed"] is True and result["claim"] is None
        assert result["setup"]["cache_dir"] == str(cache)
        tensor = result["legs"]["tensor"]
        assert tensor["measured"]["compiles"] == 0
        # one landing, so no "paths" to tell apart (the key is absent):
        # every message of the pass landed, and the sizes lap the window
        assert "paths" not in tensor["measured"]
        assert (tensor["measured"]["landed"]
                == tensor["messages_per_pass"] > 4)
        assert tensor["wrapped_spans_per_pass"] >= 2
        assert "dma_d2d" not in tensor["measured"]["ledger"]
        assert result["legs"]["serving"]["batches"] < \
            result["legs"]["serving"]["rows"]
        assert result["data_plane"]["plane"] in ("native", "python")
        counts.append(len(os.listdir(cache)))
    assert counts[0] > 0 and counts[1] == counts[0], counts
