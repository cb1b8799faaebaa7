#!/usr/bin/env python3
"""The control, on the chip, at each cell's own size (not a pytest file: it
needs the TPU; ``chiprun -- python3 benchmarks/tests/control_on_chip.py``).

The configurations state no precision but three guarantees, so the control
breaks one of them: the handler rounds every payload to bfloat16's precision
(``lax.reduce_precision``; a plain ``astype`` round trip is elided by the TPU
compiler and stores exact data) before it stores it ("nothing is dropped or approximated"), the step that
would tempt a later PR (the program already has a ``transfer_dtype=bf16``
for its batcher). Every run must read ``correct: false``, with
``folds_wrong``, ``slots_wrong`` or ``sample_bytes_wrong`` over its limit of
0. ``--faults`` adds the other planted faults (drop, alter, reorder). The same
faults are held to the same result at KiB sizes by ``test_rehearsal.py``.
"""

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", nargs="*", default=None)
    ap.add_argument("--faults", nargs="*", default=["approx_bf16"])
    ap.add_argument("--seeds", nargs="*", type=int,
                    default=[2600000001, 2600000002, 2600000003])
    ap.add_argument("--seconds", default="5")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = args.cells or [w["name"] for w in json.load(f)["workloads"]]
    bad = 0
    for cell in cells:
        for fault in args.faults:
            for seed in args.seeds:
                proc = subprocess.run(
                    [sys.executable, os.path.join(ROOT, "benchmarks",
                                                  "run.py"),
                     "--workload", cell, "--seed", str(seed), "--seconds",
                     args.seconds, "--trace", "0", "--fault", fault],
                    capture_output=True, text=True, cwd=ROOT)
                try:
                    last = json.loads(proc.stdout.strip().splitlines()[-1])
                except (IndexError, ValueError):
                    print(f"{cell} {fault} {seed}: no result (rc "
                          f"{proc.returncode})\n{proc.stderr[-2000:]}")
                    bad += 1
                    continue
                over = {k: v["value"] for k, v in last["compared"].items()
                        if v["value"] > v["limit"]}
                ok = last["correct"] is False and bool(over)
                bad += not ok
                print(f"{cell} {fault} seed {seed}: correct="
                      f"{last['correct']} attempted={last['attempted']} "
                      f"over their limits: {over} "
                      f"{'(as it must)' if ok else 'THE CONTROL PASSED'}",
                      flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
