"""Tests of the benchmark's own machinery (not tier-1: run them with
``python -m pytest benchmarks/tests -q``). Everything here runs on the CPU at
KiB sizes and proves nothing about the chip."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
