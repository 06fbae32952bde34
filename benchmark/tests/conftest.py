"""Tests of the benchmark's own files.  They run on the sandbox CPU
(``python3 -m pytest benchmark/tests -q``) and are not part of the
repo's tier-1 tests."""

import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for path in (ROOT, BENCH_DIR):
    if path not in sys.path:
        sys.path.insert(0, path)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
