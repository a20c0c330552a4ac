"""``python -m pytest benchmark/tests -q`` from the repo root: CPU only, the
benchmark's directory and the repo on the path.  Tier-1 collects ``tests/``
and never sees this directory."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
