"""The benchmark's own tests (run: ``JAX_PLATFORMS=cpu python -m pytest
benchmark/tests -q``). They live outside ``tests/`` so that the repo's tier-1
count is untouched, and run at test sizes on the CPU."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "benchmark")):
    if p not in sys.path:
        sys.path.insert(0, p)
