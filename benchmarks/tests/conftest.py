"""The benchmark's own tests: CPU only, tiny sizes.

Run with ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``.  They
live under ``benchmarks/`` (the benchmark's own directory, which later PRs
may add to but not edit); tier 1 collects ``tests/`` only, so these do not
count there (PERF.md, Open questions).
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
