"""benchmarks/tests: run by hand, not part of tier-1.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

The rehearsals drive `run.py` on the CPU at a tiny scale with the persistent
compile cache off, so nothing is written into the checkout.
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "0")

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
