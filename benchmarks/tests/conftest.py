"""These are the benchmark's own rehearsals: CPU-only, run by hand with

    python -m pytest benchmarks/tests -q

and not by tier-1 (which collects ``tests/`` only). They put ``benchmarks/``
on the path the way ``python3 benchmarks/run.py`` does."""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
for p in (str(BENCH.parent), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
