"""The benchmark's own tests, run by hand on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They drive ``bench/run.py`` at each file's rehearsal sizes (kernels in
interpret mode), so they need no chip.  The repo's tier-1 suite collects
only ``tests/``.
"""
import sys
from pathlib import Path

import jax

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH.parent / "src", BENCH):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

jax.config.update("jax_enable_compilation_cache", False)
CELLS = sorted(p.stem for p in (BENCH / "workloads").glob("*.json"))
