"""CPU tests of the benchmark harness: ``python -m pytest bench/tests``.

They run on the CPU at tiny sizes, with the served path's XLA references
in place of the Pallas kernels; no device metric is computed here."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
