"""The benchmark's own tests run on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

They are not part of the repository's test suite (``pytest.ini`` collects
``tests/`` only)."""
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
