"""The benchmark's own tests: CPU only, at toy sizes. Run them with

    JAX_PLATFORMS=cpu python -m pytest bench/tests

They put the benchmark's modules and the program (``src``, and the repo
root for its chip entry ``chip_smoke.py``) on the path."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.append(str(BENCH.parent))
