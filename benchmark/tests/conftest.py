"""`python -m pytest benchmark/tests -q` from the repository root, on the
CPU. These tests are the benchmark's own and are not part of tier 1."""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
