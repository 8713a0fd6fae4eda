"""Rehearsal tests of the benchmark harness, run on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

They import the harness as ``bench/run.py`` does (``bench/`` on the
path) and keep JAX's persistent compilation cache off.
"""

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE)]

import jax  # noqa: E402
import run  # noqa: E402,F401  (sets the path to the program)

sys.path.insert(0, str(run.ROOT / "src"))
jax.config.update("jax_enable_compilation_cache", False)
