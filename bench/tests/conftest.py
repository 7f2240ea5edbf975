"""Tests of the benchmark itself, on the CPU at tiny sizes.

    python -m pytest bench/tests -q
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for path in (ROOT, os.path.join(ROOT, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)
