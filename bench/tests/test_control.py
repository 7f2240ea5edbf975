"""The control of every cell fails its limit, at a size a test run holds.

The control is the configuration's ``control`` for the cell's dtype: the
program's own float32 path for a float64 cell, the reference computed in
bfloat16 for a float32 cell.  The program itself passes on the same
problems.  On the chip, ``bench/calibrate.py`` reads the same numbers at
the cells' own sizes.
"""

import jax
import pytest

from bench import calibrate, harness
from bench.clock import CompileClock
from bench.tests.test_harness import CELLS, HELD, tiny


@pytest.mark.parametrize("name", CELLS + HELD)
def test_control_fails_and_program_passes(name):
    cell = tiny(name)
    batches = calibrate.collect(cell, [11, 2**31 + 5], [12], 0.2,
                                CompileClock(jax), jax)
    rows = calibrate.readings(cell, batches)
    bar = cell.config["bar_eps"]
    program = [r for r in rows if r["kind"] == "program"]
    control = [r for r in rows if r["kind"] == "control"]
    assert all(harness.passed(r["checks"]) for r in program)
    assert not any(harness.passed(r["checks"]) for r in control)
    assert max(r["max_err_eps"] for r in program) < bar / 2
    assert min(r["max_err_eps"] for r in control) > 10 * bar
