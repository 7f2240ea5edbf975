#!/usr/bin/env python3
"""Readings that set a cell's correctness limit: the program's on many
seeds, and the control's.

    python bench/calibrate.py --workload <name> --seeds 1,2,... \\
        --control-seeds 7,8,9 --seconds <s>

One process, on the chip: for each ``--seeds`` seed it sets the cell up
and drives a window of ``--seconds`` exactly as ``bench/run.py`` does;
for each ``--control-seeds`` seed it puts the control in the program's
place.  The control is the configuration's ``control`` for the cell's
dtype: the program's own lower-precision path where it has one
(``program_knobs``, e.g. float32 requests for a float64 cell), else the
plain reference computed in a lower precision (``reference_dtype``: the
problem and its eigenvalues rounded to it).  Every answer is then
compared with the reference, in one pool of workers, by the harness's
own ``check``, and one JSON line per seed gives its ``max_err_eps`` and
whether the run's checks passed; the last line gives the largest program
reading (the lower one) and the smallest control reading (the upper
one).  The benchmark's own runs never run this.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lower_precision(problems, driver: str, dtype: str) -> list:
    """The reference computed in ``dtype``: each problem rounded to it,
    its spectrum computed, and the spectrum rounded to it; as
    (index, eigenvalues) answers."""
    import ml_dtypes
    import numpy as np

    from bench import reference
    low = np.dtype(getattr(ml_dtypes, dtype, None) or dtype)
    rounded = [(d.astype(low).astype(np.float64),
                e.astype(low).astype(np.float64)) for d, e in problems]
    return [(i, lam.astype(low).astype(np.float64))
            for i, lam in enumerate(reference.spectra(rounded, driver))]


def readings(cell, batches) -> list:
    """The harness's own checks (``harness.check``) of each (label, seed,
    problems, answers) batch, with the reference of every problem
    answered computed in one call."""
    from bench import harness, reference
    dtype = cell.traffic["dtype"]
    keys = sorted({(b, i) for b, (_, _, _, answers) in enumerate(batches)
                   for i, _ in answers})
    refs = dict(zip(keys, reference.spectra(
        [batches[b][2][i] for b, i in keys],
        cell.config["reference"][dtype])))
    out = []
    for b, (label, seed, problems, answers) in enumerate(batches):
        checks, failed = harness.check(
            cell, problems, answers, 0, dtype,
            refs={i: refs[b, i] for i, _ in answers})
        out.append({"kind": label, "seed": seed, "answers": len(answers),
                    "max_err_eps": checks["max_err_eps"]["value"],
                    "failed": failed, "passed": harness.passed(checks),
                    "checks": checks})
    return out


def collect(cell, seeds, control_seeds, seconds, clock, jax) -> list:
    """Drive the program (and the control) on each seed; returns the
    batches ``readings`` compares."""
    from bench import harness
    mod = harness.driver(cell)
    control = cell.config["control"][cell.traffic["dtype"]]
    batches = []
    for label, group in (("program", seeds), ("control", control_seeds)):
        for seed in group:
            drv = mod.Driver(cell, seed)
            if label == "control" and "reference_dtype" in control:
                answers = lower_precision(
                    drv.problems,
                    cell.config["reference"][cell.traffic["dtype"]],
                    control["reference_dtype"])
            else:
                drv.setup(clock)
                if label == "program":
                    answers = drv.window(seconds).answers
                else:
                    answers = drv.answers_with(control["program_knobs"])
                drv.close()
            batches.append((label, seed, drv.problems, answers))
    return batches


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", required=True)
    p.add_argument("--seconds", type=float, default=2.0)
    args = p.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import harness
    cell = harness.load_cell(args.workload, ROOT)
    jax = harness.start_jax(ROOT)
    device = harness.tpu_device(jax, cell.chips, "bench/calibrate.py", ROOT)
    if device is None:
        return 2
    from bench.clock import CompileClock
    seeds = [int(s) for s in args.seeds.split(",")]
    control_seeds = [int(s) for s in args.control_seeds.split(",")]
    batches = collect(cell, seeds, control_seeds, args.seconds,
                      CompileClock(jax), jax)
    rows = readings(cell, batches)
    for row in rows:
        print(json.dumps(row), flush=True)
    lower = max(r["max_err_eps"] for r in rows if r["kind"] == "program")
    upper = min(r["max_err_eps"] for r in rows if r["kind"] == "control")
    print(json.dumps({"workload": args.workload, "lower": lower,
                      "upper": upper, "limit": cell.config["bar_eps"],
                      "device": device["kind"],
                      "seconds": time.perf_counter() - T0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
