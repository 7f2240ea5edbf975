#!/usr/bin/env python3
"""Run one benchmark cell once, in this process, on the chips it holds.

    python bench/run.py --workload <name> --seed <n>
        --seconds <s> --trace <0|1>

Set-up runs from process start to the start of the measured window:
JAX start-up, compile-cache reads, problem generation from ``--seed`` and
the warm-up of the cell's own shapes.  The window is a closed loop that
issues requests until ``--seconds`` have passed and finishes the one in
flight; a program compiled or loaded inside it fails the run.  After it,
the device's peak memory is read, the program's state is freed, and every
answer of the window is compared with the plain LAPACK reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
the window), ``device`` and, last, ``checks``: each number compared,
beside its limit.  The same numbers are the last lines of standard
error.  A host whose JAX finds no TPU, or fewer chips than the cell asks
for, exits with code 2 and prints no result.

JAX's persistent compilation cache is ``.jax_cache`` at the root of the
checkout, whatever the environment names.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import harness
    cell = harness.load_cell(args.workload, ROOT)
    jax = harness.start_jax(ROOT)
    device = harness.tpu_device(jax, cell.chips,
                                f"bench/run.py: {args.workload}", ROOT)
    if device is None:
        return 2

    from bench.clock import CompileClock
    clock = CompileClock(jax)
    out = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), t0=T0,
        clock=clock, jax=jax, device=device)
    harness.say_checks(out["checks"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
