#!/usr/bin/env python3
"""Record the small TPU trace that bench/tests/test_trace.py reduces.

Runs, on one TPU chip, a float32 solve of one n = 1024 ``uniform``
problem (the boundary-row tree with its Pallas kernels and sorts) and a
default float64 solve of one n = 64 problem (the certified mixed route:
f32 tree, then f64 certify/polish), each under a ``bench.solve`` host
annotation, then 50 ms of host-only work under ``bench.wait``; the
profiler is on only around the ``bench.window`` annotation that holds
them, with the options the harness uses.  Warm-up runs first, so the
trace holds no compile.  Writes ``<out>/tpu_small.xplane.pb.gz`` and, beside
it, ``tpu_small.json`` with what the window did, and prints each line of
the trace with its event count.

Usage:  python bench/testdata/record.py [--out DIR]
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, ".jax_cache"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

N_F32, N_F64 = 1024, 64


def describe(pb: str) -> None:
    """Print the trace's planes and lines, with event counts and the most
    frequent event names and stat keys of each line."""
    from collections import Counter
    from jax.profiler import ProfileData
    print(f"{pb}: {os.path.getsize(pb)} bytes")
    for plane in ProfileData.from_file(pb).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            names, keys = Counter(), Counter()
            for ev in line.events:
                names[ev.name] += 1
                keys.update(k for k, _ in ev.stats)
            print(f"  line {line.name!r}: {sum(names.values())} events; "
                  f"names {names.most_common(40)}; stats {dict(keys)}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--out", default=os.path.join(ROOT, "bench", "testdata"))
    args = p.parse_args(argv)
    jax.config.update("jax_enable_x64", True)
    if jax.devices()[0].platform != "tpu":
        print("record.py: needs a TPU", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    from bench import generate
    from repro.core import eigvalsh_tridiagonal

    d32, e32 = (jnp.asarray(a, jnp.float32)
                for a in generate.family("uniform", N_F32, 1))
    d64, e64 = (jnp.asarray(a) for a in generate.family("uniform", N_F64, 2))

    def work():
        with jax.profiler.TraceAnnotation("bench.solve"):
            np.asarray(eigvalsh_tridiagonal(d32, e32, dtype=jnp.float32))
        with jax.profiler.TraceAnnotation("bench.solve"):
            np.asarray(eigvalsh_tridiagonal(d64, e64))
        with jax.profiler.TraceAnnotation("bench.wait"):
            time.sleep(0.05)

    work()
    work()
    tmp = tempfile.mkdtemp()
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        with jax.profiler.trace(tmp, profiler_options=opts):
            with jax.profiler.TraceAnnotation("bench.window"):
                work()
        (pb,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        os.makedirs(args.out, exist_ok=True)
        with open(pb, "rb") as src, gzip.open(
                os.path.join(args.out, "tpu_small.xplane.pb.gz"),
                "wb") as dst:
            shutil.copyfileobj(src, dst)
        describe(pb)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    dev = jax.devices()[0]
    meta = {"solves": 2, "n_float32": N_F32, "n_float64": N_F64,
            "device_kind": dev.device_kind, "jax": jax.__version__}
    with open(os.path.join(args.out, "tpu_small.json"), "w") as f:
        json.dump(meta, f, indent=1)
    print(json.dumps(meta))
    return 0


if __name__ == "__main__":
    sys.exit(main())
