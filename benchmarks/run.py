"""Benchmark driver: one module per paper table + roofline aggregation.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME] [--json [PATH]]

Prints ``name,us_per_call,derived`` CSV rows.  With ``--json`` the rows
are also written as structured JSON (default path BENCH_conquer.json in
the repo root) so perf PRs leave a machine-readable trajectory.  Exits
non-zero when any suite raised (after recording it as a ``<suite>_ERROR``
row) and when ``--only`` names no suite.
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller sizes (CI-friendly)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", nargs="?", const="BENCH_conquer.json",
                    default=None, metavar="PATH",
                    help="also write results as JSON (default "
                         "BENCH_conquer.json)")
    ap.add_argument("--tune", action="store_true",
                    help="run the offline autotuner (plan.tune) on a small "
                         "workload: persists winners to the tuning cache "
                         "(~/.cache/repro-tune or $REPRO_TUNE_CACHE) and "
                         "writes the before/after table to BENCH_tune.json; "
                         "shorthand for --only tune with a tune-specific "
                         "default --json path")
    ap.add_argument("--prewarm", action="store_true",
                    help="compile the common benchmark plan buckets before "
                         "timing (plan.prewarm) so suite rows measure "
                         "steady-state executables, not first-call traces")
    ap.add_argument("--host-devices", type=int, default=None,
                    help="force this many XLA host CPU devices (default: "
                         "cpu count) so batched solves shard across cores; "
                         "0 leaves XLA_FLAGS untouched")
    ap.add_argument("--mesh", type=int, default=None,
                    help="solver mesh width for the dist suite: force at "
                         "least this many host devices (power of two) and "
                         "shard huge solves over them; errors out if jax "
                         "was initialized first instead of silently "
                         "falling back to one device")
    args = ap.parse_args(argv)

    if args.tune:
        if args.only and args.only != "tune":
            ap.error(f"--tune conflicts with --only {args.only}")
        args.only = "tune"
        if args.json is None:
            args.json = "BENCH_tune.json"

    if args.mesh is not None:
        if args.mesh < 1 or (args.mesh & (args.mesh - 1)):
            ap.error(f"--mesh must be a positive power of two, "
                     f"got {args.mesh}")
        if args.host_devices is not None and args.host_devices < args.mesh:
            ap.error(f"--host-devices {args.host_devices} is smaller than "
                     f"--mesh {args.mesh}")
        if "jax" in sys.modules:
            raise RuntimeError(
                "--mesh must take effect before first jax init, but jax "
                "is already imported in this process; run the benchmark "
                "driver as the entry point (python -m benchmarks.run)")

    # Must happen before the first jax import: forced host devices let the
    # batched plan executor shard problem batches across CPU cores (the
    # looped baselines are one problem wide and cannot use them).  Only
    # `--only batched` runs get this by default -- partitioning the host
    # would silently change the measured environment of every other
    # suite and break comparability with committed snapshots (full-suite
    # runs therefore record the batched rows UNSHARDED; pass
    # --host-devices explicitly to override either way).
    from repro.hostdev import force_host_devices  # jax-free
    if args.host_devices is not None:
        force_host_devices(args.host_devices)
    elif args.mesh is not None:
        force_host_devices(args.mesh)
    elif args.only in ("batched", "serve"):
        # serve: coalesced flushes shard across host devices exactly like
        # the batched suite; the one-by-one baseline is one problem wide
        # and cannot, which is the point of the comparison.
        force_host_devices()
    elif args.only == "dist":
        # Strong scaling needs >= 4 shards even on small hosts.
        force_host_devices(max(4, os.cpu_count() or 1))

    import jax
    jax.config.update("jax_enable_x64", True)
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.mesh is not None and jax.device_count() < args.mesh:
        raise RuntimeError(
            f"--mesh {args.mesh} requested but only {jax.device_count()} "
            f"devices came up; XLA_FLAGS already configured "
            f"a smaller host-device count before this run "
            f"(XLA_FLAGS={os.environ.get('XLA_FLAGS', '')!r})")

    from benchmarks import (bench_accuracy, bench_batched, bench_dist,
                            bench_fused, bench_kernels, bench_merge,
                            bench_mixed, bench_monitor, bench_partial,
                            bench_robust, bench_scaling, bench_serve,
                            bench_tune, bench_vs_lazy, bench_vs_sterf,
                            bench_workspace, roofline)

    if args.prewarm:
        from repro.core.plan import prewarm
        sizes = (256, 512) if args.quick else (256, 512, 1024, 2048)
        spec = [{"kind": "solve", "n": nn, "batch": 1} for nn in sizes]
        spec.append({"kind": "range", "n": 1024 if args.quick else 4096,
                     "k": 32, "batch": 1})
        info = prewarm(spec)
        print(f"# prewarm: {info['plans']} plans, {info['traces']} traces, "
              f"{info['seconds']:.1f}s", flush=True)

    rows = []
    records = []
    current_suite = [""]

    def report(name, seconds, derived=""):
        line = f"{name},{seconds * 1e6:.1f},{derived}"
        rows.append(line)
        records.append({"suite": current_suite[0], "name": name,
                        "us_per_call": round(seconds * 1e6, 1),
                        "derived": derived})
        print(line, flush=True)

    suites = {
        "workspace": lambda: bench_workspace.run(report),
        "vs_sterf": lambda: bench_vs_sterf.run(
            report, sizes=(512, 1024) if args.quick else (1024, 2048),
            sterf_max=1024 if args.quick else 2048),
        "vs_lazy": lambda: bench_vs_lazy.run(
            report, sizes=(512, 1024) if args.quick else (1024, 2048, 4096)),
        "batched": lambda: bench_batched.run(report, quick=args.quick),
        "scaling": lambda: bench_scaling.run(
            report, sizes=(256, 512, 1024) if args.quick
            else (512, 1024, 2048, 4096)),
        "accuracy": lambda: bench_accuracy.run(
            report, n=1024 if args.quick else 4096),
        "kernels": lambda: bench_kernels.run(
            report, K=512 if args.quick else 2048),
        "fused": lambda: bench_fused.run(
            report, sizes=(512, 1024) if args.quick else (1024, 2048, 4096)),
        "merge": lambda: bench_merge.run(report, quick=args.quick),
        "partial": lambda: bench_partial.run(report, quick=args.quick),
        "mixed": lambda: bench_mixed.run(report, quick=args.quick),
        "serve": lambda: bench_serve.run(report, quick=args.quick),
        "robust": lambda: bench_robust.run(report, quick=args.quick),
        "monitor": lambda: bench_monitor.run(report, quick=args.quick),
        "dist": lambda: bench_dist.run(report, quick=args.quick,
                                       max_shards=args.mesh),
        "roofline": lambda: roofline.run(report),
        # Not part of the default sweep: tuning mutates the on-disk knob
        # cache, so it only runs when explicitly selected (--tune).
        "tune": lambda: bench_tune.run(report, quick=args.quick),
    }

    if args.only and args.only not in suites:
        ap.error(f"--only {args.only!r} names no suite; choose from "
                 f"{', '.join(suites)}")

    failed = []
    for name, fn in suites.items():
        if args.only and name != args.only:
            continue
        if name == "tune" and args.only != "tune":
            continue
        t0 = time.time()
        print(f"# --- {name} ---", flush=True)
        current_suite[0] = name
        try:
            fn()
        except Exception as e:  # record it, run the rest, exit non-zero
            report(f"{name}_ERROR", 0.0, repr(e))
            failed.append(name)
        print(f"# {name} took {time.time() - t0:.1f}s", flush=True)

    print(f"# total rows: {len(rows)}")

    if args.json:
        payload = {
            "meta": {
                "backend": jax.default_backend(),
                "device": str(jax.devices()[0]),
                "num_devices": len(jax.devices()),
                "cpu_count": os.cpu_count(),
                "platform": platform.platform(),
                "jax": jax.__version__,
                "quick": bool(args.quick),
                "only": args.only,
                "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
            },
            "rows": records,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {os.path.abspath(args.json)}")
    if failed:
        sys.exit(f"# suites raised: {', '.join(failed)}")


if __name__ == "__main__":
    main()
