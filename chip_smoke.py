#!/usr/bin/env python3
"""Smoke test of the eigensolver's default path on one TPU chip.

Drives the system once through the entry points a user calls, in one
process, and checks every answer against
``scipy.linalg.eigvalsh_tridiagonal`` in float64 at the README's external
bar, ``64 * eps * max(1, ||T||_inf)``.  The reference is LAPACK bisection
(``lapack_driver="stebz"``): scipy's default MRRR driver itself departs
from bisection by more than the bar once n reaches a few thousand (1.6x
the bar on ``uniform`` and 11.6x on glued Wilkinson at n = 8192, on CPU).
Problems above REF_FULL_N are checked on three windows of REF_WINDOW
eigenvalues (bottom, middle, top), which bisection reaches in seconds;
every eigenvalue of the certified routes is also count-certified by the
solver itself.


  arith    -- float64 arithmetic on the device against numpy: the largest
              relative error of add, mul, div, sqrt and log in units of
              eps_f64, and the smallest power of two the device keeps.
              A TPU emulates float64 with fewer mantissa bits and
              float32's exponent range, which is why the default float64
              solve on a TPU takes the certified mixed route;
  large    -- ``repro.core.eigvalsh_tridiagonal`` on one n = 65536 problem
              (the paper's case: its f32 eigenvector matrix alone would be
              17.2 GB, more than a v5e's 16 GB of HBM), for the weakly
              deflating ``uniform`` family and the strongly deflating
              glued Wilkinson family: the default float64 call (on a TPU
              ``precision="auto"`` resolves to the mixed route: the f32
              tree through the Pallas kernels, then float64 Sturm
              certification and polish through XLA), cold and warm, and
              ``precision="mixed", certify=True`` with no escalation down
              the degradation ladder allowed;
  serve    -- ``repro.serve.EigensolverClient``: a burst of 256 independent
              n = 96 SLQ-shaped problems through ``solve_async``, then a
              top-32 ``solve_range`` slice of the n = 65536 problem in
              float64 (Sturm counts through XLA) and in float32 (the Pallas
              ``sturm_count`` kernel), checked at float32's eps.

``--chips 4`` runs only the sharded path: one n = SHARDED_N default
float64 solve with ``mesh=4`` (what ``mesh="auto"`` picks on a four-chip
host for padded n >= 16384), checked against scipy, and its largest
difference from the same solve with ``mesh=1``.

Earlier lines report, per phase: the route, the backend each kernel
resolves to, compile seconds (set-up: tracing, lowering, compiling and
persistent-cache reads), one warm call's seconds, the worst error against
the bar, and the device's peak memory.  The last line is one JSON object,
``{"ok": true, "device": {...}}``.  Any failure raises and exits non-zero;
a host without a TPU exits non-zero before solving anything.

Usage:  python chip_smoke.py [--seed N] [--chips 1|4]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import scipy.linalg  # noqa: E402

from repro.core import eigvalsh_tridiagonal, make_family  # noqa: E402
from repro.core import plan as _plan  # noqa: E402
from repro.core.request import (SolveRequest, execute_request,  # noqa: E402
                                route_request)
from repro.kernels import ops  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402
from repro.serve import EigensolverClient  # noqa: E402

LARGE_N = 65536
FAMILIES = ("uniform", "glued_wilkinson")
SLQ_N, SLQ_BATCH = 96, 256
TOP_K = 32
SHARDED_N = 262144
BAR_ULPS = 64
REF_FULL_N = 4096
REF_WINDOW = 256

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Sums JAX's own compile-time events (set-up time, never solve time)."""

    def __init__(self):
        self.seconds = 0.0
        self.backend_compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration
            self.backend_compiles += event == _BACKEND_COMPILE


CLOCK = None


def say(tag: str, **fields) -> None:
    print(f"{tag}: " + json.dumps(fields, default=str), flush=True)


def peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def bar(d, e, dtype=np.float64) -> float:
    """64 * eps * max(1, ||T||_inf) -- the README's external bar."""
    ad, ae = np.abs(d), np.abs(e)
    row = ad.copy()
    row[:-1] += ae
    row[1:] += ae
    return BAR_ULPS * float(np.finfo(dtype).eps) * max(1.0, float(row.max()))


def reference(d, e, lo=0, hi=None):
    """{index: eigenvalue} from LAPACK bisection, for indices lo..hi
    (default: all, or three windows when n > REF_FULL_N)."""
    n = len(d)
    if hi is not None:
        windows = [(lo, hi)]
    elif n <= REF_FULL_N:
        windows = [(0, n - 1)]
    else:
        w = REF_WINDOW
        windows = [(0, w - 1), (n // 2 - w // 2, n // 2 + w // 2 - 1),
                   (n - w, n - 1)]
    idx = np.concatenate([np.arange(a, b + 1) for a, b in windows])
    val = np.concatenate([scipy.linalg.eigvalsh_tridiagonal(
        d, e, select="i", select_range=(a, b), lapack_driver="stebz")
        for a, b in windows])
    return idx, val


def worst_error(lam, ref, limit, what) -> float:
    """Largest |lam[i] - ref[i]| over the reference's indices (lam holds
    the full spectrum, or exactly the reference's indices)."""
    idx, val = ref
    lam = np.asarray(lam, np.float64).reshape(-1)
    if not np.all(np.isfinite(lam)):
        raise AssertionError(f"{what}: non-finite eigenvalues")
    if lam.shape != val.shape:
        lam = lam[idx]
    err = float(np.max(np.abs(lam - val)))
    if err > limit:
        raise AssertionError(f"{what}: error {err:.3e} > bar {limit:.3e}")
    return err


def timed(fn):
    """(result, wall seconds, compile seconds, backend compiles) of fn()."""
    s0, c0 = CLOCK.seconds, CLOCK.backend_compiles
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(getattr(out, "eigenvalues", out))
    return (out, time.perf_counter() - t0, CLOCK.seconds - s0,
            CLOCK.backend_compiles - c0)


def cold_warm(fn):
    """Run fn twice: the first call pays set-up, the second is timed warm
    and must compile nothing."""
    out, cold_s, compile_s, _ = timed(fn)
    out2, warm_s, _, compiles = timed(fn)
    if compiles:
        raise AssertionError(f"warm call compiled {compiles} programs")
    return out, out2, cold_s, compile_s, warm_s


def kernel_backends(tree_dtype, sturm_dtype) -> dict:
    return {"secular_roots": ops.resolve_backend(tree_dtype),
            "fused_update": ops.resolve_backend(tree_dtype),
            "resident_merge": ops.resolve_backend(tree_dtype),
            "sturm_count": ops.resolve_backend(sturm_dtype)}


def executable_stats(key) -> dict:
    """tpu_custom_call sites and memory of the compiled tree executable of
    a route (already in the process's cache: no recompile)."""
    tree = jnp.float32 if key.precision == "mixed" else jnp.dtype(key.dtype)
    arg = jax.ShapeDtypeStruct((1, key.padded_n), tree)
    compiled = _plan._executor.lower(
        arg, arg, None, leaf=key.leaf, chunk=key.chunk, niter=key.niter,
        use_zhat=key.use_zhat, return_boundary=key.return_boundary,
        tol_factor=key.tol_factor, stream_threshold=key.stream_threshold,
        deflate_budget=key.deflate_budget,
        resident_threshold=key.resident_threshold,
        fused=key.fused).compile()
    mem = compiled.memory_analysis()
    return {"tpu_custom_call": compiled.as_text().count("tpu_custom_call"),
            "temp_bytes": mem.temp_size_in_bytes,
            "argument_bytes": mem.argument_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes}


def arith_phase(seed: int) -> None:
    """float64 arithmetic on the device, in eps_f64 units (informative:
    the bar is checked on the solves)."""
    rng = np.random.default_rng(seed)
    a = rng.uniform(0.5, 2.0, 1 << 16) * 2.0 ** rng.integers(-20, 20, 1 << 16)
    b = rng.uniform(0.5, 2.0, 1 << 16) * 2.0 ** rng.integers(-20, 20, 1 << 16)
    ops_ = {"add": (jnp.add, np.add), "mul": (jnp.multiply, np.multiply),
            "div": (jnp.divide, np.divide),
            "sqrt": (lambda x, _: jnp.sqrt(x), lambda x, _: np.sqrt(x)),
            "log": (lambda x, _: jnp.log(x), lambda x, _: np.log(x))}
    ad, bd = jnp.asarray(a), jnp.asarray(b)
    err = {}
    for name, (fj, fn) in ops_.items():
        got = np.asarray(jax.jit(fj)(ad, bd))
        want = fn(a, b)
        err[name] = float(np.max(np.abs(got - want) / np.abs(want))
                          / np.finfo(np.float64).eps)
    k = np.arange(100, 1100)
    kept = np.asarray(jax.jit(jnp.multiply)(jnp.ones(len(k)),
                                            jnp.asarray(2.0 ** -k)))
    say("arith", dtype="float64", max_rel_err_eps64=err,
        smallest_kept_power_of_two=int(-k[kept > 0].max()))


def large_phase(seed: int) -> None:
    """The default float64 call on ``uniform`` pays the route's compile
    and is repeated warm (no compile allowed); then each family runs
    ``precision="mixed", certify=True`` (the same tree executable plus
    the certificate sweep) and may not escalate."""
    on_tpu = jax.devices()[0].platform == "tpu"
    for fam in FAMILIES:
        d, e = make_family(fam, LARGE_N, seed=seed)
        ref, limit = reference(d, e), bar(d, e)
        # Device arrays, as eigvalsh_tridiagonal makes them: every call
        # then takes the same front-door path.
        d, e = jnp.asarray(d), jnp.asarray(e)
        runs = [("mixed_certified", True, {"precision": "mixed"})]
        if fam == "uniform":
            runs.insert(0, ("default", False, {}))
        for name, certify, knobs in runs:
            req = SolveRequest(d=d, e=e, certify=certify, knobs=knobs)
            key = route_request(req).route
            if on_tpu and key.precision != "mixed":
                raise AssertionError(
                    f"float64 on TPU routed to {key.precision!r}")
            res, first_s, compile_s, _ = timed(lambda: execute_request(req))
            diag = res.diagnostics or {}
            if diag.get("escalations"):
                raise AssertionError(
                    f"{name} {fam}: degradation ladder fired: "
                    f"{diag['escalations']}")
            if certify and diag.get("certified") != LARGE_N:
                raise AssertionError(f"{name} {fam}: {diag}")
            err = worst_error(res.eigenvalues, ref, limit,
                              f"{name} {fam} n={LARGE_N}")
            warm_s = None
            if name == "default":
                lam, warm_s, _, compiles = timed(
                    lambda: eigvalsh_tridiagonal(d, e))
                err = max(err, worst_error(lam, ref, limit,
                                           f"{name} {fam} n={LARGE_N}"))
                if compiles:
                    raise AssertionError(f"warm solve compiled {compiles}")
            say("large", phase=f"{name}/{fam}", n=LARGE_N,
                route=key._asdict(),
                kernels=kernel_backends(jnp.float32, jnp.float64),
                compile_s=compile_s, first_call_s=first_s,
                warm_solve_s=warm_s, worst_error=err, bar=limit,
                certified=diag.get("certified"), lanes=diag.get("lanes"),
                peak_bytes_in_use=peak_bytes())
    stats = executable_stats(key)
    say("large", phase="mixed/executable", n=LARGE_N, **stats)
    if stats["tpu_custom_call"] == 0:
        raise AssertionError("no Pallas kernel in the mixed route")


def serve_phase(seed: int) -> None:
    rng = np.random.default_rng(seed)
    burst = [make_family("normal", SLQ_N, seed=int(s))
             for s in rng.integers(0, 2**31, SLQ_BATCH)]
    refs = [(reference(d, e), bar(d, e)) for d, e in burst]
    d_big, e_big = make_family("uniform", LARGE_N, seed=seed)
    top_ref = reference(d_big, e_big, LARGE_N - TOP_K, LARGE_N - 1)

    with EigensolverClient() as client:
        def run_burst():
            futs = [client.solve_async(d, e) for d, e in burst]
            return [f.result().eigenvalues for f in futs]
        lams, _, cold_s, compile_s, warm_s = cold_warm(run_burst)
        err = max(worst_error(lam, ref, limit, f"slq problem {i}")
                  for i, (lam, (ref, limit)) in enumerate(zip(lams, refs)))
        key = route_request(SolveRequest(d=burst[0][0],
                                         e=burst[0][1])).route
        tree = jnp.float32 if key.precision == "mixed" else jnp.float64
        say("serve", phase="slq_burst", problems=SLQ_BATCH, n=SLQ_N,
            route=key._asdict(),
            kernels=kernel_backends(tree, jnp.float64),
            compile_s=compile_s, first_burst_s=cold_s, warm_burst_s=warm_s,
            worst_error=err, bar=max(r[1] for r in refs),
            peak_bytes_in_use=peak_bytes())

        for dtype in (jnp.float64, jnp.float32):
            def run_range(dtype=dtype):
                return client.solve_range(d_big, e_big, select="i",
                                          il=LARGE_N - TOP_K,
                                          iu=LARGE_N - 1, dtype=dtype)
            lam, _, cold_s, compile_s, warm_s = cold_warm(run_range)
            limit = bar(d_big, e_big, dtype)
            err = worst_error(lam, top_ref, limit,
                              f"top-{TOP_K} {jnp.dtype(dtype).name}")
            key = route_request(SolveRequest(
                d=d_big, e=e_big, kind="range", il=LARGE_N - TOP_K,
                iu=LARGE_N - 1, knobs={"dtype": dtype})).route
            say("serve", phase=f"top{TOP_K}/{jnp.dtype(dtype).name}",
                n=LARGE_N, route=key._asdict(),
                kernels={"sturm_count": ops.resolve_backend(dtype)},
                compile_s=compile_s, first_call_s=cold_s, warm_call_s=warm_s,
                worst_error=err, bar=limit, peak_bytes_in_use=peak_bytes())

        buckets = client.metrics()["buckets"]
    bad = {label: {k: row[k] for k in ("errors", "retries", "fallbacks",
                                       "degradations") if row[k]}
           for label, row in buckets.items()}
    bad = {label: v for label, v in bad.items() if v}
    say("serve", phase="metrics", buckets=len(buckets), faults=bad)
    if bad:
        raise AssertionError(f"serving recorded faults: {bad}")


def sharded_phase(seed: int) -> None:
    d, e = make_family("uniform", SHARDED_N, seed=seed)
    ref, limit = reference(d, e), bar(d, e)
    d, e = jnp.asarray(d), jnp.asarray(e)
    key = route_request(SolveRequest(d=d, e=e, knobs={"mesh": 4})).route
    mesh = _plan._solver_mesh(key.shards)
    ids = sorted(dev.id for dev in mesh.devices.flat)
    batch = _plan._batch_sharding(4)
    batch_ids = sorted(dev.id for dev in batch.mesh.devices.flat)
    say("sharded", phase="placement", solver_mesh_devices=ids,
        batch_mesh_devices=batch_ids)
    if len(set(ids)) != 4 or len(set(batch_ids)) != 4:
        raise AssertionError("shards are not on four distinct devices")
    if key.shards != 4:
        raise AssertionError(f"mesh=4 routed to {key.shards} shards")

    lam4, _, cold_s, compile_s, warm_s = cold_warm(
        lambda: eigvalsh_tridiagonal(d, e, mesh=4))
    err = worst_error(lam4, ref, limit, f"mesh=4 n={SHARDED_N}")
    tree = jnp.float32 if key.precision == "mixed" else jnp.float64
    say("sharded", phase="mesh=4", n=SHARDED_N, route=key._asdict(),
        kernels=kernel_backends(tree, jnp.float64),
        compile_s=compile_s, first_call_s=cold_s, warm_solve_s=warm_s,
        worst_error=err, bar=limit, peak_bytes_in_use=peak_bytes())
    lam1, _, cold_s, compile_s, warm_s = cold_warm(
        lambda: eigvalsh_tridiagonal(d, e, mesh=1))
    err1 = worst_error(lam1, ref, limit, f"mesh=1 n={SHARDED_N}")
    diff = float(np.max(np.abs(np.asarray(lam4) - np.asarray(lam1))))
    say("sharded", phase="mesh=1", n=SHARDED_N, compile_s=compile_s,
        first_call_s=cold_s, warm_solve_s=warm_s, worst_error=err1,
        max_abs_diff_vs_mesh4=diff, bit_identical=diff == 0.0)


def main(argv=None) -> int:
    global CLOCK
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = p.parse_args(argv)

    jax.config.update("jax_enable_x64", True)
    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({len(devices)} device(s))", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    CLOCK = CompileClock()
    say("device", kind=dev.device_kind, count=len(devices),
        jax=jax.__version__, compile_cache=cache_dir)

    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase(args.seed)
    else:
        arith_phase(args.seed)
        large_phase(args.seed)
        serve_phase(args.seed)
    say("total", seconds=time.perf_counter() - t0,
        compile_s=CLOCK.seconds, backend_compiles=CLOCK.backend_compiles)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
