"""Spectral curvature monitoring as a product workload of the
eigensolver service.

Each trainer job runs its own reduced model + SpectralGovernor; every
--probe-every steps it builds a Krylov tridiagonal (HVP -> Lanczos, one
shared jitted function) and submits the k=1 sliced extremal solve
through ONE shared EigensolverClient as a ``kind="edges"`` request.
Because every job's probe resolves to the same route key (same m, k,
dtype), the scheduler coalesces concurrent probes into a single device
launch -- with --jobs N > 1 the per-flush problem count demonstrably
exceeds one request's worth.

    PYTHONPATH=src python examples/spectral_monitor.py            # 1 job
    PYTHONPATH=src python examples/spectral_monitor.py --jobs 4   # coalesced

Single-job mode also prints the SLQ spectral-density sketch (the full
Gauss-quadrature view the boundary-row solver produces natively).
"""

import argparse
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.data import SyntheticTokens
from repro.models import transformer as tf
from repro.optim.optimizers import adamw
from repro.optim.spectral_adapt import SpectralGovernor
from repro.serve import EigensolverClient
from repro.spectral import lanczos_tridiag_batch, make_hvp, slq_spectrum
from repro.spectral.slq import _rademacher_like
from repro.runtime.compile_cache import enable_compile_cache

PROBE_STEPS = 8


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--jobs", type=int, default=1,
                    help="concurrent trainer jobs sharing one client")
    ap.add_argument("--steps", type=int, default=45)
    ap.add_argument("--probe-every", type=int, default=15)
    ap.add_argument("--seq", type=int, default=64)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config("qwen3-0.6b")
    opt = adamw(lr=3e-3)

    @jax.jit
    def step(params, state, batch, lr_scale):
        (loss, _), grads = jax.value_and_grad(
            lambda p: tf.loss_fn(p, cfg, batch), has_aux=True)(params)
        params, state = opt.apply(params, grads, state, lr_scale=lr_scale)
        return params, state, loss

    @jax.jit
    def krylov(params, batch, key):
        sub = {k: v[:1] for k, v in batch.items()}
        hvp = make_hvp(lambda p: tf.loss_fn(p, cfg, sub)[0], params)
        probe = jax.tree.map(lambda x: x[None],
                             _rademacher_like(key, params))
        return lanczos_tridiag_batch(hvp, probe, PROBE_STEPS)

    # One client for all jobs.  A generous flush window lets concurrent
    # probes land in the same flush; the barrier below makes the demo
    # deterministic rather than racing the window.
    client = EigensolverClient(
        max_wait_us=50_000,
        prewarm=[{"kind": "edges", "n": PROBE_STEPS, "k": 1,
                  "batch": args.jobs}])
    barrier = threading.Barrier(args.jobs)

    def run_job(job: int, out: dict):
        rng = jax.random.PRNGKey(job)
        params = tf.init_model(rng, cfg)
        state = opt.init(params)
        src = SyntheticTokens(cfg.vocab_size, args.seq, seed=job)
        governor = SpectralGovernor(target_sharpness=50.0,
                                    period=args.probe_every)
        lr_scale = 1.0
        for i in range(args.steps):
            batch = {k: jnp.asarray(v) for k, v in src.batch(i, 0, 8).items()}
            params, state, loss = step(params, state, batch, lr_scale)
            if i % args.probe_every == 0:
                alpha, beta = krylov(params, batch,
                                     jax.random.fold_in(rng, i))
                jax.block_until_ready(alpha)
                # All jobs reach the service inside one flush window.
                barrier.wait()
                lr_scale = governor.probe_tridiag(alpha, beta,
                                                  client=client)
                print(f"[job {job}] step {i:3d} loss={float(loss):.3f} "
                      f"lam_max={governor.lam_max:9.2f} "
                      f"lr_scale={lr_scale:.3f}", flush=True)
                if args.jobs == 1 and i == 0:
                    # Bonus: the full SLQ density view (direct path).
                    hvp = make_hvp(
                        lambda p: tf.loss_fn(p, cfg, batch)[0], params)
                    est = slq_spectrum(hvp, params, rng, num_probes=2,
                                       num_steps=12)
                    grid = np.linspace(est.lam_min, est.lam_max, 7)
                    dens = est.density(grid)
                    bars = "".join("#" if x > np.max(dens) / 4 else "."
                                   for x in dens)
                    print(f"[job {job}] density[{bars}] "
                          f"trace~{est.trace_est:.1f}")
        out[job] = governor

    results: dict = {}
    threads = [threading.Thread(target=run_job, args=(j, results))
               for j in range(args.jobs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = client.metrics()
    client.close()

    label = f"range/n{PROBE_STEPS}/k1/" \
            f"{'float64' if jax.config.jax_enable_x64 else 'float32'}"
    bucket = snap["buckets"][label]
    print(f"\n[serve] bucket {label}: requests={bucket['requests']} "
          f"flushes={bucket['flushes']} "
          f"coalesce_factor={bucket['coalesce_factor']:.2f} "
          f"errors={bucket['errors']}")
    assert bucket["errors"] == 0, "serve errors during monitoring"
    assert bucket["coalesce_factor"] > 1.0, "no coalescing observed"
    if args.jobs > 1:
        # Cross-job merging: strictly fewer launches than requests, and
        # each flush carried more than one probe's 2 problems.
        assert bucket["flushes"] < bucket["requests"], \
            f"probes were not coalesced: {bucket}"
        assert bucket["coalesce_factor"] > 2.0, \
            f"flushes never merged concurrent probes: {bucket}"
        print(f"[serve] {args.jobs} jobs coalesced: "
              f"{bucket['requests']} probe requests -> "
              f"{bucket['flushes']} device launches")


if __name__ == "__main__":
    main()
