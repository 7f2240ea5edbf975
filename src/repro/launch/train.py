"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --steps 200 --batch 8 --seq 128 --smoke

Wires together every substrate layer: config -> model -> sharded train
step (pjit) -> data pipeline -> checkpoint manager (atomic, auto-resume)
-> watchdog + straggler monitor -> spectral governor (the paper's
eigenvalue-only workflow driving the LR).

The governor's measurement is a product workload: every --spectral-every
steps one jitted Krylov pass (HVP on a --probe-batch sub-batch -> Lanczos
tridiagonal; batch is a traced argument so repeated probes never
retrace) feeds a k=1 sliced extremal solve.  With --serve-monitor that
solve is submitted through an EigensolverClient as a ``kind="edges"``
request -- same route key as plain range traffic, so concurrent
trainers' probes coalesce into shared launches -- and is bit-identical
to the direct path.  Probe wall time lands on SOLVE_COUNTER's probe
gauge and in the returned report (probe_seconds / step_seconds is the
monitoring overhead).

On the CPU container this runs reduced configs end-to-end (--smoke); on a
TPU cluster the same driver runs the full configs against
make_production_mesh().
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import ARCHS, get_config, get_smoke_config
from repro.data import DataPipeline, SyntheticTokens
from repro.dist.sharding import param_shardings, set_activation_mesh
from repro.launch.mesh import make_mesh_for
from repro.launch.steps import make_train_step
from repro.models import transformer as tf
from repro.optim.optimizers import get_optimizer
from repro.optim.spectral_adapt import SpectralGovernor
from repro.runtime import StragglerMonitor, Watchdog
from repro.runtime.compile_cache import enable_compile_cache
from repro.spectral import lanczos_tridiag_batch, make_hvp
from repro.spectral.slq import _rademacher_like, edges_from_tridiag


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b", choices=ARCHS)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adafactor", "sgd"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--spectral-every", type=int, default=0,
                    help="probe curvature every N steps (0 = off)")
    ap.add_argument("--serve-monitor", action="store_true",
                    help="route curvature probes through the eigensolver "
                         "service (kind='edges') instead of direct solves")
    ap.add_argument("--probe-steps", type=int, default=8,
                    help="Lanczos steps per curvature probe")
    ap.add_argument("--probe-batch", type=int, default=2,
                    help="data sub-batch the probe HVP runs on")
    ap.add_argument("--target-sharpness", type=float, default=100.0)
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    n_dev = len(jax.devices())
    mesh = make_mesh_for(n_dev) if n_dev > 1 else None
    if mesh is not None:
        set_activation_mesh(mesh)

    rng = jax.random.PRNGKey(args.seed)
    params = tf.init_model(rng, cfg)
    opt = get_optimizer(args.optimizer, lr=args.lr)
    opt_state = opt.init(params)

    step_fn = make_train_step(cfg, opt, remat=args.remat)
    if mesh is not None:
        p_sh = param_shardings(params, mesh)
        jitted = jax.jit(step_fn, in_shardings=(p_sh, None, None),
                         donate_argnums=(0, 1))
        params = jax.device_put(params, p_sh)
    else:
        jitted = jax.jit(step_fn, donate_argnums=(0, 1))

    # --- curvature probe (jitted once; batch is an argument, so every
    # probe reuses the same executable -- no per-probe retrace) ----------
    pb = max(1, min(args.probe_batch, args.batch))

    @jax.jit
    def krylov(p, full_batch, key):
        sub = {k: v[:pb] for k, v in full_batch.items()}

        def loss_of(pp):
            return tf.loss_fn(pp, cfg, sub)[0]

        hvp = make_hvp(loss_of, p)
        probe = _rademacher_like(key, p)
        stacked = jax.tree.map(lambda x: x[None], probe)   # 1-probe batch
        return lanczos_tridiag_batch(hvp, stacked, args.probe_steps)

    # --- data -------------------------------------------------------------
    extra_fn = None
    if cfg.is_encdec:
        def extra_fn(step, shard, bsz):
            r = np.random.default_rng(np.random.SeedSequence([7, step, shard]))
            return {"frames": r.standard_normal(
                (bsz, cfg.encoder_seq_len, cfg.d_model)).astype(np.float32)}
    pipe = DataPipeline(
        SyntheticTokens(cfg.vocab_size, args.seq, seed=args.seed),
        global_batch=args.batch, extra_fn=extra_fn).start()

    # --- fault tolerance ---------------------------------------------------
    ckpt = CheckpointManager(args.ckpt_dir, period=args.ckpt_every)
    restored, meta, start_step = ckpt.resume((params, opt_state))
    if restored is not None:
        params, opt_state = restored
        print(f"[train] resumed from step {start_step}")
    watchdog = Watchdog(args.ckpt_dir + "/heartbeat.json",
                        timeout_s=600).start()
    straggler = StragglerMonitor()
    governor = SpectralGovernor(period=max(args.spectral_every, 1),
                                target_sharpness=args.target_sharpness)

    client = None
    if args.serve_monitor and args.spectral_every:
        from repro.serve import EigensolverClient
        # Prewarm the edges bucket so the first probe hits a compiled
        # executable; batch=1 prewarms the (2*1 problems) launch shape a
        # single trainer's probe flush produces.
        client = EigensolverClient(
            max_wait_us=100,
            prewarm=[{"kind": "edges", "n": args.probe_steps, "k": 1,
                      "batch": 1}])

    it = iter(pipe)
    queued = None
    if args.spectral_every:
        # Warm the probe path (Lanczos jit + solve executable) outside the
        # timed loop; the batch is handed back to step 0 afterwards.
        queued = {k: jnp.asarray(v) for k, v in next(it).items()}
        alpha, beta = krylov(params, queued, rng)
        edges_from_tridiag(alpha, beta, k=1, client=client)

    lr_scale = 1.0
    losses, lr_scales = [], []
    step_seconds = 0.0   # excludes this run's first step (jit compile)
    probe_seconds = 0.0
    first_step = True
    try:
        for step in range(start_step, args.steps):
            if queued is not None:
                batch, queued = queued, None
            else:
                batch = {k: jnp.asarray(v) for k, v in next(it).items()}
            t0 = time.time()
            params, opt_state, metrics = jitted(params, opt_state, batch,
                                                lr_scale)
            loss = float(metrics["loss"])
            dt = time.time() - t0
            if not first_step:
                step_seconds += dt
            first_step = False
            straggler.record(step, dt)
            watchdog.beat(step, loss=loss)
            losses.append(loss)
            lr_scales.append(lr_scale)

            if (args.spectral_every and step
                    and step % args.spectral_every == 0):
                # Eigenvalue-only curvature probe (paper's workflow):
                # jitted Lanczos + k=1 sliced extremal solve, optionally
                # serve-routed (bit-identical either way).
                tp = time.time()
                alpha, beta = krylov(params, batch,
                                     jax.random.fold_in(rng, step))
                lr_scale = governor.probe_tridiag(alpha, beta,
                                                  client=client)
                probe_seconds += time.time() - tp
                print(f"[spectral] step={step} "
                      f"lam_max={governor.lam_max:.3e} "
                      f"lr_scale={lr_scale:.3f}")

            ckpt.maybe_save(step + 1, (params, opt_state),
                            meta={"loss": loss})
            if step % args.log_every == 0:
                print(f"step={step:5d} loss={loss:.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"{dt*1e3:.0f}ms", flush=True)
    finally:
        pipe.stop()
        watchdog.stop()

    serve_snap = None
    if client is not None:
        serve_snap = client.metrics()
        client.close()
    print(f"[train] done. loss {losses[0]:.4f} -> {losses[-1]:.4f}; "
          f"probes={governor.probes} probe_s={probe_seconds:.3f} "
          f"step_s={step_seconds:.3f}; "
          f"straggler report: {straggler.report()}")
    return {"losses": losses, "lr_scales": lr_scales,
            "lam_max": governor.lam_max, "probes": governor.probes,
            "probe_seconds": probe_seconds, "step_seconds": step_seconds,
            "steps": len(losses), "min_scale": governor.min_scale,
            "serve": serve_snap, "straggler": straggler.report()}


if __name__ == "__main__":
    enable_compile_cache()
    main()
