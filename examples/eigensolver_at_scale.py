"""The eigensolver as a standalone service: large tridiagonals, all
methods, timing + workspace accounting (paper Tables 1-3 in miniature).

    PYTHONPATH=src python examples/eigensolver_at_scale.py [--n 8192]

Batched serving (the plan/executor front door -- one device solve for a
whole batch of problems, B * O(n) persistent state):

    PYTHONPATH=src python examples/eigensolver_at_scale.py --n 1024 --batch 64
"""

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=8192)
    ap.add_argument("--family", default="uniform")
    ap.add_argument("--batch", type=int, default=1,
                    help="solve a batch of B independent problems through "
                         "the plan/executor core (1 = single-problem mode)")
    args = ap.parse_args()

    # Before jax imports: forced host devices let batched solves shard
    # problem batches across CPU cores.
    if args.batch > 1:
        from repro.hostdev import force_host_devices  # jax-free
        force_host_devices()

    import jax
    jax.config.update("jax_enable_x64", True)
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    import numpy as np
    import scipy.linalg as sla

    from repro.core import (eigvalsh_tridiagonal_batch,
                            eigvalsh_tridiagonal_br, make_family,
                            make_family_batch, plan_cache_stats,
                            workspace_model, workspace_model_lazy)

    n = args.n
    if args.batch > 1:
        B = args.batch
        ds, es = make_family_batch(args.family, n, B)
        print(f"family={args.family} n={n} batch={B} "
              f"devices={len(jax.devices())}")

        t0 = time.time()
        res = eigvalsh_tridiagonal_batch(ds, es)
        res.eigenvalues.block_until_ready()
        t_cold = time.time() - t0
        t0 = time.time()
        res = eigvalsh_tridiagonal_batch(ds, es)
        res.eigenvalues.block_until_ready()
        t_warm = time.time() - t0

        # warm the single-solve executable so the loop timing is compile-free
        eigvalsh_tridiagonal_br(ds[0], es[0]).eigenvalues.block_until_ready()
        t0 = time.time()
        for b in range(B):
            out = eigvalsh_tridiagonal_br(ds[b], es[b]).eigenvalues
        out.block_until_ready()
        t_loop = time.time() - t0

        ref = sla.eigh_tridiagonal(ds[0], es[0], eigvals_only=True)
        err = np.max(np.abs(np.asarray(res.eigenvalues[0]) - ref)) / \
            max(1, np.max(np.abs(ref)))
        ws = workspace_model(n, batch=B)
        print(f"batched: cold {t_cold:.2f}s, warm {t_warm:.3f}s "
              f"({t_warm / B * 1e3:.2f} ms/problem), e_fwd {err:.2e}")
        print(f"looped singles: {t_loop:.3f}s "
              f"({t_loop / B * 1e3:.2f} ms/problem) "
              f"-> batching speedup {t_loop / t_warm:.2f}x")
        print(f"batch workspace: {ws['persistent_bytes'] / 2**20:8.2f} MiB "
              f"persistent ({ws['model']})")
        print(f"plan cache: {plan_cache_stats()}")
        return

    d, e = make_family(args.family, n)
    print(f"family={args.family} n={n}")

    t0 = time.time()
    res = eigvalsh_tridiagonal_br(d, e)
    res.eigenvalues.block_until_ready()
    t_cold = time.time() - t0
    t0 = time.time()
    res = eigvalsh_tridiagonal_br(d, e)
    res.eigenvalues.block_until_ready()
    t_warm = time.time() - t0

    t0 = time.time()
    ref = sla.eigh_tridiagonal(d, e, eigvals_only=True)
    t_scipy = time.time() - t0
    err = np.max(np.abs(np.asarray(res.eigenvalues) - ref)) / \
        max(1, np.max(np.abs(ref)))

    ws_br = workspace_model(n)
    ws_lazy = workspace_model_lazy(n)
    print(f"BR:    cold {t_cold:.2f}s, warm {t_warm:.2f}s, e_fwd {err:.2e}")
    print(f"scipy stemr reference: {t_scipy:.2f}s")
    print(f"BR workspace:   {ws_br['persistent_bytes']/2**20:8.2f} MiB  "
          f"({ws_br['model']})")
    print(f"lazy workspace: {ws_lazy['persistent_bytes']/2**20:8.2f} MiB  "
          f"({ws_lazy['model']})")
    print(f"deflation profile (active rank per level): "
          f"{[int(np.mean(k)) for k in res.kprime_per_level]}")


if __name__ == "__main__":
    main()
