"""Quickstart: the boundary-row eigensolver public API.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax
jax.config.update("jax_enable_x64", True)

import numpy as np
import scipy.linalg as sla

from repro.core import (eigvalsh_tridiagonal, eigvalsh_tridiagonal_br,
                        eigvalsh_tridiagonal_range, make_family,
                        workspace_model, workspace_model_lazy)
from repro.runtime.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    # A symmetric tridiagonal from the paper's `uniform` family.
    n = 2048
    d, e = make_family("uniform", n)

    # --- eigenvalues via boundary-row D&C (the paper's algorithm) --------
    lam = eigvalsh_tridiagonal(d, e)                    # method="br"
    ref = sla.eigh_tridiagonal(d, e, eigvals_only=True)
    err = np.max(np.abs(np.asarray(lam) - ref)) / np.max(np.abs(ref))
    print(f"BR vs LAPACK stemr: e_fwd = {err:.2e}  (n = {n})")

    # --- the other design points ------------------------------------------
    for method in ("sterf", "lazy", "full"):
        lam_m = eigvalsh_tridiagonal(d, e, method=method)
        err_m = np.max(np.abs(np.asarray(lam_m) - ref))
        print(f"  method={method:6s} max|diff vs ref| = {err_m:.2e}")

    # --- partial spectrum: k << n eigenvalues by index or value window ----
    top8 = eigvalsh_tridiagonal_range(d, e, select="i", il=n - 8, iu=n - 1)
    err_p = np.max(np.abs(np.asarray(top8) - ref[n - 8:]))
    print(f"top-8 slice (Sturm bisection): max|diff vs ref| = {err_p:.2e}")

    # --- boundary rows: the O(n) state that replaces dense eigenvectors ---
    res = eigvalsh_tridiagonal_br(d, e, return_boundary=True)
    print(f"boundary rows: |blo| = {np.linalg.norm(res.blo):.6f}, "
          f"|bhi| = {np.linalg.norm(res.bhi):.6f}  (unit rows of Q)")

    # --- the memory story (paper Table 1) ----------------------------------
    n_big = 65536
    br = workspace_model(n_big)["persistent_bytes"] / 2**20
    lazy = workspace_model_lazy(n_big)["persistent_bytes"] / 2**30
    print(f"workspace at n={n_big}: BR = {br:.1f} MiB (O(n)), "
          f"lazy-replay D&C = {lazy:.1f} GiB (O(n^2))")


if __name__ == "__main__":
    main()
