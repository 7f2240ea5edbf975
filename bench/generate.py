"""Problem generators of the benchmark, seeded; numpy only.

``family`` copies the two LAPACK-test-set families the large cells use
(``uniform``: weakly deflating; ``glued_wilkinson``: strongly deflating)
from the program's own ``core/tridiag.py::make_family``, so that a change
to the program cannot change the yardstick.

``lanczos`` makes the tridiagonals of stochastic Lanczos quadrature as a
Hessian spectral-density estimate makes them (Ghorbani, Krishnan & Xiao,
ICML 2019; PyHessian, arXiv:1912.07145): m steps of Lanczos with full
reorthogonalisation on an operator whose spectrum is a dense bulk near
zero plus a few large outliers.  The operator is kept diagonal, in its
own eigenbasis, so a probe is given by its coordinates there: Gaussian,
as those of a Rademacher probe of a Hessian with dense eigenvectors are.
(A Rademacher probe of the diagonal operator itself would give every
probe the same tridiagonal: Lanczos sees only the squared coordinates.)
"""

from __future__ import annotations

import numpy as np


def family(name: str, n: int, seed: int):
    """(d, e) float64 of one LAPACK test-set family."""
    rng = np.random.default_rng(seed)
    if name == "uniform":
        d = rng.uniform(-1.0, 1.0, n)
        e = rng.uniform(0.10, 0.30, n - 1)
    elif name == "glued_wilkinson":
        # Copies of a W_21^+ block glued by weak couplings, 1e-4 times a
        # factor in [0.9, 1.1] drawn per glue: nearly every merge deflates
        # nearly everything.
        blk = min(21, n)
        blk -= (blk % 2 == 0)
        ib = np.arange(1, blk + 1, dtype=np.float64)
        db = np.abs(ib - 1 - (blk - 1) / 2.0)
        d = np.tile(db, n // blk + 1)[:n]
        e = np.ones(n - 1)
        glue = e[blk - 1::blk]
        e[blk - 1::blk] = 1e-4 * rng.uniform(0.9, 1.1, glue.shape)
    else:
        raise ValueError(f"unknown family {name!r}")
    return d, e


def hessian_spectrum(rng, dim: int, outliers: int, outlier_range,
                     bulk_std: float) -> np.ndarray:
    """A Hessian-like spectrum: ``outliers`` log-uniform eigenvalues in
    ``outlier_range`` over a Gaussian bulk of width ``bulk_std`` at 0."""
    lo, hi = outlier_range
    top = np.exp(rng.uniform(np.log(lo), np.log(hi), outliers))
    bulk = rng.normal(0.0, bulk_std, dim - outliers)
    return np.concatenate([bulk, top])


def lanczos(diag: np.ndarray, probes: np.ndarray, m: int):
    """m-step Lanczos tridiagonals of diag(diag), one per probe row.

    probes: (P, D) start vectors.  Full reorthogonalisation (twice, as
    classical Gram-Schmidt) against every earlier Lanczos vector.
    Returns (alpha (P, m), beta (P, m - 1)), float64.
    """
    P, D = probes.shape
    Q = np.zeros((P, m, D))
    q = probes / np.linalg.norm(probes, axis=1, keepdims=True)
    alpha = np.zeros((P, m))
    beta = np.zeros((P, m - 1))
    for j in range(m):
        Q[:, j] = q
        w = diag * q
        alpha[:, j] = np.einsum("pd,pd->p", q, w)
        for _ in range(2):
            h = np.matmul(Q[:, :j + 1], w[:, :, None])
            w = w - np.matmul(Q[:, :j + 1].transpose(0, 2, 1), h)[:, :, 0]
        if j + 1 < m:
            beta[:, j] = np.linalg.norm(w, axis=1)
            q = w / beta[:, j:j + 1]
    return alpha, beta


def slq_problems(seed: int, count: int, m: int, dim: int, outliers: int,
                 outlier_range, bulk_std: float, operator_seed: int):
    """``count`` SLQ tridiagonals (d (count, m), e (count, m - 1)) from
    probes drawn from ``seed``, on the Hessian-like operator drawn from
    ``operator_seed``: one deployment's Hessian, new probes in every
    run."""
    diag = hessian_spectrum(np.random.default_rng(operator_seed), dim,
                            outliers, outlier_range, bulk_std)
    probes = np.random.default_rng(seed).standard_normal((count, dim))
    return lanczos(diag, probes, m)
