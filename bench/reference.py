"""The plain reference: LAPACK eigenvalues of a symmetric tridiagonal.

Imports nothing of the program under test and takes nothing it made:
the reference is computed from the same host (d, e) the benchmark
generated, after the measured window has closed.

Two LAPACK drivers, chosen per request dtype by the configuration:

- ``stebz`` (bisection) referees float64 answers.  It is accurate to a
  few eps_f64 * ||T||; scipy's default MRRR driver and ``sterf`` are not
  good enough for a 64 eps_f64 bar (on ``uniform`` n = 4096 on a CPU
  they depart from bisection by 116 and 83 eps_f64 * ||T||).  Its cost
  is O(n^2) per spectrum with a large constant, so the indices are split
  into chunks over a pool of spawned worker processes.
- ``sterf`` (Pal-Walker-Kahan QR) referees float32 answers: its error is
  far below eps_f32 * ||T||, and at n = 65536 it is ~30x cheaper than
  bisection.

The bar is the README's external one, ``bar_eps * eps * max(1,
||T||_inf)`` with eps of the request's own dtype; errors are reported in
units of ``eps * max(1, ||T||_inf)``.
"""

from __future__ import annotations

import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor

import numpy as np
import scipy.linalg

# Below this size one process computes the whole spectrum itself.
POOL_MIN_N = 2048


def norm_inf(d, e) -> float:
    """max(1, ||T||_inf) of the tridiagonal (d, e)."""
    d = np.asarray(d, np.float64)
    e = np.abs(np.asarray(e, np.float64))
    row = np.abs(d)
    row[:-1] += e
    row[1:] += e
    return max(1.0, float(row.max()))


def error_units(lam, ref, d, e, dtype) -> float:
    """Largest |lam - ref| in units of eps(dtype) * max(1, ||T||_inf);
    inf when lam is not a finite spectrum of the right length."""
    lam = np.asarray(lam, np.float64).reshape(-1)
    if lam.shape != ref.shape or not np.all(np.isfinite(lam)):
        return float("inf")
    unit = float(np.finfo(dtype).eps) * norm_inf(d, e)
    return float(np.max(np.abs(lam - ref))) / unit


def _stebz_range(d, e, lo, hi):
    return scipy.linalg.eigvalsh_tridiagonal(
        d, e, select="i", select_range=(lo, hi), lapack_driver="stebz")


def _chunks(n: int, parts: int):
    edges = np.linspace(0, n, parts + 1).astype(int)
    return [(int(a), int(b) - 1) for a, b in zip(edges[:-1], edges[1:])
            if b > a]


def workers() -> int:
    """Reference worker processes: every core but one."""
    return max(1, min(16, (os.cpu_count() or 2) - 1))


def spectra(problems, driver: str) -> list:
    """Ascending float64 eigenvalues of each host (d, e) in ``problems``.

    ``driver`` is ``"stebz"`` or ``"sterf"``.  Large problems are split
    over a pool of spawned processes (by index chunks for bisection, one
    problem per process for QR); small ones run here.
    """
    problems = [(np.asarray(d, np.float64), np.asarray(e, np.float64))
                for d, e in problems]
    if driver not in ("stebz", "sterf"):
        raise ValueError(f"unknown reference driver {driver!r}")
    big = [i for i, (d, _) in enumerate(problems) if len(d) >= POOL_MIN_N]
    out: list = [None] * len(problems)
    for i, (d, e) in enumerate(problems):
        if i not in big:
            out[i] = scipy.linalg.eigvalsh_tridiagonal(
                d, e, lapack_driver=driver)
    if not big:
        return out
    ctx = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(workers(), mp_context=ctx) as pool:
        if driver == "sterf":
            futs = {i: [pool.submit(scipy.linalg.eigvalsh_tridiagonal,
                                    *problems[i], lapack_driver="sterf")]
                    for i in big}
        else:
            futs = {i: [pool.submit(_stebz_range, *problems[i], lo, hi)
                        for lo, hi in _chunks(len(problems[i][0]),
                                              4 * workers())]
                    for i in big}
        for i, parts in futs.items():
            out[i] = np.sort(np.concatenate([f.result() for f in parts]))
    return out
