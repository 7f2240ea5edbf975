"""Accuracy regressions pinned at the sizes where they first failed.

Each case holds the solver to the README's external bar,
``64 * eps * max(1, ||T||_inf)`` against LAPACK bisection, or to finite
output where the failure was non-finite:

  * native float64 on glued Wilkinson: the secular iteration budget (16
    steps missed by ~4e-8 from n = 64 on, 24 steps by 969x the bar at
    n = 512);
  * the mixed route with ``certify=True`` on clustered and glued spectra
    at n = 4096: the float64 polish diverged (7e6x and 4e11x the bar) and
    the certificate then escalated lanes down the degradation ladder;
  * the Gu-Eisenstat weights of a merge whose poles coincide (two copies
    of one glued Wilkinson half): non-finite weights and rows.
"""

import numpy as np
import pytest
import scipy.linalg

import jax.numpy as jnp

from repro.core import eigvalsh_tridiagonal, make_family
from repro.core import secular as sec
from repro.core.request import SolveRequest, execute_request
from repro.kernels.fused_update import secular_postpass_pallas
from repro.kernels.resident_merge import resident_merge_pallas


def _bar(d, e):
    row = np.abs(d).copy()
    row[:-1] += np.abs(e)
    row[1:] += np.abs(e)
    return 64 * np.finfo(np.float64).eps * max(1.0, float(row.max()))


def _reference(d, e):
    return scipy.linalg.eigvalsh_tridiagonal(d, e, lapack_driver="stebz")


def test_native_float64_glued_wilkinson_meets_the_bar():
    d, e = make_family("glued_wilkinson", 512, seed=0)
    lam = np.asarray(eigvalsh_tridiagonal(d, e, precision="native"))
    assert np.max(np.abs(lam - _reference(d, e))) <= _bar(d, e)


@pytest.mark.parametrize("family", ["clustered", "glued_wilkinson"])
def test_mixed_certified_solve_needs_no_escalation(family):
    d, e = make_family(family, 4096, seed=0)
    res = execute_request(SolveRequest(d=d, e=e, certify=True,
                                       knobs={"precision": "mixed"}))
    diag = res.diagnostics or {}
    assert not diag.get("escalations"), diag
    assert diag["certified"] == diag["lanes"] == 4096
    lam = np.asarray(res.eigenvalues)
    assert np.max(np.abs(lam - _reference(d, e))) <= _bar(d, e)


def _coincident_merge(K, dtype):
    """Merge of two identical glued-Wilkinson halves, undeflated: every
    pole appears twice, so some pole differences are exactly zero."""
    d, e = make_family("glued_wilkinson", K // 2, seed=0)
    w, v = scipy.linalg.eigh_tridiagonal(d, e)
    poles = np.concatenate([w, w])
    z = np.concatenate([v[-1], v[0]]) / np.sqrt(2.0)
    p = np.argsort(poles, kind="stable")
    R = np.stack([np.concatenate([v[0], np.zeros(K // 2)]),
                  np.concatenate([np.zeros(K // 2), v[-1]])])[:, p]
    return (jnp.asarray(poles[p], dtype), jnp.asarray(z[p], dtype),
            jnp.asarray(R, dtype), dtype(0.25))


def _assert_finite(*arrays):
    for a in arrays:
        assert np.all(np.isfinite(np.asarray(a)))


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_postpass_weights_stay_finite_on_coincident_poles(dtype):
    K = 4096
    d, z, R, rho = _coincident_merge(K, dtype)
    origin, tau = sec.secular_solve(d, z * z, rho, K)
    zhat, rows = sec.secular_postpass(R, d, z, origin, tau, K, rho)
    _assert_finite(tau, zhat, rows)


def test_fused_kernel_weights_stay_finite_on_coincident_poles():
    K = 1024
    d, z, R, rho = _coincident_merge(K, np.float32)
    origin, tau = sec.secular_solve(d, z * z, rho, K)
    zhat, rows = secular_postpass_pallas(R, d, z, origin, tau,
                                         jnp.asarray(K), jnp.asarray(rho),
                                         interpret=True)
    _assert_finite(zhat, rows)


def test_resident_kernel_weights_stay_finite_on_coincident_poles():
    K = 512
    d, z, R, rho = _coincident_merge(K, np.float32)
    _, tau, zhat, rows = resident_merge_pallas(d, z, R, rho, K,
                                               interpret=True)
    _assert_finite(tau, zhat, rows)


@pytest.mark.parametrize("family", ["uniform", "glued_wilkinson"])
def test_secular_iteration_cap_stops_only_at_fixed_points(family):
    """The loop ends early only once every root is at a fixed point, so a
    larger cap cannot change a root that converged inside the smaller."""
    d, e = make_family(family, 1024, seed=0)
    w, v = scipy.linalg.eigh_tridiagonal(d[:512], e[:511])
    w2, v2 = scipy.linalg.eigh_tridiagonal(d[512:], e[512:])
    poles = np.concatenate([w, w2])
    z = np.concatenate([v[-1], v2[0]])
    p = np.argsort(poles)
    dd, zz = jnp.asarray(poles[p]), jnp.asarray(z[p])
    o32, t32 = sec.secular_solve(dd, zz * zz, 0.5, 1024, niter=32)
    o64, t64 = sec.secular_solve(dd, zz * zz, 0.5, 1024, niter=64)
    np.testing.assert_array_equal(np.asarray(o32), np.asarray(o64))
    np.testing.assert_array_equal(np.asarray(t32), np.asarray(t64))
