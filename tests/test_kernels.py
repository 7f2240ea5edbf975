"""Pallas kernel validation: interpret-mode vs pure-jnp oracles vs XLA path.

Sweeps shapes (incl. non-divisible-by-block), dtypes, deflation patterns
and block sizes, asserting allclose against ref.py (which deliberately
materializes the dense K x K intermediates the kernels must avoid).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import secular as sec
from repro.kernels import ref
from repro.kernels.secular_roots import secular_solve_pallas
from repro.kernels.fused_update import secular_postpass_pallas
from repro.kernels.resident_merge import (resident_merge_pallas,
                                          resident_merge_pallas_batch)


def _problem(K, kprime, seed=0, dtype=np.float64):
    rng = np.random.default_rng(seed)
    d = np.sort(rng.standard_normal(K))
    d[kprime:] += 10.0                     # deflated values parked high
    z = rng.standard_normal(K)
    z[kprime:] = 0.0
    z /= np.linalg.norm(z)
    return (jnp.asarray(d, dtype), jnp.asarray(z, dtype), 0.7)


SHAPES = [(8, 8), (32, 17), (64, 64), (130, 101), (256, 1), (257, 256)]


@pytest.mark.parametrize("K,kprime", SHAPES)
@pytest.mark.parametrize("dtype", [np.float64, np.float32])
def test_secular_kernel_vs_oracle(K, kprime, dtype):
    d, z, rho = _problem(K, kprime, dtype=dtype)
    o_p, t_p = secular_solve_pallas(d, z * z, jnp.asarray(rho, d.dtype),
                                    jnp.asarray(kprime), niter=24,
                                    interpret=True)
    lam_p = np.sort(np.asarray(d)[np.asarray(o_p)[:kprime]]
                    + np.asarray(t_p)[:kprime])
    o_r, t_r = ref.secular_roots_ref(d, z * z, rho, kprime)
    lam_r = np.sort(np.asarray(d)[np.asarray(o_r)[:kprime]]
                    + np.asarray(t_r)[:kprime])
    tol = 1e-10 if dtype == np.float64 else 2e-3
    np.testing.assert_allclose(lam_p, lam_r, atol=tol * 10, rtol=tol)


@pytest.mark.parametrize("K,kprime", SHAPES)
def test_secular_kernel_vs_xla_path(K, kprime):
    """The Pallas kernel and the chunked XLA fallback implement the same
    algorithm -- they must agree to machine precision."""
    d, z, rho = _problem(K, kprime, seed=1)
    o_x, t_x = sec.secular_solve(d, z * z, rho, kprime, niter=16)
    o_p, t_p = secular_solve_pallas(d, z * z, jnp.asarray(rho, d.dtype),
                                    jnp.asarray(kprime), niter=16,
                                    interpret=True)
    lam_x = np.asarray(d)[np.asarray(o_x)] + np.asarray(t_x)
    lam_p = np.asarray(d)[np.asarray(o_p)] + np.asarray(t_p)
    np.testing.assert_allclose(lam_x, lam_p, atol=1e-13, rtol=0)


@pytest.mark.parametrize("root_block", [32, 128])
@pytest.mark.parametrize("pole_tile", [64, 1024])
def test_secular_kernel_tiling_invariance(root_block, pole_tile):
    """BlockSpec tiling is a perf knob, never a semantics knob."""
    d, z, rho = _problem(200, 163, seed=2)
    o_p, t_p = secular_solve_pallas(d, z * z, jnp.asarray(rho, d.dtype),
                                    jnp.asarray(163), niter=16,
                                    root_block=root_block,
                                    pole_tile=pole_tile, interpret=True)
    o_0, t_0 = secular_solve_pallas(d, z * z, jnp.asarray(rho, d.dtype),
                                    jnp.asarray(163), niter=16,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(t_p), np.asarray(t_0),
                               atol=1e-14, rtol=0)


@pytest.mark.parametrize("K,kprime", SHAPES)
@pytest.mark.parametrize("r", [1, 2, 4])
def test_boundary_update_kernel(K, kprime, r):
    rng = np.random.default_rng(3)
    d, z, rho = _problem(K, kprime, seed=3)
    origin, tau = sec.secular_solve(d, z * z, rho, kprime, niter=16)
    R = jnp.asarray(rng.standard_normal((r, K)))
    got = sec.boundary_rows_update(R, d, z, origin, tau,
                                   jnp.asarray(kprime), chunk=32)
    want = ref.boundary_rows_update_ref(R, d, z, origin, tau, kprime)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-12, rtol=1e-12)


@pytest.mark.parametrize("K,kprime", SHAPES)
def test_zhat_kernel(K, kprime):
    d, z, rho = _problem(K, kprime, seed=4)
    origin, tau = sec.secular_solve(d, z * z, rho, kprime, niter=16)
    got = sec.zhat_reconstruct(d, z, origin, tau, jnp.asarray(kprime),
                               jnp.asarray(rho, d.dtype), chunk=32)
    want = ref.zhat_reconstruct_ref(d, z, origin, tau, kprime, rho)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-10, rtol=1e-8)


@pytest.mark.parametrize("K,kprime", SHAPES)
@pytest.mark.parametrize("r", [1, 2, 3])
def test_fused_postpass_kernel_vs_oracle(K, kprime, r):
    """The fused kernel's single delta sweep == dense zhat + dense row
    update (the two intermediates it exists to avoid materializing)."""
    rng = np.random.default_rng(6)
    d, z, rho = _problem(K, kprime, seed=6)
    origin, tau = sec.secular_solve(d, z * z, rho, kprime, niter=16)
    R = jnp.asarray(rng.standard_normal((r, K)))
    zh_p, rows_p = secular_postpass_pallas(
        R, d, z, origin, tau, jnp.asarray(kprime),
        jnp.asarray(rho, d.dtype), interpret=True)
    zh_o, rows_o = ref.secular_postpass_ref(R, d, z, origin, tau, kprime, rho)
    np.testing.assert_allclose(np.asarray(zh_p), np.asarray(zh_o),
                               atol=1e-10, rtol=1e-8)
    np.testing.assert_allclose(np.asarray(rows_p), np.asarray(rows_o),
                               atol=1e-10, rtol=1e-8)


@pytest.mark.parametrize("pole_block", [32, 128])
@pytest.mark.parametrize("root_tile", [64, 1024])
def test_fused_postpass_kernel_tiling_invariance(pole_block, root_tile):
    """BlockSpec tiling is a perf knob, never a semantics knob."""
    d, z, rho = _problem(200, 163, seed=7)
    origin, tau = sec.secular_solve(d, z * z, rho, 163, niter=16)
    R = jnp.asarray(np.random.default_rng(7).standard_normal((2, 200)))
    args = (R, d, z, origin, tau, jnp.asarray(163), jnp.asarray(rho, d.dtype))
    zh_t, rows_t = secular_postpass_pallas(*args, pole_block=pole_block,
                                           root_tile=root_tile,
                                           interpret=True)
    zh_0, rows_0 = secular_postpass_pallas(*args, interpret=True)
    np.testing.assert_allclose(np.asarray(zh_t), np.asarray(zh_0),
                               atol=1e-13, rtol=0)
    np.testing.assert_allclose(np.asarray(rows_t), np.asarray(rows_0),
                               atol=1e-13, rtol=0)


@pytest.mark.parametrize("K,kprime", [(64, 64), (130, 101)])
def test_fused_postpass_kernel_vs_xla_fused(K, kprime):
    """Pallas fused kernel vs the XLA fused path (same algorithm, same
    single-sweep structure) -- agreement to near machine precision."""
    d, z, rho = _problem(K, kprime, seed=8)
    origin, tau = sec.secular_solve(d, z * z, rho, kprime, niter=16)
    R = jnp.asarray(np.random.default_rng(8).standard_normal((2, K)))
    zh_p, rows_p = secular_postpass_pallas(
        R, d, z, origin, tau, jnp.asarray(kprime),
        jnp.asarray(rho, d.dtype), interpret=True)
    zh_x, rows_x = sec.secular_postpass(R, d, z, origin, tau, kprime, rho)
    np.testing.assert_allclose(np.asarray(zh_p), np.asarray(zh_x),
                               atol=1e-12, rtol=1e-10)
    np.testing.assert_allclose(np.asarray(rows_p), np.asarray(rows_x),
                               atol=1e-12, rtol=1e-10)


@pytest.mark.parametrize("K,kprime", [(32, 17), (64, 64), (130, 101)])
def test_resident_merge_kernel_vs_oracle(K, kprime):
    """The single-launch resident kernel == bisection root solve followed
    by the dense post-pass (every intermediate it keeps on-chip)."""
    rng = np.random.default_rng(9)
    d, z, rho = _problem(K, kprime, seed=9)
    R = jnp.asarray(rng.standard_normal((2, K)))
    o_p, t_p, zh_p, rows_p = resident_merge_pallas(
        d, z, R, jnp.asarray(rho, d.dtype), jnp.asarray(kprime),
        niter=24, interpret=True)
    o_r, t_r, zh_r, rows_r = ref.resident_merge_ref(d, z, R, rho, kprime)
    lam_p = np.sort(np.asarray(d)[np.asarray(o_p)[:kprime]]
                    + np.asarray(t_p)[:kprime])
    lam_r = np.sort(np.asarray(d)[np.asarray(o_r)[:kprime]]
                    + np.asarray(t_r)[:kprime])
    np.testing.assert_allclose(lam_p, lam_r, atol=1e-9, rtol=1e-10)
    np.testing.assert_allclose(np.asarray(zh_p), np.asarray(zh_r),
                               atol=1e-8, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(rows_p), np.asarray(rows_r),
                               atol=1e-8, rtol=1e-6)


@pytest.mark.parametrize("K,kprime", [(32, 17), (64, 64), (130, 101)])
def test_resident_merge_kernel_vs_xla_resident(K, kprime):
    """Pallas resident kernel vs the fused dense XLA composition (same
    algorithm end to end) -- agreement to near machine precision."""
    rng = np.random.default_rng(10)
    d, z, rho = _problem(K, kprime, seed=10)
    R = jnp.asarray(rng.standard_normal((3, K)))
    o_p, t_p, zh_p, rows_p = resident_merge_pallas(
        d, z, R, jnp.asarray(rho, d.dtype), jnp.asarray(kprime),
        interpret=True)
    o_x, t_x, zh_x, rows_x = sec.secular_merge_resident(d, z, R, rho, kprime)
    lam_p = np.asarray(d)[np.asarray(o_p)] + np.asarray(t_p)
    lam_x = np.asarray(d)[np.asarray(o_x)] + np.asarray(t_x)
    np.testing.assert_allclose(lam_p, lam_x, atol=1e-13, rtol=0)
    np.testing.assert_allclose(np.asarray(zh_p), np.asarray(zh_x),
                               atol=1e-12, rtol=1e-10)
    np.testing.assert_allclose(np.asarray(rows_p), np.asarray(rows_x),
                               atol=1e-12, rtol=1e-10)


def test_resident_merge_xla_matches_two_launch():
    """The XLA resident composition is EXACTLY the dense two-launch
    pipeline (same functions, one traced region): dispatch collapse is a
    launch-count knob, never a semantics knob."""
    d, z, rho = _problem(64, 50, seed=11)
    R = jnp.asarray(np.random.default_rng(11).standard_normal((2, 64)))
    o1, t1, zh1, rows1 = sec.secular_merge_resident(d, z, R, rho, 50)
    o2, t2 = sec.secular_solve(d, z * z, rho, 50, dense=True)
    zh2, rows2 = sec.secular_postpass(R, d, z, o2, t2, 50, rho, dense=True)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    np.testing.assert_array_equal(np.asarray(zh1), np.asarray(zh2))
    np.testing.assert_array_equal(np.asarray(rows1), np.asarray(rows2))


def test_resident_merge_batch_kernel():
    """Batched resident kernel (problems on the grid axis) vs a loop of
    single-problem kernel calls and vs the batched oracle."""
    B, K = 3, 48
    rng = np.random.default_rng(12)
    ds, zs, kps = [], [], []
    for b in range(B):
        kp = (8, 48, 31)[b]
        d, z, _ = _problem(K, kp, seed=20 + b)
        ds.append(d); zs.append(z); kps.append(kp)
    d = jnp.stack(ds); z = jnp.stack(zs)
    kprime = jnp.asarray(kps, jnp.int32)
    rho = jnp.asarray([0.7, 1.3, 0.2], d.dtype)
    R = jnp.asarray(rng.standard_normal((B, 2, K)))

    o_b, t_b, zh_b, rows_b = resident_merge_pallas_batch(
        d, z, R, rho, kprime, interpret=True)
    for b in range(B):
        o_s, t_s, zh_s, rows_s = resident_merge_pallas(
            d[b], z[b], R[b], rho[b], kprime[b], interpret=True)
        np.testing.assert_array_equal(np.asarray(o_b[b]), np.asarray(o_s))
        np.testing.assert_array_equal(np.asarray(t_b[b]), np.asarray(t_s))
        np.testing.assert_array_equal(np.asarray(zh_b[b]), np.asarray(zh_s))
        np.testing.assert_array_equal(np.asarray(rows_b[b]),
                                      np.asarray(rows_s))
    o_r, t_r, zh_r, rows_r = ref.resident_merge_batch_ref(
        d, z, R, np.asarray(rho), np.asarray(kprime))
    for b in range(B):
        kp = kps[b]
        lam_b = np.sort(np.asarray(d[b])[np.asarray(o_b[b])[:kp]]
                        + np.asarray(t_b[b])[:kp])
        lam_r = np.sort(np.asarray(d[b])[np.asarray(o_r[b])[:kp]]
                        + np.asarray(t_r[b])[:kp])
        np.testing.assert_allclose(lam_b, lam_r, atol=1e-9, rtol=1e-9)
    np.testing.assert_allclose(np.asarray(rows_b), np.asarray(rows_r),
                               atol=1e-8, rtol=1e-6)


def test_solver_resident_threshold_is_dispatch_knob_only():
    """Full solver with every level under the residency threshold vs the
    streamed two-launch pipeline: identical spectra and boundary rows."""
    from repro.core import eigvalsh_tridiagonal_br, make_family
    d, e = make_family("normal", 200)
    r_res = eigvalsh_tridiagonal_br(d, e, leaf=8, return_boundary=True,
                                    resident_threshold=1 << 20,
                                    stream_threshold=1 << 20)
    r_two = eigvalsh_tridiagonal_br(d, e, leaf=8, return_boundary=True,
                                    resident_threshold=0,
                                    stream_threshold=1 << 20)
    np.testing.assert_allclose(np.asarray(r_res.eigenvalues),
                               np.asarray(r_two.eigenvalues),
                               rtol=0, atol=1e-12)
    np.testing.assert_allclose(np.asarray(r_res.bhi), np.asarray(r_two.bhi),
                               rtol=0, atol=1e-10)


# ---------------------------------------------------------------------------
# Sturm-count kernel (partial-spectrum front end)
# ---------------------------------------------------------------------------

@pytest.mark.partial
@pytest.mark.parametrize("B,n,S", [(1, 8, 4), (4, 64, 130), (3, 1, 5),
                                   (2, 257, 1), (8, 33, 32)])
@pytest.mark.parametrize("dtype", [np.float64, np.float32])
def test_sturm_kernel_vs_oracle(B, n, S, dtype):
    """Counts are integers: the Pallas kernel must match the scalar
    Python-loop oracle EXACTLY (and the XLA scan too) on every lane."""
    from repro.core.bisect import _pivot_floor, sturm_count_xla
    from repro.kernels.sturm_count import sturm_count_pallas_batch

    rng = np.random.default_rng(B * 1000 + n)
    d = jnp.asarray(rng.standard_normal((B, n)), dtype)
    e = rng.uniform(0.05, 0.5, (B, max(n - 1, 0)))
    e2 = jnp.asarray(e * e, dtype)
    shifts = jnp.asarray(rng.uniform(-3, 3, (B, S)), dtype)
    pivmin = _pivot_floor(e2, d.dtype)
    got = sturm_count_pallas_batch(d, e2, shifts, pivmin, shift_block=32,
                                   interpret=True)
    want = ref.sturm_count_ref(np.asarray(d), np.asarray(e2),
                               np.asarray(shifts), np.asarray(pivmin))
    xla = sturm_count_xla(d, e2, shifts, pivmin)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(xla), np.asarray(want))


@pytest.mark.partial
def test_sturm_kernel_shift_block_invariance():
    """The shift-block width is a tiling knob, never a semantics knob."""
    from repro.core.bisect import _pivot_floor
    from repro.kernels.sturm_count import sturm_count_pallas_batch

    rng = np.random.default_rng(11)
    d = jnp.asarray(rng.standard_normal((2, 100)))
    e2 = jnp.asarray(rng.uniform(0.01, 0.25, (2, 99)))
    shifts = jnp.asarray(rng.uniform(-3, 3, (2, 77)))
    pivmin = _pivot_floor(e2, d.dtype)
    outs = [np.asarray(sturm_count_pallas_batch(
        d, e2, shifts, pivmin, shift_block=sb, interpret=True))
        for sb in (8, 64, 128)]
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])


def test_zhat_improves_or_matches_weights():
    """Reconstructed weights stay close to the originals for a
    well-conditioned problem (sanity on the log-product path)."""
    d, z, rho = _problem(64, 64, seed=5)
    origin, tau = sec.secular_solve(d, z * z, rho, 64, niter=24)
    zhat = sec.zhat_reconstruct(d, z, origin, tau, jnp.asarray(64),
                                jnp.asarray(rho, d.dtype))
    np.testing.assert_allclose(np.asarray(zhat), np.asarray(z),
                               atol=1e-8, rtol=1e-6)
