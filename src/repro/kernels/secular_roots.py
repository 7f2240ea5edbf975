"""Pallas TPU kernel: batched secular-equation root solve.

TPU adaptation of the paper's CUDA block-reduction root solver
(Section 4.1: "parallelize both across roots and across the pole
reductions inside each root").  Mapping:

  CUDA                          TPU / Pallas
  ----------------------------  ------------------------------------------
  one block per root batch      grid step per (problem, root block)
  shared-mem pole staging       (1, K) pole/weight rows resident in VMEM
  warp reductions over poles    fori over (C, T) slabs on the VPU -- roots
                                on the sublanes, a pole tile on the lanes --
                                accumulating g / g' partial sums
  per-thread Newton state       per-sublane root state (tau, lo, hi, best)

Layout (see ``kernels/layout.py``): problems are a ``(B, 1, K)`` view read
with ``(1, 1, K)`` blocks.  The only gathers the iteration needs are a
root's own pole and its +-1 neighbours; the wrapper builds those as
pre-shifted rows in XLA and the kernel transposes its root block's slice
of them into columns.

VMEM budget per grid step: 2K + O(ROOT_BLOCK * POLE_TILE) floats -- the
(C, K) broadcast that a naive formulation would materialize is never
formed; this is the same streaming contract as the XLA fallback in
repro.core.secular.

The root iteration is the safeguarded DLAED4 middle-way scheme, identical
in math to repro.core.secular._solve_chunk (ref.py / tests assert
agreement to ~machine precision across shapes, dtypes and deflation
patterns).  ``middle_way`` is shared with the resident-merge kernel.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.secular import DEFAULT_NITER
from repro.kernels.layout import (col_iota, col_to_row, fori, per_block,
                                  per_problem, round_up, row_iota,
                                  row_to_col, where)

DEFAULT_ROOT_BLOCK = 128
DEFAULT_POLE_TILE = 1024


def _finite(x):
    return jnp.abs(x) < jnp.inf


def middle_way(eval_g, *, f_mid, tau_mid, span, is_last, use_left, p_lo,
               p_hi, r0, rp0, c_org, A_lo, A_hi, niter):
    """Safeguarded DLAED4 middle-way iteration on (C, 1) root columns.

    ``eval_g(tau)`` returns (g, w_lo, w_hi) at the column of offsets.
    Returns the converged offsets tau from each root's origin pole.
    """
    lo = where(use_left, jnp.zeros_like(tau_mid), tau_mid)
    hi = where(use_left,
                   where(is_last & (f_mid <= 0.0), span, tau_mid),
                   jnp.zeros_like(tau_mid))
    lo = where(is_last & (f_mid <= 0.0), tau_mid, lo)

    # Pole-hugging guess (mirrors core.secular._solve_chunk): linearized
    # origin-dominant model r0 + r0' tau - rho*z2_org/tau = 0, preferred
    # over the value-matched quadratic when it lands farther from the
    # origin pole -- kills the near-double-root geometric crawl.
    sq_h = jnp.sqrt(jnp.maximum(r0 * r0 + 4.0 * rp0 * c_org, 0.0))
    tau_m = where(use_left, -r0 + sq_h, -(r0 + sq_h)) \
        / where(rp0 > 0.0, 2.0 * rp0, 1.0)
    valid_m = (rp0 > 0.0) & _finite(tau_m)

    # Initial guess: value-matching 2-pole quadratic at tau_mid.
    c0 = f_mid - A_lo / (p_lo - tau_mid) - A_hi / (p_hi - tau_mid)
    qb = -(c0 * (p_lo + p_hi) + A_lo + A_hi)
    qc = c0 * p_lo * p_hi + A_lo * p_hi + A_hi * p_lo
    sq0 = jnp.sqrt(jnp.maximum(qb * qb - 4.0 * c0 * qc, 0.0))
    qq0 = -0.5 * (qb + where(qb >= 0.0, sq0, -sq0))
    g1 = where(c0 != 0.0, qq0 / where(c0 == 0.0, 1.0, c0), jnp.inf)
    g2 = where(qq0 != 0.0, qc / where(qq0 == 0.0, 1.0, qq0), jnp.inf)
    in1 = _finite(g1) & (g1 > lo) & (g1 < hi)
    in2 = _finite(g2) & (g2 > lo) & (g2 < hi)
    tau0 = where(in1, g1, where(in2, g2, 0.5 * (lo + hi)))
    use_m = (valid_m & (tau_m > lo) & (tau_m < hi)
             & (jnp.abs(tau_m) > jnp.abs(tau0)))
    tau0 = where(use_m, tau_m, tau0)

    tiny = jnp.finfo(tau_mid.dtype).tiny

    def body(_, state):
        tau, lo, hi, best_tau, best_g = state
        g, w_lo, w_hi = eval_g(tau)
        gp = w_lo + w_hi

        better = jnp.abs(g) < best_g
        best_tau = where(better, tau, best_tau)
        best_g = where(better, jnp.abs(g), best_g)

        hi = where(g > 0.0, tau, hi)
        lo = where(g <= 0.0, tau, lo)

        D_lo = p_lo - tau
        D_hi = p_hi - tau
        Cc = g - D_lo * w_lo - D_hi * w_hi
        Aa = (D_lo + D_hi) * g - D_lo * D_hi * gp
        Bb = D_lo * D_hi * g
        sq = jnp.sqrt(jnp.maximum(Aa * Aa - 4.0 * Bb * Cc, 0.0))
        eta_neg = (Aa - sq) / where(Cc == 0.0, 1.0, 2.0 * Cc)
        eta_pos = 2.0 * Bb / where(Aa + sq == 0.0, 1.0, Aa + sq)
        eta = where(Aa <= 0.0, eta_neg, eta_pos)
        eta_lin = Bb / where(Aa == 0.0, 1.0, Aa)
        newton = -g / jnp.maximum(gp, tiny)
        eta = where(Cc == 0.0, where(Aa != 0.0, eta_lin, newton), eta)
        eta = where(g * eta >= 0.0, newton, eta)

        cand = tau + eta
        inb = _finite(cand) & (cand > lo) & (cand < hi)
        tau_next = where(inb, cand, 0.5 * (lo + hi))
        tau_next = where(g == 0.0, tau, tau_next)
        return tau_next, lo, hi, best_tau, best_g

    big = jnp.full(tau0.shape, jnp.inf, tau0.dtype)
    tau, lo, hi, best_tau, best_g = fori(
        niter, body, (tau0, lo, hi, tau0, big))
    g_fin, _, _ = eval_g(tau)
    return where(jnp.abs(g_fin) < best_g, tau, best_tau)


def _secular_kernel(d_ref, z2_ref, nbr_ref, rho_ref, kprime_ref,
                    origin_ref, tau_ref, *, niter, pole_tile, K):
    # Blocks: d, z2 (1, 1, Kp) resident rows; nbr (1, 6, C) this root
    # block's [d[j-1], d[j], d[j+1], z2[j-1], z2[j], z2[j+1]] (indices
    # clamped to [0, K-1]); rho, kprime (1, 1, 1); grid = (B, root_blocks).
    C = origin_ref.shape[-1]
    Kp = d_ref.shape[-1]
    T = min(pole_tile, Kp)
    dtype = d_ref.dtype
    rho = rho_ref[0]
    kprime = kprime_ref[0]

    nb = nbr_ref[0]
    d_m1, d_j, d_p1, z2_m1, z2_j, z2_p1 = (
        row_to_col(nb[k:k + 1]) for k in range(6))
    jc = pl.program_id(1) * C + col_iota(C)
    jc_safe = jnp.minimum(jc, K - 1)
    jnext = jnp.minimum(jc_safe + 1, K - 1)
    active_root = jc < kprime
    is_last = jc == (kprime - 1)

    def reduce_tiles(fn, init):
        """Accumulate fn(acc, d_tile, zw_tile, idx_tile) over pole tiles."""
        def body(t, acc):
            start = pl.multiple_of(t * T, T)
            it = start + row_iota(T)
            dt = d_ref[0, :, pl.ds(start, T)]
            zt = where(it < kprime, z2_ref[0, :, pl.ds(start, T)], 0.0)
            return fn(acc, dt, zt, it)
        return fori(Kp // T, body, init)

    span = rho * reduce_tiles(
        lambda acc, dt, zt, it: acc + jnp.sum(zt, axis=1, keepdims=True),
        jnp.zeros((1, 1), dtype))
    gap_hi = where(is_last, d_j + span, d_p1)
    mid_lam = 0.5 * (d_j + gap_hi)

    # f(mid): one tiled sweep.
    def fmid_acc(acc, dt, zt, it):
        delta = dt - mid_lam
        ok = (it < kprime) & (delta != 0.0)
        return acc + jnp.sum(where(ok, zt / where(ok, delta, 1.0),
                                       0.0), axis=1, keepdims=True)
    zc = jnp.zeros((C, 1), dtype)
    f_mid = 1.0 + rho * reduce_tiles(fmid_acc, zc)

    use_left = (f_mid > 0.0) | is_last
    origin = where(use_left, jc_safe, jnext)
    d_org = where(use_left, d_j, d_p1)
    tau_mid = mid_lam - d_org

    # Bracketing neighbours: n_lo = j-1 (last root) or j; n_hi = j or j+1.
    n_lo = where(is_last, jnp.maximum(jc_safe - 1, 0), jc_safe)
    p_lo = where(is_last, d_m1, d_j) - d_org
    p_hi = where(is_last, d_j, d_p1) - d_org

    def rest_acc(acc, dt, zt, it):
        r_a, rp_a = acc
        delta = dt - d_org
        ok = (it < kprime) & (it != origin) & (delta != 0.0)
        safe = where(ok, delta, 1.0)
        t0 = where(ok, zt / safe, 0.0)
        return (r_a + jnp.sum(t0, axis=1, keepdims=True),
                rp_a + jnp.sum(t0 / safe, axis=1, keepdims=True))

    r0s, rp0s = reduce_tiles(rest_acc, (zc, zc))

    def eval_g(tau):
        """Tiled g(tau) and side-split derivative sums."""
        def acc_fn(acc, dt, zt, it):
            g_a, wlo_a, whi_a = acc
            delta = (dt - d_org) - tau                           # (C, T)
            ok = (it < kprime) & (delta != 0.0)
            safe = where(ok, delta, 1.0)
            terms = where(ok, zt / safe, 0.0)
            dterms = terms / safe
            sl = it <= n_lo
            g_a = g_a + jnp.sum(terms, axis=1, keepdims=True)
            wlo_a = wlo_a + jnp.sum(where(sl, dterms, 0.0), axis=1,
                                    keepdims=True)
            whi_a = whi_a + jnp.sum(where(sl, 0.0, dterms), axis=1,
                                    keepdims=True)
            return g_a, wlo_a, whi_a
        g_s, wlo_s, whi_s = reduce_tiles(acc_fn, (zc, zc, zc))
        return 1.0 + rho * g_s, rho * wlo_s, rho * whi_s

    tau = middle_way(
        eval_g, f_mid=f_mid, tau_mid=tau_mid, span=span, is_last=is_last,
        use_left=use_left, p_lo=p_lo, p_hi=p_hi, r0=1.0 + rho * r0s,
        rp0=rho * rp0s, c_org=rho * where(use_left, z2_j, z2_p1),
        A_lo=rho * where(is_last, z2_m1, z2_j),
        A_hi=rho * where(is_last, z2_j, z2_p1), niter=niter)

    single = active_root & (kprime == 1)
    tau = where(single, rho * z2_ref[0, :, 0:1], tau)
    origin = where(single, jnp.zeros_like(origin), origin)
    tau = where(active_root, tau, jnp.zeros_like(tau))
    origin = where(active_root, origin, jc_safe)

    origin_ref[0] = col_to_row(origin.astype(dtype)).astype(jnp.int32)
    tau_ref[0] = col_to_row(tau)


def _neighbour_rows(d, z2, Kp):
    """(B, 6, Kp) rows [d[j-1], d[j], d[j+1], z2[j-1], z2[j], z2[j+1]],
    indices clamped to [0, K-1] (padded roots read the last pole)."""
    K = d.shape[1]
    j = jnp.minimum(jnp.arange(Kp), K - 1)
    idx = (jnp.maximum(j - 1, 0), j, jnp.minimum(j + 1, K - 1))
    return jnp.stack([v[:, i] for v in (d, z2) for i in idx], axis=1)


@functools.partial(jax.jit, static_argnames=("niter", "root_block",
                                             "pole_tile", "interpret"))
def secular_solve_pallas_batch(d, z2, rho, kprime, *, niter: int = DEFAULT_NITER,
                               root_block: int = DEFAULT_ROOT_BLOCK,
                               pole_tile: int = DEFAULT_POLE_TILE,
                               interpret: bool = False):
    """Problem-batched Pallas secular solve: grid = (B, root_blocks).

    d, z2: (B, K); rho, kprime: (B,).  Each grid row owns one problem's
    VMEM-resident pole/weight rows; the root blocks of different
    problems are fully independent grid steps, so a whole level of the
    batched merge tree is ONE kernel launch.  Same contract as
    core.secular.secular_solve_batched.

    Returns (origin (B, K) int32, tau (B, K)).
    """
    B, K = d.shape
    C = min(root_block, K)
    T = min(pole_tile, round_up(K, C))
    Kp = round_up(K, math.lcm(C, T))
    nbr = _neighbour_rows(d, z2, Kp)
    pad = ((0, 0), (0, Kp - K))
    d_p = jnp.pad(d, pad)[:, None, :]
    z2_p = jnp.pad(z2, pad)[:, None, :]
    rho_arr = jnp.asarray(rho, d.dtype).reshape(B, 1, 1)
    kp_arr = jnp.asarray(kprime, jnp.int32).reshape(B, 1, 1)

    kernel = functools.partial(_secular_kernel, niter=niter,
                               pole_tile=T, K=K)
    row = per_problem((1, 1, Kp))
    scalar = per_problem((1, 1, 1))
    out = per_block((1, 1, C))
    origin, tau = pl.pallas_call(
        kernel,
        grid=(B, Kp // C),
        in_specs=[row, row,
                  per_block((1, 6, C)),
                  scalar, scalar],
        out_specs=[out, out],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, Kp), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, Kp), d.dtype),
        ],
        interpret=interpret,
    )(d_p, z2_p, nbr, rho_arr, kp_arr)
    return origin[:, 0, :K], tau[:, 0, :K]


def secular_solve_pallas(d, z2, rho, kprime, *, niter: int = DEFAULT_NITER,
                         root_block: int = DEFAULT_ROOT_BLOCK,
                         pole_tile: int = DEFAULT_POLE_TILE,
                         interpret: bool = False):
    """Single-problem view of :func:`secular_solve_pallas_batch`
    (contract of core.secular.secular_solve)."""
    origin, tau = secular_solve_pallas_batch(
        d[None], z2[None], jnp.asarray(rho, d.dtype)[None],
        jnp.asarray(kprime, jnp.int32)[None], niter=niter,
        root_block=root_block, pole_tile=pole_tile, interpret=interpret)
    return origin[0], tau[0]
