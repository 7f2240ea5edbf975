"""Pallas TPU kernel: VMEM-resident single-launch merge (solve + post-pass).

For merge levels with K at or below the residency threshold the two-launch
pipeline (``secular_roots`` kernel, HBM round-trip of (origin, tau), then
the fused post-pass kernel) is launch- and bandwidth-bound, not
compute-bound: every one of the root solve's fixed ``niter`` iterations
re-reads the (K,) pole/weight vectors, and the post-pass then reloads the
same structure from HBM a second time.  This kernel loads each problem's
pole/root tile ONCE, runs the full safeguarded middle-way iteration
on-chip, and flows the converged (origin, tau) straight into the
Gu-Eisenstat weight reconstruction and the selected-row update -- no HBM
round-trip between the phases, one kernel launch per merge level.

Grid mapping: one grid step per PROBLEM (the level batch W = B x nodes is
the major axis of the batched merge tree).  Within a step everything is
dense: K <= threshold guarantees the (K, K) delta tile fits VMEM
(~2 MiB at K = 512 f64), which is exactly the residency contract the
size-adaptive dispatch enforces -- large-K levels keep the streamed
two-launch path.

Layout (see ``kernels/layout.py``): d, z are ``(1, K)`` rows from a
``(B, 1, K)`` view; roots (and, in the post-pass, poles being weighted)
sit on the sublanes as ``(K, 1)`` columns.  Every gather the iteration
needs is a one-hot select over the ``(K, K)`` tile the kernel forms
anyway, so it is exact and costs one sweep.

Math is identical to ``core.secular.secular_merge_resident`` (the dense
XLA composition): the DLAED4 middle-way iteration shared with
``kernels/secular_roots.py`` (``middle_way``) followed by the
DLAED3 post-pass of ``kernels/fused_update.py``,
specialized to the fully-resident case.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.secular import DEFAULT_NITER
from repro.kernels.layout import (col_iota, col_to_row, gather_col,
                                  per_problem, row_iota, where)
from repro.kernels.secular_roots import middle_way


def _resident_kernel(d_ref, z_ref, R_ref, rho_ref, kprime_ref,
                     origin_ref, tau_ref, zhat_ref, rows_ref, *,
                     niter, use_zhat):
    # Blocks: d, z (1, 1, K); R (1, r, K); rho, kprime (1, 1, 1); the
    # outputs mirror them.  Grid = (B,).
    K = d_ref.shape[-1]
    r = R_ref.shape[-2]
    dtype = d_ref.dtype

    d = d_ref[0]                                   # (1, K) poles on lanes
    z = z_ref[0]
    rho = rho_ref[0]
    kprime = kprime_ref[0]
    z2 = z * z

    jc = col_iota(K)                               # (K, 1) roots
    lane = row_iota(K)
    active_root = jc < kprime
    is_last = jc == (kprime - 1)
    active_pole = lane < kprime
    zw = where(active_pole, z2, 0.0)
    span = rho * jnp.sum(zw, axis=1, keepdims=True)

    # ---- phase 1: dense safeguarded middle-way root solve ---------------
    d_j = gather_col(d, jc)
    jnext = jnp.minimum(jc + 1, K - 1)
    d_next = gather_col(d, jnext)
    gap_hi = where(is_last, d_j + span, d_next)
    mid_lam = 0.5 * (d_j + gap_hi)

    delta_mid = d - mid_lam                                      # (K, K)
    ok_mid = active_pole & (delta_mid != 0.0)
    f_mid = 1.0 + rho * jnp.sum(
        where(ok_mid, zw / where(ok_mid, delta_mid, 1.0), 0.0),
        axis=1, keepdims=True)

    use_left = (f_mid > 0.0) | is_last
    origin = where(use_left, jc, jnext)
    d_org = where(use_left, d_j, d_next)
    tau_mid = mid_lam - d_org

    n_lo = where(is_last, jnp.maximum(jc - 1, 0), jc)
    n_hi = where(is_last, jc, jnext)
    side_lo = (lane <= n_lo) & active_pole
    d_shift = d - d_org                                          # (K, K)

    mask_rest = active_pole & (lane != origin) & (d_shift != 0.0)
    dsafe_h = where(mask_rest, d_shift, 1.0)
    terms0 = where(mask_rest, z2 / dsafe_h, 0.0)

    def eval_g(tau):
        delta = d_shift - tau                                    # (K, K)
        ok = active_pole & (delta != 0.0)
        safe = where(ok, delta, 1.0)
        terms = where(ok, zw / safe, 0.0)
        dterms = terms / safe
        g = 1.0 + rho * jnp.sum(terms, axis=1, keepdims=True)
        w_lo = rho * jnp.sum(where(side_lo, dterms, 0.0), axis=1,
                             keepdims=True)
        w_hi = rho * jnp.sum(where(side_lo, 0.0, dterms), axis=1,
                             keepdims=True)
        return g, w_lo, w_hi

    tau = middle_way(
        eval_g, f_mid=f_mid, tau_mid=tau_mid, span=span, is_last=is_last,
        use_left=use_left, p_lo=gather_col(d, n_lo) - d_org,
        p_hi=gather_col(d, n_hi) - d_org,
        r0=1.0 + rho * jnp.sum(terms0, axis=1, keepdims=True),
        rp0=rho * jnp.sum(terms0 / dsafe_h, axis=1, keepdims=True),
        c_org=rho * gather_col(z2, origin), A_lo=rho * gather_col(z2, n_lo),
        A_hi=rho * gather_col(z2, n_hi), niter=niter)

    single = active_root & (kprime == 1)
    tau = where(single, rho * z2[:, 0:1], tau)
    origin = where(single, jnp.zeros_like(origin), origin)
    tau = where(active_root, tau, jnp.zeros_like(tau))
    origin = where(active_root, origin, jc)

    tau_row = col_to_row(tau)
    origin_ref[0] = col_to_row(origin.astype(dtype)).astype(jnp.int32)
    tau_ref[0] = tau_row

    # ---- phase 2: fused post-pass, (origin, tau) still on-chip ----------
    # (origin, tau) never round-trip HBM: this is the round-trip the
    # two-launch pipeline pays and this kernel exists to remove.  Poles i
    # on the sublanes, roots j on the lanes.
    d_org = gather_col(d, origin)
    lam_diff = (col_to_row(d_org) - d_j) + tau_row              # (K_i, K_j)
    valid_i = active_root                                        # poles axis
    z_i = gather_col(z, jc)

    if use_zhat:
        pole_diff = d - d_j
        # Ratio product summed in logs (see kernels/fused_update.py).
        ok = active_pole & (lane != jc) & (pole_diff != 0.0)
        ratio = where(ok, lam_diff / where(ok, pole_diff, 1.0), 1.0)
        self_term = (d_org - d_j) + tau                          # lam_i - d_i
        tiny = jnp.finfo(dtype).tiny
        log_abs = lambda x: jnp.log(jnp.maximum(jnp.abs(x), tiny))
        z2hat = jnp.exp(jnp.sum(log_abs(ratio), axis=1, keepdims=True)
                        + log_abs(self_term)) / rho
        zhat = jnp.sign(z_i) * jnp.sqrt(z2hat)
        zhat = where(valid_i, zhat, z_i)
    else:
        zhat = z_i
    zhat_ref[0] = col_to_row(zhat)
    w = where(valid_i, zhat, 0.0)

    delta = -lam_diff                         # (d_i - d_org_j) - tau_j
    ok = valid_i & (delta != 0.0)
    y = where(ok, w / where(ok, delta, 1.0), 0.0)       # (K, K)
    nrm = jnp.sqrt(jnp.sum(y * y, axis=0, keepdims=True))
    nrm = where(nrm > 0.0, nrm, 1.0)
    for k in range(r):
        R_k = R_ref[0, k:k + 1, :]
        col = jnp.sum(gather_col(R_k, jc) * y, axis=0, keepdims=True)
        rows_ref[0, k:k + 1, :] = where(active_pole, col / nrm, R_k)


@functools.partial(jax.jit, static_argnames=("niter", "use_zhat",
                                             "interpret"))
def resident_merge_pallas_batch(d, z, R, rho, kprime, *, niter: int = DEFAULT_NITER,
                                use_zhat: bool = True,
                                interpret: bool = False):
    """Problem-batched single-launch resident merge: grid = (B,).

    d, z: (B, K); R: (B, r, K); rho, kprime: (B,).  Each grid step owns
    one problem's fully-resident pole/root structure and emits its
    (origin, tau, zhat, rows) in one pass -- a whole batched merge
    level's solve + conquer is ONE kernel launch.  Same contract as
    ``core.secular.secular_merge_resident_batched``.

    Returns (origin (B, K) int32, tau (B, K), zhat (B, K), rows (B, r, K)).
    """
    B, r, K = R.shape
    rho_arr = jnp.asarray(rho, d.dtype).reshape(B, 1, 1)
    kp_arr = jnp.asarray(kprime, jnp.int32).reshape(B, 1, 1)

    kernel = functools.partial(_resident_kernel, niter=niter,
                               use_zhat=use_zhat)
    row = per_problem((1, 1, K))
    rows_spec = per_problem((1, r, K))
    scalar = per_problem((1, 1, 1))
    origin, tau, zhat, rows = pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[row, row, rows_spec, scalar, scalar],
        out_specs=[row, row, row, rows_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, K), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, K), d.dtype),
            jax.ShapeDtypeStruct((B, 1, K), d.dtype),
            jax.ShapeDtypeStruct((B, r, K), R.dtype),
        ],
        interpret=interpret,
    )(d[:, None, :], z[:, None, :], R, rho_arr, kp_arr)
    return origin[:, 0], tau[:, 0], zhat[:, 0], rows


def resident_merge_pallas(d, z, R, rho, kprime, *, niter: int = DEFAULT_NITER,
                          use_zhat: bool = True, interpret: bool = False):
    """Single-problem view of :func:`resident_merge_pallas_batch`."""
    origin, tau, zhat, rows = resident_merge_pallas_batch(
        d[None], z[None], R[None], jnp.asarray(rho, d.dtype)[None],
        jnp.asarray(kprime, jnp.int32)[None], niter=niter,
        use_zhat=use_zhat, interpret=interpret)
    return origin[0], tau[0], zhat[0], rows[0]
