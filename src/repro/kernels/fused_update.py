"""Pallas TPU kernel: fused conquer post-pass (zhat + selected-row update).

Single-kernel realization of ``core.secular.secular_postpass``: one sweep
over the delta structure ``(d_i - d_org_j) - tau_j`` produces BOTH the
Gu-Eisenstat reconstructed weights (DLAED3) and the r-row selected-row
update (paper Lemma 3.2).  The two-kernel formulation reads the O(K)
vectors (d, z, d_org, tau, R) from HBM twice and round-trips the full
zhat vector through HBM between kernels; the fused kernel reads them once
and keeps zhat in VMEM for the tile it was just reconstructed in -- the
merge is bandwidth-bound (paper Section 4.1), so this halves the streamed
traffic of the conquer post-phase.

Grid mapping: one grid step per POLE block (C poles).  A pole block's zhat
needs only its own rows of the delta structure over the full root range,
which is exactly the (C, K) tile the step forms -- so zhat finalizes
in-register and immediately weights the block's additive contribution to
every root column.  Column contributions and squared norms accumulate
across the (sequential) TPU grid into VMEM-resident output blocks; the
O(K) normalization happens on the final grid step.

Layout (see ``kernels/layout.py``): every per-problem vector is a
``(B, 1, K)`` view read with ``(1, 1, K)`` blocks.  A pole block's own
poles are transposed onto the sublanes, so each (C, T) slab has poles on
the sublanes and a root tile on the lanes; the row update is r
multiply-and-sublane-sum passes on the VPU (r is 2 or 3), in the
working precision.

VMEM budget per step: O(K) vectors + the (C, T) root-tile slab; the dense
(K, K) secular eigenvector block is never materialized.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.layout import (col_iota, col_to_row, fori, per_block,
                                  per_problem, row_iota, row_to_col, where)

DEFAULT_POLE_BLOCK = 128
DEFAULT_ROOT_TILE = 1024


def _root_tile_for(Kp: int, root_tile: int) -> int:
    """Largest tile <= root_tile that divides the padded length exactly
    (tiles must never clamp: clamped dynamic_slice would double-count)."""
    T = min(root_tile, Kp)
    while Kp % T:
        T //= 2
    return max(T, 1)


def _fused_kernel(R_ref, d_ref, z_ref, dorg_ref, tau_ref, rho_ref,
                  kprime_ref, zhat_ref, cols_ref, nrm2_ref, *,
                  root_tile, use_zhat):
    # Blocks: R (1, r, Kp) and d, z, d[origin], tau (1, 1, Kp) resident;
    # rho, kprime (1, 1, 1); zhat (1, 1, C) per pole block; cols
    # (1, r, Kp) and nrm2 (1, 1, Kp) accumulate across the pole blocks of
    # one problem.  Grid = (B, pole_blocks): the grid iterates problems in
    # the major axis, so each problem's accumulator init (first pole
    # block) and normalization (last pole block) stay correctly sequenced.
    r, Kp = R_ref.shape[-2:]
    C = zhat_ref.shape[-1]
    T = _root_tile_for(Kp, root_tile)
    num_tiles = Kp // T
    rho = rho_ref[0]
    kprime = kprime_ref[0]
    i = pl.program_id(1)
    own = pl.ds(pl.multiple_of(i * C, C), C)

    def own_col(ref, k=0):
        return row_to_col(ref[0, k:k + 1, own])

    def tile_at(t):
        return pl.ds(pl.multiple_of(t * T, T), T)

    ic = i * C + col_iota(C)
    valid_i = ic < kprime            # active, non-padded poles only
    d_i = own_col(d_ref)
    z_i = own_col(z_ref)

    @pl.when(i == 0)
    def _init():
        cols_ref[...] = jnp.zeros(cols_ref.shape, cols_ref.dtype)
        nrm2_ref[...] = jnp.zeros(nrm2_ref.shape, nrm2_ref.dtype)

    # ---- phase 1: zhat for this pole block (row reduction over roots) ---
    # DLAED3 ratio form: numerator/denominator factors pair up as
    # interlaced ratios (lam_j - d_i)/(d_j - d_i), whose full product is
    # O(1) -- but a partial product over a contiguous run of j (a tile)
    # over- or underflows f32 at large K, so the product is summed in logs.
    tiny = jnp.finfo(d_ref.dtype).tiny

    def log_abs(x):
        return jnp.log(jnp.maximum(jnp.abs(x), tiny))

    def tile(t, lsum):
        cols = tile_at(t)
        jt = t * T + row_iota(T)
        lam_diff = (dorg_ref[0, :, cols] - d_i) + tau_ref[0, :, cols]
        pole_diff = d_ref[0, :, cols] - d_i                     # (C, T)
        ok = (jt < kprime) & (jt != ic) & (pole_diff != 0.0)
        ratio = where(ok, lam_diff / where(ok, pole_diff, 1.0), 1.0)
        return lsum + jnp.sum(log_abs(ratio), axis=1, keepdims=True)

    if use_zhat:
        lsum = fori(num_tiles, tile, jnp.zeros_like(d_i))
        self_term = (own_col(dorg_ref) - d_i) + own_col(tau_ref)
        z2hat = jnp.exp(lsum + log_abs(self_term)) / rho
        zhat_c = jnp.sign(z_i) * jnp.sqrt(z2hat)
        zhat_c = where(valid_i, zhat_c, z_i)
    else:
        zhat_c = z_i
    zhat_ref[0] = col_to_row(zhat_c)
    w = where(valid_i, zhat_c, 0.0)

    # ---- phase 2: this block's contribution to every root column --------
    # zhat is still in VMEM; no HBM round-trip between the phases.
    R_i = [own_col(R_ref, k) for k in range(r)]

    def tile2(t, carry):
        cols = tile_at(t)
        delta = (d_i - dorg_ref[0, :, cols]) - tau_ref[0, :, cols]  # (C, T)
        ok = valid_i & (delta != 0.0)
        y = where(ok, w / where(ok, delta, 1.0), 0.0)
        for k in range(r):
            cols_ref[0, k:k + 1, cols] += jnp.sum(R_i[k] * y, axis=0,
                                                  keepdims=True)
        nrm2_ref[0, :, cols] += jnp.sum(y * y, axis=0, keepdims=True)
        return carry

    fori(num_tiles, tile2, None)

    # Final grid step for this problem: apply the normalization in-place.
    @pl.when(i == pl.num_programs(1) - 1)
    def _finalize():
        def fin(t, carry):
            cols = tile_at(t)
            nrm = jnp.sqrt(nrm2_ref[0, :, cols])
            cols_ref[0, :, cols] = cols_ref[0, :, cols] / where(
                nrm > 0.0, nrm, 1.0)
            return carry
        fori(num_tiles, fin, None)


@functools.partial(jax.jit, static_argnames=("use_zhat", "pole_block",
                                             "root_tile", "interpret"))
def secular_postpass_pallas_batch(R, d, z, origin, tau, kprime, rho, *,
                                  use_zhat: bool = True,
                                  pole_block: int = DEFAULT_POLE_BLOCK,
                                  root_tile: int = DEFAULT_ROOT_TILE,
                                  interpret: bool = False):
    """Problem-batched fused post-pass: grid = (B, pole_blocks).

    R: (B, r, K); d, z, origin, tau: (B, K); kprime, rho: (B,).  Problems
    map to the major grid axis (their accumulator blocks are disjoint),
    pole blocks to the minor axis -- a whole batched merge level's
    post-pass is ONE kernel launch.  Contract of
    core.secular.secular_postpass_batched.

    Returns (zhat (B, K), rows (B, r, K)).  Both the pole and root index
    spaces are padded to the pole-block multiple; padded poles satisfy
    ic >= K >= kprime and contribute nothing, padded root columns are
    sliced off.
    """
    B, r, K = R.shape
    C = min(pole_block, K)
    Kp = -(-K // C) * C

    d_org = jnp.take_along_axis(d, jnp.minimum(origin, K - 1), axis=1)
    pad = ((0, 0), (0, Kp - K))
    vec = [jnp.pad(v, pad)[:, None, :] for v in (d, z, d_org, tau)]
    R_p = jnp.pad(R, ((0, 0), (0, 0), (0, Kp - K)))
    rho_arr = jnp.asarray(rho, d.dtype).reshape(B, 1, 1)
    kp_arr = jnp.asarray(kprime, jnp.int32).reshape(B, 1, 1)

    kernel = functools.partial(_fused_kernel, root_tile=root_tile,
                               use_zhat=use_zhat)
    row = per_problem((1, 1, Kp))
    rows_spec = per_problem((1, r, Kp))
    scalar = per_problem((1, 1, 1))
    zhat, cols, _ = pl.pallas_call(
        kernel,
        grid=(B, Kp // C),
        in_specs=[rows_spec, row, row, row, row, scalar, scalar],
        out_specs=[
            per_block((1, 1, C)),                              # zhat
            rows_spec,                                         # cols acc
            row,                                               # nrm2 acc
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, Kp), d.dtype),
            jax.ShapeDtypeStruct((B, r, Kp), R.dtype),
            jax.ShapeDtypeStruct((B, 1, Kp), d.dtype),
        ],
        interpret=interpret,
    )(R_p, *vec, rho_arr, kp_arr)

    active = jnp.arange(K)[None, :] < kprime[:, None]
    zhat = where(active, zhat[:, 0, :K], z).astype(d.dtype)
    rows = where(active[:, None, :], cols[:, :, :K], R).astype(R.dtype)
    return zhat, rows


def secular_postpass_pallas(R, d, z, origin, tau, kprime, rho, *,
                            use_zhat: bool = True,
                            pole_block: int = DEFAULT_POLE_BLOCK,
                            root_tile: int = DEFAULT_ROOT_TILE,
                            interpret: bool = False):
    """Single-problem view of :func:`secular_postpass_pallas_batch`
    (contract of core.secular.secular_postpass)."""
    zhat, rows = secular_postpass_pallas_batch(
        R[None], d[None], z[None], origin[None], tau[None],
        jnp.asarray(kprime, jnp.int32)[None],
        jnp.asarray(rho, d.dtype)[None], use_zhat=use_zhat,
        pole_block=pole_block, root_tile=root_tile, interpret=interpret)
    return zhat[0], rows[0]
