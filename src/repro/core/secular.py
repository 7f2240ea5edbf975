"""Secular equation solver for diagonal-plus-rank-one eigenproblems.

Solves for the roots of

    g(lam) = 1 + rho * sum_i z2_i / (d_i - lam) = 0

where ``d`` holds ``kprime`` *active* poles sorted ascending in its prefix
(entries at index >= kprime are deflated/padding and carry ``z2 == 0``).

Roots interlace the active poles:  d_0 < lam_0 < d_1 < ... < lam_{K'-1} <
d_{K'-1} + rho * sum(z2).  Every root is represented in the paper's compact
delta form ``lam_j = d[origin_j] + tau_j`` (Section 4.1 of the paper:
"origin pole + offset tau") so that denominators ``delta_i = (d_i -
d_origin) - tau`` never suffer catastrophic cancellation near the pole.

The iteration is a safeguarded fixed-weight (two-pole rational
interpolation) scheme in the spirit of LAPACK's DLAED4, with a bisection
bracket that guarantees convergence within the iteration budget
(bisection alone contracts the bracket by 2^-niter; the rational step is
superlinear once close).  The budget caps one loop per chunk of roots,
which ends early once every root of the chunk is at a fixed point (no
per-root early exit): jit- and vmap-compatible, the TPU/XLA adaptation of
the paper's per-root CUDA loops.

Memory: all evaluations are chunked over roots -- peak temporary is
O(chunk * K), never O(K^2).  This is the JAX realization of the paper's
"stream each secular vector column" contract.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# The ONE secular iteration budget, shared by every entry point (core
# solvers, kernel dispatchers, merge_level, the plan cache key).  The
# safeguarded middle-way step is quadratically convergent once bracketed
# and each iteration also halves the bisection bracket, so 16 iterations
# deliver <= 2^-16 of the gap width even in the pure-bisection worst case
# and ~machine-precision residuals in practice (the accuracy benchmarks
# hold at 8*eps*||T|| across all paper families).  Raising it buys
# nothing measurable at float64; lowering below ~12 starts to show on
# clustered spectra.  Historically ``secular_solve`` defaulted to 40
# while the merge tree passed 16 -- one knob now, one value.
#
# A 16-step budget is only sufficient together with the pole-hugging
# initial guess in ``_solve_chunk``: roots whose origin weight is tiny
# but above the deflation threshold (Wilkinson-type spectra produce
# them at padded sizes) otherwise enter a geometric "double tau each
# step" crawl that needs ~30 iterations -- the reason LAPACK's DLAED4
# carries MAXIT = 30.  The model guess starts such roots on their own
# magnitude, restoring quadratic convergence inside the budget (found
# by the cross-method conformance sweep; pinned by its n = 17..25
# Wilkinson points).  Glued Wilkinson spectra (copies of a W+ block
# coupled by 1e-4, pole pairs ~1e-8 apart) still need up to ~30 steps on
# some roots -- DLAED4's own MAXIT: at 16 the native f64 tree missed them
# by ~4e-8 from n = 64 on (3e7 x the 64 eps ||T|| bar at n = 1024), at 24
# by 969 x the bar at n = 512; at 32 glued Wilkinson sits inside the bar
# at n = 256 through 4096.  The budget is a cap, not a count: a chunk's
# loop ends once every active root sits on a fixed point, so roots that
# converge early stop paying for the crawlers' budget.
DEFAULT_NITER = 32

# The f32-aware budget for single-precision trees (the mixed-precision
# pipeline and explicit dtype=float32 solves).  The safeguarded iteration
# hits the f32 accuracy floor (~eps_f32 * ||T|| residuals) by ~8-10
# steps: measured across the conformance families at n = 4096, the tree's
# max error against the f64 solve is IDENTICAL at niter in {8, 10, 16}
# (the floor, not the budget, binds), while each extra iteration still
# pays a full streamed secular sweep.  10 keeps two safety steps over the
# observed floor; the f64 rationale above (and its Wilkinson crawl
# guard) does not shrink, so DEFAULT_NITER stays higher for f64 routes.
DEFAULT_NITER_F32 = 10


def _pad_len(k: int, chunk: int) -> int:
    return ((k + chunk - 1) // chunk) * chunk


def _eval_g(tau, d_shift, z2, rho, active_mask):
    """g(tau), g'(tau) split by pole side, for a batch of roots.

    tau: (C,); d_shift: (C, K) = d_i - d_origin; z2: (K,); rho scalar.
    Returns (g, w_lo, w_hi) where w_lo/w_hi are the derivative parts from
    poles at/below vs above the gap's left pole (the 'middle way' split).
    """
    delta = d_shift - tau[:, None]  # (C, K)
    # Guard exact pole hits (only possible for inactive/zero-weight terms:
    # tau stays strictly inside the pole-free bracket for active terms).
    safe = jnp.where(active_mask & (delta != 0.0), delta, 1.0)
    w = jnp.where(active_mask, z2[None, :], 0.0)
    g = 1.0 + rho * jnp.sum(w / safe, axis=-1)
    dterms = w / (safe * safe)
    return g, dterms


def _solve_chunk(jc, d, z2, rho, kprime, niter):
    """Solve a chunk of secular roots (safeguarded DLAED4 'middle way').

    jc: (C,) int32 root indices (may exceed K-1 for tail padding).
    d:  (K,) poles, active prefix sorted ascending.
    z2: (K,) squared weights (zero at deflated/padded entries).
    Returns (origin (C,) int32, tau (C,)).
    """
    K = d.shape[0]
    dtype = d.dtype
    jc_safe = jnp.minimum(jc, K - 1)
    active_root = jc < kprime
    is_last = jc == (kprime - 1)

    sum_z2 = jnp.sum(z2)
    span = rho * sum_z2  # upper bound on lam_max - d_max

    d_j = d[jc_safe]
    jnext = jnp.minimum(jc_safe + 1, K - 1)
    d_next_pole = d[jnext]
    # Right end of the gap: next active pole, or d_j + span for the last root.
    gap_hi = jnp.where(is_last, d_j + span, d_next_pole)
    mid_lam = 0.5 * (d_j + gap_hi)

    active_mask = (jnp.arange(K) < kprime)[None, :]

    # f(mid) decides which gap endpoint becomes the origin pole and gives
    # the first bracket halving for free.
    delta_mid = d[None, :] - mid_lam[:, None]
    safe = jnp.where(active_mask & (delta_mid != 0.0), delta_mid, 1.0)
    w = jnp.where(active_mask, z2[None, :], 0.0)
    f_mid = 1.0 + rho * jnp.sum(w / safe, axis=-1)

    use_left = (f_mid > 0.0) | is_last
    origin = jnp.where(use_left, jc_safe, jnext).astype(jnp.int32)
    d_org = d[origin]
    tau_mid = mid_lam - d_org

    # Bracket in tau (relative to the origin pole), refined by f(mid).
    lo = jnp.where(use_left,
                   jnp.zeros_like(tau_mid),
                   tau_mid)
    hi = jnp.where(use_left,
                   jnp.where(is_last & (f_mid <= 0.0), span, tau_mid),
                   jnp.zeros_like(tau_mid))
    lo = jnp.where(is_last & (f_mid <= 0.0), tau_mid, lo)

    # Near poles: gap endpoints for interior roots; for the last root the
    # origin pole and its lower neighbour (LAPACK DLAED4's I=N branch).
    n_lo = jnp.where(is_last, jnp.maximum(jc_safe - 1, 0), jc_safe)
    n_hi = jnp.where(is_last, jc_safe, jnext)
    p_lo = d[n_lo] - d_org
    p_hi = d[n_hi] - d_org
    # Derivative side split: poles with index <= n_lo attach to p_lo.
    side_lo = (jnp.arange(K)[None, :] <= n_lo[:, None]) & active_mask

    d_shift = d[None, :] - d_org[:, None]  # (C, K)

    # ---- pole-hugging guess (origin-dominant 3-term model) --------------
    # Write g(tau) = r(tau) - c / tau with c = rho * z2_org and
    # r(tau) = 1 + rho * sum_{i != org} z2_i / (d_i - d_org - tau), and
    # linearize r at the origin pole: r0 + r0' tau - c / tau = 0, i.e.
    #
    #     tau_m = (-r0 +- sqrt(r0^2 + 4 r0' c)) / (2 r0')
    #
    # (sign by which side of the pole the root lies).  This matters
    # exactly when the origin weight is tiny-but-not-deflated (z2_org ~
    # eps^2): the root then hugs its pole at |tau*| ~ sqrt(c / r0') --
    # many orders of magnitude inside the gap -- and the value-matched
    # quadratic guess below can undershoot it by decades, after which
    # the safeguarded rational steps merely double tau per iteration
    # (the near-double-root crawl that forces LAPACK's DLAED4 to carry
    # MAXIT = 30).  tau_m is immune to that failure: the discriminant
    # rides on 4 r0' c, which cancellation noise in r0 (absolute error
    # ~ eps * sum|terms|) cannot corrupt.  The guess is only *preferred*
    # when it lands farther from the pole than the quadratic guess and
    # still inside the safeguard bracket, so well-conditioned roots keep
    # their value-matched guess and identical iteration behavior.
    mask_rest = (active_mask
                 & (jnp.arange(K)[None, :] != origin[:, None])
                 & (d_shift != 0.0))
    dsafe = jnp.where(mask_rest, d_shift, 1.0)
    terms0 = jnp.where(mask_rest, z2[None, :] / dsafe, 0.0)
    r0 = 1.0 + rho * jnp.sum(terms0, axis=-1)
    rp0 = rho * jnp.sum(terms0 / dsafe, axis=-1)
    c_org = rho * z2[jnp.minimum(origin, K - 1)]
    sq_h = jnp.sqrt(jnp.maximum(r0 * r0 + 4.0 * rp0 * c_org, 0.0))
    tau_m = jnp.where(use_left, -r0 + sq_h, -(r0 + sq_h)) \
        / jnp.where(rp0 > 0.0, 2.0 * rp0, 1.0)
    valid_m = (rp0 > 0.0) & jnp.isfinite(tau_m)

    # ---- initial guess: value-matching 2-pole quadratic at tau_mid ------
    A_lo = rho * z2[n_lo]
    A_hi = rho * z2[n_hi]
    c0 = f_mid - A_lo / (p_lo - tau_mid) - A_hi / (p_hi - tau_mid)
    qb = -(c0 * (p_lo + p_hi) + A_lo + A_hi)
    qc = c0 * p_lo * p_hi + A_lo * p_hi + A_hi * p_lo
    disc0 = jnp.maximum(qb * qb - 4.0 * c0 * qc, 0.0)
    sq0 = jnp.sqrt(disc0)
    qq0 = -0.5 * (qb + jnp.where(qb >= 0.0, 1.0, -1.0) * sq0)
    g1 = qq0 / jnp.where(c0 == 0.0, 1.0, c0)
    g2 = qc / jnp.where(qq0 == 0.0, 1.0, qq0)
    g1 = jnp.where(c0 != 0.0, g1, jnp.inf)
    g2 = jnp.where(qq0 != 0.0, g2, jnp.inf)
    in1 = jnp.isfinite(g1) & (g1 > lo) & (g1 < hi)
    in2 = jnp.isfinite(g2) & (g2 > lo) & (g2 < hi)
    tau0 = jnp.where(in1, g1, jnp.where(in2, g2, 0.5 * (lo + hi)))
    use_m = (valid_m & (tau_m > lo) & (tau_m < hi)
             & (jnp.abs(tau_m) > jnp.abs(tau0)))
    tau0 = jnp.where(use_m, tau_m, tau0)

    # ---- safeguarded middle-way iteration (DLAED4) -----------------------
    def body(_, state):
        tau, lo, hi, best_tau, best_g = state
        g, dterms = _eval_g(tau, d_shift, z2, rho, active_mask)
        w_lo = rho * jnp.sum(jnp.where(side_lo, dterms, 0.0), axis=-1)
        w_hi = rho * jnp.sum(jnp.where(~side_lo, dterms, 0.0), axis=-1)
        gp = w_lo + w_hi

        better = jnp.abs(g) < best_g
        best_tau = jnp.where(better, tau, best_tau)
        best_g = jnp.where(better, jnp.abs(g), best_g)

        hi = jnp.where(g > 0.0, tau, hi)
        lo = jnp.where(g <= 0.0, tau, lo)

        D_lo = p_lo - tau
        D_hi = p_hi - tau
        C = g - D_lo * w_lo - D_hi * w_hi
        A = (D_lo + D_hi) * g - D_lo * D_hi * gp
        B = D_lo * D_hi * g
        disc = jnp.maximum(A * A - 4.0 * B * C, 0.0)
        sq = jnp.sqrt(disc)
        eta_neg = (A - sq) / jnp.where(C == 0.0, 1.0, 2.0 * C)
        eta_pos = 2.0 * B / jnp.where(A + sq == 0.0, 1.0, A + sq)
        eta = jnp.where(A <= 0.0, eta_neg, eta_pos)
        eta_lin = B / jnp.where(A == 0.0, 1.0, A)
        eta = jnp.where(C == 0.0, jnp.where(A != 0.0, eta_lin, -g / jnp.maximum(gp, jnp.finfo(dtype).tiny)), eta)
        # eta must move against the sign of g (g increasing in tau).
        newton = -g / jnp.maximum(gp, jnp.finfo(dtype).tiny)
        eta = jnp.where(g * eta >= 0.0, newton, eta)

        cand = tau + eta
        inb = jnp.isfinite(cand) & (cand > lo) & (cand < hi)
        tau_next = jnp.where(inb, cand, 0.5 * (lo + hi))
        # Freeze once converged exactly.
        tau_next = jnp.where(g == 0.0, tau, tau_next)
        return tau_next, lo, hi, best_tau, best_g

    # The budget is an upper bound: the loop stops as soon as every active
    # root of the chunk sits on a fixed point of `body` (state unchanged,
    # NaN counted equal), after which further steps could change nothing
    # -- so results are bit-identical to running all niter steps.
    # Inactive roots are discarded below and never hold the loop open.
    def moved(new, old):
        return jnp.any(jnp.stack([
            jnp.any(active_root & (a != b) & ~(jnp.isnan(a) & jnp.isnan(b)))
            for a, b in zip(new, old)]))

    def step(carry):
        k, state, _ = carry
        new = body(k, state)
        return k + 1, new, moved(new, state)

    big = jnp.full_like(tau0, jnp.inf)
    _, (tau, lo, hi, best_tau, best_g), _ = jax.lax.while_loop(
        lambda carry: (carry[0] < niter) & carry[2], step,
        (jnp.int32(0), (tau0, lo, hi, tau0, big), True))
    # Final evaluation so the last tau competes with the best seen.
    g_fin, _ = _eval_g(tau, d_shift, z2, rho, active_mask)
    tau = jnp.where(jnp.abs(g_fin) < best_g, tau, best_tau)

    # Exact closed form when only one active pole remains.
    tau = jnp.where(active_root & (kprime == 1), rho * z2[0], tau)
    origin = jnp.where(active_root & (kprime == 1), 0, origin)

    tau = jnp.where(active_root, tau, jnp.zeros_like(tau))
    origin = jnp.where(active_root, origin, jc_safe.astype(jnp.int32))
    return origin.astype(jnp.int32), tau.astype(dtype)


def secular_solve(d, z2, rho, kprime, *, niter: int = DEFAULT_NITER,
                  chunk: int = 128, dense: bool = False):
    """Find all K eigenvalues of diag(d) + rho * z z^T in compact delta form.

    Args:
      d: (K,) poles; the first ``kprime`` entries are active & sorted
        ascending, the rest are deflated values (already eigenvalues).
      z2: (K,) squared secular weights; exactly zero outside the active set.
      rho: positive scalar.
      kprime: traced int32 -- number of active (non-deflated) poles.
      niter: safeguarded-iteration cap (see ``DEFAULT_NITER`` for
        the accuracy-vs-iterations tradeoff).
      chunk: roots per streamed chunk (memory = O(chunk * K)).
      dense: solve every root in one vectorized batch (no streaming loop;
        memory O(K^2)).  Per-root math is elementwise so results are
        bit-identical to the chunked path -- this is the small-K fast path
        used by the size-adaptive level dispatch (chunked ``lax.map``
        serializes under vmap exactly where K is small and batch is large).

    Returns:
      (origin, tau): int32 (K,) and float (K,).  Eigenvalue j is
      ``d[origin[j]] + tau[j]``.  Deflated j get (j, 0) -- i.e. pass-through.
    """
    K = d.shape[0]
    if dense:
        jc = jnp.arange(K, dtype=jnp.int32)
        return _solve_chunk(jc, d, z2, rho, kprime, niter)
    C = min(chunk, K)
    Kp = _pad_len(K, C)
    idx = jnp.arange(Kp, dtype=jnp.int32).reshape(-1, C)

    fn = functools.partial(_solve_chunk, d=d, z2=z2, rho=rho,
                           kprime=kprime, niter=niter)
    origin, tau = jax.lax.map(lambda j: fn(j), idx)
    return origin.reshape(-1)[:K], tau.reshape(-1)[:K]


def secular_solve_window(d, z2, rho, kprime, start, nroots: int, *,
                         niter: int = DEFAULT_NITER, chunk: int = 128,
                         dense: bool = False):
    """Solve a contiguous window of ``nroots`` secular roots.

    The root-sharding primitive of the distributed conquer phase: each
    device of the solver mesh solves roots ``[start, start + nroots)`` of
    a cooperative merge and the windows are all-gathered back into the
    full (origin, tau) arrays.  ``start`` may be traced (it is the device
    index times the window width inside a shard_map body); ``nroots`` is
    static.  Per-root arithmetic is exactly :func:`_solve_chunk`'s --
    every root's iteration depends only on its own index plus the full
    (d, z2) pole state, so a window solve is bit-identical to the same
    roots of a full :func:`secular_solve` regardless of how either call
    tiles the root axis.

    Returns (origin (nroots,) int32, tau (nroots,)).
    """
    start = jnp.asarray(start, jnp.int32)
    if dense or nroots <= chunk:
        jc = start + jnp.arange(nroots, dtype=jnp.int32)
        return _solve_chunk(jc, d, z2, rho, kprime, niter)
    C = min(chunk, nroots)
    Kp = _pad_len(nroots, C)
    idx = start + jnp.arange(Kp, dtype=jnp.int32).reshape(-1, C)
    fn = functools.partial(_solve_chunk, d=d, z2=z2, rho=rho,
                           kprime=kprime, niter=niter)
    origin, tau = jax.lax.map(lambda j: fn(j), idx)
    return origin.reshape(-1)[:nroots], tau.reshape(-1)[:nroots]


def secular_solve_window_batched(d, z2, rho, kprime, start, nroots: int, *,
                                 niter: int = DEFAULT_NITER,
                                 chunk: int = 128, dense: bool = False):
    """Problem-batched window solve: d, z2 (B, K); rho, kprime (B,);
    ``start`` scalar (the same window of every problem in the batch --
    the cooperative level's layout).  Returns (origin (B, nroots) int32,
    tau (B, nroots))."""
    fn = functools.partial(secular_solve_window, nroots=nroots, niter=niter,
                           chunk=chunk, dense=dense)
    return jax.vmap(fn, in_axes=(0, 0, 0, 0, None))(d, z2, rho, kprime,
                                                    start)


def secular_solve_batched(d, z2, rho, kprime, *, niter: int = DEFAULT_NITER,
                          chunk: int = 128, dense: bool = False):
    """Problem-batched secular solve: one launch for B independent merges.

    d, z2: (B, K); rho, kprime: (B,).  The chunked single-problem path is
    rank-polymorphic under vmap (``lax.map``/``fori_loop`` batch their
    bodies), so the batched form is the same streamed kernel with every
    chunk evaluation B-wide -- per-problem results are bit-identical to
    the unbatched call.  Returns (origin (B, K) int32, tau (B, K)).
    """
    fn = functools.partial(secular_solve, niter=niter, chunk=chunk,
                           dense=dense)
    return jax.vmap(fn)(d, z2, rho, kprime)


def secular_postpass_batched(R, d, z, origin, tau, kprime, rho, *,
                             use_zhat: bool = True, chunk: int = 128,
                             dense: bool = False):
    """Problem-batched fused post-pass (see :func:`secular_postpass`).

    R: (B, r, K); d, z, origin, tau: (B, K); kprime, rho: (B,).
    Returns (zhat (B, K), rows (B, r, K)).
    """
    fn = functools.partial(secular_postpass, use_zhat=use_zhat, chunk=chunk,
                           dense=dense)
    return jax.vmap(fn)(R, d, z, origin, tau, kprime, rho)


def secular_eigenvalues(d, origin, tau):
    """Materialize eigenvalues from compact delta representation."""
    return d[origin] + tau


def zhat_reconstruct(d, z, origin, tau, kprime, rho, *, chunk: int = 128):
    """Gu-Eisenstat stable weight reconstruction (LAPACK DLAED3 analogue).

    Recomputes |zhat_i| such that the poles ``d`` with weights ``zhat`` have
    *exactly* the computed roots, which keeps the streamed secular vectors
    (and therefore the propagated boundary rows) numerically orthogonal.

      zhat_i^2 = prod_j (lam_j - d_i) / [rho * prod_{j != i} (d_j - d_i)]

    computed in log space, streaming over j so peak memory is O(chunk * K).
    Inactive entries pass through unchanged.
    """
    K = d.shape[0]
    dtype = d.dtype
    d_org = d[origin]  # (K,)
    active = jnp.arange(K) < kprime

    C = min(chunk, K)
    Kp = _pad_len(K, C)
    idx = jnp.arange(Kp, dtype=jnp.int32).reshape(-1, C)
    tiny = jnp.finfo(dtype).tiny

    def chunk_fn(ic):
        ic_safe = jnp.minimum(ic, K - 1)
        d_i = d[ic_safe]  # (C,)
        # lam_j - d_i via the compact representation: (d_org_j - d_i) + tau_j
        lam_diff = (d_org[None, :] - d_i[:, None]) + tau[None, :]  # (C, K)
        pole_diff = d[None, :] - d_i[:, None]
        jmask = active[None, :]
        selfmask = jnp.arange(K)[None, :] == ic_safe[:, None]
        log_num = jnp.sum(
            jnp.where(jmask, jnp.log(jnp.maximum(jnp.abs(lam_diff), tiny)), 0.0),
            axis=-1)
        log_den = jnp.sum(
            jnp.where(jmask & ~selfmask,
                      jnp.log(jnp.maximum(jnp.abs(pole_diff), tiny)), 0.0),
            axis=-1)
        z2 = jnp.exp(log_num - log_den) / rho
        return z2

    z2hat = jax.lax.map(chunk_fn, idx).reshape(-1)[:K]
    zhat = jnp.sign(z) * jnp.sqrt(jnp.maximum(z2hat, 0.0))
    return jnp.where(active, zhat, z).astype(dtype)


def boundary_rows_update(R, d, z, origin, tau, kprime, *, chunk: int = 128):
    """Selected-row update: R_parent[:, j] = R_child @ yhat_j (paper Eq. in 4.1).

    For each active root j the normalized secular eigenvector is

        y_j(i) = (z_i / ((d_i - d_origin_j) - tau_j)) / ||.||

    and the parent rows are streamed dot products -- the K x K secular
    eigenvector block is never materialized (chunked: O(r * K + chunk * K)).
    Deflated columns pass through.

    Args:
      R: (r, K) selected child rows (r == 2 for BR; r == K for the
        full-vector / lazy baselines which reuse this routine).
    Returns: (r, K) updated rows.
    """
    r, K = R.shape
    dtype = R.dtype
    d_org = d[origin]
    active_i = (jnp.arange(K) < kprime)

    C = min(chunk, K)
    Kp = _pad_len(K, C)
    idx = jnp.arange(Kp, dtype=jnp.int32).reshape(-1, C)

    def chunk_fn(jc):
        jc_safe = jnp.minimum(jc, K - 1)
        do = d_org[jc_safe]
        tj = tau[jc_safe]
        delta = (d[None, :] - do[:, None]) - tj[:, None]  # (C, K)
        safe = jnp.where(active_i[None, :] & (delta != 0.0), delta, 1.0)
        y = jnp.where(active_i[None, :], z[None, :] / safe, 0.0)  # (C, K)
        nrm = jnp.sqrt(jnp.sum(y * y, axis=-1))
        nrm = jnp.where(nrm > 0.0, nrm, 1.0)
        cols = _rows_dot(R, y.T) / nrm[None, :]  # (r, C)
        return cols

    cols = jax.lax.map(chunk_fn, idx)             # (nchunk, r, C)
    cols = jnp.moveaxis(cols, 1, 0).reshape(r, -1)[:, :K]
    active_j = (jnp.arange(K) < kprime)[None, :]
    return jnp.where(active_j, cols, R).astype(dtype)


def _rows_dot(R, y):
    """R @ y at full precision: the default f32 matmul on TPU is one bf16
    pass, which leaves the propagated rows ~1e-2 off (measured on a v5e)."""
    return jnp.matmul(R, y, precision=jax.lax.Precision.HIGHEST)


def _postpass_tile(ic, d, z, d_org, tau, kprime, rho, use_zhat):
    """One fused (C, K) delta tile: rows = poles ``ic``, columns = all roots.

    The tile ``lam_diff[c, j] = (d_org_j - d_i) + tau_j`` is formed ONCE and
    serves both reductions:

      * row-reduction over j -> Gu-Eisenstat weight zhat_i for the tile's
        poles (DLAED3's ratio form: the full root range is resident in the
        tile, so the numerator/denominator factors pair up as interlaced
        ratios (lam_j - d_i)/(d_j - d_i) and zhat finalizes inside the
        tile.  The ratios are summed in logs: their full product is O(1),
        but partial products over- or underflow at large K, and on TPU
        (emulated float64 included) that left non-finite weights),
      * the tile's poles' additive contribution to EVERY root column of the
        selected-row update, using ``delta = -lam_diff`` and the freshly
        reconstructed weights.

    Returns (zhat_c (C,), y (C, K)) where y holds the *unnormalized* secular
    eigenvector entries y_j(i) = w_i / ((d_i - d_org_j) - tau_j); column
    norms are accumulated by the caller across tiles.
    """
    K = d.shape[0]
    dtype = d.dtype
    active_j = (jnp.arange(K) < kprime)[None, :]

    ic_safe = jnp.minimum(ic, K - 1)
    # valid poles: active AND not tail padding (padded ic duplicate pole
    # K-1 and must contribute nothing; ic >= K implies ic >= kprime).
    valid_i = ic < kprime
    d_i = d[ic_safe]
    z_i = z[ic_safe]

    lam_diff = (d_org[None, :] - d_i[:, None]) + tau[None, :]      # (C, K)

    if use_zhat:
        pole_diff = d[None, :] - d_i[:, None]
        selfmask = jnp.arange(K)[None, :] == ic_safe[:, None]
        ok = active_j & ~selfmask & (pole_diff != 0.0)
        ratio = jnp.where(ok, lam_diff / jnp.where(ok, pole_diff, 1.0), 1.0)
        tiny = jnp.finfo(dtype).tiny
        log_abs = lambda x: jnp.log(jnp.maximum(jnp.abs(x), tiny))
        self_term = (d_org[ic_safe] - d_i) + tau[ic_safe]   # lam_i - d_i
        z2hat = jnp.exp(jnp.sum(log_abs(ratio), axis=-1)
                        + log_abs(self_term)) / rho
        zhat_c = jnp.sign(z_i) * jnp.sqrt(z2hat)
        zhat_c = jnp.where(valid_i, zhat_c, z_i).astype(dtype)
        w = jnp.where(valid_i, zhat_c, 0.0)
    else:
        zhat_c = z_i
        w = jnp.where(valid_i, z_i, 0.0)

    delta = -lam_diff                         # (d_i - d_org_j) - tau_j
    safe = jnp.where(valid_i[:, None] & (delta != 0.0), delta, 1.0)
    y = jnp.where(valid_i[:, None], w[:, None] / safe, 0.0)        # (C, K)
    return zhat_c, y


def secular_postpass(R, d, z, origin, tau, kprime, rho, *,
                     use_zhat: bool = True, chunk: int = 128,
                     dense: bool = False):
    """Fused conquer post-pass: weight reconstruction + selected-row update.

    Replaces the two independent streamed passes ``zhat_reconstruct`` +
    ``boundary_rows_update`` with a single sweep over the delta structure
    ``(d_i - d_org_j) - tau_j``: each (chunk, K) tile is materialized once
    and feeds both the Gu-Eisenstat weights and the r-row update (the merge
    is bandwidth-bound, so halving the streamed traffic over the delta
    structure is the paper's Section 4.1 lever).

    The key reorganization vs the two-pass form: the sweep is chunked over
    POLES (not roots).  A pole chunk's zhat only needs its own tile rows
    (full root range, resident), so the reconstructed weights are final
    within the tile and immediately usable for that chunk's additive
    contribution to every root column; per-column norms accumulate across
    chunks and are applied once at the end.

    Args:
      R: (r, K) selected child rows.  dense: single (K, K) vectorized tile
      (no scan -- the small-K path that stays parallel under vmap).

    Returns:
      (zhat, rows): reconstructed weights (== z when use_zhat=False or
      deflated) and the updated selected rows, matching the two-pass
      ``zhat_reconstruct`` + ``boundary_rows_update`` composition to
      rounding (the fused pass sums DLAED3's interlaced ratios in logs,
      the two-pass form sums numerator and denominator logs apart).
    """
    r, K = R.shape
    dtype = R.dtype

    d_org = d[jnp.minimum(origin, K - 1)]
    active_j = (jnp.arange(K) < kprime)[None, :]

    if dense:
        ic = jnp.arange(K, dtype=jnp.int32)
        zhat, y = _postpass_tile(ic, d, z, d_org, tau, kprime, rho,
                                 use_zhat)
        cols = _rows_dot(R, y)                            # (r, K)
        nrm2 = jnp.sum(y * y, axis=0)
    else:
        C = min(chunk, K)
        Kp = _pad_len(K, C)
        idx = jnp.arange(Kp, dtype=jnp.int32).reshape(-1, C)

        def step(carry, ic):
            cols_acc, nrm2_acc = carry
            zhat_c, y = _postpass_tile(ic, d, z, d_org, tau, kprime,
                                       rho, use_zhat)
            Rc = jnp.take(R, jnp.minimum(ic, K - 1), axis=1)   # (r, C)
            cols_acc = cols_acc + _rows_dot(Rc, y)
            nrm2_acc = nrm2_acc + jnp.sum(y * y, axis=0)
            return (cols_acc, nrm2_acc), zhat_c

        init = (jnp.zeros((r, K), dtype), jnp.zeros((K,), dtype))
        (cols, nrm2), zhat_chunks = jax.lax.scan(step, init, idx)
        zhat = zhat_chunks.reshape(-1)[:K]

    nrm = jnp.sqrt(nrm2)
    cols = cols / jnp.where(nrm > 0.0, nrm, 1.0)[None, :]
    rows = jnp.where(active_j, cols, R).astype(dtype)
    zhat = jnp.where(active_j[0], zhat, z).astype(dtype)
    return zhat, rows


def secular_merge_resident(d, z, R, rho, kprime, *,
                           niter: int = DEFAULT_NITER,
                           use_zhat: bool = True):
    """Single-pass resident merge: dense secular solve + fused post-pass.

    XLA realization of the VMEM-resident merge kernel contract: the root
    solve and the conquer post-pass are one traced region, so the
    converged (origin, tau) flow straight into the zhat/row-update
    reductions with no intermediate dispatch boundary (on the Pallas
    backend this is literally one kernel launch; here it is one fused
    XLA computation).  Everything is dense -- the caller gates on K being
    at or below the residency threshold.

    Args:
      d: (K,) poles (active prefix sorted ascending); z: (K,) signed
        secular weights (zero at deflated entries); R: (r, K) selected
        rows; rho scalar > 0; kprime traced int32.

    Returns:
      (origin (K,) int32, tau (K,), zhat (K,), rows (r, K)).
    """
    origin, tau = secular_solve(d, z * z, rho, kprime, niter=niter,
                                dense=True)
    zhat, rows = secular_postpass(R, d, z, origin, tau, kprime, rho,
                                  use_zhat=use_zhat, dense=True)
    return origin, tau, zhat, rows


def secular_merge_resident_batched(d, z, R, rho, kprime, *,
                                   niter: int = DEFAULT_NITER,
                                   use_zhat: bool = True):
    """Problem-batched resident merge (see :func:`secular_merge_resident`).

    d, z: (B, K); R: (B, r, K); rho, kprime: (B,).  Returns
    (origin (B, K) int32, tau (B, K), zhat (B, K), rows (B, r, K)).
    """
    fn = functools.partial(secular_merge_resident, niter=niter,
                           use_zhat=use_zhat)
    return jax.vmap(fn)(d, z, R, rho, kprime)
