"""Partial-spectrum slicing: Sturm-count bisection + safeguarded Newton.

The BR algorithm makes *all-eigenvalue* solves linear-space, but real
spectral workloads (SLQ edge estimates, extremal-mode monitoring,
condition numbers) want k << n eigenvalues.  This module brackets exactly
the requested eigenvalues with Gershgorin bounds + vectorized
Sturm-sequence counts and refines every bracket **in parallel** inside
one ``lax.while_loop`` -- the spectrum-slicing front end of Keyes et
al.'s partial-spectrum D&C, realized on the library's batch-first
substrate:

  * ``sturm_count``      -- #{eigenvalues <= shift} via the LAPACK DSTEBZ
                            pivot recurrence (negcount of LDL^T), vectorized
                            over arbitrary shift batches.  The hot batched
                            form dispatches through ``kernels/ops`` (Pallas
                            kernel with a problems x shift-blocks grid on
                            TPU, fused XLA scan elsewhere).
  * ``_slice_targets``   -- all requested roots bisect their brackets
                            simultaneously (one count sweep refines every
                            interval at once), then a short safeguarded
                            Newton polish sharpens each root using the
                            derivative of the same pivot recurrence --
                            the secular solver's bracket-guarded iteration
                            pattern applied to the characteristic
                            polynomial (candidate outside the bracket ->
                            bisection step; counts keep the bracket exact).
  * ``eigvalsh_tridiagonal_range`` -- the public select-by-index /
                            select-by-value API.  Compiled executables are
                            cached by ``repro.core.plan.make_range_plan``
                            (k rounds up to a power-of-two bucket and the
                            target indices are a *traced* input, so
                            repeated top-k traffic of any (il, iu) window
                            hits one executable).
  * ``refine_clusters``  -- the mixed-precision pipeline's f64 stage:
                            certify approximate (f32-tree) eigenvalues
                            with ONE sorted f64 count sweep, then polish
                            only the non-certified clusters with a
                            bracket-guarded secant/Newton/bisection loop
                            against the original (d, e) -- the same
                            freeze-per-bracket pattern as
                            ``_slice_targets``, with the live set
                            compacted between launches so refinement cost
                            is proportional to the miss set, not n.

Memory: O(B * (n + k)) total -- no merge tree, no selected rows; work is
O(B * k * n) per bisection sweep.  For k << n this undercuts the full
conquer by the measured multiples in BENCH_partial.json.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import guard as _guard
from repro.core import tune as _tune
from repro.core.instrument import SolveCounter, span

# Bisection halvings cap.  The while_loop exits as soon as every bracket
# is below its tolerance (~53 + log2(spread/scale) halvings at float64);
# the cap only bounds the trip count for adversarial inputs.
DEFAULT_MAX_BISECT = 96

# Safeguarded Newton polish steps after bisection.  Newton on the
# characteristic polynomial is quadratically convergent from inside an
# isolated bracket, so 2 steps pin the root to ~eps * ||T|| even when the
# bisection tolerance stopped a few ulps short; each step also refines
# the bracket via its own Sturm count, so the polish can never leave it.
DEFAULT_POLISH = 2

# Unroll factor of the pivot-recurrence scans.  The recurrence is a
# sequential dependency chain per shift lane, so unrolling changes loop
# structure only, never per-lane arithmetic order (counts and derivative
# sums are bit-identical at any unroll); it cuts the CPU sweep cost
# ~35-40% at wide lane counts by amortizing the scan's per-step dispatch.
_SCAN_UNROLL = 8

# Certification tolerance of the mixed-precision pipeline, in units of
# eps_f64 * max(1, ||T||_inf).  16 keeps the mixed path's contribution at
# a quarter of the 64-eps cross-library conformance budget: the f64 D&C
# itself and LAPACK's drivers each deviate up to ~50 eps * ||T|| from one
# another at n = 4096, so certifying tighter buys nothing observable while
# costing refinement iterations on every near-degenerate cluster.
DEFAULT_REFINE_TOL = 16.0

# Certify -> refine rounds cap.  The refine loop's delta-freeze criterion
# is a heuristic (a tiny secant step near an unresolved pair can freeze a
# lane early), so soundness comes from *re-certifying* after each refine
# pass -- certification is sound by construction (count-verified
# two-sided brackets).  Measured round trajectories collapse after one
# pass (miss counts [4096, 0] at n = 4096 random); 4 bounds adversarial
# spectra.
DEFAULT_REFINE_ROUNDS = 4

# while_loop trips per refine launch before the host loop compacts the
# live set: long enough to amortize a launch, short enough that lanes
# converging at the secant's superlinear rate stop paying for stragglers.
_REFINE_TRIPS = 4

# Refine launches per certify round: 24 launches * 4 trips = 96 bracket
# halvings even in the pure-bisection worst case -- the same budget as
# DEFAULT_MAX_BISECT, reached only if both secant and Newton candidates
# fail every trip.
_REFINE_MAX_LAUNCHES = 24

# One trace per (batch, lane-bucket) shape of the certify/refine
# executors -- same contract as plan.EXECUTOR_TRACES; surfaced through
# plan.plan_cache_stats() and reset by plan.clear_plan_cache().
REFINE_EXECUTOR_TRACES = SolveCounter("refine_executor_traces")


def _safmin(dtype) -> float:
    """Smallest normal number of ``dtype`` that the device keeps.  A TPU
    emulates float64 with float32's exponent range and flushes anything
    below 2**-126 to zero (measured on a v5e), ``finfo(float64).tiny``
    included -- a floor of that size would vanish on the chip."""
    tiny = float(jnp.finfo(dtype).tiny)
    if jnp.dtype(dtype).itemsize > 4 and _tune.pinned_backend() == "tpu":
        return max(tiny, float(jnp.finfo(jnp.float32).tiny))
    return tiny


def _pivot_floor(e2, dtype):
    """DSTEBZ-style pivot floor: guards the count recurrence's division.

    e2: (..., n-1) squared off-diagonals (may have zero length).  Returns
    a (..., 1)-shaped floor ``safmin * max(1, max e2)`` so that a pivot
    landing exactly on an eigenvalue is replaced by ``-pivmin`` (counted
    as negative, matching LAPACK's "eigenvalues <= shift" convention).
    """
    safmin = _safmin(dtype)
    emax = (jnp.max(e2, axis=-1, keepdims=True) if e2.shape[-1]
            else jnp.zeros(e2.shape[:-1] + (1,), dtype))
    return safmin * jnp.maximum(1.0, emax)


def sturm_count_xla(d, e2, shifts, pivmin):
    """Batched Sturm counts: #{eigenvalues of problem b <= shifts[b, s]}.

    d: (B, n); e2: (B, n-1) squared off-diagonals; shifts: (B, S);
    pivmin: (B, 1).  One fused scan over the matrix rows carries all
    B x S pivot lanes at once -- the XLA realization of the Pallas
    kernel's problems x shift-blocks grid.  Returns (B, S) int32.
    """
    q = d[:, :1] - shifts                             # (B, S)
    q = jnp.where(jnp.abs(q) < pivmin, -pivmin, q)
    cnt = (q <= 0.0).astype(jnp.int32)
    if d.shape[1] == 1:
        return cnt

    def step(carry, inp):
        q, cnt = carry
        di, e2i = inp                                 # (B,), (B,)
        q = (di[:, None] - shifts) - e2i[:, None] / q
        q = jnp.where(jnp.abs(q) < pivmin, -pivmin, q)
        return (q, cnt + (q <= 0.0).astype(jnp.int32)), None

    (q, cnt), _ = jax.lax.scan(
        step, (q, cnt), (d[:, 1:].T, e2.T), unroll=_SCAN_UNROLL)
    return cnt


def _count_and_newton(d, e2, x, pivmin):
    """One pivot sweep returning (count, logdet') at each shift.

    Same recurrence as :func:`sturm_count_xla` plus its derivative:
    with q_i the pivots of T - xI, r_i = q_i'/q_i accumulates

        s = sum_i q_i'/q_i = d/dx log|det(T - xI)| = -sum_k 1/(lam_k - x)

    so the Newton step for the nearest eigenvalue is ``x - 1/s`` (near an
    isolated root the k-th term dominates and x - 1/s -> lam_k).  The
    derivative rides the count sweep for free -- one extra multiply-add
    per row, no extra memory.
    """
    q = d[:, :1] - x
    q = jnp.where(jnp.abs(q) < pivmin, -pivmin, q)
    cnt = (q <= 0.0).astype(jnp.int32)
    r = -1.0 / q                                      # q_1' = -1
    s = r
    if d.shape[1] == 1:
        return cnt, s

    def step(carry, inp):
        q, cnt, r, s = carry
        di, e2i = inp
        u = e2i[:, None] / q                          # e2 / q_{i-1}
        qn = (di[:, None] - x) - u
        qn = jnp.where(jnp.abs(qn) < pivmin, -pivmin, qn)
        dq = -1.0 + u * r                             # q_i' via r_{i-1}
        rn = dq / qn
        return (qn, cnt + (qn <= 0.0).astype(jnp.int32), rn, s + rn), None

    (q, cnt, r, s), _ = jax.lax.scan(
        step, (q, cnt, r, s), (d[:, 1:].T, e2.T), unroll=_SCAN_UNROLL)
    return cnt, s


def _slice_targets(d, e, targets, *, maxiter: int = DEFAULT_MAX_BISECT,
                   polish: int = DEFAULT_POLISH):
    """Eigenvalues lam[targets[b]] of each problem b (traced core).

    d: (B, n); e: (B, n-1); targets: (B, k) int32 ascending indices in
    [0, n).  All B x k brackets are initialized from the per-problem
    Gershgorin bounds and refined together: every while_loop trip runs
    ONE batched Sturm sweep at the k midpoints and halves each bracket on
    its own count, exiting when the *widest* bracket converges.  A short
    safeguarded Newton polish (bracket-guarded like the secular
    iteration; out-of-bracket candidates fall back to the midpoint)
    follows.  Returns (B, k) eigenvalues, ascending along k for
    ascending targets.
    """
    from repro.kernels import ops as _ops  # deferred: kernels import core

    B, n = d.shape
    dtype = d.dtype
    eps = jnp.finfo(dtype).eps
    e2 = e * e
    pivmin = _pivot_floor(e2, dtype)                  # (B, 1)

    # Gershgorin enclosure per problem, pre-widened by one pivot floor so
    # the invariant count(lo) <= j < count(hi) holds at the endpoints.
    if n > 1:
        radius = jnp.zeros_like(d)
        radius = radius.at[:, :-1].add(jnp.abs(e)).at[:, 1:].add(jnp.abs(e))
    else:
        radius = jnp.zeros_like(d)
    glo = jnp.min(d - radius, axis=1, keepdims=True) - pivmin  # (B, 1)
    ghi = jnp.max(d + radius, axis=1, keepdims=True) + pivmin
    scale = jnp.maximum(jnp.abs(glo), jnp.abs(ghi))            # ~ ||T||
    tol = 2.0 * eps * jnp.maximum(scale, _safmin(dtype)) + 2.0 * pivmin

    k = targets.shape[1]
    lo = jnp.broadcast_to(glo, (B, k))
    hi = jnp.broadcast_to(ghi, (B, k))

    def count(x):
        return _ops.sturm_count_batched(d, e2, x, pivmin)

    def cond(state):
        it, lo, hi = state
        return (it < maxiter) & jnp.any(hi - lo > tol)

    def body(state):
        it, lo, hi = state
        mid = 0.5 * (lo + hi)
        above = count(mid) > targets       # count(mid) >= j+1: lam_j <= mid
        # Freeze converged brackets: their result must not depend on how
        # long the *widest* bracket in the launch keeps iterating, so a
        # root's eigenvalue is bit-identical across batch shapes, k
        # buckets and window positions that happen to share its bracket.
        live = (hi - lo) > tol
        hi = jnp.where(above & live, mid, hi)
        lo = jnp.where(~above & live, mid, lo)
        return it + 1, lo, hi

    _, lo, hi = jax.lax.while_loop(
        cond, body, (jnp.asarray(0, jnp.int32), lo, hi))
    x = 0.5 * (lo + hi)

    for _ in range(polish):
        cnt, s = _count_and_newton(d, e2, x, pivmin)
        above = cnt > targets
        hi = jnp.where(above, x, hi)
        lo = jnp.where(above, lo, x)
        cand = x - 1.0 / s
        inb = jnp.isfinite(cand) & (cand > lo) & (cand < hi)
        x = jnp.where(inb, cand, 0.5 * (lo + hi))
    return x.astype(dtype)


@jax.jit
def _sturm_count_flat(d, e2, shifts):
    return sturm_count_xla(d[None, :], e2[None, :], shifts[None, :],
                           _pivot_floor(e2[None, :], d.dtype))[0]


def sturm_count(d, e, shifts):
    """#{eigenvalues of the tridiagonal (d, e) <= shift}, any shift shape.

    Single-problem convenience wrapper over the batched count (LAPACK
    DSTEBZ negcount convention: a pivot within the floor of zero counts
    as negative).  d: (n,); e: (n-1,); shifts: any shape.  Returns int32
    of ``shifts.shape``.  Malformed input (empty/non-1-D ``d``, ``e`` not
    of width n-1, NaN/Inf entries) raises
    :class:`repro.core.guard.InvalidInputError`.
    """
    if np.ndim(d) != 1:
        raise _guard.InvalidInputError(
            f"sturm_count: d must be 1-D (n,), got shape {np.shape(d)} "
            f"(use the plan/request layer for batched problems)",
            field="d")
    _guard.validate_problem(d, e, name="sturm_count")
    d = jnp.asarray(d)
    e = jnp.asarray(e)
    shifts = jnp.asarray(shifts, d.dtype)
    cnt = _sturm_count_flat(d, e * e, shifts.reshape(-1))
    return cnt.reshape(shifts.shape)


class SpectrumCertificate(NamedTuple):
    """Result of :func:`certify_spectrum`.

    certified: (n,) or (B, n) bool -- True where the true j-th eigenvalue
        provably lies within ``tol`` of ``lam[..., j]``.
    lo / hi: tightest count-verified enclosure the sweep observed for
        each eigenvalue (always valid, certified or not).
    tol: (1,) or (B, 1) absolute tolerance the certificate used,
        ``tol_factor * eps * max(1, ||T||_inf)`` per problem.
    """
    certified: object
    lo: object
    hi: object
    tol: object

    @property
    def all_certified(self) -> bool:
        return bool(np.asarray(self.certified).all())


def certify_spectrum(d, e, lam, *, tol: float = DEFAULT_REFINE_TOL,
                     nvalid=None):
    """Certify approximate eigenvalues with ONE batched Sturm count sweep.

    The robustness layer's product-facing certifier (PR 7's mixed-
    precision ``_certify_executor``, generalized to every method and
    precision): for each approximate eigenvalue ``lam[..., j]`` the sweep
    verifies -- by exact integer Sturm counts against the ORIGINAL
    ``(d, e)``, sound in any precision -- whether the true j-th
    eigenvalue lies in ``(lam_j - tol_abs, lam_j + tol_abs]`` where
    ``tol_abs = tol * eps * max(1, ||T||_inf)`` in the input dtype.
    Cost is one fused count sweep over 2n shifts per problem, the same
    executable the mixed pipeline reuses, amortized across coalesced
    flushes by the serving layer.

    Args:
      d: (n,) or (B, n) diagonals.
      e: (n-1,) or (B, n-1) off-diagonals.
      lam: approximate eigenvalues, ascending, same leading shape as d.
      tol: tolerance in ``eps * max(1, ||T||_inf)`` units (eps of the
        INPUT dtype, so f32 problems certify against an f32-meaningful
        bound).
      nvalid: optional (B,) real-lane counts for rows carrying decoupled
        sentinel padding (the plan/serve convention); padded lanes
        certify vacuously.

    Returns:
      :class:`SpectrumCertificate`; shapes follow the input (1-D in,
      1-D out).
    """
    _guard.validate_problem(d, e, name="certify_spectrum")
    single = np.ndim(d) == 1
    d = jnp.atleast_2d(jnp.asarray(d))
    e = jnp.atleast_2d(jnp.asarray(e))
    lam = jnp.atleast_2d(jnp.asarray(lam, d.dtype))
    if lam.shape != d.shape:
        raise _guard.InvalidInputError(
            f"certify_spectrum: lam must match d's shape {tuple(d.shape)} "
            f"(one estimate per eigenvalue), got {tuple(lam.shape)}",
            field="lam")
    B, n = d.shape
    nvalid_arr = (jnp.full((B,), n, jnp.int32) if nvalid is None
                  else jnp.atleast_1d(jnp.asarray(nvalid, jnp.int32)))
    if float(tol) <= 0.0:
        raise _guard.InvalidInputError(
            f"certify_spectrum: tol must be positive, got {tol}",
            field="tol")
    tol_arr = jnp.asarray(float(tol), d.dtype)
    cert, lo, hi, tol_abs = _certify_executor(d, e * e, lam, nvalid_arr,
                                              tol_arr)
    if single:
        cert, lo, hi, tol_abs = cert[0], lo[0], hi[0], tol_abs[0]
    return SpectrumCertificate(cert, lo, hi, tol_abs)


# ---------------------------------------------------------------------------
# Mixed-precision refinement: f64 Sturm certification + targeted polish
# ---------------------------------------------------------------------------


@jax.jit
def _certify_executor(d, e2, lam, nvalid, tol_factor):
    """Certify all approximate eigenvalues with ONE f64 count sweep.

    d: (B, N); e2: (B, N-1); lam: (B, N) approximate eigenvalues (rows may
    carry decoupled sentinel padding at index >= nvalid[b]); nvalid: (B,)
    int32 real targets per row; tol_factor: f64 scalar (traced, so one
    executable serves every tolerance).

    The 2N shifts ``lam_j -+ tol`` are evaluated in one fused sweep;
    target j is certified iff ``count(lam_j - tol) <= j`` and
    ``count(lam_j + tol) >= j + 1`` -- i.e. the true lam_j provably lies
    in ``(lam_j - tol, lam_j + tol]``.  Sorting the evaluated (shift,
    count) pairs makes the counts monotone, so each target also extracts
    the TIGHTEST verified bracket the sweep observed anywhere (a
    neighbour's shift is often far closer than the lane's own +-tol
    endpoints), which is what lets the refine loop start a few bisections
    from done.  Returns (cert (B, N) bool, lo (B, N), hi (B, N),
    tol (B, 1)); non-real lanes certify vacuously.
    """
    REFINE_EXECUTOR_TRACES.increment()
    B, N = d.shape
    dtype = d.dtype
    pivmin = _pivot_floor(e2, dtype)
    j = jnp.arange(N, dtype=jnp.int32)[None, :]
    valid = j < nvalid[:, None]

    # Per-problem scale masked to real rows: padded sentinel diagonals sit
    # ABOVE the real Gershgorin bound by construction and would inflate
    # the tolerance; sentinel couplings are exactly zero, so e2 needs no
    # mask.
    e_abs = jnp.sqrt(e2)
    dmax = jnp.max(jnp.where(valid, jnp.abs(d), 0.0), axis=1, keepdims=True)
    emax = (jnp.max(e_abs, axis=1, keepdims=True) if e2.shape[1]
            else jnp.zeros((B, 1), dtype))
    tol = tol_factor * jnp.finfo(dtype).eps * jnp.maximum(
        1.0, dmax + 2.0 * emax)

    shifts = jnp.concatenate([lam - tol, lam + tol], axis=1)     # (B, 2N)
    cnt = sturm_count_xla(d, e2, shifts, pivmin)                 # (B, 2N)
    cert = (cnt[:, :N] <= j) & (cnt[:, N:] >= j + 1) | ~valid

    order = jnp.argsort(shifts, axis=1)
    ss = jnp.take_along_axis(shifts, order, axis=1)
    cs = jnp.take_along_axis(cnt, order, axis=1)

    def brackets(cs_b):
        # cs_b is monotone nondecreasing along the sorted shifts:
        # largest evaluated shift with count <= j is a verified lower
        # bound (lam_j > shift), smallest with count >= j+1 a verified
        # upper bound (lam_j <= shift).
        ilo = jnp.searchsorted(cs_b, j[0], side="right") - 1
        ihi = jnp.searchsorted(cs_b, j[0] + 1, side="left")
        return ilo, ihi

    ilo, ihi = jax.vmap(brackets)(cs)
    # Gershgorin fallback at the sweep's extremes (padded rows only widen
    # the enclosure -- sentinel diagonals raise ghi, never lower glo --
    # so the unmasked bound stays sound).
    radius = jnp.zeros_like(d)
    if e2.shape[1]:
        radius = radius.at[:, :-1].add(e_abs).at[:, 1:].add(e_abs)
    glo = jnp.min(d - radius, axis=1, keepdims=True) - pivmin
    ghi = jnp.max(d + radius, axis=1, keepdims=True) + pivmin
    lo = jnp.where(ilo >= 0,
                   jnp.take_along_axis(ss, jnp.maximum(ilo, 0), axis=1), glo)
    hi = jnp.where(ihi < 2 * N,
                   jnp.take_along_axis(ss, jnp.minimum(ihi, 2 * N - 1),
                                       axis=1), ghi)
    return cert, lo, hi, tol


@functools.partial(jax.jit, static_argnames=("maxiter",))
def _refine_executor(d, e2, x, lo, hi, xp, gp, mode, tgt, live, tol, *,
                     maxiter):
    """Bracket-guarded f64 polish of the compacted live lanes.

    d: (B, n); e2: (B, n-1); x, lo, hi: (B, k) iterates and count-verified
    brackets; xp, gp: previous (iterate, g) pair seeding the secant slope
    (xp == x flags "no history": the slope divides to non-finite and the
    first trip falls back to Newton); mode: (B, k) int32 lane mode (see
    below); tgt: (B, k) int32 target indices;
    live: (B, k); tol: (B, 1) certification tolerance.

    Each trip runs ONE fused count+derivative sweep over all lanes.  With
    s = -sum_k 1/(lam_k - x), the function g(x) = 1/s has a simple zero
    at each eigenvalue -- but its local slope is NOT 1 near close pairs
    (g there is ~the harmonic mean of the pole distances), which is why
    plain Newton ``x - g`` degrades to rate-1/2 linear convergence
    exactly on the clusters the f32 tree missed.  The secant step
    ``x - g * (x - xp) / (g - gp)`` measures the true slope and restores
    superlinear convergence (measured: halves total polish iterations);
    candidates are accepted only when finite, strictly inside the
    count-updated bracket, and on a credibly positive slope, falling back
    to Newton then to the bisection midpoint.

    A lane converges only on a count-verified bracket no wider than half
    the tolerance.  A vanishing step means x sits on SOME eigenvalue --
    inside a tight cluster possibly a neighbour of the target -- so the
    lane (mode 0) probes half a tolerance to the bracket's other side
    (mode 1).  A probe that closes the bracket converges; one that does
    not shows x had locked onto a neighbour, and the lane bisects from
    then on (mode 2), which the trip budget always completes.  Convergence
    freezes a lane's entire state (freeze-per-bracket: results never
    depend on how long stragglers iterate, so refinement is deterministic
    across live-set compactions).  Returns (x, lo, hi, xp, gp, mode, live,
    iters).
    """
    REFINE_EXECUTOR_TRACES.increment()
    pivmin = _pivot_floor(e2, d.dtype)
    tolf = 0.5 * tol     # freeze at half the certification tolerance

    def cond(state):
        it, x, lo, hi, xp, gp, mode, live, its = state
        return (it < maxiter) & jnp.any(live)

    def body(state):
        it, x, lo, hi, xp, gp, mode, live, its = state
        cnt, s = _count_and_newton(d, e2, x, pivmin)
        above = cnt > tgt                  # count(x) >= j+1: lam_j <= x
        # The count at x only ever tightens the bracket (an iterate may
        # start outside it).
        nhi = jnp.where(above & live, jnp.minimum(x, hi), hi)
        nlo = jnp.where(~above & live, jnp.maximum(x, lo), lo)
        g = 1.0 / s
        cand_n = x - g
        slope = (g - gp) / (x - xp)
        cand_s = x - g / slope
        ok_s = (jnp.isfinite(cand_s) & (cand_s > nlo) & (cand_s < nhi)
                & (slope > 0.05))
        ok_n = jnp.isfinite(cand_n) & (cand_n > nlo) & (cand_n < nhi)
        mid = 0.5 * (nlo + nhi)
        cand = jnp.where(ok_s, cand_s, cand_n)
        # Bisect unless the step at least halves the previous one (the
        # rtsafe rule): next to a cluster of m eigenvalues, 1/s moves by
        # about distance/m per trip and would crawl.
        fast = (xp == x) | (2.0 * jnp.abs(cand - x) <= jnp.abs(x - xp))
        nx = jnp.where((ok_s | ok_n) & fast, cand, mid)
        conv = nhi - nlo <= tolf
        small = jnp.abs(nx - x) <= 0.25 * tolf
        probe = jnp.where(above, x - 0.5 * tolf, x + 0.5 * tolf)
        nmode = jnp.where(mode == 1, 2, jnp.where(small & (mode == 0), 1,
                                                  mode))
        nx = jnp.where(nmode == 2, mid, jnp.where(nmode == 1, probe, nx))
        nmode = jnp.where(live, nmode, mode)
        nxp = jnp.where(live, x, xp)
        ngp = jnp.where(live, g, gp)
        nx = jnp.where(live, nx, x)
        its = its + jnp.sum(live, dtype=jnp.int32)
        return it + 1, nx, nlo, nhi, nxp, ngp, nmode, live & ~conv, its

    state = (jnp.asarray(0, jnp.int32), x, lo, hi, xp, gp, mode, live,
             jnp.asarray(0, jnp.int32))
    _, x, lo, hi, xp, gp, mode, live, its = jax.lax.while_loop(cond, body,
                                                               state)
    return x, lo, hi, xp, gp, mode, live, its


def _bucket(k: int) -> int:
    """Next power of two (min 1) -- lane-count buckets keep the refine
    executor's trace count logarithmic in n."""
    return 1 << max(0, (int(k) - 1).bit_length())


def _refine_misses(d, e2, lamh, loh, hih, tol_dev, miss):
    """Host-driven refinement of the miss set with live-lane compaction.

    d, e2: device (B, n)/(B, n-1); lamh, loh, hih: HOST (B, n) float64
    state arrays (mutated in place: refined lanes are scattered back);
    tol_dev: (B, 1) device tolerance; miss: host (B, n) bool.

    Every ``_REFINE_TRIPS`` while_loop trips, still-live lanes are
    gathered to the host, compacted to each problem's live set (padded to
    the batch max, bucketed to a power of two so launches reuse cached
    executables), and re-launched -- the full-width sweep cost decays
    with the live set instead of paying n lanes until the last straggler
    freezes.  Secant history (xp, gp) is carried across compactions.
    Freeze-per-bracket makes per-lane trajectories independent of the
    compaction schedule, so results are deterministic.  Returns total
    polish iterations.

    Each launch is three spans: ``repro.polish.compact`` (the gather and
    transfer of the ``live`` lanes into ``width`` launched lanes),
    ``repro.polish.sweep`` (the launch and the wait for its outputs) and
    ``repro.polish.scatter``.
    """
    B, n = miss.shape
    xph = lamh.copy()      # xp == x: no secant history yet
    gph = np.zeros_like(lamh)
    modeh = np.zeros(lamh.shape, np.int32)
    idxs = [np.nonzero(miss[b])[0].astype(np.int32) for b in range(B)]
    iters = 0
    for launch in range(_REFINE_MAX_LAUNCHES):
        sizes = [len(ix) for ix in idxs]
        kmax = max(sizes)
        if kmax == 0:
            break
        k = min(_bucket(kmax), n)
        with span("repro.polish.compact", launch=launch, live=sum(sizes),
                  width=B * k):
            gidx = np.zeros((B, k), np.int32)
            live = np.zeros((B, k), bool)
            for b, ix in enumerate(idxs):
                gidx[b, :len(ix)] = ix
                live[b, :len(ix)] = True
            lanes = [jnp.asarray(np.take_along_axis(a, gidx, axis=1))
                     for a in (lamh, loh, hih, xph, gph, modeh)]
            lanes += [jnp.asarray(gidx), jnp.asarray(live)]
        with span("repro.polish.sweep", launch=launch):
            x1, lo1, hi1, xp1, gp1, mode1, live1, its = _refine_executor(
                d, e2, *lanes, tol_dev, maxiter=_REFINE_TRIPS)
            iters += int(its)
            x1, lo1, hi1 = np.asarray(x1), np.asarray(lo1), np.asarray(hi1)
            xp1, gp1 = np.asarray(xp1), np.asarray(gp1)
            live1, mode1 = np.asarray(live1), np.asarray(mode1)
        with span("repro.polish.scatter", launch=launch):
            for b in range(B):
                ix = gidx[b, live[b]]
                for src, dst in ((x1, lamh), (lo1, loh), (hi1, hih),
                                 (xp1, xph), (gp1, gph), (mode1, modeh)):
                    dst[b, ix] = src[b, live[b]]
                idxs[b] = gidx[b, live[b] & live1[b]]
    return iters


def refine_clusters(d, e, lam, *, nvalid=None,
                    tol_factor: float = DEFAULT_REFINE_TOL,
                    rounds: int = DEFAULT_REFINE_ROUNDS, sort: bool = True):
    """Sturm-certified f64 refinement of approximate eigenvalues.

    The mixed-precision pipeline's second stage: ``lam`` holds all n
    eigenvalue estimates of each problem (typically the f32 D&C tree's
    output, upcast), and this stage makes them meet the documented
    ``tol_factor * eps_f64 * max(1, ||T||_inf)`` bound against the
    original float64 (d, e) -- certifying everything with one f64 count
    sweep per round and polishing ONLY the non-certified clusters, so the
    f64 work is proportional to the miss set.

    Args:
      d: (B, n) float64 diagonals (rows may carry decoupled sentinel
        padding above ``nvalid[b]`` -- the plan/serve padding convention;
        sentinel lanes are never touched).
      e: (B, n-1) float64 off-diagonals.
      lam: (B, n) approximate eigenvalues, ascending per problem.
      nvalid: optional (B,) int32 count of real eigenvalues per row
        (default: n).
      tol_factor: certification tolerance in eps_f64 * ||T|| units.
      rounds: certify->refine rounds cap (see DEFAULT_REFINE_ROUNDS; the
        loop exits as soon as a certify pass accepts every target, which
        is what makes the heuristic freeze criteria sound).
      sort: re-sort each row ascending before returning (refined values
        each lie within tol of the sorted truth, so the sort restores
        exact ordering without breaking any per-index bound).  Callers
        that must permute companion state identically -- boundary rows --
        pass False and apply their own argsort.

    Returns:
      (lam_refined (B, n) float64, info) with info keys ``targets``
      (real eigenvalues certified), ``polished`` (lanes refined),
      ``iterations`` (total polish sweeps), ``rounds`` (certify->refine
      rounds that found misses), and ``polished_mask`` (host (B, n) bool:
      exactly the lanes the polish loop touched -- unset lanes are
      returned bit-identical to their input).
    """
    if not jax.config.jax_enable_x64:
        raise ValueError(
            "refine_clusters certifies against float64 Sturm counts; "
            "enable jax_enable_x64 (see the README mixed-precision "
            "runbook)")
    d = jnp.asarray(d, jnp.float64)
    e = jnp.asarray(e, jnp.float64)
    lam = jnp.asarray(lam, jnp.float64)
    B, n = d.shape
    e2 = e * e
    nvalid_arr = (jnp.full((B,), n, jnp.int32) if nvalid is None
                  else jnp.asarray(nvalid, jnp.int32))
    tol_arr = jnp.asarray(float(tol_factor), jnp.float64)

    polished_mask = np.zeros((B, n), bool)
    iters = 0
    rounds_used = 0
    for r in range(max(1, int(rounds))):
        # The span holds the certify launch and the host read of its
        # outputs: the mask, and the state the polish needs on a miss.
        with span("repro.polish.certify", round=r):
            cert, lo, hi, tol_dev = _certify_executor(d, e2, lam,
                                                      nvalid_arr, tol_arr)
            miss = ~np.asarray(cert)
            if miss.any():
                lamh = np.asarray(lam).copy()
                loh, hih = np.asarray(lo).copy(), np.asarray(hi).copy()
        if not miss.any():
            break
        rounds_used += 1
        polished_mask |= miss
        iters += _refine_misses(d, e2, lamh, loh, hih, tol_dev, miss)
        lam = jnp.asarray(lamh)
    if sort:
        lam = jnp.sort(lam, axis=1)
    info = {"targets": int(np.asarray(
                jnp.sum(jnp.minimum(nvalid_arr, n)))),
            "polished": int(polished_mask.sum()),
            "iterations": iters, "rounds": rounds_used,
            "polished_mask": polished_mask}
    return lam, info


def _validate_index_range(n: int, il, iu):
    il, iu = int(il), int(iu)
    if not (0 <= il <= iu < n):
        raise ValueError(
            f"index range must satisfy 0 <= il <= iu < n; got il={il}, "
            f"iu={iu}, n={n} (indices are 0-based and inclusive)")
    return il, iu


def eigvalsh_tridiagonal_range(d, e, *, select: str = "i",
                               il=None, iu=None, vl=None, vu=None,
                               maxiter: int | None = None,
                               polish: int | None = None,
                               dtype=None):
    """Selected eigenvalues of the symmetric tridiagonal (d, e).

    The partial-spectrum front door: brackets exactly the requested
    eigenvalues with Sturm-count bisection (all intervals refined in
    parallel) and polishes each with a bracket-safeguarded Newton
    iteration -- O(k * n) work and O(n + k) memory, no merge tree, which
    beats the full conquer by multiples for k << n (BENCH_partial.json).

    Args:
      d: (n,) diagonal, or (B, n) for a problem batch.
      e: (n-1,) off-diagonal, or (B, n-1).
      select: "i" -- eigenvalues with 0-based ascending indices in the
        inclusive range [il, iu] (scipy's ``select='i'`` convention);
        "v" -- eigenvalues in the half-open interval (vl, vu]
        (single-problem only: the per-problem hit count would be ragged
        across a batch).
      maxiter: bisection halvings cap (the loop exits early on
        convergence).  None consults the tuning cache (``core.tune``),
        falling back to DEFAULT_MAX_BISECT.
      polish: safeguarded Newton polish steps after bisection.  None
        consults the tuning cache, falling back to DEFAULT_POLISH.

    Returns:
      (k,) ascending eigenvalues (or (B, k) for batched inputs) where
      k = iu - il + 1 for select="i" and the count of eigenvalues in
      (vl, vu] for select="v" (possibly 0).  Accuracy contract: each
      returned eigenvalue matches the corresponding entry of the full
      solve to <= 8 * eps * ||T||.
    """
    # The request core (repro.core.request) owns selection resolution
    # (select="v" becomes an index window via two Sturm counts there) and
    # the plan-cache launch; this wrapper exists for the keyword-argument
    # surface.  Service and sync range requests therefore share one code
    # path by construction.
    from repro.core.request import SolveRequest, execute_request
    knobs = {"maxiter": maxiter, "polish": polish}
    if dtype is not None:
        knobs["dtype"] = dtype
    req = SolveRequest(d=d, e=e, kind="range", select=select, il=il, iu=iu,
                       vl=vl, vu=vu, knobs=knobs)
    return execute_request(req).eigenvalues
