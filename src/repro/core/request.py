"""Request/response core: every eigensolve is a routable SolveRequest.

The sync API (``repro.core.api``), the serving layer (``repro.serve``)
and SLQ all funnel through this module.  A request is normalized and
validated once, *routed* to the bucketed compile-cache key its launch
will use (a :class:`~repro.core.plan.PlanKey` or
:class:`~repro.core.plan.RangePlanKey` with the batch axis unresolved),
and executed by exactly one code path:

    SolveRequest -> route_request -> RoutedRequest -> execute_request

Routing is pure (no cache mutation, no device work except the two Sturm
counts a ``select="v"`` window needs) and total: requests that cannot
share a compiled executable -- the quadratic-state baselines, the n == 1
short circuits -- route to ``None`` and execute directly.  Everything
else carries the key the serving scheduler groups on: two requests with
equal route keys are guaranteed to coalesce into one device launch, and
:func:`execute_request` on a routed request is bit-for-bit the solve the
service performs for it (the property ``tests/test_serve.py`` pins).

Request kinds:

    full   -- one problem, all eigenvalues            -> (n,)
    batch  -- B stacked problems, all eigenvalues     -> (B, n)
    range  -- selected eigenvalues by index or value  -> (k,) / (B, k)
    slq    -- batch + boundary rows (the SLQ quadrature rule: nodes are
              the eigenvalues, weights are blo(Q)^2)  -> (B, n) + rows
    edges  -- k smallest + k largest of each problem  -> (2B, k)
              (rows [0, B) the ascending bottom-k, rows [B, 2B) the
              ascending top-k -- the spectral monitor's lam_min/lam_max
              probe).  Routed onto the SAME RangePlanKey as plain range
              traffic of equal (n, k, dtype): the problem rows are
              duplicated and each copy slices its own per-problem index
              window inside ONE launch, so concurrent trainers' probes
              (and ordinary sliced requests) coalesce into shared
              launches.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping

import jax.numpy as jnp
import numpy as np

from repro.core import guard as _guard
from repro.core.instrument import span
from repro.runtime import faults as _faults

KINDS = ("full", "batch", "range", "slq", "edges")

METHODS = ("br", "sterf", "lazy", "full", "eigh", "bisect")

# Methods whose solves route through a bucketed plan cache and can
# therefore coalesce; the rest exist to model quadratic-state baselines
# and execute one problem at a time.
_PLANNED_METHODS = ("br", "bisect")


@dataclasses.dataclass(frozen=True)
class SolveRequest:
    """One eigensolve, as data.  ``knobs`` holds the solver keywords of
    the matching sync entry point (leaf, chunk, niter, ... for "br";
    maxiter, polish for "bisect"/range; dtype for any).

    Distributed conquer rides the same knobs: "br" requests accept
    ``mesh`` (default "auto": huge-n problems shard over the visible
    devices, see ``plan.DIST_AUTO_MIN_N``) and ``compress_halo``.  The
    shard count lands in the route key, so the serving scheduler
    coalesces same-mesh traffic and never mixes mesh shapes in a flush.

    So does the mixed-precision pipeline: "br" requests accept
    ``precision`` ("auto"/"native"/"mixed") and ``refine_tol``; both land
    in the route key ("auto" resolved), so mixed traffic coalesces with
    (only) other mixed traffic of the same tolerance and prewarms its own
    executables.  Mixed
    requests with no explicit dtype normalize to float64 (the output
    dtype) before routing.

    Robustness knobs (first-class fields, not ``knobs`` entries, because
    they apply to EVERY method):

    ``certify=True`` asks for a Sturm-certified result: one extra batched
    count sweep (``bisect.certify_spectrum``) verifies every returned
    eigenvalue against the original (d, e) and any miss -- or non-finite
    output -- escalates down the graceful-degradation ladder
    (mixed -> native D&C -> per-lane Sturm bisection) before the result
    is returned; what happened is recorded in ``SolveResult.diagnostics``
    and the degradation gauge.  ``range``/``bisect`` solves are
    count-verified by construction and certify for free.

    ``deadline_ms`` (serve-only budget, measured from submission) fails
    the request's future with :class:`repro.core.guard.DeadlineExceeded`
    instead of letting it hold a flush slot past its usefulness; the sync
    path validates but does not enforce it (there is no queueing to
    outlive).
    """
    d: Any
    e: Any
    kind: str = "full"
    method: str = "br"
    return_boundary: bool = False
    select: str = "i"
    il: int | None = None
    iu: int | None = None
    vl: float | None = None
    vu: float | None = None
    certify: bool = False
    deadline_ms: float | None = None
    knobs: Mapping[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class SolveResult:
    """What comes back: eigenvalues in the kind's natural shape, plus
    boundary rows when the request asked for them.

    ``diagnostics`` is None on the steady-state path and a small dict
    when the robustness layer has something to report: ``certified`` /
    ``lanes`` (certificate tally for ``certify=True``), ``escalations``
    (tuple of ``{"from", "to", "lanes"}`` degradation-ladder records),
    and ``equilibration_scale`` (the exact power-of-two factor applied to
    a pathologically scaled input; eigenvalues are already inverse-scaled
    back).
    """
    eigenvalues: Any
    blo: Any = None
    bhi: Any = None
    kind: str = "full"
    method: str = "br"
    diagnostics: Any = None


@dataclasses.dataclass(frozen=True)
class RoutedRequest:
    """A validated request bound to its route.

    ``d``/``e`` are normalized to stacked (B, n)/(B, n-1) arrays of the
    solve dtype; ``route`` is the batch-unresolved PlanKey/RangePlanKey
    (None: direct execution, uncoalescable).  Range routes carry the
    resolved index window (select="v" is turned into indices here, so the
    scheduler never sees values); ``kind="edges"`` routes carry a
    per-problem ``il`` ARRAY of shape (batch,) -- the duplicated-rows
    form in which rows [0, B) slice the bottom-k window and rows [B, 2B)
    the top-k window of the same problems (``RangePlan.execute`` accepts
    both forms through one traced-targets executable).  ``empty`` marks
    a value window that contains no eigenvalues -- nothing to launch.
    """
    request: SolveRequest
    d: Any
    e: Any
    batch: int
    n: int
    route: Any
    il: Any = 0
    k: int = 0
    empty: bool = False
    single: bool = False   # caller passed 1-D arrays: unwrap on the way out
    # Equilibration factor applied to (d, e) at normalization time (an
    # exact power of two; 1.0 for almost all traffic).  The solve runs in
    # scaled space and the finalizer multiplies eigenvalues by 1/scale on
    # the way out -- both exact, so scaled solves lose no accuracy.
    # Deliberately NOT part of the route key: scale is per-problem data,
    # and differently-scaled requests still coalesce into one flush.
    scale: float = 1.0

    @property
    def return_boundary(self) -> bool:
        return bool(getattr(self.route, "return_boundary", False))


def _as_host(x):
    """asarray that never moves data: jax arrays stay on device (the sync
    path's inputs usually already live there), everything else becomes
    numpy -- so service submissions of host data cost no device round
    trip until their flush stages the coalesced batch."""
    import jax
    return x if isinstance(x, jax.Array) else np.asarray(x)


def _normalize(req: SolveRequest):
    """Validate kind/method/input and normalize d, e to stacked (B, n)
    arrays; the guarded front door.

    All structural problems -- bad shapes, non-float dtypes, NaN/Inf
    entries, a nonsensical deadline -- raise HERE, host-side at route
    time, as ValueError subclasses (:class:`guard.InvalidInputError` for
    input poison), so the serving scheduler fails a malformed request's
    own future before it can join (and poison) a coalesced flush.
    Pathologically scaled inputs are equilibrated by an exact power of
    two (returned as ``scale``; 1.0 -- with the input arrays untouched --
    for in-range traffic).
    """
    if req.kind not in KINDS:
        raise ValueError(f"unknown kind {req.kind!r}; choose from {KINDS}")
    if req.method not in METHODS:
        raise ValueError(
            f"unknown method {req.method!r}; choose from {METHODS}")
    if req.deadline_ms is not None:
        deadline = float(req.deadline_ms)
        if not (deadline > 0.0) or not np.isfinite(deadline):
            raise _guard.InvalidInputError(
                f"deadline_ms must be a positive finite budget, got "
                f"{req.deadline_ms!r}", field="deadline_ms")
    d = _as_host(req.d)
    e = _as_host(req.e)
    dtype = req.knobs.get("dtype")
    if dtype is None and req.knobs.get("precision") == "mixed":
        dtype = np.float64   # mixed certifies / returns in f64
    if dtype is not None:
        d = d.astype(dtype)
        e = e.astype(dtype)
    if e.dtype != d.dtype:
        e = e.astype(d.dtype)
    single = d.ndim == 1
    if req.kind == "full" and not single:
        raise ValueError(
            f"kind='full' expects 1-D d, got shape {d.shape}")
    if req.kind in ("batch", "slq", "edges") and single:
        raise ValueError(
            f"kind={req.kind!r} expects stacked (B, n) d, got 1-D")
    if single:
        d = d[None, :]
        e = e[None, :] if e.ndim == 1 else e
    # Same contract (and message) as br_dc._as_batch, without forcing a
    # device transfer at submit time.
    if (d.ndim != 2 or e.ndim != 2 or e.shape[0] != d.shape[0]
            or e.shape[1] != max(d.shape[1] - 1, 0)):
        raise ValueError(
            f"batched solve expects d (B, n) and e (B, n-1); "
            f"got {d.shape} / {e.shape}")
    _guard.validate_problem(d, e, name="request")
    d, e, scale = _guard.equilibrate(d, e)
    return d, e, single, scale


def _solve_knobs(req: SolveRequest) -> dict:
    kw = {k: v for k, v in req.knobs.items() if k != "return_boundary"}
    return kw


def route_request(req: SolveRequest) -> RoutedRequest:
    """Resolve a request to its (batch-unresolved) compile-cache key.

    Pure with respect to the plan cache; raises on malformed requests --
    the serving scheduler turns that into a failed future without
    touching flushmates.
    """
    from repro.core import plan as _plan
    d, e, single, scale = _normalize(req)
    B, n = d.shape
    kw = _solve_knobs(req)

    if req.method != "br" and (req.return_boundary or req.kind == "slq"):
        # Boundary rows are BR selected-row state; silently returning a
        # result without them would let a caller believe the flag took
        # effect (the old per-method signatures raised TypeError too).
        raise TypeError(
            "return_boundary (and kind='slq') require method='br'; "
            f"got method={req.method!r}")

    if req.kind == "edges":
        # The spectral monitor's extremal probe: k smallest + k largest
        # of each problem as ONE sliced launch.  The (B, n) rows are
        # duplicated -- rows [0, B) carry the bottom-k window il=0, rows
        # [B, 2B) the top-k window il=n-k -- and the per-problem il array
        # rides the traced-targets executor, so the route key is the
        # PLAIN range key for (n, k): edges probes coalesce with each
        # other and with ordinary sliced traffic of the same bucket.
        if req.return_boundary:
            raise TypeError(
                "kind='edges' returns extremal eigenvalues only; boundary "
                "rows are full-conquer state (use kind='slq')")
        if (req.il, req.iu, req.vl, req.vu) != (None, None, None, None):
            raise ValueError(
                "kind='edges' selects its own index windows (bottom-k and "
                "top-k); pass k via knobs, not il/iu/vl/vu")
        k = kw.pop("k", 1)
        if not (isinstance(k, (int, np.integer)) and 1 <= int(k) <= n):
            raise ValueError(
                f"edges knob k must be an int in [1, n={n}]; got {k!r}")
        k = int(k)
        unknown = set(kw) - {"maxiter", "polish", "dtype"}
        if unknown:
            raise TypeError(
                f"edges requests accept knobs (k, maxiter, polish, dtype); "
                f"got unexpected {sorted(unknown)}")
        range_kw = {kk: v for kk, v in kw.items()
                    if kk in ("maxiter", "polish")}
        # (d, e) are already equilibrated, so both copies share the exact
        # power-of-two scale and the inverse scaling below stays exact.
        cat = (jnp.concatenate if isinstance(d, jnp.ndarray)
               else np.concatenate)
        d2 = cat([d, d], axis=0)
        e2 = cat([e, e], axis=0)
        il = np.concatenate([np.zeros(B, np.int64),
                             np.full(B, n - k, np.int64)])
        route = _plan.resolve_range_route(n, k, dtype=d.dtype, **range_kw)
        return RoutedRequest(request=req, d=d2, e=e2, batch=2 * B, n=n,
                             route=route, il=il, k=k, single=False,
                             scale=scale)

    if req.kind == "range" or req.method == "bisect":
        range_kw = {k: v for k, v in kw.items()
                    if k in ("maxiter", "polish")}
        unknown = set(kw) - {"maxiter", "polish", "dtype"}
        if unknown:
            raise TypeError(
                f"{'range' if req.kind == 'range' else 'bisect'} requests "
                f"accept knobs (maxiter, polish, dtype); "
                f"got unexpected {sorted(unknown)}")
        if req.kind == "range":
            il, k, empty = _resolve_window(req, d, e, B, n, single, scale)
        else:
            il, k, empty = 0, n, False   # full-spectrum bisect reference
        route = None
        if not empty:
            route = _plan.resolve_range_route(n, k, dtype=d.dtype,
                                              **range_kw)
        return RoutedRequest(request=req, d=d, e=e, batch=B, n=n,
                             route=route, il=il, k=k, empty=empty,
                             single=single, scale=scale)

    if req.method == "br" and n > 1:
        return_boundary = req.return_boundary or req.kind == "slq"
        if req.kind == "full":
            # Single (possibly padded) leaf trees return their boundary
            # rows for free -- mirror eigvalsh_tridiagonal_br's contract
            # that L == 0 always yields (blo, bhi).
            from repro.core.br_dc import _tree_shape
            leaf = _plan.resolve_leaf(kw.get("leaf"), n, d.dtype,
                                      kw.get("precision", "auto"))
            return_boundary = return_boundary or _tree_shape(n, leaf)[1] == 0
        route = _plan.resolve_solve_route(
            n, return_boundary=return_boundary, dtype=d.dtype,
            certify=req.certify,
            **{k: v for k, v in kw.items() if k != "dtype"})
        return RoutedRequest(request=req, d=d, e=e, batch=B, n=n,
                             route=route, single=single, scale=scale)

    # Baselines (and the n == 1 short circuits): direct, uncoalescable.
    return RoutedRequest(request=req, d=d, e=e, batch=B, n=n, route=None,
                         single=single, scale=scale)


def _resolve_window(req: SolveRequest, d, e, B: int, n: int, single: bool,
                    scale: float = 1.0):
    """Turn a range request's selection into an index window (il, k)."""
    from repro.core.bisect import _validate_index_range, sturm_count
    if req.select == "i":
        if req.il is None or req.iu is None:
            raise ValueError("select='i' requires il and iu")
        il, iu = _validate_index_range(n, req.il, req.iu)
        return il, iu - il + 1, False
    if req.select == "v":
        if req.vl is None or req.vu is None:
            raise ValueError("select='v' requires vl and vu")
        if not (float(req.vl) < float(req.vu)):
            raise ValueError(
                f"select='v' requires vl < vu; got ({req.vl}, {req.vu})")
        if not single:
            raise ValueError(
                "select='v' supports single problems only (the number of "
                "eigenvalues in (vl, vu] differs per problem); loop or "
                "use select='i'")
        # Two Sturm counts turn the value window into an index window
        # (one tiny host sync; the sliced solve then reuses the same
        # bucketed executables as any select='i' request).  (d, e) are
        # already equilibrated, so the window endpoints scale by the same
        # exact power of two: count(scale*v; scaled T) == count(v; T).
        shifts = jnp.asarray([req.vl, req.vu], d.dtype)
        if scale != 1.0:
            shifts = shifts * jnp.asarray(scale, d.dtype)
        bounds = sturm_count(d[0], e[0], shifts)
        c_lo, c_hi = int(bounds[0]), int(bounds[1])
        if c_hi <= c_lo:
            return 0, 0, True
        return c_lo, c_hi - c_lo, False
    raise ValueError(f"select must be 'i' or 'v', got {req.select!r}")


def _native_knobs(req: SolveRequest) -> dict:
    """Solver knobs for a degradation-ladder native re-solve: strip the
    knobs that name the stage being escalated AWAY from (precision /
    refine_tol) or that a single-problem recovery solve must not inherit
    (mesh topology, halo compression -- the re-solve is the ladder's
    independent second opinion, so it runs the classic single-device
    path)."""
    drop = ("precision", "refine_tol", "mesh", "compress_halo",
            "return_boundary")
    kw = {k: v for k, v in req.knobs.items() if k not in drop}
    kw["mesh"] = None
    kw["precision"] = "native"
    return kw


def _bisect_lanes(routed: RoutedRequest, lam_h: np.ndarray,
                  mask: np.ndarray) -> None:
    """Final ladder rung: re-solve the masked eigenvalue lanes by Sturm
    bisection against the (scaled) inputs, scattering into ``lam_h``.

    Bisection brackets every target with exact integer counts, so its
    results are certified by construction -- and it runs eagerly through
    ``bisect._slice_targets`` without touching the plan cache or the
    fault-instrumented launch path, which is what guarantees the ladder
    terminates even under a persistent launch-fault schedule.
    """
    from repro.core import bisect as _bis
    for b in np.nonzero(mask.any(axis=1))[0]:
        idx = np.nonzero(mask[b])[0].astype(np.int32)
        d_b = jnp.asarray(routed.d[int(b)])[None, :]
        e_b = jnp.asarray(routed.e[int(b)])[None, :]
        vals = _bis._slice_targets(d_b, e_b, jnp.asarray(idx[None, :]))
        lam_h[b, idx] = np.asarray(vals)[0]


def _resolve_native_rows(routed: RoutedRequest, prob: np.ndarray,
                         lam_h, blo_h, bhi_h) -> np.ndarray:
    """Ladder rung: full native re-solve of the masked problems (the only
    rung that can regenerate boundary rows).  Returns the mask of
    problems that were successfully re-solved; failures (e.g. a
    persistent injected launch fault) are left for the next rung."""
    kw = _native_knobs(routed.request)
    if blo_h is not None:
        kw["return_boundary"] = True
    done = np.zeros_like(prob)
    for b in np.nonzero(prob)[0]:
        try:
            lamb, lob, hib = _solve_direct_single(
                routed.d[int(b)], routed.e[int(b)], "br", kw)
            lam_h[b] = np.asarray(lamb)
            if blo_h is not None and lob is not None:
                blo_h[b] = np.asarray(lob)
                bhi_h[b] = np.asarray(hib)
            done[b] = True
        except Exception:
            continue
    return done


def _finalize_lanes(routed: RoutedRequest, lam, blo, bhi, *,
                    cert=None, check_finite: bool = True):
    """The graceful-degradation ladder + inverse equilibration.

    Shared by the sync ``execute_request`` and the serve engine's demux,
    so both paths escalate identically (and deterministically) -- a
    request gets the same answer whether it ran alone or in a flush.

    lam/blo/bhi are the solve's stacked (B, n) outputs in SCALED space;
    ``cert`` is an optional host (B, n) certificate mask from
    ``certify_spectrum``.  Ladder, applied per-lane where possible:

      1. non-finite outputs: full native re-solve of the affected
         problems when the stage was mixed (escalate precision) or when
         boundary rows are owed (bisection cannot produce rows);
      2. lanes still bad, and any certificate misses: per-lane Sturm
         bisection -- certified by construction, never launches through
         the fault-instrumented plan path;
      3. still bad (rows owed but unrecoverable): CertificationError.

    Every escalation is recorded in the SOLVE_COUNTER degradation gauge
    and the process-wide ``guard.DEGRADATIONS`` counter, and reported in
    the returned diagnostics.  Returns (lam, blo, bhi, diagnostics).
    """
    from repro.core import br_dc as _br
    req = routed.request
    mixed = getattr(routed.route, "precision", "native") == "mixed"
    planned = routed.route is not None
    stage = "mixed" if mixed else ("native" if planned else req.method)
    rows = blo is not None
    escalations: list = []
    cert_h = None if cert is None else np.asarray(cert).copy()
    first_sweep_certified = (None if cert_h is None
                             else int(cert_h.sum()))

    def record(frm: str, to: str, lanes: int) -> None:
        _br.SOLVE_COUNTER.record_degradation(frm, to, lanes)
        _guard.DEGRADATIONS.increment()
        escalations.append({"from": frm, "to": to, "lanes": int(lanes)})

    if check_finite:
        lam_h = np.asarray(lam)
        bad = ~np.isfinite(lam_h)
        if rows:
            bad |= ~np.isfinite(np.asarray(blo)).all(axis=1, keepdims=True)
            bad |= ~np.isfinite(np.asarray(bhi)).all(axis=1, keepdims=True)
        if bad.any():
            with span("repro.request.ladder", lanes=int(bad.sum())):
                lam_h = lam_h.copy()
                blo_h = np.asarray(blo).copy() if rows else None
                bhi_h = np.asarray(bhi).copy() if rows else None
                at = stage
                if mixed or rows:
                    done = _resolve_native_rows(routed, bad.any(axis=1),
                                                lam_h, blo_h, bhi_h)
                    if done.any():
                        record(stage, "native", int(bad[done].sum()))
                        at = "native"
                    bad = ~np.isfinite(lam_h)
                    if rows:
                        bad |= ~np.isfinite(blo_h).all(axis=1, keepdims=True)
                        bad |= ~np.isfinite(bhi_h).all(axis=1, keepdims=True)
                if bad.any():
                    if rows:
                        raise _guard.CertificationError(
                            f"degradation ladder exhausted: {int(bad.sum())} "
                            f"non-finite output lanes remain and the request "
                            f"owes boundary rows, which bisection cannot "
                            f"produce")
                    record(at, "bisect", int(bad.sum()))
                    _bisect_lanes(routed, lam_h, bad)
                    if cert_h is not None:
                        cert_h[bad] = True   # count-verified by construction
                    still = ~np.isfinite(lam_h)
                    if still.any():
                        raise _guard.CertificationError(
                            f"degradation ladder exhausted: "
                            f"{int(still.sum())} lanes non-finite even "
                            f"after Sturm bisection")
                # Re-certify lanes repaired by a native re-solve (bisected
                # lanes are already accounted above).
                if cert_h is not None and not cert_h.all():
                    from repro.core import bisect as _bis
                    unchecked = (~cert_h).any(axis=1)
                    for b in np.nonzero(unchecked)[0]:
                        c = _bis.certify_spectrum(
                            routed.d[int(b)], routed.e[int(b)], lam_h[b],
                            tol=getattr(routed.route, "refine_tol", 0.0)
                            or None or _bis.DEFAULT_REFINE_TOL)
                        cert_h[b] = np.asarray(c.certified)
                lam, blo, bhi = lam_h, blo_h, bhi_h

    if cert_h is not None and not cert_h.all():
        miss = ~cert_h
        lanes = int(miss.sum())
        with span("repro.request.ladder", lanes=lanes):
            lam_h = np.asarray(lam).copy()
            record(stage, "bisect", lanes)
            _bisect_lanes(routed, lam_h, miss)
        lam = lam_h

    if routed.scale != 1.0:
        # Exact inverse of the equilibration factor (a power of two), so
        # the multiply introduces no rounding.
        inv = np.asarray(lam).dtype.type(1.0 / routed.scale)
        lam = lam * inv

    diag = None
    if escalations or cert_h is not None or routed.scale != 1.0:
        diag = {}
        if cert_h is not None:
            diag["certified"] = first_sweep_certified
            diag["lanes"] = int(np.asarray(cert_h).size)
        if escalations:
            diag["escalations"] = tuple(escalations)
        if routed.scale != 1.0:
            diag["equilibration_scale"] = routed.scale
    return lam, blo, bhi, diag


def _solve_direct_single(d, e, method: str, kw: dict):
    """One problem through the non-plan paths (moved from core.api)."""
    from repro.core import baselines as _bl
    from repro.core.br_dc import eigvalsh_tridiagonal_br
    from repro.core.sterf import eigvalsh_tridiagonal_sterf
    if method == "br":
        res = eigvalsh_tridiagonal_br(d, e, **kw)
        return res.eigenvalues, res.blo, res.bhi
    if method == "sterf":
        return eigvalsh_tridiagonal_sterf(d, e, **kw), None, None
    if method == "lazy":
        return _bl.eigvalsh_tridiagonal_lazy(d, e, **kw), None, None
    if method == "full":
        return _bl.eigvalsh_tridiagonal_full_discard(d, e, **kw), None, None
    if method == "eigh":
        from repro.core.tridiag import dense_from_tridiag
        return jnp.linalg.eigvalsh(dense_from_tridiag(d, e)), None, None
    raise ValueError(f"unknown method {method!r}; choose from {METHODS}")


def execute_request(req: SolveRequest | RoutedRequest) -> SolveResult:
    """Execute a (routed) request synchronously.

    This is the single launch path: the sync API wraps it, and the
    serving engine's flush is the same plan execution over a coalesced
    batch -- which is why service results are bit-for-bit the sync ones.
    Recorded, routing included, as the span ``repro.request``.
    """
    fresh = isinstance(req, SolveRequest)
    with span("repro.request",
              kind=req.kind if fresh else req.request.kind) as s:
        routed = route_request(req) if fresh else req
        s.set_metadata(batch=routed.batch, n=routed.n)
        return _execute_routed(routed)


def _execute_routed(routed: RoutedRequest) -> SolveResult:
    req = routed.request
    single = routed.single

    if routed.empty:
        lam = jnp.zeros((0,), routed.d.dtype)
        return SolveResult(eigenvalues=lam if single else lam[None, :],
                           kind=req.kind, method=req.method)

    from repro.core import plan as _plan
    if isinstance(routed.route, _plan.PlanKey):
        plan = _plan.plan_for_route(routed.route, routed.batch)
        res = plan.execute(routed.d, routed.e)
        cert = None
        if routed.route.certify:
            from repro.core import bisect as _bis
            cert = _bis.certify_spectrum(
                routed.d, routed.e, res.eigenvalues,
                tol=routed.route.refine_tol).certified
        # Output finiteness is only checked when something already forces
        # a host round trip (certification, the mixed pipeline's host-
        # driven refinement) or when the chaos harness is live: the
        # steady-state native path keeps its async dispatch, and the
        # front-door guard already rejected input poison, so a non-finite
        # native output means a device fault -- which certify=True exists
        # to catch.
        check = (routed.route.certify or _faults.faults_enabled()
                 or routed.route.precision == "mixed")
        lam, blo, bhi, diag = _finalize_lanes(
            routed, res.eigenvalues, res.blo, res.bhi, cert=cert,
            check_finite=check)
        if single:
            return SolveResult(
                eigenvalues=lam[0],
                blo=None if blo is None else blo[0],
                bhi=None if bhi is None else bhi[0],
                kind=req.kind, method=req.method, diagnostics=diag)
        return SolveResult(eigenvalues=lam, blo=blo, bhi=bhi,
                           kind=req.kind, method=req.method,
                           diagnostics=diag)
    if isinstance(routed.route, _plan.RangePlanKey):
        plan = _plan.range_plan_for_route(routed.route, routed.batch)
        lam = plan.execute(routed.d, routed.e, routed.il, routed.k)
        diag = None
        if routed.scale != 1.0:
            inv = np.dtype(routed.d.dtype).type(1.0 / routed.scale)
            lam = lam * inv
            diag = {"equilibration_scale": routed.scale}
        if req.certify:
            # Sturm bisection IS a certificate: every returned value is
            # enclosed by exact integer counts, so the sweep would be
            # redundant work -- report the tally without launching it.
            diag = dict(diag or ())
            diag.update(certified=int(routed.batch * routed.k),
                        lanes=int(routed.batch * routed.k))
        return SolveResult(eigenvalues=lam[0] if single else lam,
                           kind=req.kind, method=req.method,
                           diagnostics=diag)

    # Direct path: baselines and n == 1 short circuits, one problem at a
    # time (these methods exist to model per-problem quadratic state).
    kw = _solve_knobs(req)
    if req.return_boundary and req.method == "br":
        kw["return_boundary"] = True
    outs = [_solve_direct_single(routed.d[b], routed.e[b], req.method, kw)
            for b in range(routed.batch)]
    lam = jnp.stack([o[0] for o in outs])
    blo = (jnp.stack([o[1] for o in outs])
           if outs and outs[0][1] is not None else None)
    bhi = (jnp.stack([o[2] for o in outs])
           if outs and outs[0][2] is not None else None)
    diag = None
    if req.certify or routed.scale != 1.0 or _faults.faults_enabled():
        cert = None
        if req.certify:
            from repro.core import bisect as _bis
            cert = _bis.certify_spectrum(routed.d, routed.e, lam).certified
        lam, blo, bhi, diag = _finalize_lanes(routed, lam, blo, bhi,
                                              cert=cert)
    if single:
        lam = lam[0]
        blo = None if blo is None else blo[0]
        bhi = None if bhi is None else bhi[0]
    return SolveResult(eigenvalues=lam, blo=blo, bhi=bhi, kind=req.kind,
                       method=req.method, diagnostics=diag)
