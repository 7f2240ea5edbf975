"""Backend-dispatching jit wrappers for the eigensolver hot-spot kernels.

``backend``:
  * "xla"    -- chunked pure-JAX implementations (repro.core.secular,
                repro.core.bisect); the default off TPU.
  * "pallas" -- Pallas kernels; compiled by Mosaic on TPU, run with
                ``interpret=True`` on every other backend (the test suite
                validates the TPU kernels on CPU this way, in any dtype).
  * "auto"   -- "pallas" on TPU for 32-bit floats, "xla" otherwise.

The dtype rule.  Mosaic has no 64-bit scalars, so no Pallas kernel can
take a float64 operand on a TPU.  Every dispatcher resolves the backend
from its operand dtype (:func:`resolve_backend`): on a TPU, "auto" runs
float32 work (plain f32 solves and the f32 tree of ``precision="mixed"``)
through the kernels and float64 work through XLA.  The choice is a static,
trace-time function of (device backend, dtype) -- never a try/except --
and an explicit ``set_backend("pallas")`` with a float64 operand on a TPU
raises instead of changing path.

Size-adaptive dispatch: the merge ops take ``dense=`` -- when True (small
K, decided per merge-tree level by ``stream_threshold``) the op runs the
dense vectorized XLA path regardless of backend.  Small merges are
launch/loop-overhead-bound, not bandwidth-bound, and the chunked/streamed
formulations serialize under vmap exactly where K is small and the level
batch is large; the dense path stays fully batched.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import secular as _sec
from repro.core.secular import DEFAULT_NITER, DEFAULT_NITER_F32
from repro.kernels.secular_roots import (secular_solve_pallas,
                                         secular_solve_pallas_batch)
from repro.kernels.fused_update import (secular_postpass_pallas,
                                        secular_postpass_pallas_batch)
from repro.kernels.resident_merge import (resident_merge_pallas,
                                          resident_merge_pallas_batch)
from repro.kernels.sturm_count import (DEFAULT_SHIFT_BLOCK,
                                       sturm_count_pallas_batch)

_BACKEND = "auto"


def set_backend(name: str) -> None:
    global _BACKEND
    if name not in ("auto", "xla", "pallas"):
        raise ValueError(name)
    _BACKEND = name


def _device_backend() -> str:
    # Memoized probe (core.tune) -- one jax.default_backend() call per
    # process so a mid-process backend flip cannot split kernel dispatch.
    from repro.core import tune as _tune
    return _tune.pinned_backend()


def resolve_backend(dtype, backend: str | None = None) -> str:
    """The backend one dispatch runs on, from its operand dtype.

    ``backend`` overrides the process-wide :func:`set_backend` choice.
    Raises ValueError for an explicit "pallas" with a 64-bit operand on a
    TPU (Mosaic has no 64-bit scalars).
    """
    b = backend or _BACKEND
    on_tpu = _device_backend() == "tpu"
    wide = jnp.dtype(dtype).itemsize > 4
    if b == "auto":
        return "pallas" if on_tpu and not wide else "xla"
    if b == "pallas" and on_tpu and wide:
        raise ValueError(
            f"backend='pallas' cannot run {jnp.dtype(dtype).name} operands "
            f"on TPU: Mosaic has no 64-bit scalars.  Use backend='auto' "
            f"(float64 goes through XLA) or a float32 solve.")
    return b


def _interpret() -> bool:
    return _device_backend() != "tpu"


def resolve_niter(niter: int | None, dtype) -> int:
    """Resolve the per-dtype default secular iteration budget.

    ``niter=None`` picks the dtype's budget: f32 trees hit their accuracy
    floor earlier than f64 (DEFAULT_NITER_F32 vs DEFAULT_NITER -- see
    ``core.secular``), so the dispatchers below default their iteration
    count off the pole-array dtype.  An explicit niter always wins.
    """
    if niter is not None:
        return int(niter)
    return (DEFAULT_NITER_F32 if jnp.dtype(dtype) == jnp.dtype(jnp.float32)
            else DEFAULT_NITER)


def secular_solve(d, z2, rho, kprime, *, niter: int | None = None,
                  chunk: int = 256,
                  dense: bool = False, backend: str | None = None):
    niter = resolve_niter(niter, d.dtype)
    if dense:
        return _sec.secular_solve(d, z2, rho, kprime, niter=niter,
                                  dense=True)
    if resolve_backend(d.dtype, backend) == "pallas":
        return secular_solve_pallas(d, z2, rho, kprime, niter=niter,
                                    root_block=chunk, interpret=_interpret())
    return _sec.secular_solve(d, z2, rho, kprime, niter=niter, chunk=chunk)


def secular_postpass(R, d, z, origin, tau, kprime, rho, *,
                     use_zhat: bool = True, chunk: int = 256,
                     dense: bool = False, backend: str | None = None):
    """Fused zhat reconstruction + selected-row update: (zhat, rows)."""
    if dense:
        return _sec.secular_postpass(R, d, z, origin, tau, kprime, rho,
                                     use_zhat=use_zhat, dense=True)
    if resolve_backend(d.dtype, backend) == "pallas":
        return secular_postpass_pallas(R, d, z, origin, tau, kprime, rho,
                                       use_zhat=use_zhat, pole_block=chunk,
                                       interpret=_interpret())
    return _sec.secular_postpass(R, d, z, origin, tau, kprime, rho,
                                 use_zhat=use_zhat, chunk=chunk)


def secular_solve_batched(d, z2, rho, kprime, *, niter: int | None = None,
                          chunk: int = 256, dense: bool = False,
                          backend: str | None = None):
    """Problem-batched secular solve: d, z2 (B, K); rho, kprime (B,).

    Pallas backend maps problems onto a leading grid axis (one launch for
    the whole batch); XLA runs the chunked path vmapped over problems.
    Returns (origin (B, K) int32, tau (B, K)).
    """
    niter = resolve_niter(niter, d.dtype)
    if dense:
        return _sec.secular_solve_batched(d, z2, rho, kprime, niter=niter,
                                          dense=True)
    if resolve_backend(d.dtype, backend) == "pallas":
        return secular_solve_pallas_batch(d, z2, rho, kprime, niter=niter,
                                          root_block=chunk,
                                          interpret=_interpret())
    return _sec.secular_solve_batched(d, z2, rho, kprime, niter=niter,
                                      chunk=chunk)


def secular_postpass_batched(R, d, z, origin, tau, kprime, rho, *,
                             use_zhat: bool = True, chunk: int = 256,
                             dense: bool = False,
                             backend: str | None = None):
    """Problem-batched fused post-pass: R (B, r, K); kprime, rho (B,).

    Returns (zhat (B, K), rows (B, r, K)); see ``secular_postpass``.
    """
    if dense:
        return _sec.secular_postpass_batched(R, d, z, origin, tau, kprime,
                                             rho, use_zhat=use_zhat,
                                             dense=True)
    if resolve_backend(d.dtype, backend) == "pallas":
        return secular_postpass_pallas_batch(R, d, z, origin, tau, kprime,
                                             rho, use_zhat=use_zhat,
                                             pole_block=chunk,
                                             interpret=_interpret())
    return _sec.secular_postpass_batched(R, d, z, origin, tau, kprime, rho,
                                         use_zhat=use_zhat, chunk=chunk)


def secular_merge_resident(d, z, R, rho, kprime, *,
                           niter: int | None = None,
                           use_zhat: bool = True,
                           backend: str | None = None):
    """Single-launch resident merge: solve + fused post-pass in ONE dispatch.

    Returns (origin, tau, zhat, rows); see
    ``core.secular.secular_merge_resident``.  Pallas backend runs the
    VMEM-resident kernel (the (origin, tau) never round-trip HBM between
    the phases); XLA runs the dense fused composition as one traced
    region.  Callers gate on K <= resident_threshold.
    """
    niter = resolve_niter(niter, d.dtype)
    if resolve_backend(d.dtype, backend) == "pallas":
        return resident_merge_pallas(d, z, R, rho, kprime, niter=niter,
                                     use_zhat=use_zhat,
                                     interpret=_interpret())
    return _sec.secular_merge_resident(d, z, R, rho, kprime, niter=niter,
                                       use_zhat=use_zhat)


def secular_merge_resident_batched(d, z, R, rho, kprime, *,
                                   niter: int | None = None,
                                   use_zhat: bool = True,
                                   backend: str | None = None):
    """Problem-batched resident merge: d, z (B, K); R (B, r, K).

    One kernel launch for the whole merge level on the Pallas backend
    (problems on the grid axis, each fully VMEM-resident); one fused
    traced region vmapped over problems on XLA.  Returns
    (origin (B, K) int32, tau (B, K), zhat (B, K), rows (B, r, K)).
    """
    niter = resolve_niter(niter, d.dtype)
    if resolve_backend(d.dtype, backend) == "pallas":
        return resident_merge_pallas_batch(d, z, R, rho, kprime,
                                           niter=niter, use_zhat=use_zhat,
                                           interpret=_interpret())
    return _sec.secular_merge_resident_batched(d, z, R, rho, kprime,
                                               niter=niter,
                                               use_zhat=use_zhat)


def sturm_count_batched(d, e2, shifts, pivmin, *,
                        shift_block: int = DEFAULT_SHIFT_BLOCK,
                        backend: str | None = None):
    """Batched Sturm eigenvalue counts: d (B, n), e2 (B, n-1),
    shifts (B, S), pivmin (B, 1) -> (B, S) int32 (#eigenvalues <= shift).

    The bisection front end's per-iteration workhorse.  Pallas backend
    runs one kernel launch with a problems x shift-blocks grid (each
    step's pole rows VMEM-resident); XLA runs one fused scan over matrix
    rows carrying all B x S pivot lanes.  Integer-exact across backends.
    """
    from repro.core import bisect as _bis  # deferred: core imports ops
    if resolve_backend(d.dtype, backend) == "pallas":
        return sturm_count_pallas_batch(d, e2, shifts, pivmin,
                                        shift_block=shift_block,
                                        interpret=_interpret())
    return _bis.sturm_count_xla(d, e2, shifts, pivmin)

