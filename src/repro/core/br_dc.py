"""Boundary-row divide-and-conquer driver (paper Algorithm 1), batch-first.

Level-synchronous bottom-up realization of the recursion: all merges at the
same tree depth are independent and executed as one vmapped batch -- the JAX
analogue of the paper's per-level batched CUDA kernels (Section 4.1).

The core is *batch-first*: every internal array carries a leading problem
axis ``B`` and the per-level merge batch is the flattened ``B x num_nodes``
product, so a batch of independent tridiagonals costs one executor launch
and one XLA program (the distributed-memory hybrid-D&C direction of
arXiv:1612.07526, realized here as a single fused level schedule).
Persistent eigenvector-derived state per level:

    lam   (B, num_nodes, node_size)      -- child spectra
    rows  (B, num_nodes, r, node_size)   -- selected eigenvector-matrix rows

with r == 2 for the plain eigenvalue run (blo, bhi -- the rows that feed
the rank-one coupling vectors) and r == 3 when boundary rows of the full
matrix are requested: the third slot tracks the row at *original* index
n-1 through the tree (a traced per-problem index, so mixed original sizes
inside one padded bucket share one compiled executable), which keeps
``return_boundary`` at one D&C solve even when padding appends sentinel
rows below it.

State is B * (3N-4N) floats total, B * O(N).  Transients are
O(B * chunk * K) on streamed levels and O(B * nodes * K^2) <=
O(B * N * stream_threshold) on dense levels (see merge.py's size-adaptive
dispatch).  The conventional baselines in baselines.py carry quadratic
state instead; nothing else differs.

Compilation is owned by ``repro.core.plan``: both public drivers below
build a :class:`~repro.core.plan.SolvePlan` (single solves are the
batch == 1 bucket) and run its cached executor.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import merge as _merge
from repro.core.instrument import SolveCounter
from repro.core.secular import DEFAULT_NITER

# Device-solve instrumentation: one increment per executor launch (a batch
# of B problems is ONE solve).  Regression tests pin one-solve invariants
# (padded ``return_boundary``, whole-batch SLQ) through this counter.
SOLVE_COUNTER = SolveCounter("device_solves")


class BRResult(NamedTuple):
    eigenvalues: jax.Array     # (n,) ascending
    blo: jax.Array | None      # (n,) first row of Q (None in root mode)
    bhi: jax.Array | None      # (n,) last row of Q
    kprime_per_level: tuple    # diagnostics: active ranks per level


class BRBatchResult(NamedTuple):
    eigenvalues: jax.Array     # (B, n) ascending per problem
    blo: jax.Array | None      # (B, n) first rows of Q (None unless requested)
    bhi: jax.Array | None      # (B, n) last rows of Q
    kprime_per_level: tuple    # diagnostics: (B, num_merges) per level


def _tree_shape(n: int, leaf: int):
    """Static padded size N = leaf * 2^L with N >= n."""
    nblocks = max(1, math.ceil(n / leaf))
    L = math.ceil(math.log2(nblocks))
    return leaf * (1 << L), L


def _pad_problem(d, e, leaf):
    """Pad a batch to N = leaf * 2^L with decoupled sentinel 1x1 blocks.

    d: (B, n), e: (B, n-1).  Returns (d_pad (B, N), e_pad (B, N), N, L);
    e is padded to length N for uniform split indexing.  Sentinels sit
    above each problem's own Gershgorin upper bound, so pads sort to the
    top and deflate exactly (their z entries are identically zero).
    """
    B, n = d.shape
    N, L = _tree_shape(n, leaf)
    if N == n:
        return d, jnp.pad(e, ((0, 0), (0, 1))), N, L
    emax = (jnp.max(jnp.abs(e), axis=1) if e.shape[1]
            else jnp.zeros((B,), d.dtype))
    sentinel = jnp.max(jnp.abs(d), axis=1) + 2.0 * emax + 1.0
    d_pad = jnp.concatenate(
        [d, jnp.broadcast_to(sentinel[:, None], (B, N - n)).astype(d.dtype)],
        axis=1)
    e_pad = jnp.concatenate([e, jnp.zeros((B, N - n + 1), d.dtype)], axis=1)
    return d_pad, e_pad, N, L


def _leaf_solve(d_adj, e_pad, leaf, track_local=None):
    """Batched leaf eigensolves (paper Sec. 4: parallel leaf initialization).

    d_adj, e_pad: (B, N).  Builds the (B, nb, leaf, leaf) dense leaf blocks
    (off-diagonals at block boundaries excluded -- they are the rank-one
    couplings) and eigendecomposes them in one batch.  Keeps the first/last
    eigenvector rows, plus the per-problem row at local index
    ``track_local`` ((B,) int32, traced) when given -- the selected-row
    slot that follows original row n-1 through padding; only the leaf that
    actually contains it propagates a meaningful value upward.

    Returns (lam (B, nb, leaf), rows (B, nb, r, leaf)).
    """
    B, N = d_adj.shape
    nb = N // leaf
    db = d_adj.reshape(B, nb, leaf)
    # e within a block: positions [b*leaf, b*leaf + leaf - 2]
    eb = (e_pad[:, :N].reshape(B, nb, leaf)[:, :, : leaf - 1]
          if leaf > 1 else None)

    if leaf == 1:                        # 1x1 blocks: no eigensolve
        lam, Q = db, jnp.ones((B, nb, 1, 1), d_adj.dtype)
    else:
        ii = jnp.arange(leaf)
        j = jnp.arange(leaf - 1)
        T = jnp.zeros((B, nb, leaf, leaf), d_adj.dtype)
        T = T.at[:, :, ii, ii].set(db)
        T = T.at[:, :, j, j + 1].set(eb).at[:, :, j + 1, j].set(eb)
        lam, Q = jnp.linalg.eigh(T)      # ascending
    selected = [Q[:, :, 0, :], Q[:, :, leaf - 1, :]]
    if track_local is not None:
        tl = jnp.asarray(track_local, jnp.int32)
        idx = jnp.broadcast_to(tl[:, None, None, None], (B, nb, 1, leaf))
        selected.append(jnp.take_along_axis(Q, idx, axis=2)[:, :, 0, :])
    rows = jnp.stack(selected, axis=2)   # (B, nb, r, leaf)
    return lam, rows


def _level_coupling(e_pad, level: int, leaf: int, num_merges: int):
    """(rho, sgn), each (B, num_merges), for every merge at this level.

    Merge i at level ``level`` joins nodes of size M = leaf * 2^level; the
    split sits at original index k = (2i+1) * M, coupling strength e[k-1].
    """
    M = leaf * (1 << level)
    k = (2 * jnp.arange(num_merges) + 1) * M
    beta = e_pad[:, k - 1]
    return jnp.abs(beta), jnp.where(beta >= 0.0, 1.0, -1.0).astype(e_pad.dtype)


def _level_pairs(lam, rows, track, M):
    """Pair adjacent nodes for one level of merges.

    lam: (B, 2*nm, M); rows: (B, 2*nm, r, M); track: (B,) int32 *global*
    tracked row index or None; M the child node size.  Returns
    (lam_pairs (B, nm, 2, M), z_inner (B, nm, 2, M), R (B, nm, r, 2M)).

    Parent slot sources: blo <- [blo_L, 0]; bhi <- [0, bhi_R]; the
    tracked row lives in whichever child spans index track[b] at this
    level -- a traced per-problem side, identical for every node of that
    problem (only the one node on the tracked row's spine carries a
    meaningful value).  ``(track // M) % 2`` uses the *global* index even
    for shard-local subtrees: a shard's origin is a multiple of 2M at
    every subtree level, so the parity is the same in local and global
    coordinates.
    """
    B = lam.shape[0]
    nm = lam.shape[1] // 2
    r = rows.shape[2]
    lam_pairs = lam.reshape(B, nm, 2, M)
    rows_pairs = rows.reshape(B, nm, 2, r, M)  # (B, merge, child, slot, M)
    z_inner = jnp.stack(
        [rows_pairs[:, :, 0, 1, :], rows_pairs[:, :, 1, 0, :]], axis=2)
    zeros = jnp.zeros((B, nm, M), lam.dtype)
    selected = [
        jnp.concatenate([rows_pairs[:, :, 0, 0, :], zeros], axis=-1),
        jnp.concatenate([zeros, rows_pairs[:, :, 1, 1, :]], axis=-1),
    ]
    if track is not None:
        side = (track // M) % 2                            # (B,)
        left = jnp.concatenate([rows_pairs[:, :, 0, 2, :], zeros],
                               axis=-1)
        right = jnp.concatenate([zeros, rows_pairs[:, :, 1, 2, :]],
                                axis=-1)
        selected.append(
            jnp.where((side == 0)[:, None, None], left, right))
    return lam_pairs, z_inner, jnp.stack(selected, axis=2)  # R (B,nm,r,2M)


def _br_dc_padded_batch(d_pad, e_pad, track, *, leaf, chunk, niter, use_zhat,
                        return_boundary, tol_factor, stream_threshold,
                        deflate_budget, resident_threshold, fused):
    """Batch-first padded D&C body (traced; jitted by plan._executor).

    d_pad, e_pad: (B, N); track: (B,) int32 per-problem tracked original
    row index, or None.  Returns (lam (B, N), rows (B, r, N), kprimes:
    list of (B, num_merges) per level).
    """
    B, N = d_pad.shape
    L = int(math.log2(N // leaf))
    nb = N // leaf

    # Pre-subtract every rank-one coupling from the boundary diagonals
    # (each interior leaf boundary is split exactly once in the tree).
    if nb > 1:
        k = leaf * jnp.arange(1, nb)
        rho_all = jnp.abs(e_pad[:, k - 1])
        sub = jnp.zeros_like(d_pad).at[:, k - 1].add(rho_all) \
                                   .at[:, k].add(rho_all)
        d_adj = d_pad - sub
    else:
        d_adj = d_pad

    track_local = None if track is None else track % leaf
    lam, rows = _leaf_solve(d_adj, e_pad, leaf, track_local=track_local)

    kprimes = []
    for level in range(L):
        nm = lam.shape[1] // 2
        M = lam.shape[2]
        root = (nm == 1) and not return_boundary
        rho, sgn = _level_coupling(e_pad, level, leaf, nm)   # (B, nm)
        lam_pairs, z_inner, R = _level_pairs(lam, rows, track, M)

        res = _merge.merge_level_batched(
            lam_pairs, z_inner, R, rho, sgn,
            niter=niter, chunk=chunk, use_zhat=use_zhat,
            root_mode=root, tol_factor=tol_factor,
            stream_threshold=stream_threshold,
            deflate_budget=deflate_budget,
            resident_threshold=resident_threshold, fused=fused)
        lam, rows = res.lam, res.rows             # (B, nm, 2M) / (B, nm, r, 2M)
        kprimes.append(res.kprime)                # (B, nm)

    return lam[:, 0], rows[:, 0], kprimes


def _br_dc_sharded_batch(d_loc, e_loc, track, *, shards, axis_name, leaf,
                         chunk, niter, use_zhat, return_boundary, tol_factor,
                         stream_threshold, deflate_budget,
                         resident_threshold, fused, compress_halo=False):
    """Distributed-conquer D&C body: runs inside a 1-D shard_map mesh.

    d_loc, e_loc: (B, Np) -- this device's contiguous slice of the padded
    (B, N = shards * Np) problem; track: (B,) int32 *global* tracked row
    index or None (replicated).  Returns the same (lam (B, N), rows
    (B, r, N), kprimes) as :func:`_br_dc_padded_batch`, replicated on
    every device.

    Phase structure (the paper's O(n) conquer state is what makes every
    cross-device transfer linear):

      1. *Divide*: rank-one coupling pre-subtraction.  Couplings interior
         to the shard are local; each shard-edge coupling lives in the
         left neighbour's last ``e`` slot, fetched with a one-element
         ppermute halo (`dist.sharding.halo_from_left`).  Scatter-add
         grouping mirrors the single-device path (all ``k-1`` slots, then
         all ``k`` slots) so ``d_adj`` is bit-identical to its slice of
         the unsharded computation.
      2. *Independent subtrees*: leaves and the ``log2(Np/leaf)`` low
         merge levels run embarrassingly parallel per device -- the same
         level loop as the single-device path on the local slice, never
         in root mode.
      3. *Transition*: one all-gather of the O(n) state -- each shard's
         eigenvalues (Np) and r selected rows (r * Np); optionally int8
         error-feedback compressed rows (``compress_halo``).
      4. *Cooperative levels*: state is replicated; each level's merge
         head and post-pass run replicated while the O(K^2) secular root
         solve is sharded into N/shards-root windows per device and the
         (origin, tau) windows all-gathered (see
         :func:`repro.core.merge.merge_level_coop`).
    """
    from repro.dist import sharding as _dist
    B, Np = d_loc.shape
    if Np % leaf:
        raise ValueError(
            f"shard width {Np} must be a multiple of leaf={leaf} "
            f"(route resolution guarantees 2^L >= shards)")
    L_loc = int(math.log2(Np // leaf))
    L_coop = int(math.log2(shards))
    nb_loc = Np // leaf
    p = jax.lax.axis_index(axis_name)

    # ---- divide: coupling pre-subtraction with shard-edge halo ----------
    edge = jnp.abs(e_loc[:, -1])                       # right-edge coupling
    from_left = _dist.halo_from_left(edge, shards, axis_name)  # 0 on shard 0
    sub = jnp.zeros_like(d_loc)
    # Group scatter-adds exactly like the single-device path: first every
    # boundary's k-1 slot, then every k slot (a position can receive one
    # of each; FP addition order must match for bit-identity).
    if nb_loc > 1:
        k = leaf * jnp.arange(1, nb_loc)
        rho_int = jnp.abs(e_loc[:, k - 1])
        sub = sub.at[:, k - 1].add(rho_int)
    # e_loc[:, -1] is zero-padded on the last shard, so its edge term
    # vanishes there exactly as the global boundary list ends at N - leaf.
    sub = sub.at[:, Np - 1].add(edge)
    if nb_loc > 1:
        sub = sub.at[:, k].add(rho_int)
    sub = sub.at[:, 0].add(from_left)
    d_adj = d_loc - sub

    # ---- phase 2: leaves + independent local subtree --------------------
    # Shard origins are multiples of leaf, so leaf-local positions (and
    # the level-side parities in _level_pairs) match global coordinates.
    track_local = None if track is None else track % leaf
    lam, rows = _leaf_solve(d_adj, e_loc, leaf, track_local=track_local)

    kprimes = []
    for level in range(L_loc):
        nm_loc = lam.shape[1] // 2
        M = lam.shape[2]
        rho, sgn = _level_coupling(e_loc, level, leaf, nm_loc)
        lam_pairs, z_inner, R = _level_pairs(lam, rows, track, M)
        res = _merge.merge_level_batched(
            lam_pairs, z_inner, R, rho, sgn,
            niter=niter, chunk=chunk, use_zhat=use_zhat,
            root_mode=False,  # the local root is never the global root
            tol_factor=tol_factor, stream_threshold=stream_threshold,
            deflate_budget=deflate_budget,
            resident_threshold=resident_threshold, fused=fused)
        lam, rows = res.lam, res.rows
        # Diagnostics keep the global (B, num_merges) layout: shard-local
        # nodes are contiguous in the global node order.
        kprimes.append(_dist.gather_lanes(res.kprime, axis_name))

    # ---- phase 3: the O(n) state all-gather -----------------------------
    lam, rows = _dist.gather_tree_state(lam[:, 0], rows[:, 0], axis_name,
                                        compress=compress_halo)
    # Shard-edge couplings for the cooperative levels (one (B,) value per
    # shard; sgn needs the raw signed e, so gather before the abs).
    e_edges = _dist.gather_lanes(e_loc[:, -1:], axis_name)   # (B, shards)

    # ---- phase 4: cooperative levels ------------------------------------
    for _ in range(L_coop):
        nm = lam.shape[1] // 2
        M = lam.shape[2]
        root = (nm == 1) and not return_boundary
        q = (2 * jnp.arange(nm) + 1) * (M // Np) - 1
        beta = e_edges[:, q]                               # (B, nm)
        rho = jnp.abs(beta)
        sgn = jnp.where(beta >= 0.0, 1.0, -1.0).astype(e_loc.dtype)
        lam_pairs, z_inner, R = _level_pairs(lam, rows, track, M)
        res = _merge.merge_level_coop(
            lam_pairs, z_inner, R, rho, sgn,
            axis_name=axis_name, shards=shards,
            niter=niter, chunk=chunk, use_zhat=use_zhat,
            root_mode=root, tol_factor=tol_factor,
            stream_threshold=stream_threshold,
            deflate_budget=deflate_budget,
            resident_threshold=resident_threshold, fused=fused)
        lam, rows = res.lam, res.rows
        kprimes.append(res.kprime)

    return lam[:, 0], rows[:, 0], kprimes


def _as_batch(d, e, dtype):
    d = jnp.asarray(d)
    e = jnp.asarray(e)
    if dtype is not None:
        d = d.astype(dtype)
        e = e.astype(dtype)
    if (d.ndim != 2 or e.ndim != 2 or e.shape[0] != d.shape[0]
            or e.shape[1] != max(d.shape[1] - 1, 0)):
        raise ValueError(
            f"batched solve expects d (B, n) and e (B, n-1); "
            f"got {d.shape} / {e.shape}")
    return d, e


def eigvalsh_tridiagonal_batch(d, e, *, leaf: int | None = None,
                               chunk: int = 256,
                               niter: int | None = None,
                               use_zhat: bool = True,
                               return_boundary: bool = False,
                               tol_factor: float = 8.0,
                               stream_threshold: int | None = None,
                               deflate_budget: int | None = None,
                               resident_threshold: int | None = None,
                               fused: bool = True,
                               dtype=None, mesh="auto",
                               compress_halo: bool = False,
                               precision: str = "auto",
                               refine_tol: float | None = None
                               ) -> BRBatchResult:
    """All eigenvalues of B independent symmetric tridiagonals at once.

    One executor launch, one XLA program, B * O(n) persistent state: the
    per-level merge batch absorbs the problem axis, so every secular
    solve / deflation scan across the whole batch runs in a single
    vectorized sweep.  Compiled executables are cached per
    ``(padded N, leaf, batch bucket, dtype, flags)`` bucket with batch
    buckets rounded up to powers of two (see ``repro.core.plan``), so
    arbitrary request batches hit a handful of traces.

    Args:
      d: (B, n) diagonals.  e: (B, n-1) off-diagonals.
      return_boundary: also return (blo, bhi) of each problem's full
        eigenvector matrix (one extra tracked selected row; still one
        solve).
      Remaining knobs as in :func:`eigvalsh_tridiagonal_br`.

    Returns:
      BRBatchResult with eigenvalues (B, n) ascending per problem.
    """
    from repro.core import plan as _plan  # deferred: plan imports br_dc
    precision = _plan.resolve_precision(
        precision, dtype if dtype is not None else jnp.asarray(d).dtype)
    if precision == "mixed" and dtype is None:
        dtype = jnp.float64   # mixed certifies / returns in f64
    d, e = _as_batch(d, e, dtype)
    B, n = d.shape
    if n == 1:
        ones = jnp.ones((B, 1), d.dtype)
        SOLVE_COUNTER.increment()
        return BRBatchResult(d, ones if return_boundary else None,
                             ones if return_boundary else None, ())

    p = _plan.make_plan(n, B, leaf=leaf, chunk=chunk, niter=niter,
                        use_zhat=use_zhat, return_boundary=return_boundary,
                        tol_factor=tol_factor,
                        stream_threshold=stream_threshold,
                        deflate_budget=deflate_budget,
                        resident_threshold=resident_threshold, fused=fused,
                        dtype=d.dtype, mesh=mesh, compress_halo=compress_halo,
                        precision=precision, refine_tol=refine_tol)
    return p.execute(d, e)


def eigvalsh_tridiagonal_br(d, e, *, leaf: int | None = None,
                            chunk: int = 256,
                            niter: int | None = None,
                            use_zhat: bool = True,
                            return_boundary: bool = False,
                            tol_factor: float = 8.0,
                            stream_threshold: int | None = None,
                            deflate_budget: int | None = None,
                            resident_threshold: int | None = None,
                            fused: bool = True,
                            dtype=None, mesh="auto",
                            compress_halo: bool = False,
                            precision: str = "auto",
                            refine_tol: float | None = None) -> BRResult:
    """All eigenvalues of the symmetric tridiagonal (d, e) via boundary-row D&C.

    O(n) auxiliary memory; same secular merges as conventional D&C
    (paper Theorem 3.3).  A single solve is the batch == 1 bucket of the
    plan/executor core -- see :func:`eigvalsh_tridiagonal_batch` for the
    many-problem front door sharing the same compiled executables.

    Args:
      d: (n,) diagonal.  e: (n-1,) off-diagonal.
      leaf: leaf block size (power-of-two tree is built above it).
        None consults the tuning cache (``core.tune``), falling back to
        the built-in default of 32.
      chunk: streaming chunk for secular/row updates (memory knob).
      niter: secular iteration cap.  None picks the precision's
        default: ``secular.DEFAULT_NITER`` for native trees,
        ``secular.DEFAULT_NITER_F32`` for f32/mixed trees (single
        precision hits its accuracy floor in fewer iterations).
      use_zhat: Gu-Eisenstat weight reconstruction for propagated rows.
      return_boundary: also return (blo, bhi) of the full eigenvector matrix
        (propagates rows through the root merge -- tests/consumers).  Costs
        exactly one solve: on padded sizes the last *original* row is
        tracked as an extra selected row instead of re-solving the flipped
        problem.
      stream_threshold: merges with K <= threshold take the dense
        vectorized path (speed knob; larger values trade O(B K^2) transient
        memory for batch parallelism at the bottom of the tree).  None
        picks the backend-aware default: 0 on CPU (stream everything),
        512 on accelerators (see merge.default_stream_threshold).
      deflate_budget: rotation-candidate budget of the parallel deflation
        head (merges run a short exact close-pole chain over at most this
        many candidates instead of a K-step scan; overflow escalates to
        exact K/2 / full-K tiers).  None: the library default
        (merge.DEFAULT_DEFLATE_BUDGET); <= 0 forces the sequential chain.
      resident_threshold: merges with K at or below it run the secular
        solve + fused post-pass as ONE resident dispatch (a single Pallas
        launch per level on TPU).  None picks the backend-aware default:
        0 on CPU, 512 on accelerators (merge.default_resident_threshold).
      fused: use the single-pass fused conquer post-phase (False: legacy
        two-pass, kept as benchmark baseline).
      mesh: distributed-conquer routing.  "auto" (default) shards huge
        problems (padded N >= plan.DIST_AUTO_MIN_N) over the largest
        power-of-two device count available -- a no-op on one device; an
        int or a Mesh demands exactly that many contiguous problem
        shards and raises when devices or tree leaves are short; 1/None
        forces the single-device path.
      compress_halo: int8-compress the boundary rows in the sharded
        path's subtree->cooperative all-gather (off by default; the
        uncompressed sharded path is bit-identical to single-device).
      precision: "auto" (default) is "mixed" for float64 on a TPU and
        "native" otherwise (``plan.resolve_precision``); "native" runs
        the tree in the input dtype; "mixed" runs the ENTIRE tree --
        leaves, deflation, secular iteration, fused post-pass, resident
        kernel, sharded halo -- in f32, then certifies every eigenvalue
        with f64 Sturm counts against the original (d, e) and polishes
        only the non-certified clusters with bracket-guarded f64
        iteration (``bisect.refine_clusters``).  Output is float64 with every
        eigenvalue within ``refine_tol * eps_f64 * ||T||_1`` of a true
        eigenvalue.  Requires x64 mode.  Boundary rows under mixed are
        f32-accurate (cast to f64, permuted with the eigenvalues) --
        only the eigenvalues are refined.
      refine_tol: mixed-precision certification tolerance in units of
        ``eps_f64 * ||T||_1`` (default ``bisect.DEFAULT_REFINE_TOL``);
        only valid with precision="mixed".
    """
    from repro.core import plan as _plan  # deferred: plan imports br_dc
    d = jnp.asarray(d)
    e = jnp.asarray(e)
    precision = _plan.resolve_precision(
        precision, dtype if dtype is not None else d.dtype)
    if precision == "mixed" and dtype is None:
        dtype = jnp.float64   # mixed certifies / returns in f64
    if dtype is not None:
        d = d.astype(dtype)
        e = e.astype(dtype)
    n = d.shape[0]
    if n == 1:
        one = jnp.ones((1,), d.dtype)
        SOLVE_COUNTER.increment()
        return BRResult(d, one, one, ())

    leaf = _plan.resolve_leaf(leaf, n, d.dtype, precision)
    N, L = _tree_shape(n, leaf)
    # Single (possibly padded) leaf trees carry their boundary rows for
    # free (no root merge to skip them at), matching the historical
    # contract that L == 0 always returns (blo, bhi).
    p = _plan.make_plan(n, 1, leaf=leaf, chunk=chunk, niter=niter,
                        use_zhat=use_zhat,
                        return_boundary=return_boundary or L == 0,
                        tol_factor=tol_factor,
                        stream_threshold=stream_threshold,
                        deflate_budget=deflate_budget,
                        resident_threshold=resident_threshold, fused=fused,
                        dtype=d.dtype, mesh=mesh, compress_halo=compress_halo,
                        precision=precision, refine_tol=refine_tol)
    res = p.execute(d[None, :], e[None, :])
    blo = None if res.blo is None else res.blo[0]
    bhi = None if res.bhi is None else res.bhi[0]
    return BRResult(res.eigenvalues[0], blo, bhi,
                    tuple(k[0] for k in res.kprime_per_level))


def workspace_model(n: int, leaf: int = 32, chunk: int = 128,
                    itemsize: int = 8, stream_threshold: int = 512,
                    batch: int = 1) -> dict:
    """Analytic auxiliary-workspace model (Table 1 accounting).

    BR persistent state per problem: lam (N) + rows (2N) + d,e inputs held
    once (2N); transients: the larger of the streamed secular evaluation
    at the top merge, O(chunk * K), the dense small-K levels' batched
    tiles, O(N * min(stream_threshold, N)), and the leaf
    eigendecomposition batch (N * leaf).  A batch of B problems scales
    every term linearly: B * O(N) persistent -- the memory model that
    makes many-problem workloads viable (the lazy/full baselines would
    pay B * O(N^2)).
    """
    N, _ = _tree_shape(n, leaf)
    persistent = batch * 3 * N * itemsize
    dense_tile = N * min(stream_threshold, N)
    transient = batch * (max(chunk * 2 * N, dense_tile) + N * leaf) * itemsize
    return {
        "persistent_bytes": persistent,
        "transient_bytes": transient,
        "total_bytes": persistent + transient,
        "model": f"B*(3N + (max(2*chunk, min(T,N)) + leaf)*N) floats, "
                 f"N={N}, B={batch}",
    }
