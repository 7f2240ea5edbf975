"""Batch-first solve plans: static tree shape + bucketed compile cache.

Every solve -- single or batched -- goes through a :class:`SolvePlan`.
A plan captures everything *static* about a solve up front:

  * the padded problem size ``N = leaf * 2^L`` and tree depth ``L``,
  * the per-level rank-one coupling indices (where each merge's split
    off-diagonal lives in ``e``),
  * the selected-row track slots (2 boundary rows, +1 tracked original
    row when boundary output is requested),
  * the batch bucket: request batches are rounded **up to the next power
    of two**, so arbitrary traffic (B = 1, 3, 5, 97, ...) lands on a
    handful of compiled executables instead of one trace per batch size.

and owns the process-wide cache of compiled executables, keyed on

    (padded N, leaf, batch bucket, dtype, chunk, niter, use_zhat,
     return_boundary, tol_factor, stream_threshold, deflate_budget,
     resident_threshold, fused, shards, compress_halo, precision,
     refine_tol)

Two requests that differ only in original size n (same padded bucket) or
only in batch size (same power-of-two bucket) share one executable: the
tracked-row index is a *traced* per-problem input and short batches are
padded with trivial dummy problems, both sliced away on exit.  This is
what lets the solver run as a service under real traffic -- steady-state
request handling is cache lookups + one device launch, never a retrace.

``stream_threshold=None``, ``deflate_budget=None`` and
``resident_threshold=None`` are resolved to backend-aware concrete values
at plan-construction time so the cache key is always fully concrete.

Memory model: persistent state for a bucket of B problems is B * O(N)
(lam + selected rows + inputs), never B * O(N^2) -- the paper's O(n)
boundary-row state is exactly what makes the batched front door viable.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core import br_dc as _br
from repro.core import guard as _guard
from repro.core import merge as _merge
from repro.core import secular as _sec
from repro.core import tune as _tune
from repro.core.instrument import SolveCounter
from repro.runtime import faults as _faults

# Built-in leaf block size; ``leaf=None`` resolves to the tuning cache's
# winner for the coordinate (if any) and falls back to this.
LEAF_DEFAULT = 32

# Incremented once per executor *trace* (Python-level side effect inside
# the jitted body runs only when XLA actually retraces).  Tests assert
# that a second same-bucket request performs zero new traces.
EXECUTOR_TRACES = SolveCounter("executor_traces")

# Same contract for the partial-spectrum (range) executor.
RANGE_EXECUTOR_TRACES = SolveCounter("range_executor_traces")


class PlanKey(NamedTuple):
    """Bucketed compile-cache key; every field is static/hashable."""
    padded_n: int
    leaf: int
    batch_bucket: int
    dtype: str
    chunk: int
    niter: int
    use_zhat: bool
    return_boundary: bool
    tol_factor: float
    stream_threshold: int
    deflate_budget: int
    resident_threshold: int
    fused: bool
    # Distributed conquer: number of contiguous problem shards on the 1-D
    # solver mesh (1 == classic single-device path) and whether the
    # subtree->cooperative all-gather int8-compresses the boundary rows.
    # Mesh shape is executable identity: same N on a different shard
    # count is a different XLA program, so it must split the cache.
    shards: int = 1
    compress_halo: bool = False
    # Mixed-precision pipeline: "native" runs the tree in `dtype`;
    # "mixed" runs the whole tree in f32 and then Sturm-certifies /
    # polishes the eigenvalues against the original f64 (d, e) to
    # refine_tol * eps_f64 * ||T||.  `dtype` stays the OUTPUT dtype
    # (float64 for mixed), so the f32 tree executable is shared with
    # plain-f32 traffic of the same knobs; refine_tol is normalized to
    # 0.0 on uncertified native routes so it never splits their cache.
    precision: str = "native"
    refine_tol: float = 0.0
    # Certified solves (the robustness layer's product knob): the request
    # finalizer runs one extra batched Sturm sweep (certify_spectrum)
    # over the outputs and escalates misses down the degradation ladder.
    # The flag joins the key so the serving scheduler groups certified
    # traffic into its own flushes (one amortized sweep per flush) -- but
    # the TREE executable is untouched: `certify` is not a static arg of
    # `_executor`, so certified and uncertified routes of equal knobs
    # share one compiled solve.
    certify: bool = False


def batch_bucket(batch: int) -> int:
    """Round a request batch up to the next power of two (min 1)."""
    if batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")
    return 1 << (batch - 1).bit_length()


def _refine_default_tol() -> float:
    from repro.core import bisect as _bis  # deferred: bisect imports plan
    return _bis.DEFAULT_REFINE_TOL


def _refine_traces() -> SolveCounter:
    from repro.core import bisect as _bis  # deferred: bisect imports plan
    return _bis.REFINE_EXECUTOR_TRACES


# Auto-routing floor: padded problems at least this large pick the
# sharded path when several devices are visible.  Below it the all-gather
# plus replicated merge-head overhead outweighs the sharded subtree/
# secular work (the distributed crossover heuristic in the README).
DIST_AUTO_MIN_N = 16384


def _resolve_shards(mesh, padded_n: int, leaf: int) -> int:
    """Resolve the ``mesh`` routing knob to a concrete shard count.

    ``mesh`` may be None / 1 (single device), "auto" (shard huge
    problems over the largest usable power-of-two device count), an int
    shard count, or a Mesh object (its total size is used).  Explicit
    requests validate hard -- a clear error beats a silent single-device
    fallback; "auto" degrades to 1 instead.
    """
    max_shards = padded_n // leaf        # one leaf per shard at minimum
    if mesh is None or mesh == 1:
        return 1
    if isinstance(mesh, str):
        if mesh != "auto":
            raise ValueError(f"mesh must be 'auto', an int shard count, "
                             f"or a Mesh; got {mesh!r}")
        if padded_n < DIST_AUTO_MIN_N:
            return 1
        shards = jax.device_count()
        shards = 1 << (shards.bit_length() - 1)   # largest pow2 <= devices
        while shards > max_shards:
            shards //= 2
        return max(1, shards)
    shards = int(mesh.size) if hasattr(mesh, "size") else int(mesh)
    if shards < 1:
        raise ValueError(f"mesh shard count must be >= 1, got {shards}")
    if shards == 1:
        return 1
    if shards & (shards - 1):
        raise ValueError(
            f"mesh shard count must be a power of two (the D&C tree "
            f"pairs nodes), got {shards}")
    if shards > jax.device_count():
        raise ValueError(
            f"mesh={shards} but only {jax.device_count()} devices are "
            f"visible; force host devices before first jax init "
            f"(XLA_FLAGS=--xla_force_host_platform_device_count={shards}, "
            f"or run.py --mesh {shards})")
    if shards > max_shards:
        raise ValueError(
            f"mesh={shards} needs at least {shards} leaves but "
            f"padded n={padded_n} with leaf={leaf} has {max_shards}; "
            f"use fewer shards or a smaller leaf")
    return shards


def default_dtype():
    """The dtype a request without one solves in: float64 under x64."""
    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


def resolve_precision(precision: str, dtype=None) -> str:
    """Resolve the ``precision`` knob: "native" and "mixed" stand; "auto"
    (the default) is ``tune.default_precision`` of the solve dtype --
    "mixed" for float64 on a TPU, "native" otherwise."""
    if precision != "auto":
        return precision
    dtype = default_dtype() if dtype is None else dtype
    return _tune.default_precision(jnp.dtype(dtype).name)


def _tuned_leaf(n: int, dtype_name: str, precision: str):
    """Tuning-cache leaf for a request size, as (leaf, came_from_cache).

    The coordinate is keyed by the padded size under ``LEAF_DEFAULT``
    (the only tree shape known before the knob resolves); the tuner
    writes its ``leaf`` winner under the same coordinate.  A leaf that
    accuracy pins (``tune.pinned_leaf``) never consults the cache.
    """
    pinned = _tune.pinned_leaf(dtype_name, precision)
    if pinned is not None:
        return pinned, False
    N0, _ = _br._tree_shape(n, LEAF_DEFAULT)
    tuned = _tune.lookup("solve", n=N0, dtype=dtype_name,
                         precision=precision)
    return int(tuned.get("leaf", LEAF_DEFAULT)), "leaf" in tuned


def resolve_leaf(leaf, n: int, dtype, precision: str = "auto") -> int:
    """Resolve the ``leaf`` knob exactly like ``resolve_solve_route``.

    Shared with request routing's single-leaf (L == 0) boundary-row
    check so both seams agree on the tree shape even when the tuning
    cache overrides the default.
    """
    if leaf is not None:
        return int(leaf)
    precision = resolve_precision(precision, dtype)
    return _tuned_leaf(n, jnp.dtype(dtype).name, precision)[0]


def resolve_solve_route(n: int, *, leaf: int | None = None,
                        chunk: int = 256,
                        niter: int | None = None,
                        use_zhat: bool = True,
                        return_boundary: bool = False,
                        tol_factor: float = 8.0,
                        stream_threshold: int | None = None,
                        deflate_budget: int | None = None,
                        resident_threshold: int | None = None,
                        fused: bool = True, dtype=None,
                        mesh="auto",
                        compress_halo: bool = False,
                        precision: str = "auto",
                        refine_tol: float | None = None,
                        certify: bool = False) -> PlanKey:
    """Resolve a full-spectrum request to its bucketed route key -- pure.

    The returned :class:`PlanKey` has every request-determined field
    concrete (None knobs resolved to backend defaults, n absorbed into
    its padded size) but the batch axis *unresolved*: ``batch_bucket`` is
    0 and ``chunk`` is the requested upper bound, both fixed by
    :func:`plan_for_route` once the launch batch is known.  Two requests
    with equal route keys are guaranteed to share one compiled executable
    when coalesced into the same flush -- the grouping invariant the
    serving scheduler (``repro.serve``) is built on.  Never touches the
    plan cache.

    ``mesh`` routes distributed conquer: the default "auto" shards
    problems with padded N >= ``DIST_AUTO_MIN_N`` over the largest
    power-of-two device count available (a no-op on one device); an int /
    Mesh demands exactly that shard count and raises when the devices or
    tree leaves are not there.  ``compress_halo`` opts the sharded
    path's boundary-row all-gather into int8 compression; it is
    normalized to False on the single-device route so it never splits
    that cache.

    ``precision="mixed"`` routes the mixed-precision pipeline: the D&C
    tree runs in f32 (the ``dtype`` field stays the OUTPUT dtype,
    float64) and the eigenvalues are Sturm-certified / cluster-polished
    to ``refine_tol * eps_f64 * ||T||`` against the original (d, e).
    The default ``precision="auto"`` is :func:`resolve_precision`: mixed
    for float64 on a TPU, native otherwise; the key holds the result.
    ``niter=None`` resolves to the precision's default iteration budget
    (f32 trees hit their accuracy floor earlier -- see
    ``secular.DEFAULT_NITER_F32``); an explicit niter always wins.
    """
    if n < 1:
        raise ValueError(f"n must be >= 1, got {n}")
    if precision not in ("auto", "native", "mixed"):
        raise ValueError(f"precision must be 'auto', 'native' or 'mixed', "
                         f"got {precision!r}")
    precision = resolve_precision(precision, dtype)
    if precision == "mixed":
        if not jax.config.jax_enable_x64:
            raise ValueError(
                "precision='mixed' certifies against float64 Sturm "
                "counts; enable jax_enable_x64 first (JAX_ENABLE_X64=1 "
                "-- see the README mixed-precision runbook)")
        if dtype is not None and jnp.dtype(dtype) != jnp.dtype(jnp.float64):
            raise ValueError(
                f"precision='mixed' returns float64 eigenvalues; dtype "
                f"must be float64 or None, got {jnp.dtype(dtype).name} "
                f"(for a pure-f32 solve use dtype=float32 with "
                f"precision='native')")
        dtype = jnp.float64
        refine_tol = float(refine_tol if refine_tol is not None
                           else _refine_default_tol())
        if refine_tol <= 0.0:
            raise ValueError(
                f"refine_tol must be positive (eps_f64 * ||T|| units), "
                f"got {refine_tol}")
    else:
        if refine_tol is not None and not certify:
            raise ValueError(
                "refine_tol only applies to precision='mixed' or "
                "certify=True routes")
        # Certified native routes carry the certification tolerance in the
        # refine_tol field (same eps * ||T|| units the mixed pipeline
        # uses); uncertified native routes normalize it to 0.0 so it never
        # splits their cache.
        refine_tol = (float(refine_tol if refine_tol is not None
                            else _refine_default_tol()) if certify else 0.0)
        if certify and refine_tol <= 0.0:
            raise ValueError(
                f"refine_tol must be positive (eps * ||T|| units), "
                f"got {refine_tol}")
    if niter is None:
        niter = (_sec.DEFAULT_NITER_F32 if precision == "mixed"
                 else _sec.DEFAULT_NITER)
    if dtype is None:
        dtype = default_dtype()
    dtype_name = jnp.dtype(dtype).name
    # Tuning-cache consult: every knob that arrives as None resolves
    # tuned-winner-first, backend-default second -- the same seam prewarm
    # established, so tuned values land in the PlanKey before executable
    # lookup and equal route keys still mean one shared executable.
    consulted = used_tuned = False
    if leaf is None:
        consulted = True
        leaf, from_cache = _tuned_leaf(n, dtype_name, precision)
        used_tuned |= from_cache
    N, _ = _br._tree_shape(n, leaf)
    shards = _resolve_shards(mesh, N, leaf)
    if (stream_threshold is None or deflate_budget is None
            or resident_threshold is None):
        consulted = True
        tuned = _tune.lookup("solve", n=N, dtype=dtype_name,
                             precision=precision, shards=shards)
        if stream_threshold is None:
            used_tuned |= "stream_threshold" in tuned
            stream_threshold = tuned.get(
                "stream_threshold", _merge.default_stream_threshold())
        if deflate_budget is None:
            used_tuned |= "deflate_budget" in tuned
            deflate_budget = tuned.get(
                "deflate_budget", _merge.DEFAULT_DEFLATE_BUDGET)
        if resident_threshold is None:
            used_tuned |= "resident_threshold" in tuned
            resident_threshold = tuned.get(
                "resident_threshold", _merge.default_resident_threshold())
    if consulted:
        _tune.note_route(used_tuned)
    return PlanKey(padded_n=N, leaf=leaf, batch_bucket=0,
                   dtype=jnp.dtype(dtype).name, chunk=int(chunk),
                   niter=int(niter), use_zhat=use_zhat,
                   return_boundary=return_boundary,
                   tol_factor=float(tol_factor),
                   stream_threshold=int(stream_threshold),
                   deflate_budget=int(deflate_budget),
                   resident_threshold=int(resident_threshold), fused=fused,
                   shards=shards,
                   compress_halo=bool(compress_halo) and shards > 1,
                   precision=precision, refine_tol=refine_tol,
                   certify=bool(certify))


# Elements per streamed secular tile the CPU path aims for (~2 MiB f64):
# big enough to amortize loop steps, small enough to stay cache-resident.
_CPU_TILE_BUDGET = 256 * 1024


def _resolve_chunk(chunk: int, bucket: int, padded_n: int) -> int:
    """Batch-aware effective streaming chunk (CPU only).

    The requested ``chunk`` is an upper bound.  Under a wide batch the
    vmapped streamed tiles are (bucket * nodes, chunk, K): a chunk sized
    for one problem blows the cache by the batch factor and the secular
    iteration turns memory-bound (measured ~4x slower per problem at
    bucket=64, K=256 with chunk=256 vs 16 on 2-core CPU).  The effective
    chunk targets a fixed tile budget at the top merge (K = padded N,
    width = bucket), keeping per-eval tiles cache-resident; results are
    equivalent to rounding (chunking is a pure scheduling knob).
    Accelerator backends keep the requested chunk -- their kernels tile
    explicitly.
    """
    if bucket <= 1 or _tune.pinned_backend() != "cpu":
        return chunk
    return max(8, min(chunk, _CPU_TILE_BUDGET // (bucket * padded_n)))


_MESH_LOCK = threading.Lock()
_MESH_CACHE: dict[int, Mesh] = {}


def _batch_sharding(bucket: int):
    """NamedSharding over the batch axis when multiple devices exist.

    A batched solve is embarrassingly parallel across problems, so the
    bucket is split across all default-backend devices (forced host CPU
    devices count too: set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=<cores>`` to give
    the executor one device per core).  The Python loop of single solves
    can never use this -- each of its launches is one problem wide.
    Buckets are powers of two, so the mesh uses the largest power-of-two
    device count available (a 6-core host shards over 4 devices rather
    than not at all).  Returns None when sharding does not apply
    (single device, or bucket smaller than two shards).
    """
    devs = jax.devices()
    if len(devs) <= 1:
        return None
    n = 1 << (len(devs).bit_length() - 1)   # largest pow2 <= len(devs)
    n = min(n, bucket)                      # bucket is pow2 -> divisible
    if n <= 1:
        return None
    with _MESH_LOCK:
        mesh = _MESH_CACHE.get(n)
        if mesh is None:
            mesh = Mesh(np.array(devs[:n]), ("batch",))
            _MESH_CACHE[n] = mesh
    return NamedSharding(mesh, PartitionSpec("batch"))


_SOLVER_MESH_CACHE: dict[int, Mesh] = {}


def _dist_axis() -> str:
    from repro.dist.sharding import SOLVER_AXIS
    return SOLVER_AXIS


def _solver_mesh(shards: int) -> Mesh:
    """Cached 1-D solver mesh (one Mesh object per shard count, so the
    mesh is a stable static jit argument and never causes a retrace)."""
    with _MESH_LOCK:
        mesh = _SOLVER_MESH_CACHE.get(shards)
        if mesh is None:
            from repro.launch.mesh import make_solver_mesh
            mesh = make_solver_mesh(shards)
            _SOLVER_MESH_CACHE[shards] = mesh
    return mesh


@functools.partial(jax.jit, static_argnames=(
    "leaf", "chunk", "niter", "use_zhat", "return_boundary", "tol_factor",
    "stream_threshold", "deflate_budget", "resident_threshold", "fused"))
def _executor(d_pad, e_pad, track, *, leaf, chunk, niter, use_zhat,
              return_boundary, tol_factor, stream_threshold,
              deflate_budget, resident_threshold, fused):
    """The one compiled entry point for every solve.

    A module-level jit (not per-plan) so the executable cache is shared by
    all SolvePlan instances: same bucket shapes + same static flags ==
    same executable, even across plan objects and original sizes n.
    """
    EXECUTOR_TRACES.increment()
    return _br._br_dc_padded_batch(
        d_pad, e_pad, track, leaf=leaf, chunk=chunk, niter=niter,
        use_zhat=use_zhat, return_boundary=return_boundary,
        tol_factor=tol_factor, stream_threshold=stream_threshold,
        deflate_budget=deflate_budget,
        resident_threshold=resident_threshold, fused=fused)


@functools.partial(jax.jit, static_argnames=(
    "mesh", "shards", "compress_halo", "leaf", "chunk", "niter", "use_zhat",
    "return_boundary", "tol_factor", "stream_threshold", "deflate_budget",
    "resident_threshold", "fused"))
def _executor_sharded(d_pad, e_pad, track, *, mesh, shards, compress_halo,
                      leaf, chunk, niter, use_zhat, return_boundary,
                      tol_factor, stream_threshold, deflate_budget,
                      resident_threshold, fused):
    """Distributed-conquer entry point: one shard_map launch over the 1-D
    solver mesh.  Module-level jit like `_executor`, with the mesh as a
    static argument (cached Mesh objects in `_solver_mesh` keep it a
    stable cache key), so same-mesh traffic never retraces.
    """
    from repro.dist.sharding import SOLVER_AXIS
    EXECUTOR_TRACES.increment()
    body = functools.partial(
        _br._br_dc_sharded_batch, shards=shards, axis_name=SOLVER_AXIS,
        leaf=leaf, chunk=chunk, niter=niter, use_zhat=use_zhat,
        return_boundary=return_boundary, tol_factor=tol_factor,
        stream_threshold=stream_threshold, deflate_budget=deflate_budget,
        resident_threshold=resident_threshold, fused=fused,
        compress_halo=compress_halo)
    sliced = PartitionSpec(None, SOLVER_AXIS)
    # Outputs are genuinely replicated: everything past the transition
    # all-gather is computed identically on every device (the replication
    # checker cannot prove that through ppermute/axis_index, hence off).
    mapped = jax.shard_map(body, mesh=mesh,
                           in_specs=(sliced, sliced, PartitionSpec()),
                           out_specs=PartitionSpec(), check_vma=False)
    return mapped(d_pad, e_pad, track)


@dataclasses.dataclass(frozen=True)
class SolvePlan:
    """Static solve schedule for one (padded N, batch bucket) class."""
    key: PlanKey
    levels: int
    # Per-level tuples of the original indices k whose off-diagonal
    # e[k-1] couples each merge at that level (diagnostics/scheduling).
    coupling_index: tuple
    # Selected-row slots: ("blo", "bhi") (+ "track" with boundary output).
    track_slots: tuple

    @property
    def padded_n(self) -> int:
        return self.key.padded_n

    @property
    def batch_bucket_size(self) -> int:
        return self.key.batch_bucket

    @property
    def devices(self) -> int:
        """Shard count of the 1-D solver mesh this plan launches on
        (1 == the classic single-device executor)."""
        return self.key.shards

    @property
    def state_bytes(self) -> int:
        """Persistent-state byte model for one full-bucket launch.

        B * O(N): inputs (d_pad, e_pad), child spectra (lam) and the r
        selected rows -- the paper's linear-space bound, scaled by the
        batch bucket.  Transients (streamed tiles / dense small-K blocks)
        are excluded; see ``workspace_model`` for those.
        """
        r = 3 if self.key.return_boundary else 2
        itemsize = jnp.dtype(self.key.dtype).itemsize
        return (3 + r) * self.key.padded_n * self.key.batch_bucket * itemsize

    def execute(self, d, e, orig_n=None) -> "_br.BRBatchResult":
        """Run the plan's cached executor on a (B, n) problem batch.

        B may be anything <= the plan's batch bucket (short batches are
        padded with dummy problems and sliced away); n may be anything
        that pads to this plan's N.  Exactly one device launch.

        ``orig_n`` is the mixed-size hook the serving coalescer uses: a
        (B,) array of *original* problem sizes when the batch rows were
        host-padded (with decoupled sentinel blocks) to the common width
        ``n`` before stacking.  It routes each problem's own boundary row
        ``orig_n[b] - 1`` into the tracked selected-row slot (a traced
        input -- no retrace), so mixed-n flushes still return correct
        per-problem (blo, bhi); eigenvalue demux (slicing row b to
        ``orig_n[b]``) is the caller's job since rows here keep the
        common width.
        """
        key = self.key
        dtype = jnp.dtype(key.dtype)
        d = jnp.asarray(d, dtype)
        e = jnp.asarray(e, dtype)
        d, e = _br._as_batch(d, e, None)   # enforce (B, n)/(B, n-1)
        B, n = d.shape
        Bb = key.batch_bucket
        if B > Bb:
            raise ValueError(
                f"batch {B} exceeds plan bucket {Bb}; make a bigger plan")
        if _br._tree_shape(n, key.leaf)[0] != key.padded_n:
            raise ValueError(
                f"n={n} pads to {_br._tree_shape(n, key.leaf)[0]}, but this "
                f"plan was built for padded N={key.padded_n}")

        if orig_n is not None:
            orig_n = jnp.asarray(orig_n, jnp.int32)
            if orig_n.shape != (B,):
                raise ValueError(
                    f"orig_n must have shape ({B},), got {orig_n.shape}")

        if B < Bb:
            # Dummy problems: zero diagonals decouple exactly and cost one
            # deflated pass-through per merge; sliced off below.
            d = jnp.concatenate([d, jnp.zeros((Bb - B, n), dtype)], axis=0)
            e = jnp.concatenate(
                [e, jnp.zeros((Bb - B, max(n - 1, 0)), dtype)], axis=0)

        d_pad, e_pad, N, L = _br._pad_problem(d, e, key.leaf)
        # The tracked third row slot is only needed when padding appends
        # sentinel rows below row n-1; unpadded problems (n == N) already
        # carry that row as the bhi slot, so they run with r == 2.  With
        # per-problem original sizes the track slot always runs (some
        # problems may be host-padded even when n == N) and each problem
        # follows its own row orig_n[b] - 1.
        if key.return_boundary and orig_n is not None:
            track = jnp.concatenate(
                [orig_n - 1, jnp.full((Bb - B,), n - 1, jnp.int32)])
        elif key.return_boundary and n != N:
            track = jnp.full((Bb,), n - 1, jnp.int32)
        else:
            track = None

        if key.precision == "mixed":
            # The whole D&C tree runs in f32; the f64 (d_pad, e_pad) stay
            # behind for the Sturm certification / cluster polish below.
            d_run = d_pad.astype(jnp.float32)
            e_run = e_pad.astype(jnp.float32)
        else:
            d_run, e_run = d_pad, e_pad

        # Chaos-harness hook: a scheduled launch fault raises here --
        # after input staging, before any executor runs -- exactly where
        # a real device/compile fault would surface to the caller.  The
        # hook is one global-flag read when no schedule is configured.
        _faults.inject("plan.launch")

        if key.shards > 1:
            # Distributed conquer: the *problem* axis is sharded over the
            # 1-D solver mesh (batch sharding does not compose with it --
            # every device works on every problem's slice).
            mesh = _solver_mesh(key.shards)
            sliced = NamedSharding(
                mesh, PartitionSpec(None, _dist_axis()))
            # Chaos-harness hook: corrupts one staged off-diagonal entry
            # (default: the last, a shard-boundary coupling) -- the "halo
            # exchange delivered a damaged value" scenario.
            e_run = _faults.corrupt_entry("dist.halo", e_run)
            d_run = jax.device_put(d_run, sliced)
            e_run = jax.device_put(e_run, sliced)
            if track is not None:
                track = jax.device_put(
                    track, NamedSharding(mesh, PartitionSpec()))
            lam, rows, kprimes = _executor_sharded(
                d_run, e_run, track, mesh=mesh, shards=key.shards,
                compress_halo=key.compress_halo, leaf=key.leaf,
                chunk=key.chunk, niter=key.niter, use_zhat=key.use_zhat,
                return_boundary=key.return_boundary,
                tol_factor=key.tol_factor,
                stream_threshold=key.stream_threshold,
                deflate_budget=key.deflate_budget,
                resident_threshold=key.resident_threshold, fused=key.fused)
        else:
            sharding = _batch_sharding(Bb)
            if sharding is not None:
                d_run = jax.device_put(d_run, sharding)
                e_run = jax.device_put(e_run, sharding)
                if track is not None:
                    track = jax.device_put(track, sharding)

            lam, rows, kprimes = _executor(
                d_run, e_run, track, leaf=key.leaf, chunk=key.chunk,
                niter=key.niter, use_zhat=key.use_zhat,
                return_boundary=key.return_boundary,
                tol_factor=key.tol_factor,
                stream_threshold=key.stream_threshold,
                deflate_budget=key.deflate_budget,
                resident_threshold=key.resident_threshold, fused=key.fused)
        _br.SOLVE_COUNTER.increment()
        # Chaos-harness hook: NaN-poisons configured eigenvalue rows ("the
        # device returned garbage") so tests can drive the degradation
        # ladder.  Sits BEFORE the mixed-precision refinement stage: a
        # poisoned mixed solve exercises recovery-by-refinement, a
        # poisoned native solve exercises the finalizer's ladder.
        lam = _faults.poison_rows("plan.output", lam)

        if _br.SOLVE_COUNTER.deflation_enabled:
            # Deflation-ratio gauge (opt-in via measure(deflation=True)):
            # kprime per level is already an executor output, so observing
            # it costs one tiny host transfer, never a recomputation.
            # Restrict to merge nodes that touch real data -- nodes lying
            # entirely in the padded sentinel region [n, N) deflate almost
            # completely and would bias the reported ratio downwards.
            for level, kp in enumerate(kprimes):
                K_level = 2 * key.leaf * (1 << level)
                nm_real = min(kp.shape[1], -(-n // K_level))
                _br.SOLVE_COUNTER.record_deflation(
                    level, float(jnp.sum(kp[:B, :nm_real])),
                    B * nm_real * K_level)

        lam = lam[:B]
        rows_b = rows[:B] if key.return_boundary else None
        if key.precision == "mixed":
            # Certify the f32 tree's eigenvalues with f64 Sturm counts
            # against the ORIGINAL (d, e) and polish only the misses.
            # Runs on the full padded width: sentinel lanes are exactly
            # decoupled and certify vacuously (nvalid masks them), so the
            # padded counts equal the original problem's counts.  The
            # polish moves each lane by at most refine_tol * eps * ||T||,
            # which can reorder ties -- one argsort restores ascending
            # order and (for boundary output) permutes the selected rows
            # by the identical permutation.
            from repro.core import bisect as _bis  # deferred: imports plan
            nvalid = (orig_n if orig_n is not None
                      else jnp.full((B,), n, jnp.int32))
            lam_ref, rinfo = _bis.refine_clusters(
                d_pad[:B], e_pad[:B, : N - 1], lam.astype(dtype),
                nvalid=nvalid, tol_factor=key.refine_tol, sort=False)
            order = jnp.argsort(lam_ref, axis=1)
            lam = jnp.take_along_axis(lam_ref, order, axis=1)
            if rows_b is not None:
                rows_b = jnp.take_along_axis(
                    rows_b.astype(dtype), order[:, None, :], axis=2)
            if _br.SOLVE_COUNTER.refinement_enabled:
                _br.SOLVE_COUNTER.record_refinement(
                    rinfo["targets"], rinfo["polished"],
                    rinfo["iterations"], rinfo["rounds"])

        lam = lam[:, :n]  # sentinels sort above the Gershgorin bound
        if key.return_boundary:
            blo = rows_b[:, 0, :n]
            bhi = rows_b[:, 2 if track is not None else 1, :n]
        else:
            blo = bhi = None
        return _br.BRBatchResult(lam, blo, bhi,
                                 tuple(k[:B] for k in kprimes))


class RangePlanKey(NamedTuple):
    """Bucketed cache key for partial-spectrum (sliced) solves.

    ``k_bucket`` rounds the requested slice width up to the next power of
    two and the target *indices* are a traced executor input, so every
    (il, iu) window of the same bucketed width -- top-k, bottom-k, or an
    interior band -- shares one executable.  ``select`` is deliberately
    NOT a key field: select-by-value requests are resolved host-side to
    an index window (two Sturm counts) and then reuse the select-by-index
    executables instead of splitting the cache.
    """
    n: int
    k_bucket: int
    batch_bucket: int
    dtype: str
    maxiter: int
    polish: int


@functools.partial(jax.jit, static_argnames=("maxiter", "polish"))
def _range_executor(d, e, targets, *, maxiter, polish):
    """The one compiled entry point for every sliced solve.

    Module-level jit (not per-plan) so executables are shared across
    RangePlan instances exactly like the full-spectrum ``_executor``.
    """
    from repro.core import bisect as _bis  # deferred: bisect imports plan
    RANGE_EXECUTOR_TRACES.increment()
    return _bis._slice_targets(d, e, targets, maxiter=maxiter,
                               polish=polish)


@dataclasses.dataclass(frozen=True)
class RangePlan:
    """Static schedule for one (n, k bucket, batch bucket) sliced-solve
    class; ``execute`` is the only entry point that launches work."""
    key: RangePlanKey

    @property
    def k_bucket_size(self) -> int:
        return self.key.k_bucket

    @property
    def state_bytes(self) -> int:
        """Persistent-state byte model for one full-bucket launch:
        B * (2n inputs + 4k bracket state (lo, hi, lam, count)) -- the
        O(B * (n + k)) memory the sliced front end advertises."""
        key = self.key
        itemsize = jnp.dtype(key.dtype).itemsize
        return key.batch_bucket * (2 * key.n + 4 * key.k_bucket) * itemsize

    def execute(self, d, e, il, k: int | None = None):
        """Eigenvalues [il, il + k) of each problem in a (B, n) batch.

        B may be anything <= the plan's batch bucket; the slice may start
        anywhere and k may be anything <= the plan's k bucket (targets
        are traced inputs).  Short batches pad with trivial dummy
        problems and short slices pad by clamping the target indices to
        n-1 (duplicate roots, sliced away).  Exactly one device launch.
        Returns (B, k).

        ``il`` may also be a (B,) integer array -- the serving
        coalescer's mixed-window hook: each problem slices its own
        [il[b], il[b] + k) window inside one launch (targets are traced,
        so this shares the same executable).  Per-problem windows
        narrower than ``k`` clamp their tail targets to n-1; the caller
        slices each row to its own width.
        """
        key = self.key
        dtype = jnp.dtype(key.dtype)
        d = jnp.asarray(d, dtype)
        e = jnp.asarray(e, dtype)
        d, e = _br._as_batch(d, e, None)
        B, n = d.shape
        if n != key.n:
            raise ValueError(f"n={n} but this plan was built for n={key.n}")
        Bb = key.batch_bucket
        if B > Bb:
            raise ValueError(
                f"batch {B} exceeds plan bucket {Bb}; make a bigger plan")
        k = key.k_bucket if k is None else int(k)
        if not (1 <= k <= key.k_bucket):
            raise ValueError(
                f"slice width {k} exceeds plan k bucket {key.k_bucket}")
        il = np.asarray(il, np.int64)
        if il.ndim == 0:
            ilv = int(il)
            if not (0 <= ilv and ilv + k <= n):
                raise ValueError(
                    f"slice [{ilv}, {ilv + k}) out of range for n={n}")
            il = np.full((B,), ilv, np.int64)
        else:
            if il.shape != (B,):
                raise ValueError(
                    f"per-problem il must have shape ({B},), got {il.shape}")
            if il.min() < 0 or il.max() >= n:
                raise ValueError(
                    f"per-problem il must lie in [0, {n}); got "
                    f"[{il.min()}, {il.max()}]")

        if B < Bb:
            d = jnp.concatenate([d, jnp.zeros((Bb - B, n), dtype)], axis=0)
            e = jnp.concatenate(
                [e, jnp.zeros((Bb - B, max(n - 1, 0)), dtype)], axis=0)
        il_full = jnp.zeros((Bb,), jnp.int32).at[:B].set(
            jnp.asarray(il, jnp.int32))
        targets = jnp.minimum(
            il_full[:, None] + jnp.arange(key.k_bucket, dtype=jnp.int32)[None, :],
            n - 1)

        lam = _range_executor(d, e, targets, maxiter=key.maxiter,
                              polish=key.polish)
        _br.SOLVE_COUNTER.increment()
        return lam[:B, :k]


_PLAN_CACHE: dict[PlanKey, SolvePlan] = {}
_RANGE_CACHE: dict[tuple, RangePlan] = {}
_PLAN_LOCK = threading.Lock()
_STATS = {"hits": 0, "misses": 0, "range_hits": 0, "range_misses": 0}


def make_plan(n: int, batch: int = 1, *, leaf: int | None = None,
              chunk: int = 256,
              niter: int | None = None, use_zhat: bool = True,
              return_boundary: bool = False, tol_factor: float = 8.0,
              stream_threshold: int | None = None,
              deflate_budget: int | None = None,
              resident_threshold: int | None = None, fused: bool = True,
              dtype=None, mesh="auto",
              compress_halo: bool = False,
              precision: str = "auto",
              refine_tol: float | None = None) -> SolvePlan:
    """Build (or fetch) the SolvePlan for an (n, batch) request class.

    Bucketing: ``batch`` rounds up to the next power of two and ``n`` is
    absorbed into its padded ``leaf * 2^L`` size, so the cache stays a
    handful of entries under arbitrary traffic.  The returned plan is
    shared and immutable; ``plan.execute(d, e)`` is the only entry point
    that launches work.  Route resolution and plan construction are the
    same two steps the serving scheduler performs -- this is literally
    ``plan_for_route(resolve_solve_route(...), batch)``.
    """
    route = resolve_solve_route(
        n, leaf=leaf, chunk=chunk, niter=niter, use_zhat=use_zhat,
        return_boundary=return_boundary, tol_factor=tol_factor,
        stream_threshold=stream_threshold, deflate_budget=deflate_budget,
        resident_threshold=resident_threshold, fused=fused, dtype=dtype,
        mesh=mesh, compress_halo=compress_halo, precision=precision,
        refine_tol=refine_tol)
    return plan_for_route(route, batch)


def plan_for_route(route: PlanKey, batch: int = 1) -> SolvePlan:
    """Fix a route key's batch axis and build (or fetch) its SolvePlan.

    ``route`` comes from :func:`resolve_solve_route` (batch_bucket == 0,
    chunk == requested upper bound); ``batch`` is the actual launch batch,
    rounded up to its power-of-two bucket here.  This is the plan-cache
    entry point shared by the sync API and the serving scheduler's flush
    path, so coalesced and one-shot traffic hit the same cache entries.
    """
    bucket = batch_bucket(batch)
    # Per-bucket tuned chunk: the launch batch is only known here, so
    # this is where the batch-bucket dimension of the tuning coordinate
    # applies.  The route's chunk stays the requested upper bound (the
    # same contract _resolve_chunk's CPU heuristic honors).
    tuned_chunk = _tune.lookup(
        "solve", n=route.padded_n, bucket=bucket, dtype=route.dtype,
        precision=route.precision, shards=route.shards).get("chunk")
    if tuned_chunk:
        chunk = max(8, min(route.chunk, int(tuned_chunk)))
    else:
        chunk = _resolve_chunk(route.chunk, bucket, route.padded_n)
    key = route._replace(batch_bucket=bucket, chunk=chunk)
    N, leaf = key.padded_n, key.leaf
    L = (N // leaf).bit_length() - 1
    with _PLAN_LOCK:
        plan = _PLAN_CACHE.get(key)
        if plan is not None:
            _STATS["hits"] += 1
            return plan
        _STATS["misses"] += 1
        coupling = []
        for level in range(L):
            M = leaf * (1 << level)
            nm = N // (2 * M)
            coupling.append(tuple((2 * i + 1) * M for i in range(nm)))
        slots = ("blo", "bhi") + (("track",) if key.return_boundary else ())
        plan = SolvePlan(key=key, levels=L, coupling_index=tuple(coupling),
                         track_slots=slots)
        _PLAN_CACHE[key] = plan
        return plan


def resolve_range_route(n: int, k: int, *, maxiter: int | None = None,
                        polish: int | None = None,
                        dtype=None) -> RangePlanKey:
    """Resolve a sliced-solve request to its bucketed route key -- pure.

    Mirrors :func:`resolve_solve_route`: the returned key is fully
    concrete except for the batch axis (``batch_bucket`` == 0, fixed by
    :func:`range_plan_for_route`).  Never touches the plan cache.
    """
    from repro.core import bisect as _bis  # deferred: bisect imports plan
    if n < 1:
        raise ValueError(f"n must be >= 1, got {n}")
    if not (1 <= k <= n):
        raise ValueError(f"k must be in [1, n]; got k={k}, n={n}")
    if dtype is None:
        dtype = default_dtype()
    dtype_name = jnp.dtype(dtype).name
    k_bucket = min(batch_bucket(k), n)
    if maxiter is None or polish is None:
        # Same consult seam as resolve_solve_route: None knobs resolve
        # tuned-winner-first (per (n, k bucket, dtype) coordinate),
        # built-in default second.
        tuned = _tune.lookup("range", n=n, dtype=dtype_name, k=k_bucket)
        used_tuned = False
        if maxiter is None:
            used_tuned |= "maxiter" in tuned
            maxiter = tuned.get("maxiter", _bis.DEFAULT_MAX_BISECT)
        if polish is None:
            used_tuned |= "polish" in tuned
            polish = tuned.get("polish", _bis.DEFAULT_POLISH)
        _tune.note_route(used_tuned)
    return RangePlanKey(n=n, k_bucket=k_bucket,
                        batch_bucket=0, dtype=dtype_name,
                        maxiter=int(maxiter), polish=int(polish))


def make_range_plan(n: int, k: int, batch: int = 1, *,
                    maxiter: int | None = None, polish: int | None = None,
                    dtype=None) -> RangePlan:
    """Build (or fetch) the RangePlan for an (n, k, batch) sliced request.

    Bucketing: ``k`` and ``batch`` round up to the next power of two and
    the slice's start index is a traced executor input, so steady top-k /
    bottom-k / band traffic of any window position lands on a handful of
    compiled executables (``plan_cache_stats()`` exposes the range-cache
    hits/misses/traces next to the full-spectrum ones).
    """
    return range_plan_for_route(
        resolve_range_route(n, k, maxiter=maxiter, polish=polish,
                            dtype=dtype), batch)


def range_plan_for_route(route: RangePlanKey, batch: int = 1) -> RangePlan:
    """Fix a range route key's batch axis and build (or fetch) its plan."""
    key = route._replace(batch_bucket=batch_bucket(batch))
    with _PLAN_LOCK:
        plan = _RANGE_CACHE.get(key)
        if plan is not None:
            _STATS["range_hits"] += 1
            return plan
        _STATS["range_misses"] += 1
        plan = RangePlan(key=key)
        _RANGE_CACHE[key] = plan
        return plan


def plan_cache_stats() -> dict:
    """Plan-cache observability: size/hits/misses, executor trace counts,
    and the per-kind persistent-state byte budgets (sum of each cached
    plan's ``state_bytes`` model -- what a simultaneous full-bucket launch
    of every cached executable would hold resident)."""
    with _PLAN_LOCK:
        mesh_buckets: dict[int, int] = {}
        for k in _PLAN_CACHE:
            mesh_buckets[k.shards] = mesh_buckets.get(k.shards, 0) + 1
        return {"size": len(_PLAN_CACHE), "hits": _STATS["hits"],
                "misses": _STATS["misses"],
                "mesh_buckets": mesh_buckets,
                "executor_traces": EXECUTOR_TRACES.count,
                "state_bytes": sum(p.state_bytes
                                   for p in _PLAN_CACHE.values()),
                "range_size": len(_RANGE_CACHE),
                "range_hits": _STATS["range_hits"],
                "range_misses": _STATS["range_misses"],
                "range_executor_traces": RANGE_EXECUTOR_TRACES.count,
                "range_state_bytes": sum(p.state_bytes
                                         for p in _RANGE_CACHE.values()),
                "refine_executor_traces": _refine_traces().count,
                **_tuning_provenance(),
                **_guard.robustness_counters()}


def _tuning_provenance() -> dict:
    """Tuning provenance for plan_cache_stats: how many route
    resolutions ran on tuned vs default knobs, plus the cache file's
    identity -- so serve metrics (which embed these stats) can tell
    which buckets run on tuned knobs."""
    ts = _tune.tuning_stats()
    return {"tuned_routes": ts["tuned_routes"],
            "default_routes": ts["default_routes"],
            "tuning_cache": {"path": ts["path"], "entries": ts["entries"],
                             "fingerprint": ts["fingerprint"],
                             "error": ts["error"]}}


def clear_plan_cache() -> None:
    """Drop cached plans and zero every cache statistic.

    Also resets the EXECUTOR_TRACES / RANGE_EXECUTOR_TRACES counters so a
    fresh measurement window after a clear starts from zero -- without
    this, no-retrace assertions (and the serving scheduler's steady-state
    monitoring) would race on counts left over from earlier traffic.
    Compiled executables stay in jax's jit cache: clearing is a
    bookkeeping reset, not a recompile.

    Also clears the robustness layer's process-wide state -- the fault
    injection schedule and its hit counters, the degradation gauge, and
    the degradation/deadline counters -- so chaos tests can never leak a
    fault schedule or escalation counts into neighboring tests (the same
    isolation contract the trace counters got in PR 5).
    """
    with _PLAN_LOCK:
        _PLAN_CACHE.clear()
        _RANGE_CACHE.clear()
        for k in _STATS:
            _STATS[k] = 0
        EXECUTOR_TRACES.reset()
        RANGE_EXECUTOR_TRACES.reset()
        _refine_traces().reset()
    _faults.reset_faults()
    _guard.reset_robustness_counters()
    _br.SOLVE_COUNTER.clear_degradation()
    # Tuning layer: zero the tuned/default provenance counters and drop
    # the loaded-cache memo so the next consult re-reads the artifact
    # from disk (the "fresh consult" contract the tuning round-trip
    # tests pin).  The on-disk file itself is untouched.
    _tune.reset_consult_stats()
    _tune.reload_tuning_cache()


def tune(workload_spec, **kw) -> dict:
    """Offline autotuner: sweep the knob space on-device for a workload
    and persist winners to the tuning cache that ``resolve_solve_route``
    / ``resolve_range_route`` / the serve scheduler consult.  See
    :func:`repro.core.tune.tune_workload` for the spec format."""
    return _tune.tune_workload(workload_spec, **kw)


def tuning_stats() -> dict:
    """Tuning-cache observability (file, fingerprint, entry count, and
    tuned/default route counters); see ``repro.core.tune``."""
    return _tune.tuning_stats()


# Workload-spec kind aliases accepted by ``prewarm``; "solve" is the
# stacked ("batch") form.  Kinds matter: each resolves through the same
# routing rules its real traffic will use ("full" carries the single-API
# L == 0 boundary-rows rule, "slq" always has boundary rows), so the
# compiled executable is exactly the one the first request needs.
_PREWARM_KIND_ALIASES = {"solve": "batch", "batch": "batch", "full": "full",
                         "slq": "slq"}


def prewarm(workload_spec) -> dict:
    """Compile executables for an expected workload before traffic hits.

    ``workload_spec`` is an iterable of dict entries::

        {"kind": "solve", "n": 1024, "batch": 64, **make_plan knobs}
        {"kind": "full",  "n": 16}                  # single-problem API
        {"kind": "slq",   "n": 64, "batch": 8, "leaf": 8}
        {"kind": "range", "n": 4096, "k": 32, "batch": 8, **knobs}
        {"kind": "edges", "n": 16, "k": 1, "batch": 1}   # monitor probes

    Each entry is routed exactly like a real request of that kind
    (``repro.core.request.route_request`` -- one source of truth for key
    resolution), its plan is built, and one throwaway full-bucket execute
    on trivial problems compiles the XLA executable -- after ``prewarm``
    a cold service serves its first same-shaped request with zero traces
    (assert via ``plan_cache_stats()``).  Boundary-row plans execute with
    the per-problem ``orig_n`` track input, matching the serving flush
    form.  The throwaway solves do tick SOLVE_COUNTER.

    dtype / ``precision="mixed"`` knobs flow through untouched, so
    f32 and mixed traffic prewarms its OWN executables (a mixed spec
    compiles the f32 tree executor *and* the f64 certify executor --
    its throwaway solve runs the full certify stage on trivial
    problems, which certify on the first round).
    Returns ``{"plans": P, "seconds": s, "traces": t}``.
    """
    from repro.core.request import SolveRequest, route_request
    t0 = time.perf_counter()
    t_start = EXECUTOR_TRACES.count + RANGE_EXECUTOR_TRACES.count
    plans = 0
    for spec in workload_spec:
        spec = dict(spec)
        kind = spec.pop("kind", "solve")
        n = int(spec.pop("n"))
        batch = int(spec.pop("batch", 1))
        if kind in _PREWARM_KIND_ALIASES:
            req_kind = _PREWARM_KIND_ALIASES[kind]
            dtype = spec.get("dtype")
            if dtype is None:
                dtype = (jnp.float64 if jax.config.jax_enable_x64
                         else jnp.float32)
            d = np.zeros((n,) if req_kind == "full" else (batch, n),
                         jnp.dtype(dtype))
            e = np.zeros(d.shape[:-1] + (max(n - 1, 0),), d.dtype)
            routed = route_request(SolveRequest(
                d=d, e=e, kind=req_kind,
                return_boundary=bool(spec.pop("return_boundary", False)),
                certify=bool(spec.pop("certify", False)),
                knobs=spec))
            if routed.route is not None:   # n == 1 short circuits: no plan
                plan = plan_for_route(routed.route, batch)
                d2 = np.zeros((batch, n), d.dtype)
                e2 = np.zeros((batch, max(n - 1, 0)), d.dtype)
                # Serve flushes pass per-problem orig_n (a distinct traced
                # signature when boundary rows are on); "full" mirrors the
                # single-problem sync execution instead.
                orig_n = (np.full((batch,), n, np.int32)
                          if plan.key.return_boundary and req_kind != "full"
                          else None)
                jax.block_until_ready(plan.execute(d2, e2, orig_n=orig_n)
                                      .eigenvalues)
        elif kind == "range":
            k = int(spec.pop("k"))
            plan = make_range_plan(n, k, batch, **spec)
            dtype = jnp.dtype(plan.key.dtype)
            d = jnp.zeros((batch, n), dtype)
            e = jnp.zeros((batch, max(n - 1, 0)), dtype)
            jax.block_until_ready(plan.execute(d, e, 0, k))
        elif kind == "edges":
            # Spectral-monitor probes: routed through route_request (the
            # one source of truth for key resolution), so the compiled
            # executable -- the plain range executor at the duplicated-
            # rows batch bucket, per-problem il as a traced input -- is
            # exactly the one the first real probe flush needs.
            k = int(spec.pop("k", 1))
            dtype = spec.get("dtype")
            if dtype is None:
                dtype = (jnp.float64 if jax.config.jax_enable_x64
                         else jnp.float32)
            d = np.zeros((batch, n), jnp.dtype(dtype))
            e = np.zeros((batch, max(n - 1, 0)), d.dtype)
            routed = route_request(SolveRequest(
                d=d, e=e, kind="edges", knobs={"k": k, **spec}))
            plan = range_plan_for_route(routed.route, routed.batch)
            jax.block_until_ready(
                plan.execute(routed.d, routed.e, routed.il, routed.k))
        else:
            raise ValueError(
                f"unknown prewarm kind {kind!r}; use one of "
                f"{tuple(_PREWARM_KIND_ALIASES) + ('range', 'edges')}")
        plans += 1
    return {"plans": plans, "seconds": time.perf_counter() - t0,
            "traces": EXECUTOR_TRACES.count + RANGE_EXECUTOR_TRACES.count
            - t_start}
