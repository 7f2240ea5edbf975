"""Autotuned plans: on-device knob search + a persistent tuning cache.

Every perf PR so far added a knob whose default encodes the 2-core CI
container: ``leaf``, ``stream_threshold``, ``deflate_budget``,
``resident_threshold``, the bisection ``maxiter``/``polish`` budgets and
the serve scheduler's ``max_batch``/``max_wait_us``.  This module makes
those knobs *self-tuning per machine*:

  * :func:`backend_defaults` is the ONE memoized backend-defaults table.
    The backend is probed exactly once per process
    (:func:`pinned_backend`), so knob resolution is a single dict lookup
    and a mid-process backend flip can never split the plan cache into
    inconsistently-defaulted halves (the per-call
    ``jax.default_backend()`` probes that used to live in
    ``merge.default_stream_threshold`` / ``default_resident_threshold``
    are hoisted here).

  * :func:`tune_workload` (re-exported as ``plan.tune``) sweeps the knob
    space ON DEVICE per ``(kind, padded N, batch bucket, dtype,
    precision, shards)`` coordinate: coordinate descent over tiered
    candidate grids, warm-started from the backend-aware defaults, with
    interleaved best-of timing (the ``benchmarks/common.time_pair``
    discipline: candidate and incumbent alternate so background-load
    drift hits both, best-of because spikes only ever inflate a sample)
    and an acceptance margin so winners are reproducible on the same
    box.  Accuracy-bearing knobs (range ``maxiter``/``polish``) only
    accept candidates that stay inside the documented tolerance against
    a default-knob reference solve.  A final tuned-vs-default race
    guarantees the recorded winner is never slower than the hand-tuned
    defaults (falls back to the defaults otherwise).

  * Winners persist to a small on-disk JSON cache --
    ``$REPRO_TUNE_CACHE`` or ``~/.cache/repro-tune/`` -- versioned and
    fingerprinted by (backend, device kind, jax version).  A corrupt,
    stale, or foreign-machine file degrades to the built-in defaults
    with a single warning; it can NEVER raise at route time.

  * ``plan.resolve_solve_route`` / ``resolve_range_route`` /
    ``plan_for_route`` consult the cache whenever a knob arrives as
    ``None`` -- the same resolution seam ``prewarm`` established -- so
    tuned values flow into the ``PlanKey`` before executable lookup, and
    the serve scheduler consults :func:`serve_knobs` per route bucket.

Observability: :func:`tuning_stats` (re-exported as
``plan.tuning_stats``) reports the cache file, its fingerprint state and
the tuned/default route counters that also ride ``plan_cache_stats``.
"""

from __future__ import annotations

import json
import os
import threading
import time
import warnings

import numpy as np

TUNE_CACHE_VERSION = 1

# Environment override for the cache directory; default under ~/.cache.
TUNE_CACHE_ENV = "REPRO_TUNE_CACHE"
_DEFAULT_CACHE_DIR = os.path.join("~", ".cache", "repro-tune")

_LOCK = threading.RLock()

# Loaded-cache memo: None = not loaded yet; dict = entries (possibly
# empty).  _CACHE_ERROR remembers why a file was rejected (stats) and
# _WARNED ensures the degradation warning fires once per load.
_CACHE: dict | None = None
_CACHE_META: dict = {}
_CACHE_ERROR: str | None = None

# Route-provenance counters (surfaced via plan_cache_stats): a route
# resolution that filled at least one None knob from the tuning cache
# counts as tuned, every other resolution that consulted counts default.
_CONSULTS = {"tuned_routes": 0, "default_routes": 0}

# --------------------------------------------------------------------------
# Backend-defaults table (memoized -- the satellite hoist)
# --------------------------------------------------------------------------

_BACKEND: str | None = None
_DEFAULTS: dict | None = None


def pinned_backend() -> str:
    """The default backend, probed once and pinned for process lifetime.

    Every knob default and tuning coordinate keys off this value.  A
    backend that "flips" mid-process (e.g. an accelerator runtime error
    demoting to CPU) must not re-default half the plan cache -- routes
    resolved before and after the flip would stop coalescing and solves
    would silently run with mismatched thresholds.
    """
    global _BACKEND
    if _BACKEND is None:
        with _LOCK:
            if _BACKEND is None:
                import jax
                _BACKEND = jax.default_backend()
    return _BACKEND


def backend_defaults() -> dict:
    """The one backend-aware knob-defaults table (memoized).

    Knob resolution everywhere (``merge.default_stream_threshold``,
    ``merge.default_resident_threshold``, the route resolvers, the
    tuner's warm start) is a lookup into this dict, computed exactly
    once from :func:`pinned_backend`.
    """
    global _DEFAULTS
    if _DEFAULTS is None:
        with _LOCK:
            if _DEFAULTS is None:
                from repro.core import bisect as _bis
                from repro.core import merge as _merge
                from repro.core import secular as _sec
                cpu = pinned_backend() == "cpu"
                _DEFAULTS = {
                    "leaf": 32,
                    "chunk": 256,
                    "stream_threshold":
                        0 if cpu else _merge.DEFAULT_STREAM_THRESHOLD_ACCEL,
                    "resident_threshold":
                        0 if cpu else _merge.DEFAULT_RESIDENT_THRESHOLD_ACCEL,
                    "deflate_budget": _merge.DEFAULT_DEFLATE_BUDGET,
                    "niter": _sec.DEFAULT_NITER,
                    "maxiter": _bis.DEFAULT_MAX_BISECT,
                    "polish": _bis.DEFAULT_POLISH,
                    # Serve scheduler defaults (mirror ServeConfig).
                    "max_batch": 64,
                    "max_wait_us": 2000,
                }
    return _DEFAULTS


def default_precision(dtype_name: str) -> str:
    """What ``precision="auto"`` (the solvers' default) resolves to.

    On a TPU, float64 work runs the mixed route (f32 tree, then float64
    Sturm certification and polish): XLA emulates float64 there, and a
    native float64 tree misses the 64 eps_f64 bar on clustered spectra
    (README "Backend selection").  Everywhere else, and for every other
    dtype, the tree runs natively.
    """
    if dtype_name == "float64" and pinned_backend() == "tpu":
        return "mixed"
    return "native"


def pinned_leaf(dtype_name: str, precision: str) -> int | None:
    """The leaf size accuracy fixes, or None when the tuner may pick it.

    A native float64 tree on a TPU starts from 1x1 leaves: JAX lowers a
    small ``eigh`` there to a Jacobi call with a fixed 1e-6 tolerance, so
    float64 leaf eigenvalues come back ~7e-8 off.  Neither the tuning
    cache nor the tuner may move it.
    """
    if (dtype_name == "float64" and precision == "native"
            and pinned_backend() == "tpu"):
        return 1
    return None


def _reset_backend_pin_for_tests() -> None:
    """Drop the backend pin + defaults memo (unit tests only)."""
    global _BACKEND, _DEFAULTS
    with _LOCK:
        _BACKEND = None
        _DEFAULTS = None


# --------------------------------------------------------------------------
# Persistent tuning cache
# --------------------------------------------------------------------------


def cache_dir() -> str:
    return os.path.expanduser(
        os.environ.get(TUNE_CACHE_ENV) or _DEFAULT_CACHE_DIR)


def _device_kind() -> str:
    import jax
    try:
        return str(jax.devices()[0].device_kind)
    except Exception:
        return "unknown"


def fingerprint() -> dict:
    """What must match for a cache file's timings to be trusted here."""
    import jax
    return {"backend": pinned_backend(), "device_kind": _device_kind(),
            "jax": jax.__version__}


def cache_path() -> str:
    """Cache file path, keyed by backend so CPU and accelerator runs on
    one box never fight over a file (the fingerprint inside still guards
    device kind and jax version)."""
    return os.path.join(cache_dir(), f"tune-{pinned_backend()}.json")


def coord_key(kind: str, *, n: int, bucket: int, dtype: str,
              precision: str = "native", shards: int = 1,
              k: int = 0) -> str:
    """Serialize a tuning coordinate.  ``n`` is the padded problem size
    for solve coordinates and the problem size for range coordinates
    (``k`` carries the range slice bucket); ``bucket`` == 0 means the
    route-level (batch-agnostic) entry."""
    return f"{kind}|n{int(n)}|b{int(bucket)}|{dtype}|{precision}" \
           f"|s{int(shards)}|k{int(k)}"


def _load_locked() -> dict:
    """Load (memoized) the cache file's entries; never raises.

    Any defect -- unreadable file, broken JSON, wrong schema version,
    foreign fingerprint -- degrades to an empty entry set with a single
    warning, so route resolution falls back to the built-in defaults and
    the request path is never poisoned by a bad artifact.
    """
    global _CACHE, _CACHE_META, _CACHE_ERROR
    if _CACHE is not None:
        return _CACHE
    path = cache_path()
    entries: dict = {}
    meta: dict = {}
    error: str | None = None
    if os.path.exists(path):
        try:
            with open(path) as f:
                payload = json.load(f)
            if not isinstance(payload, dict):
                raise ValueError("tuning cache is not a JSON object")
            if payload.get("version") != TUNE_CACHE_VERSION:
                error = (f"version {payload.get('version')!r} != "
                         f"{TUNE_CACHE_VERSION}")
            elif payload.get("fingerprint") != fingerprint():
                error = (f"fingerprint {payload.get('fingerprint')!r} does "
                         f"not match this machine {fingerprint()!r}")
            else:
                raw = payload.get("entries")
                if not isinstance(raw, dict):
                    raise ValueError("tuning cache has no entries dict")
                for key, ent in raw.items():
                    if isinstance(ent, dict) and isinstance(
                            ent.get("knobs"), dict):
                        entries[key] = ent
                meta = dict(payload.get("fingerprint") or {})
        except Exception as exc:  # corrupt/unreadable: degrade, never raise
            error = f"{type(exc).__name__}: {exc}"
            entries = {}
        if error is not None:
            entries = {}
            warnings.warn(
                f"ignoring tuning cache {path} ({error}); solves fall "
                f"back to built-in backend defaults -- re-run "
                f"`python -m benchmarks.run --tune` to regenerate",
                RuntimeWarning, stacklevel=3)
    _CACHE, _CACHE_META, _CACHE_ERROR = entries, meta, error
    return _CACHE


def reload_tuning_cache() -> None:
    """Drop the loaded-cache memo; the next consult re-reads the file
    (and re-resolves ``$REPRO_TUNE_CACHE``)."""
    global _CACHE, _CACHE_META, _CACHE_ERROR
    with _LOCK:
        _CACHE = None
        _CACHE_META = {}
        _CACHE_ERROR = None


def lookup(kind: str, *, n: int, bucket: int = 0, dtype: str,
           precision: str = "native", shards: int = 1, k: int = 0) -> dict:
    """Tuned knobs for a coordinate (falls back from the exact batch
    bucket to the route-level ``bucket=0`` entry).  Returns ``{}`` when
    the coordinate was never tuned; never raises."""
    with _LOCK:
        entries = _load_locked()
        for b in ((bucket, 0) if bucket else (0,)):
            ent = entries.get(coord_key(kind, n=n, bucket=b, dtype=dtype,
                                        precision=precision, shards=shards,
                                        k=k))
            if ent is not None:
                return dict(ent["knobs"])
    return {}


def serve_knobs(label: str) -> dict:
    """Tuned serve-scheduler knobs (max_batch / max_wait_us) for a route
    bucket label (``serve.metrics.bucket_label``).  ``{}`` if untuned."""
    with _LOCK:
        ent = _load_locked().get(f"serve|{label}")
        return dict(ent["knobs"]) if ent is not None else {}


def note_route(used_tuned: bool) -> None:
    """Provenance tick from the route resolvers (plan_cache_stats)."""
    with _LOCK:
        _CONSULTS["tuned_routes" if used_tuned else "default_routes"] += 1


def reset_consult_stats() -> None:
    with _LOCK:
        _CONSULTS["tuned_routes"] = 0
        _CONSULTS["default_routes"] = 0


def tuning_stats() -> dict:
    """Tuning-cache observability: file/fingerprint state, entry count,
    and the tuned/default route-provenance counters."""
    with _LOCK:
        entries = _load_locked()
        return {"path": cache_path(),
                "entries": len(entries),
                "fingerprint": fingerprint(),
                "error": _CACHE_ERROR,
                "backend": pinned_backend(),
                "tuned_routes": _CONSULTS["tuned_routes"],
                "default_routes": _CONSULTS["default_routes"]}


def _save_entries(new_entries: dict) -> str:
    """Merge winners into the cache file (atomic replace) and reload."""
    path = cache_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    merged: dict = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                payload = json.load(f)
            if (payload.get("version") == TUNE_CACHE_VERSION
                    and payload.get("fingerprint") == fingerprint()
                    and isinstance(payload.get("entries"), dict)):
                merged = payload["entries"]
        except Exception:
            merged = {}   # unreadable: the new artifact replaces it
    merged.update(new_entries)
    payload = {"version": TUNE_CACHE_VERSION, "fingerprint": fingerprint(),
               "created": time.strftime("%Y-%m-%dT%H:%M:%S"),
               "entries": merged}
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    reload_tuning_cache()
    return path


# --------------------------------------------------------------------------
# On-device knob search
# --------------------------------------------------------------------------

# A candidate must beat the incumbent by this fraction to be accepted:
# best-of interleaved timing plus a margin is what makes repeated
# `run.py --tune` runs on the same box converge to the same winners
# instead of flapping on scheduler noise.
ACCEPT_MARGIN = 0.03


def _race(fn_inc, fn_cand, iters: int):
    """Interleaved best-of race (benchmarks/common.time_pair discipline):
    incumbent and candidate alternate so background-load drift hits both
    equally; best-of because spikes only ever inflate a sample."""
    import jax
    jax.block_until_ready(fn_inc())
    jax.block_until_ready(fn_cand())
    ti, tc = [], []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn_inc())
        ti.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(fn_cand())
        tc.append(time.perf_counter() - t0)
    return float(np.min(ti)), float(np.min(tc))


def _problem(n: int, batch: int, dtype) -> tuple[np.ndarray, np.ndarray]:
    """Deterministic synthetic workload (fixed seed: reproducible runs)."""
    rng = np.random.default_rng(20260810)
    d = rng.standard_normal((batch, n)).astype(dtype)
    e = rng.standard_normal((batch, max(n - 1, 0))).astype(dtype)
    return d, e


def _solve_grids(padded_n: int, defaults: dict) -> list[tuple[str, list]]:
    """Tiered candidate grids for the tree knobs, warm-started: the
    incumbent starts at the backend default so a knob whose default is
    already optimal costs one race per listed candidate and keeps it."""
    def tier(vals, lo=0):
        out = []
        for v in vals:
            v = int(v)
            if v >= lo and v <= 2 * padded_n and v not in out:
                out.append(v)
        return out
    return [
        ("stream_threshold", tier([0, 64, 256, 512])),
        ("deflate_budget", tier([16, 32, 64, 128], lo=1)),
        ("resident_threshold", tier([0, 256, 512])),
        ("chunk", tier([32, 64, 128, 256], lo=8)),
    ]


_LEAF_GRID = (16, 32, 64)


def _descend(current: dict, grids, make_fn, iters: int,
             accept_extra=None) -> dict:
    """One pass of coordinate descent: for each knob, race every listed
    candidate against the incumbent config and keep winners that clear
    ``ACCEPT_MARGIN`` (and the optional accuracy gate)."""
    for knob, candidates in grids:
        for cand in candidates:
            if cand == current[knob]:
                continue
            trial = dict(current)
            trial[knob] = cand
            try:
                fn_cand = make_fn(trial)
            except Exception:
                continue   # infeasible combo (e.g. leaf > n constraints)
            if accept_extra is not None and not accept_extra(fn_cand):
                continue
            t_inc, t_cand = _race(make_fn(current), fn_cand, iters)
            if t_cand < t_inc * (1.0 - ACCEPT_MARGIN):
                current = trial
    return current


def _tune_solve(spec: dict, iters: int, log) -> dict:
    """Tune one full-spectrum coordinate; returns its cache entries."""
    import jax.numpy as jnp

    from repro.core import plan as _plan

    n = int(spec["n"])
    batch = int(spec.get("batch", 1))
    mesh = spec.get("mesh")
    dtype_name = jnp.dtype(spec.get("dtype") or _plan.default_dtype()).name
    precision = _plan.resolve_precision(spec.get("precision", "auto"),
                                        dtype_name)
    pinned = pinned_leaf(dtype_name, precision)
    defaults = backend_defaults()
    base = {"leaf": int(spec.get("leaf", pinned or defaults["leaf"])),
            "chunk": defaults["chunk"],
            "stream_threshold": defaults["stream_threshold"],
            "deflate_budget": defaults["deflate_budget"],
            "resident_threshold": defaults["resident_threshold"]}

    def plan_of(knobs):
        return _plan.make_plan(n, batch, leaf=knobs["leaf"],
                               chunk=knobs["chunk"],
                               stream_threshold=knobs["stream_threshold"],
                               deflate_budget=knobs["deflate_budget"],
                               resident_threshold=knobs["resident_threshold"],
                               dtype=spec.get("dtype"), mesh=mesh,
                               precision=precision)

    probe = plan_of(base)
    key0 = probe.key
    dtype = jnp.dtype(key0.dtype)
    d, e = _problem(n, batch, dtype)

    def make_fn(knobs):
        p = plan_of(knobs)
        return lambda: p.execute(d, e).eigenvalues

    leaves = [] if pinned else [lf for lf in _LEAF_GRID if lf <= max(n, 1)]
    current = _descend(base, [("leaf", leaves)], make_fn, iters)
    current = _descend(current, _solve_grids(key0.padded_n, defaults),
                       make_fn, iters)

    # Never ship a regression: the recorded winner must win (or tie) a
    # final interleaved race against the hand-tuned defaults.
    t_def, t_tuned = _race(make_fn(base), make_fn(current), iters)
    if t_tuned >= t_def:
        current, t_tuned = dict(base), t_def
    ratio = t_def / max(t_tuned, 1e-12)

    key_t = plan_of(current).key     # padded N under the winning leaf
    bucket = _plan.batch_bucket(batch)
    stamp = {"baseline_us": round(t_def * 1e6, 1),
             "tuned_us": round(t_tuned * 1e6, 1),
             "ratio": round(ratio, 3),
             "batch": batch,
             "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S")}
    entries: dict = {}

    def put(coord: str, knobs: dict) -> None:
        # Coordinates can collide (winning leaf == default leaf makes
        # key0 and key_t the same padded size): merge knob dicts instead
        # of letting the later entry clobber the earlier one.
        ent = entries.setdefault(coord, {"knobs": {}, **stamp})
        ent["knobs"].update(knobs)

    # Route-level entry under the DEFAULT-leaf padded size: this is the
    # coordinate `resolve_solve_route` consults for `leaf`.
    put(coord_key("solve", n=key0.padded_n, bucket=0, dtype=key0.dtype,
                  precision=precision, shards=key0.shards),
        {"leaf": current["leaf"]})
    # Tree knobs under the winning leaf's padded size (the coordinate
    # the remaining None knobs resolve against).
    put(coord_key("solve", n=key_t.padded_n, bucket=0, dtype=key_t.dtype,
                  precision=precision, shards=key_t.shards),
        {k: current[k] for k in ("stream_threshold", "deflate_budget",
                                 "resident_threshold")})
    # Per-batch-bucket chunk (consulted by plan_for_route once the
    # launch bucket is known).
    put(coord_key("solve", n=key_t.padded_n, bucket=bucket,
                  dtype=key_t.dtype, precision=precision,
                  shards=key_t.shards),
        {"chunk": current["chunk"]})
    log(f"solve n={n} B={batch}: {current} "
        f"({t_def * 1e6:.0f}us -> {t_tuned * 1e6:.0f}us, x{ratio:.2f})")
    return entries


def _tune_range(spec: dict, iters: int, log) -> dict:
    """Tune one sliced-solve coordinate (maxiter / polish budgets).

    Both knobs are accuracy-bearing (fewer halvings / polish steps can
    loosen roots), so a candidate is only raced after it passes the
    documented range accuracy contract -- within 8 eps ||T|| of the
    default-knob reference on the same synthetic workload.
    """
    import jax.numpy as jnp

    from repro.core import plan as _plan

    n = int(spec["n"])
    k = int(spec.get("k", min(32, n)))
    batch = int(spec.get("batch", 1))
    defaults = backend_defaults()
    base = {"maxiter": defaults["maxiter"], "polish": defaults["polish"]}

    def plan_of(knobs):
        return _plan.make_range_plan(n, k, batch, maxiter=knobs["maxiter"],
                                     polish=knobs["polish"],
                                     dtype=spec.get("dtype"))

    probe = plan_of(base)
    dtype = jnp.dtype(probe.key.dtype)
    d, e = _problem(n, batch, dtype)
    il = max(0, n - k)

    def make_fn(knobs):
        p = plan_of(knobs)
        return lambda: p.execute(d, e, il, k)

    ref = np.asarray(make_fn(base)())
    normT = np.abs(d).max() + 2 * (np.abs(e).max() if n > 1 else 0.0)
    tol = 8.0 * np.finfo(dtype).eps * max(normT, 1.0)

    def accurate(fn_cand) -> bool:
        return bool(np.max(np.abs(np.asarray(fn_cand()) - ref)) <= tol)

    grids = [("maxiter", [32, 48, 64, base["maxiter"]]),
             ("polish", [0, 1, base["polish"]])]
    current = _descend(base, grids, make_fn, iters, accept_extra=accurate)
    t_def, t_tuned = _race(make_fn(base), make_fn(current), iters)
    if t_tuned >= t_def:
        current, t_tuned = dict(base), t_def
    ratio = t_def / max(t_tuned, 1e-12)
    key = probe.key
    entry = {"knobs": dict(current),
             "baseline_us": round(t_def * 1e6, 1),
             "tuned_us": round(t_tuned * 1e6, 1),
             "ratio": round(ratio, 3), "batch": batch,
             "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S")}
    log(f"range n={n} k={k}: {current} "
        f"({t_def * 1e6:.0f}us -> {t_tuned * 1e6:.0f}us, x{ratio:.2f})")
    return {coord_key("range", n=n, bucket=0, dtype=key.dtype,
                      k=key.k_bucket): entry}


def _tune_serve(spec: dict, iters: int, log) -> dict:
    """Tune the serve scheduler's flush triggers for one route bucket.

    Each candidate (max_batch, max_wait_us) spins a real
    EigensolverClient, prewarms the route's executable (compiles stay
    out of the timing), replays a fixed concurrent burst and scores
    wall-clock drain time -- the same saturating-arrival discipline
    ``bench_serve`` measures.
    """
    import concurrent.futures as cf

    from repro.core.request import SolveRequest, route_request
    from repro.serve.client import EigensolverClient
    from repro.serve.metrics import bucket_label

    n = int(spec["n"])
    requests = int(spec.get("requests", 48))
    threads = int(spec.get("threads", 4))
    defaults = backend_defaults()
    base = {"max_batch": defaults["max_batch"],
            "max_wait_us": defaults["max_wait_us"]}
    d, e = _problem(n, 1, np.float64)
    label = bucket_label(route_request(
        SolveRequest(d=d[0], e=e[0], kind="full")).route)

    def drain_time(knobs) -> float:
        with EigensolverClient(
                prewarm=[{"kind": "solve", "n": n, "batch": 1},
                         {"kind": "solve", "n": n,
                          "batch": min(int(knobs["max_batch"]), 64)}],
                max_batch=int(knobs["max_batch"]),
                max_wait_us=int(knobs["max_wait_us"])) as client:
            def burst():
                t0 = time.perf_counter()
                with cf.ThreadPoolExecutor(threads) as pool:
                    futs = [pool.submit(client.solve, d[0], e[0])
                            for _ in range(requests)]
                    for f in futs:
                        f.result()
                return time.perf_counter() - t0
            burst()                       # warm the worker loop
            return min(burst() for _ in range(max(2, iters // 2)))

    t_default = drain_time(base)
    current = dict(base)
    for knob, candidates in (("max_batch", [8, 16, 32, 64]),
                             ("max_wait_us", [200, 500, 1000, 2000])):
        best_t = drain_time(current)
        for cand in candidates:
            if cand == current[knob]:
                continue
            trial = dict(current)
            trial[knob] = cand
            t = drain_time(trial)
            if t < best_t * (1.0 - ACCEPT_MARGIN):
                current, best_t = trial, t
    t_tuned = drain_time(current)
    if t_tuned >= t_default:
        current, t_tuned = dict(base), t_default
    ratio = t_default / max(t_tuned, 1e-12)
    entry = {"knobs": dict(current),
             "baseline_us": round(t_default * 1e6, 1),
             "tuned_us": round(t_tuned * 1e6, 1),
             "ratio": round(ratio, 3), "requests": requests,
             "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S")}
    log(f"serve {label}: {current} "
        f"({t_default * 1e3:.1f}ms -> {t_tuned * 1e3:.1f}ms, x{ratio:.2f})")
    return {f"serve|{label}": entry}


def tune_workload(workload_spec, *, iters: int = 5, save: bool = True,
                  log=None) -> dict:
    """Sweep the knob space on-device for a workload and persist winners.

    ``workload_spec`` is an iterable of dict entries in the ``prewarm``
    format, plus a serve kind::

        {"kind": "solve", "n": 1024, "batch": 8}      # tree knobs+chunk
        {"kind": "range", "n": 4096, "k": 32}          # maxiter/polish
        {"kind": "serve", "n": 128, "requests": 48}    # max_batch/wait

    Each entry is tuned per its ``(kind, padded N, batch bucket, dtype,
    precision, shards)`` coordinate by coordinate descent over tiered
    candidate grids (interleaved best-of timing, warm-started from the
    backend defaults) and the winners are written to the on-disk tuning
    cache, where the route resolvers pick them up for every knob that
    arrives as ``None``.  Returns a report dict::

        {"entries": {coord: {"knobs", "baseline_us", "tuned_us",
                             "ratio", ...}},
         "path": <cache file or None>, "seconds": s}
    """
    t0 = time.perf_counter()
    log = log or (lambda msg: None)
    entries: dict = {}
    for spec in workload_spec:
        spec = dict(spec)
        kind = spec.pop("kind", "solve")
        if kind in ("solve", "batch", "full"):
            entries.update(_tune_solve(spec, iters, log))
        elif kind == "range":
            entries.update(_tune_range(spec, iters, log))
        elif kind == "serve":
            entries.update(_tune_serve(spec, iters, log))
        else:
            raise ValueError(
                f"unknown tune kind {kind!r}; use solve/batch/full, "
                f"range, or serve")
    path = _save_entries(entries) if (save and entries) else None
    return {"entries": entries, "path": path,
            "seconds": time.perf_counter() - t0}
