"""The TPU path, checked without a TPU.

Compiles each Pallas kernel that ``backend="auto"`` dispatches to on a TPU
for a described (not attached) TPU v5e chip, in float32 at the widths the
default solve path uses, so a kernel Mosaic refuses fails here instead of
on the chip.  Nothing runs: these say what lowers, never how fast.

The topology is described inside a module-scoped fixture, never while a
module is imported (only one process at a time may load the TPU library),
and JAX's persistent compilation cache is off around these compiles (an
entry compiled for a described chip cannot be read back on this host).

Also unit-tests, with the device backend steered to "tpu" inside the test,
the dtype rule of ``kernels.ops.resolve_backend``, what the default
``precision="auto"`` resolves to, and the float64 leaf that neither the
tuning cache nor the tuner may move.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import plan, tune
from repro.core.secular import DEFAULT_NITER_F32
from repro.kernels import ops
from repro.kernels.fused_update import secular_postpass_pallas_batch
from repro.kernels.resident_merge import resident_merge_pallas_batch
from repro.kernels.secular_roots import secular_solve_pallas_batch
from repro.kernels.sturm_count import sturm_count_pallas_batch

CHUNK = 256          # the route's default chunk = root/pole block width


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


F32, I32 = jnp.float32, jnp.int32


def test_resident_merge_compiles_for_v5e(one_chip):
    B, K = 8, 512
    _compile(one_chip,
             lambda d, z, R, rho, kp: resident_merge_pallas_batch(
                 d, z, R, rho, kp, niter=DEFAULT_NITER_F32),
             ((B, K), F32), ((B, K), F32), ((B, 2, K), F32), ((B,), F32),
             ((B,), I32))


def test_secular_roots_compiles_for_v5e(one_chip):
    B, K = 1, 65536
    _compile(one_chip,
             lambda d, z2, rho, kp: secular_solve_pallas_batch(
                 d, z2, rho, kp, niter=DEFAULT_NITER_F32, root_block=CHUNK),
             ((B, K), F32), ((B, K), F32), ((B,), F32), ((B,), I32))


def test_fused_update_compiles_for_v5e(one_chip):
    B, K = 1, 65536
    _compile(one_chip,
             lambda R, d, z, o, tau, kp, rho: secular_postpass_pallas_batch(
                 R, d, z, o, tau, kp, rho, pole_block=CHUNK),
             ((B, 2, K), F32), ((B, K), F32), ((B, K), F32), ((B, K), I32),
             ((B, K), F32), ((B,), I32), ((B,), F32))


def test_sturm_count_compiles_for_v5e(one_chip):
    B, n, S = 1, 65536, 256
    _compile(one_chip, sturm_count_pallas_batch,
             ((B, n), F32), ((B, n - 1), F32), ((B, S), F32), ((B, 1), F32))


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(ops, "_device_backend", lambda: "tpu")


@pytest.fixture
def routes_on_tpu(monkeypatch):
    """Route resolution as on a TPU: the one backend pin reads "tpu".
    The defaults table is memoized from the real backend first, so the
    steering never leaks a TPU table into later tests."""
    tune.backend_defaults()
    monkeypatch.setattr(tune, "pinned_backend", lambda: "tpu")
    plan.clear_plan_cache()
    yield
    plan.clear_plan_cache()


@pytest.fixture
def backend():
    yield ops.set_backend
    ops.set_backend("auto")


@pytest.mark.parametrize("dtype,want", [(jnp.float32, "pallas"),
                                        (jnp.float64, "xla")])
def test_auto_routes_by_dtype_on_tpu(on_tpu, dtype, want):
    assert ops.resolve_backend(dtype) == want


def test_explicit_pallas_with_f64_on_tpu_raises(on_tpu, backend):
    backend("pallas")
    assert ops.resolve_backend(jnp.float32) == "pallas"
    with pytest.raises(ValueError, match="64-bit scalars"):
        ops.resolve_backend(jnp.float64)
    with pytest.raises(ValueError, match="64-bit scalars"):
        ops.sturm_count_batched(jnp.zeros((1, 4)), jnp.zeros((1, 3)),
                                jnp.zeros((1, 2)), jnp.ones((1, 1)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_off_tpu_auto_is_xla_and_pallas_interprets_any_dtype(backend,
                                                            dtype):
    assert ops._device_backend() != "tpu"
    assert ops.resolve_backend(dtype) == "xla"
    backend("pallas")
    assert ops.resolve_backend(dtype) == "pallas"
    assert ops._interpret()


@pytest.mark.parametrize("dtype,want", [(jnp.float64, "mixed"),
                                        (jnp.float32, "native")])
def test_auto_precision_routes_float64_to_mixed_on_tpu(routes_on_tpu,
                                                       dtype, want):
    route = plan.resolve_solve_route(4096, dtype=dtype)
    assert route.precision == want
    assert route.leaf == tune.backend_defaults()["leaf"]
    assert plan.resolve_solve_route(
        4096, dtype=dtype, precision="native").precision == "native"


def test_auto_precision_is_native_off_tpu():
    assert tune.pinned_backend() != "tpu"
    for dtype in (jnp.float32, jnp.float64):
        assert plan.resolve_solve_route(64, dtype=dtype).precision == "native"


def test_native_float64_leaf_stays_one_on_tpu_over_a_tuned_cache(
        routes_on_tpu, monkeypatch):
    monkeypatch.setattr(tune, "lookup", lambda *a, **k: {"leaf": 32})
    assert plan.resolve_leaf(None, 4096, jnp.float64, "native") == 1
    route = plan.resolve_solve_route(4096, dtype=jnp.float64,
                                     precision="native")
    assert route.leaf == 1
    # The mixed route's f32 tree and plain f32 solves keep the tuned leaf.
    assert plan.resolve_leaf(None, 4096, jnp.float64, "mixed") == 32
    assert plan.resolve_leaf(None, 4096, jnp.float32, "native") == 32


def test_tuner_never_moves_the_native_float64_leaf_on_tpu(routes_on_tpu,
                                                         monkeypatch):
    grids = []

    def descend(current, knob_grids, make_fn, iters, accept_extra=None):
        grids.append(dict(knob_grids))
        return current

    monkeypatch.setattr(tune, "_descend", descend)
    monkeypatch.setattr(tune, "_race", lambda a, b, iters: (1.0, 1.0))
    report = tune.tune_workload([{"kind": "solve", "n": 256,
                                  "dtype": "float64",
                                  "precision": "native"}], save=False)
    assert grids[0]["leaf"] == []
    leaves = [ent["knobs"]["leaf"] for ent in report["entries"].values()
              if "leaf" in ent["knobs"]]
    assert leaves == [1]
    assert all("|float64|native|" in coord for coord in report["entries"])


def test_sturm_pivot_floor_survives_tpu_float64(routes_on_tpu):
    """TPU float64 flushes below 2**-126, finfo(float64).tiny included."""
    from repro.core import bisect
    floor = bisect._pivot_floor(jnp.zeros((1, 3), jnp.float64), jnp.float64)
    assert float(floor[0, 0]) == float(jnp.finfo(jnp.float32).tiny)
    assert bisect._safmin(jnp.float32) == float(jnp.finfo(jnp.float32).tiny)


def test_sturm_pivot_floor_off_tpu_is_the_dtype_tiny():
    from repro.core import bisect
    assert bisect._safmin(jnp.float64) == float(jnp.finfo(jnp.float64).tiny)
