"""Substrate layers: data, optimizers, spectral, checkpoint, runtime, dist."""

import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def test_synthetic_determinism_and_shards():
    from repro.data import SyntheticTokens
    src = SyntheticTokens(vocab_size=1000, seq_len=64, seed=7)
    a = src.batch(step=3, shard=0, batch_size=4)
    b = src.batch(step=3, shard=0, batch_size=4)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = src.batch(step=3, shard=1, batch_size=4)
    assert not np.array_equal(a["tokens"], c["tokens"])
    d = src.batch(step=4, shard=0, batch_size=4)
    assert not np.array_equal(a["tokens"], d["tokens"])
    assert a["tokens"].max() < 1000 and a["tokens"].min() >= 0


def test_pipeline_prefetch_and_resume():
    from repro.data import DataPipeline, SyntheticTokens
    src = SyntheticTokens(vocab_size=100, seq_len=16, seed=1)
    p1 = DataPipeline(src, global_batch=4, prefetch=2)
    it1 = iter(p1)
    first = [next(it1)["tokens"] for _ in range(5)]
    p1.stop()
    # resume at step 3 reproduces the tail exactly
    p2 = DataPipeline(src, global_batch=4, prefetch=2, start_step=3)
    it2 = iter(p2)
    resumed = [next(it2)["tokens"] for _ in range(2)]
    p2.stop()
    np.testing.assert_array_equal(first[3], resumed[0])
    np.testing.assert_array_equal(first[4], resumed[1])


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["sgd", "adamw", "adafactor"])
def test_optimizers_minimize_quadratic(name):
    from repro.optim.optimizers import get_optimizer
    opt = get_optimizer(name, lr=0.1)
    target = jnp.asarray([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3), "m": jnp.zeros((4, 5))}

    def loss(p):
        return jnp.sum((p["w"] - target) ** 2) + jnp.sum(p["m"] ** 2)

    state = opt.init(params)
    for _ in range(150):
        grads = jax.grad(loss)(params)
        params, state = opt.apply(params, grads, state)
    assert float(loss(params)) < 0.05, (name, float(loss(params)))


def test_adafactor_state_is_factored():
    from repro.optim.optimizers import adafactor
    opt = adafactor()
    params = {"w": jnp.zeros((64, 128))}
    state = opt.init(params)
    v = state["v"]["w"]
    assert v["vr"].shape == (64,) and v["vc"].shape == (128,)
    total = sum(x.size for x in jax.tree.leaves(state))
    assert total < 64 * 128 / 10       # O(m+n), not O(mn)


def test_lr_scale_hook():
    from repro.optim.optimizers import sgd
    opt = sgd(lr=1.0, momentum=0.0)
    params = {"w": jnp.ones(2)}
    grads = {"w": jnp.ones(2)}
    state = opt.init(params)
    p1, _ = opt.apply(params, grads, state, lr_scale=1.0)
    p2, _ = opt.apply(params, grads, state, lr_scale=0.5)
    assert float(p1["w"][0]) == pytest.approx(0.0)
    assert float(p2["w"][0]) == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# spectral (Lanczos + SLQ on BR)
# ---------------------------------------------------------------------------

def _sym_matvec(A):
    def mv(v):
        return {"x": A @ v["x"]}
    return mv


def test_lanczos_ritz_values_converge():
    from repro.spectral import lanczos_tridiag
    from repro.core import eigvalsh_tridiagonal
    rng = np.random.default_rng(0)
    Q, _ = np.linalg.qr(rng.standard_normal((60, 60)))
    lam_true = np.linspace(1.0, 10.0, 60)
    A = jnp.asarray(Q @ np.diag(lam_true) @ Q.T)
    probe = {"x": jnp.asarray(rng.standard_normal(60))}
    # m < dim avoids Krylov breakdown (beta -> 0 at full dimension);
    # extremal Ritz values converge long before that.
    alpha, beta = lanczos_tridiag(_sym_matvec(A), probe, 45)
    ritz = np.asarray(eigvalsh_tridiagonal(np.asarray(alpha),
                                           np.asarray(beta), leaf=8))
    assert abs(ritz[-1] - 10.0) < 1e-6
    assert abs(ritz[0] - 1.0) < 1e-6


def test_slq_trace_estimate():
    from repro.spectral import slq_spectrum
    rng = np.random.default_rng(1)
    M = rng.standard_normal((40, 40))
    A = jnp.asarray(M @ M.T / 40 + np.eye(40))
    true_trace = float(jnp.trace(A))
    est = slq_spectrum(_sym_matvec(A), {"x": jnp.zeros(40)},
                       jax.random.PRNGKey(0), num_probes=12, num_steps=20)
    assert abs(est.trace_est - true_trace) / true_trace < 0.25
    true_lmax = float(np.linalg.eigvalsh(np.asarray(A))[-1])
    assert abs(est.lam_max - true_lmax) / true_lmax < 0.05


@pytest.mark.partial
def test_spectral_edges_matches_dense_extremes():
    """The sliced extremal-edge path (no full spectrum, no boundary rows)
    agrees with the dense operator's edge eigenvalues."""
    from repro.spectral import sharpness, spectral_edges
    rng = np.random.default_rng(2)
    Q, _ = np.linalg.qr(rng.standard_normal((50, 50)))
    lam_true = np.linspace(0.5, 8.0, 50)
    A = jnp.asarray(Q @ np.diag(lam_true) @ Q.T)
    lo, hi = spectral_edges(_sym_matvec(A), {"x": jnp.zeros(50)},
                            jax.random.PRNGKey(3), num_probes=2,
                            num_steps=30, k=2)
    assert lo.shape == (2, 2) and hi.shape == (2, 2)
    assert abs(float(np.max(hi)) - 8.0) / 8.0 < 0.02
    assert abs(float(np.min(lo)) - 0.5) / 0.5 < 0.2
    s = sharpness(_sym_matvec(A), {"x": jnp.zeros(50)},
                  jax.random.PRNGKey(4), num_steps=30)
    assert abs(s - 8.0) / 8.0 < 0.02


@pytest.mark.partial
def test_governor_probe_uses_sliced_path():
    from repro.optim.spectral_adapt import SpectralGovernor
    rng = np.random.default_rng(5)
    M = rng.standard_normal((30, 30))
    A = jnp.asarray(M @ M.T / 30 + np.eye(30))
    gov = SpectralGovernor(target_sharpness=1.0, ema=0.0)
    scale = gov.probe(_sym_matvec(A), {"x": jnp.zeros(30)},
                      jax.random.PRNGKey(6), num_steps=20)
    true_lmax = float(np.linalg.eigvalsh(np.asarray(A))[-1])
    assert gov.lam_max == pytest.approx(true_lmax, rel=0.05)
    assert scale == pytest.approx(max(gov.min_scale,
                                      min(1.0, 1.0 / gov.lam_max)))


def test_hvp_on_quadratic():
    from repro.spectral import make_hvp
    A = jnp.asarray([[2.0, 1.0], [1.0, 3.0]])

    def loss(p):
        return 0.5 * p["x"] @ A @ p["x"]

    hvp = make_hvp(loss, {"x": jnp.asarray([1.0, 1.0])})
    out = hvp({"x": jnp.asarray([1.0, 0.0])})
    np.testing.assert_allclose(np.asarray(out["x"]), np.asarray(A[:, 0]),
                               atol=1e-12)


def test_spectral_governor():
    from repro.optim.spectral_adapt import SpectralGovernor
    gov = SpectralGovernor(target_sharpness=10.0, ema=0.0)
    assert gov.update(5.0) == 1.0          # flat: full LR
    assert gov.update(100.0) == pytest.approx(0.1)
    assert gov.update(1e6) == pytest.approx(gov.min_scale)


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_and_resume(tmp_path):
    from repro.checkpoint import CheckpointManager, restore_tree, save_tree
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
            "b": {"c": jnp.ones(4)}}
    d = str(tmp_path / "ckpt")
    save_tree(d, 10, tree, meta={"loss": 1.5})
    save_tree(d, 20, tree, meta={"loss": 1.0})
    got, meta = restore_tree(d, 20, tree)
    assert meta["loss"] == 1.0
    np.testing.assert_array_equal(np.asarray(got["a"]), np.asarray(tree["a"]))

    mgr = CheckpointManager(d, period=5)
    restored, meta, step = mgr.resume(tree)
    assert step == 20 and meta["loss"] == 1.0


def test_checkpoint_keep_n(tmp_path):
    from repro.checkpoint.manager import all_steps, save_tree
    d = str(tmp_path / "ckpt")
    tree = {"a": jnp.zeros(2)}
    for s in range(6):
        save_tree(d, s, tree, keep=3)
    assert all_steps(d) == [3, 4, 5]


def test_checkpoint_corruption_detected(tmp_path):
    from repro.checkpoint import restore_tree, save_tree
    d = str(tmp_path / "ckpt")
    tree = {"a": jnp.arange(8, dtype=jnp.float32)}
    path = save_tree(d, 1, tree)
    fn = [f for f in os.listdir(path) if f.endswith(".npy")][0]
    arr = np.load(os.path.join(path, fn))
    arr[0] += 1
    np.save(os.path.join(path, fn), arr)
    with pytest.raises(IOError, match="checksum"):
        restore_tree(d, 1, tree)


def test_torn_checkpoint_skipped(tmp_path):
    from repro.checkpoint.manager import latest_step, save_tree
    import json
    d = str(tmp_path / "ckpt")
    tree = {"a": jnp.zeros(2)}
    save_tree(d, 1, tree)
    save_tree(d, 2, tree)
    # tear the newest manifest
    with open(os.path.join(d, "step_00000002", "manifest.json"), "w") as f:
        f.write("{ torn")
    assert latest_step(d) == 1


def test_kill_between_steps_resume_identical_params(tmp_path):
    """Kill-between-steps drill: a run killed after an arbitrary step and
    relaunched from its CheckpointManager must land on EXACTLY the params
    of an uninterrupted run -- same optimizer trajectory, bit for bit.
    Rests on two determinism pins: restore_tree is checksummed, and
    SyntheticTokens is addressed by (step, shard) so the relaunch replays
    the identical batch stream."""
    from repro.checkpoint import CheckpointManager
    from repro.data import SyntheticTokens
    d = str(tmp_path / "ckpt")
    src = SyntheticTokens(vocab_size=64, seq_len=8, seed=3)

    @jax.jit
    def step_fn(w, tokens):
        g = jax.grad(lambda p: jnp.mean(
            (p["w"][tokens] - 0.1) ** 2))(w)
        return {"w": w["w"] - 0.5 * g["w"]}

    def run(total_steps, kill_at=None, ckpt_dir=None):
        params = {"w": jnp.linspace(0.0, 1.0, 64)}
        start = 0
        if ckpt_dir is not None:
            mgr = CheckpointManager(ckpt_dir, period=2)
            restored, _, start = mgr.resume(params)
            if restored is not None:
                params = restored
        for s in range(start, total_steps):
            tokens = jnp.asarray(src.batch(s, 0, 4)["tokens"])
            params = step_fn(params, tokens)
            if ckpt_dir is not None:
                mgr.maybe_save(s + 1, params)
            if kill_at is not None and s + 1 == kill_at:
                return params      # "SIGKILL": manager just abandoned
        return params

    golden = run(11)
    run(11, kill_at=7, ckpt_dir=d)       # dies mid-run (last ckpt: step 6)
    resumed = run(11, ckpt_dir=d)        # relaunch resumes, finishes
    np.testing.assert_array_equal(np.asarray(resumed["w"]),
                                  np.asarray(golden["w"]))


def test_pipeline_restart_determinism_with_extras(tmp_path):
    """DataPipeline restarted from scratch (process relaunch) yields the
    identical stream -- tokens AND extra_fn side inputs -- so the resumed
    trajectory above is reproducible end to end."""
    from repro.data import DataPipeline, SyntheticTokens

    def extra_fn(step, shard, bsz):
        r = np.random.default_rng(np.random.SeedSequence([7, step, shard]))
        return {"frames": r.standard_normal((bsz, 3)).astype(np.float32)}

    def stream(start_step, count):
        p = DataPipeline(SyntheticTokens(32, 8, seed=5), global_batch=4,
                         prefetch=2, extra_fn=extra_fn,
                         start_step=start_step)
        it = iter(p)
        got = [next(it) for _ in range(count)]
        p.stop()
        return got

    a = stream(0, 6)
    b = stream(0, 6)                     # same process restart
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x["tokens"], y["tokens"])
        np.testing.assert_array_equal(x["frames"], y["frames"])
    tail = stream(4, 2)                  # resume mid-stream
    for x, y in zip(a[4:], tail):
        np.testing.assert_array_equal(x["tokens"], y["tokens"])
        np.testing.assert_array_equal(x["frames"], y["frames"])


# ---------------------------------------------------------------------------
# runtime
# ---------------------------------------------------------------------------

def test_watchdog_detects_hang(tmp_path):
    from repro.runtime import Watchdog
    events = []
    wd = Watchdog(str(tmp_path / "hb.json"), timeout_s=0.2,
                  check_every_s=0.05, on_hang=lambda s: events.append(s))
    with wd:
        wd.beat(0)
        time.sleep(0.6)
    assert wd.hang_count >= 1 and events


def test_straggler_monitor_flags_outlier():
    from repro.runtime import StragglerMonitor
    mon = StragglerMonitor(window=32, threshold=2.0)
    for s in range(20):
        mon.record(s, 1.0 + 0.01 * (s % 3))
    mon.record(20, 5.0)
    assert mon.events and mon.events[-1]["step"] == 20
    rep = mon.report()
    assert rep["median_s"] == pytest.approx(1.0, abs=0.1)


def test_retry_transient():
    from repro.runtime import retry_transient
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    assert retry_transient(flaky, retries=5, backoff_s=0.01)() == "ok"
    assert len(calls) == 3

    def fatal():
        raise ValueError("no")

    with pytest.raises(ValueError):
        retry_transient(fatal, retries=2, backoff_s=0.01)()


# ---------------------------------------------------------------------------
# dist: sharding rules + compression
# ---------------------------------------------------------------------------

def test_sharding_rules_divisibility_pruning():
    from repro.dist.sharding import logical_param_specs
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    # fake a mesh with extents 16/16 by building specs against shapes
    mesh16 = jax.make_mesh((1, 1), ("data", "model"))
    params = {
        "layers": {"attn": {
            "wq": jax.ShapeDtypeStruct((2, 64, 16, 8), jnp.float32),
            "wk": jax.ShapeDtypeStruct((2, 64, 3, 8), jnp.float32),
        }},
        "embed": jax.ShapeDtypeStruct((100, 64), jnp.float32),
    }
    specs = jax.tree.map(
        lambda x: x, logical_param_specs(params, mesh16),
        is_leaf=lambda x: isinstance(x, P))
    # with extents 1 everything divides; structure must match
    assert specs["layers"]["attn"]["wq"] == P(None, "data", "model", None)
    assert specs["embed"] == P("model", "data")


def test_sharding_prunes_nondivisible():
    from types import SimpleNamespace
    from repro.dist.sharding import _prune
    mesh = SimpleNamespace(shape={"model": 4})  # _prune reads .shape only
    assert _prune(("model",), (8,), mesh) == ("model",)
    assert _prune(("model",), (6,), mesh) == (None,)


def test_int8_compression_error_feedback():
    from repro.dist.compression import (CompressionState,
                                        compressed_cross_pod_mean,
                                        init_compression_state)
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1,), ("pod",))
    grads = {"w": jnp.asarray(np.random.default_rng(0)
                              .standard_normal(128).astype(np.float32))}
    state = init_compression_state(grads)

    def f(g, err):
        return compressed_cross_pod_mean(g, CompressionState(err), "pod")

    out, new_state = jax.shard_map(
        f, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
        check_vma=False)(grads, state.error)
    # single-pod mean == dequantized self; error feedback bounds the bias
    err1 = np.abs(np.asarray(out["w"]) - np.asarray(grads["w"]))
    scale = np.max(np.abs(np.asarray(grads["w"]))) / 127
    assert np.max(err1) <= scale * 1.01
    # residual carries exactly the quantization error
    total = np.asarray(out["w"]) + np.asarray(new_state.error["w"])
    np.testing.assert_allclose(total, np.asarray(grads["w"]), atol=1e-6)
