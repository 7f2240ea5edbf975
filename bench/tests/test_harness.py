"""The harness end to end at tiny sizes on the CPU: cells found by name,
generators, the window loop, the checks, and faults that must fail."""

import json
import os
import shutil
import time

import jax
import numpy as np
import pytest

from bench import generate, harness, reference
from bench.clock import CompileClock

ROOT = harness.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)
CELLS = [w["name"] for w in SPEC["workloads"]]
# Float32 traffic of the large configuration: its cells are held out of
# BENCHMARK.json while the TPU float32 path misses its bar (PERF.md, Open
# questions); the harness still drives it.
HELD = ["closed1.float32.uniform", "closed1.float32.glued"]
BIG_SEED = 2**31 + 987654321
TINY = {"n_float64": 256, "n_float32": 512, "m": 24, "operator_dim": 128}


@pytest.fixture(scope="module")
def clock():
    return CompileClock(jax)


def held(traffic):
    """A cell of the large configuration under ``traffic``, built from its
    files alone."""
    config = next(c for c in SPEC["configs"]
                  if c["name"] == "lapack_tridiag_large")
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "bench", "traffic", traffic + ".json")) as f:
        tr = json.load(f)
    e2e = [m for m in SPEC["end_to_end"]
           if m["name"] in ("solve_s", "setup_s")]
    return harness.Cell(name=traffic, config_name=config["name"],
                        traffic_name=traffic, chips=1, config=cfg,
                        traffic=tr, end_to_end=e2e, per_layer=[])


def tiny(name, root=ROOT):
    cell = held(name) if name in HELD else harness.load_cell(name, root)
    cell.config.update({k: v for k, v in TINY.items() if k in cell.config})
    if "burst" in cell.traffic:
        cell.traffic.update(burst=32, request=16)
    return cell


def run(cell, clock, seconds=0.3, seed=BIG_SEED):
    return harness.run_cell(cell, seed, seconds, False,
                            t0=time.perf_counter(), clock=clock, jax=jax,
                            device={"platform": "cpu"})


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_its_files_and_metrics(name):
    cell = harness.load_cell(name)
    assert harness.driver(cell).Driver
    assert cell.end_to_end and cell.per_layer
    names = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    for m in cell.end_to_end + cell.per_layer:
        assert callable(harness.reader(cell, m["name"]))
    # Every per-layer metric's end-to-end metric is reported in the cell.
    assert {m["moves"] for m in cell.per_layer} <= set(names)


@pytest.mark.parametrize("name", CELLS + HELD)
def test_cell_runs_and_is_correct_at_a_tiny_size(name, clock):
    out = run(tiny(name), clock)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    want = {m["name"] for m in tiny(name).end_to_end} - {"peak_hbm_mb"}
    assert want <= set(out["metrics"])   # the CPU reports no peak memory
    assert all(v["value"] > 0 for v in out["metrics"].values())


def test_a_new_cell_and_metric_are_found_by_name(tmp_path, clock):
    """A later PR adds a BENCHMARK.json entry, a traffic data file and a
    metric reader, and edits no file the benchmark already has."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("testdata", "__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({"name": "large.f64.new", "chips": 1,
                              "config": "lapack_tridiag_large",
                              "traffic": "closed1.float64.new",
                              "why": "test"})
    spec["per_layer"].append({"name": "answers_per_solve.new",
                              "unit": "answers", "better": "higher",
                              "source": "program_counter", "layer": "test",
                              "moves": "solve_s",
                              "workloads": ["large.f64.new"]})
    # A part of a quantity whose reader is shared: no file of its own.
    spec["per_layer"].append({"name": "idle_share.new", "unit": "%",
                              "better": "lower", "source": "device_trace",
                              "layer": "device", "moves": "solve_s",
                              "workloads": ["large.f64.new"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    traffic = json.loads((tmp_path / "bench" / "traffic" /
                          "closed1.float64.uniform.json").read_text())
    traffic.update(family="glued_wilkinson", pool=1)
    (tmp_path / "bench" / "traffic" / "closed1.float64.new.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench" / "metrics" / "answers_per_solve.new.py").write_text(
        "def read(run):\n    return len(run.window.answers) / "
        "run.window.problems\n")

    cell = harness.load_cell("large.f64.new", str(tmp_path))
    assert cell.traffic["family"] == "glued_wilkinson"
    assert [m["name"] for m in cell.per_layer] == ["answers_per_solve.new",
                                                   "idle_share.new"]
    shared = harness.load_module(
        str(tmp_path / "bench" / "metrics" / "idle_share.py"), "shared")
    assert (harness.reader(cell, "idle_share.new").__code__.co_code
            == shared.read.__code__.co_code)
    with pytest.raises(harness.CellError):
        harness.reader(cell, "no_such_quantity.new")
    cell.config["n_float64"] = 128
    out = harness.run_cell(cell, 5, 0.2, False, t0=time.perf_counter(),
                           clock=clock, jax=jax, device={})
    assert out["correct"]
    drv = harness.driver(cell).Driver(cell, 5)
    drv.setup(clock)
    win = drv.window(0.1)
    drv.close()
    run_ = harness.Run(cell, 0.0, win, None, None)
    assert harness.read_metrics(run_, cell.per_layer) == {
        "answers_per_solve.new": {"value": 1.0, "unit": "answers"}}
    with pytest.raises(harness.CellError):
        harness.load_cell("no.such.cell", str(tmp_path))


def test_generators_follow_the_seed():
    a = generate.family("uniform", 64, BIG_SEED)
    b = generate.family("uniform", 64, BIG_SEED)
    c = generate.family("uniform", 64, BIG_SEED + 1)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    d, e = generate.family("glued_wilkinson", 64, 3)
    assert np.all(e[20::21] < 1.2e-4) and np.all(np.delete(e, np.s_[20::21])
                                                  == 1.0)
    al, be = generate.slq_problems(BIG_SEED, 4, 24, 128, 5, (5.0, 100.0),
                                   0.05, 7)
    al2, _ = generate.slq_problems(BIG_SEED, 4, 24, 128, 5, (5.0, 100.0),
                                   0.05, 7)
    al3, _ = generate.slq_problems(BIG_SEED + 1, 4, 24, 128, 5,
                                   (5.0, 100.0), 0.05, 7)
    assert al.shape == (4, 24) and be.shape == (4, 23)
    assert np.array_equal(al, al2) and np.all(be > 0)
    # Every probe, and every seed, gives its own tridiagonal.
    assert not np.any(al[0] == al3[0]) and not np.any(al[0] == al[1])
    # The Ritz values reach the outliers: the top one converges.
    top = [reference.spectra([(x, y)], "stebz")[0][-1] for x, y in
           zip(al, be)]
    assert np.ptp(top) < 1e-6 * max(top)


def test_reference_pool_matches_one_process():
    d, e = generate.family("uniform", 3000, 1)
    pooled = reference.spectra([(d, e)], "stebz")[0]
    import scipy.linalg
    whole = scipy.linalg.eigvalsh_tridiagonal(d, e, lapack_driver="stebz")
    assert reference.error_units(pooled, whole, d, e, np.float64) < 4


def _altered(fault):
    """Wrap SolvePlan.execute, the launch every request of every cell
    goes through, so that its eigenvalues come out wrong."""
    from repro.core import plan as _plan
    original = _plan.SolvePlan.execute

    def execute(self, *args, **kwargs):
        res = original(self, *args, **kwargs)
        lam = res.eigenvalues
        if fault == "one_answer_altered":
            lam = lam.at[:, lam.shape[1] // 2].add(
                1e-3 * jax.numpy.abs(lam).max())
        else:   # the upper half of each spectrum left out, the lower
            # half given twice in its place: finite, in order, and wrong
            half = lam[:, : (lam.shape[1] + 1) // 2]
            lam = jax.numpy.sort(jax.numpy.concatenate(
                [half, half], axis=1)[:, : lam.shape[1]], axis=1)
        return res._replace(eigenvalues=lam)
    return execute


@pytest.mark.parametrize("fault", ["one_answer_altered", "half_left_out"])
@pytest.mark.parametrize("name", ["large.f64.uniform", "slq.burst256"])
def test_a_broken_timed_path_comes_out_not_correct(name, fault, clock,
                                                   monkeypatch):
    from repro.core import plan as _plan
    monkeypatch.setattr(_plan.SolvePlan, "execute", _altered(fault))
    out = run(tiny(name), clock)
    assert not out["correct"]
    assert out["checks"]["max_err_eps"]["value"] > 64
    assert out["failed"] == out["attempted"] > 0


def test_a_compile_inside_the_window_fails_the_run(clock, monkeypatch):
    cell = tiny("large.f64.uniform")
    mod = harness.driver(cell)
    window = mod.Driver.window

    def compiling_window(self, seconds):
        jax.jit(lambda x: x * 3.0 + 1.0)(np.arange(7.0)).block_until_ready()
        return window(self, seconds)
    monkeypatch.setattr(harness, "driver", lambda cell: type(
        "M", (), {"Driver": type("D", (mod.Driver,),
                                 {"window": compiling_window})}))
    out = run(cell, clock)
    assert out["checks"]["window_compiles"]["value"] >= 1
    assert not out["correct"]
