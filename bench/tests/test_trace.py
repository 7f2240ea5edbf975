"""The trace reduction: on hand-built traces with known answers, and on a
small trace recorded on a TPU v5e (``bench/testdata/record.py``)."""

import json
import os
from types import SimpleNamespace as NS

import pytest

from bench import harness, trace

TESTDATA = os.path.join(harness.BENCH, "testdata")
with open(os.path.join(harness.BENCH, "layers.json")) as _f:
    LAYERS = json.load(_f)
OPS, MODULES = LAYERS["ops_line"], LAYERS["modules_line"]


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def profile(device_lines, host_events, planes=1):
    host = NS(name="/host:CPU", lines=[NS(name="main", events=host_events)])
    devs = [NS(name=f"{LAYERS['device_planes']}{i}",
               lines=[NS(name=k, events=v) for k, v in device_lines.items()])
            for i in range(planes)]
    return NS(planes=[host] + devs)


def test_busy_union_idle_gaps_and_layers_on_a_hand_built_trace():
    host = [ev("bench.window", 0, 40), ev("bench.submit", 12, 7),
            ev("bench.wait", 25, 15), ev("bench.solve", 26, 2),
            ev("unrelated", 14, 3)]
    fusion = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
    ops = [ev(fusion, 0, 10), ev("%sort.3 = f32[8]{0} sort(f32[8]{0} %p)",
                                 5, 10),
           ev("%secular_solve_pallas_batch.2 = (f32[1,8]{1,0}) custom-call("
              "f32[1,8]{1,0} %p), custom_call_target=\"tpu_custom_call\"",
              20, 10),
           ev("%while.4 = (s32[]) while((s32[]) %t), body=%b", 0, 15),
           ev(fusion, -5, 6), ev(fusion.replace(".1", ".9"), 45, 5)]
    mods = [ev("jit__executor(7)", -5, 20), ev("jit__refine_executor(9)",
                                                20, 10)]
    out = trace.reduce(profile({OPS: ops, MODULES: mods}, host), LAYERS)
    ns = 1e-9
    assert out["window_s"] == pytest.approx(40 * ns)
    # [0, 15] and [20, 30] inside the window; the op at -5 counts from 0,
    # the one at 45 not at all.
    assert out["busy_s"] == pytest.approx(25 * ns)
    assert out["layers"]["tree"] == {"seconds": pytest.approx(15 * ns),
                                     "launches": 1}
    assert out["layers"]["polish"] == {"seconds": pytest.approx(10 * ns),
                                       "launches": 1}
    assert out["layers"]["sorts"]["seconds"] == pytest.approx(10 * ns)
    assert out["layers"]["kernels"]["seconds"] == pytest.approx(10 * ns)
    # Gap [15, 20] is inside bench.submit; [30, 40] inside bench.wait
    # (its middle, 35, is outside the shorter bench.solve).
    assert dict(map(tuple, out["idle_gaps"])) == {
        "bench.submit": pytest.approx(5 * ns),
        "bench.wait": pytest.approx(10 * ns)}
    # Ops summed by name without the .<number>; the while loop that holds
    # other ops is left out.
    assert dict(map(tuple, out["top_ops"])) == {
        "fusion": pytest.approx(11 * ns), "sort": pytest.approx(10 * ns),
        "secular_solve_pallas_batch": pytest.approx(10 * ns)}


def test_busy_is_averaged_over_device_planes():
    host = [ev("bench.window", 0, 100)]
    out = trace.reduce(profile({OPS: [ev("%fusion = f32[] fusion()", 0, 50)]},
                               host,
                               planes=4), LAYERS)
    assert out["busy_s"] == pytest.approx(50e-9)


def test_a_trace_without_its_window_is_refused():
    with pytest.raises(ValueError):
        trace.reduce(profile({OPS: []}, [ev("bench.solve", 0, 5)]), LAYERS)


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(TESTDATA, "tpu_small.xplane.pb.gz")
    with open(os.path.join(TESTDATA, "tpu_small.json")) as f:
        meta = json.load(f)
    return trace.reduce(trace.load(path), LAYERS), meta


def test_recorded_tpu_trace_busy_and_idle(recorded):
    out, meta = recorded
    assert meta["device_kind"] == "TPU v5 lite"
    assert 0 < out["busy_s"] < out["window_s"]
    # The host-only 50 ms under bench.wait is idle and named so; every
    # gap falls under one of the harness's own spans.
    gaps = dict(map(tuple, out["idle_gaps"]))
    assert gaps["bench.wait"] >= 0.045
    assert set(gaps) <= {"bench.solve", "bench.wait", "bench.window"}
    assert len(out["top_ops"]) == 10


def test_recorded_tpu_trace_layers(recorded):
    out, meta = recorded
    layers = out["layers"]
    # One tree executable per solve: the float32 solve and the mixed one.
    assert layers["tree"]["launches"] == meta["solves"]
    # The mixed solve certifies (and may polish) in float64.
    assert layers["polish"]["launches"] >= 1
    # The Pallas kernels and the sorts run inside the tree.
    for part in ("kernels", "sorts"):
        assert 0 < layers[part]["seconds"] < layers["tree"]["seconds"]
    assert (layers["tree"]["seconds"] + layers["polish"]["seconds"]
            < out["window_s"])
