"""The program-span reduction (``bench/spans.py``) and the readers of the
span metrics, on hand-built traces with known answers and on the small
trace recorded on a TPU v5e."""

import os
import random
from types import SimpleNamespace as NS

import pytest

from bench import harness, spans, trace
from bench.tests.test_trace import LAYERS, MODULES, OPS, TESTDATA, ev, profile

NS_ = 1e-9
FUSION = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"


def served_trace():
    """One served flush: device busy [0, 10], [30, 40], [70, 80] of the
    window [0, 100]; idle [10, 30] under the stage, [40, 70] under the
    polish sweep (inside the launch), [80, 100] under the demux; the
    benchmark's bench.wait spans all of it."""
    host = [ev("bench.window", 0, 100), ev("bench.wait", 5, 95),
            ev("repro.serve.launch", 8, 62, flush=0),
            ev("repro.serve.stage", 9, 22, flush=0, problems=64,
               queue_wait_us=1500.0),
            ev("repro.polish.compact", 35, 4, launch=0, live=10, width=64),
            ev("repro.polish.sweep", 39, 31, launch=0),
            ev("repro.polish.compact", 200, 4, launch=1, live=3, width=8),
            ev("repro.serve.demux", 78, 30, flush=0, requests=4,
               kind="batch")]
    ops = [ev(FUSION, 0, 10), ev(FUSION, 30, 10), ev(FUSION, 70, 10)]
    mods = [ev("jit__refine_executor(3)", 40, 28)]
    return profile({OPS: ops, MODULES: mods}, host)


def test_host_spans_totals_and_stats():
    out = spans.reduce(served_trace(), LAYERS)
    hs = out["host_spans"]
    assert set(hs) == {"repro.serve.launch", "repro.serve.stage",
                       "repro.polish.compact", "repro.polish.sweep",
                       "repro.serve.demux"}
    assert hs["repro.serve.stage"] == {
        "seconds": pytest.approx(22 * NS_), "count": 1,
        "idle_s": pytest.approx(20 * NS_),
        "self_idle_s": pytest.approx(20 * NS_),
        "stats": {"flush": 0, "problems": 64, "queue_wait_us": 1500.0}}
    # The second compact starts after the window: counted neither in
    # seconds nor in count nor in stats.
    assert hs["repro.polish.compact"]["count"] == 1
    assert hs["repro.polish.compact"]["stats"] == {"launch": 0, "live": 10,
                                                   "width": 64}
    assert hs["repro.polish.compact"]["seconds"] == pytest.approx(4 * NS_)
    assert hs["repro.polish.sweep"]["idle_s"] == pytest.approx(30 * NS_)
    # The demux runs past the window's end: clipped; its string stat is
    # not summed.
    assert hs["repro.serve.demux"]["seconds"] == pytest.approx(22 * NS_)
    assert hs["repro.serve.demux"]["stats"] == {"flush": 0, "requests": 4}
    assert hs["repro.serve.demux"]["idle_s"] == pytest.approx(20 * NS_)
    # The launch holds idle only through the spans nested in it.
    assert hs["repro.serve.launch"]["idle_s"] == 0.0
    assert dict(map(tuple, out["idle_gaps"])) == {
        "repro.polish.sweep": pytest.approx(30 * NS_),
        "repro.serve.stage": pytest.approx(20 * NS_),
        "repro.serve.demux": pytest.approx(20 * NS_)}


def test_a_gap_under_a_program_and_a_bench_span_is_the_program_span():
    host = [ev("bench.window", 0, 40), ev("bench.solve", 10, 20),
            ev("repro.request", 0, 40, kind="full")]
    out = spans.reduce(profile({OPS: [ev(FUSION, 0, 10)]}, host), LAYERS)
    # The bench span is shorter, but a program span names the gap.
    assert dict(map(tuple, out["idle_gaps"])) == {
        "repro.request": pytest.approx(30 * NS_)}
    assert out["host_spans"]["repro.request"]["idle_s"] == pytest.approx(
        30 * NS_)


def brute(gaps, program, bench, window):
    """The middle-of-gap rule, gaps x spans."""
    idle = {}
    for s, e in gaps:
        mid = (s + e) / 2
        name = window
        for pool in (bench, program):
            covering = [(ee - ss, n) for n, ss, ee in pool
                        if ss <= mid < ee]
            if covering:
                name = min(covering)[1]
        idle[name] = idle.get(name, 0.0) + (e - s) * 1e-9
    return idle


@pytest.mark.parametrize("seed", range(8))
def test_the_sweep_equals_the_brute_force_rule(seed):
    rng = random.Random(seed)
    edges = sorted(rng.sample(range(1, 10_000), 2 * rng.randint(1, 60)))
    gaps = list(zip(edges[::2], edges[1::2]))

    def pool(prefix, k):
        out = []
        for _ in range(k):
            s = rng.randint(-100, 10_000)
            out.append((f"{prefix}{rng.randint(0, 5)}", s,
                        s + rng.choice([1, 50, 500, 5000])))
        return out

    program, bench = pool("repro.", rng.randint(0, 80)), pool("bench.", 20)
    got = spans.name_gaps(gaps, program, bench, "bench.window")
    want = brute(gaps, program, bench, "bench.window")
    assert got.keys() == want.keys()
    for k in want:
        assert got[k] == pytest.approx(want[k], abs=0)


def test_self_idle_splits_a_gap_at_span_edges():
    host = [ev("bench.window", 0, 100), ev("repro.serve.launch", 0, 100),
            ev("repro.polish.compact", 0, 30), ev("repro.polish.sweep", 30,
                                                   30)]
    out = spans.reduce(profile({OPS: [ev(FUSION, 0, 10),
                                      ev(FUSION, 50, 50)]}, host), LAYERS)
    hs = out["host_spans"]
    # The gap [10, 50] is named, by its middle, by the sweep; it is held
    # 20 by the compaction and 20 by the sweep.
    assert hs["repro.polish.sweep"]["idle_s"] == pytest.approx(40 * NS_)
    assert hs["repro.polish.compact"]["idle_s"] == 0.0
    assert hs["repro.polish.compact"]["self_idle_s"] == pytest.approx(
        20 * NS_)
    assert hs["repro.polish.sweep"]["self_idle_s"] == pytest.approx(20 * NS_)
    assert hs["repro.serve.launch"]["self_idle_s"] == 0.0


@pytest.mark.parametrize("seed", range(8))
def test_self_idle_equals_the_brute_force_split(seed):
    rng = random.Random(100 + seed)
    edges = sorted(rng.sample(range(1, 2000), 2 * rng.randint(1, 30)))
    gaps = list(zip(edges[::2], edges[1::2]))
    program = []
    for _ in range(rng.randint(0, 40)):
        s = rng.randint(-50, 2000)
        program.append((f"repro.{rng.randint(0, 4)}", s,
                        s + rng.choice([1, 20, 200, 1500])))
    want = {}
    for gs, ge in gaps:
        for t in range(gs, ge):
            covering = [(e - s, n) for n, s, e in program if s <= t < e]
            if covering:
                name = min(covering)[1]
                want[name] = want.get(name, 0) + 1
    got = spans.self_idle(gaps, program)
    assert {k: round(v * 1e9) for k, v in got.items() if v} == want


def test_without_program_spans_the_output_is_the_trace_reduction():
    host = [ev("bench.window", 0, 40), ev("bench.submit", 12, 7),
            ev("bench.wait", 25, 15), ev("bench.solve", 26, 2),
            ev("unrelated", 14, 3)]
    ops = [ev(FUSION, 0, 10), ev(FUSION, 20, 10), ev(FUSION, 33, 2)]
    prof = profile({OPS: ops, MODULES: []}, host)
    out = spans.reduce(prof, LAYERS)
    assert out.pop("host_spans") == {}
    assert out == trace.reduce(prof, LAYERS)


@pytest.fixture(scope="module")
def recorded_profile():
    return trace.load(os.path.join(TESTDATA, "tpu_small.xplane.pb.gz"))


def test_the_recorded_tpu_trace_reduces_as_before(recorded_profile):
    """The recorded trace holds no program span: every key of the trace
    reduction reads as bench/trace.py gives it."""
    before = trace.reduce(recorded_profile, LAYERS)
    after = spans.reduce(recorded_profile, LAYERS)
    assert after.pop("host_spans") == {}
    assert after.keys() == before.keys()
    for key in before:
        assert after[key] == before[key], key


def test_module_runs_inside_spans():
    out = spans.reduce(served_trace(), LAYERS,
                       modules=("jit__refine_executor",))
    runs = out["module_runs"]
    assert runs == [("jit__refine_executor", 40, 68)]
    sweep = [("repro.polish.sweep", 39, 70, {})]
    assert spans.margins(runs, sweep) == [(1, 2)]
    # A run that starts before its span: a negative margin.
    assert spans.margins(runs, [("repro.polish.sweep", 41, 70)]) == [(-1, 2)]
    # Against the span that starts nearest.
    assert spans.margins(runs, [("repro.polish.sweep", 0, 30),
                                ("repro.polish.sweep", 44, 70)]) == [(-4, 2)]
    assert spans.margins(runs, []) == [None]


def test_launch_offsets_measure_the_device_clock_lead():
    host = [ev("bench.window", 0, 100), ev("repro.polish.sweep", 10, 30),
            ev("repro.polish.sweep", 50, 30),
            ev("TpuLoadedExecutable::ExecuteLaunch", 12, 1),
            ev("TpuLoadedExecutable::ExecuteLaunch", 53, 1)]
    prof = profile({OPS: [], MODULES: []}, host)
    sweeps = spans.host_events(prof, ("repro.polish.sweep",))
    # The first run starts 2 ns after its launch; the second 5 ns before
    # its own, which only a device clock running ahead can show.
    runs = [("jit__refine_executor", 14, 20), ("jit__refine_executor", 48,
                                               52)]
    assert spans.launch_offsets(prof, runs, sweeps) == [2, -5]
    assert spans.margins(runs, sweeps) == [(4, 20), (-2, 28)]


def run_of(summary, problems=1, flushes=None):
    counters = {} if flushes is None else {"flushes": flushes}
    return NS(trace=summary, window=NS(problems=problems, counters=counters))


def read(name, run):
    cell = harness.load_cell("slq.burst256")
    return harness.reader(cell, name)(run)


def test_span_metric_readers():
    summary = spans.reduce(served_trace(), LAYERS)
    run = run_of(summary, problems=2, flushes=1)
    ms = 1e-6
    assert read("polish_idle_ms.large", run) == pytest.approx(30 * ms / 2)
    assert read("polish_lane_use.large", run) == pytest.approx(100 * 10 / 64)
    assert read("queue_wait_ms.slq", run) == pytest.approx(1.5)
    assert read("stage_idle_ms_per_flush.slq", run) == pytest.approx(20 * ms)
    assert read("polish_idle_ms_per_flush.slq", run) == pytest.approx(
        30 * ms)
    assert read("demux_idle_ms_per_flush.slq", run) == pytest.approx(20 * ms)
    # Idle 70 ns, all of it named by program spans.
    for cell in ("large", "slq"):
        assert read(f"unnamed_idle_share.{cell}", run) == pytest.approx(0)


def test_unnamed_idle_share_counts_idle_outside_program_spans():
    host = [ev("bench.window", 0, 100), ev("bench.solve", 0, 100),
            ev("repro.request", 10, 50, kind="full")]
    ops = [ev(FUSION, 0, 20), ev(FUSION, 60, 10)]
    summary = spans.reduce(profile({OPS: ops}, host), LAYERS)
    # Idle [20, 60] (middle 40) under the request, [70, 100] under
    # bench.solve alone: 30 of 70.
    assert read("unnamed_idle_share.large", run_of(summary)) == (
        pytest.approx(300 / 7))


@pytest.mark.parametrize("name", [m for ms in spans.METRICS.values()
                                  for m in ms])
def test_span_metrics_read_nothing_without_program_spans(name,
                                                         recorded_profile):
    """A program without spans (or a reduction without ``host_spans``)
    gives every span metric nothing to read."""
    base = trace.reduce(recorded_profile, LAYERS)
    assert read(name, run_of(base, problems=2, flushes=4)) is None
    summary = spans.reduce(recorded_profile, LAYERS)
    assert read(name, run_of(summary, problems=2, flushes=4)) is None
    assert read(name, run_of(None, problems=2, flushes=4)) is None
