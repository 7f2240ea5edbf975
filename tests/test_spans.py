"""Program spans (``repro.core.instrument.span``) in the profiler trace.

A short ``jax.profiler.trace`` of a mixed-precision solve, of one served
flush and of a degradation-ladder escalation must record the span
vocabulary with its stats, nested as the layers nest; with the profiler
off the spans must leave every result bit for bit as it was.
"""

import glob
import os
from typing import NamedTuple

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import bisect as bis
from repro.core import eigvalsh_tridiagonal, instrument, make_family
from repro.core import plan as plan_mod
from repro.core import request as request_mod
from repro.core.request import SolveRequest, execute_request
from repro.core.tridiag import make_family_batch
from repro.runtime.faults import FaultSpec, configure_faults, reset_faults
from repro.serve import EigensolverClient
from repro.serve import client as client_mod
from repro.serve import engine as engine_mod

POLISH = ("repro.polish.certify", "repro.polish.compact",
          "repro.polish.sweep", "repro.polish.scatter")


class Span(NamedTuple):
    name: str
    thread: int      # index of the trace line: one per host thread
    start: float
    end: float
    stats: dict

    def holds(self, other: "Span") -> bool:
        return (self.thread == other.thread and self.start <= other.start
                and other.end <= self.end)


def traced(directory, fn):
    """(fn(), [Span] of every repro.* host event) under a profiler trace
    taken with the benchmark's options."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    with jax.profiler.trace(str(directory), profiler_options=opts):
        out = fn()
    (pb,) = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                      recursive=True)
    (host,) = [p for p in ProfileData.from_file(pb).planes
               if p.name.startswith("/host:")]
    spans = [Span(ev.name, i, ev.start_ns, ev.start_ns + ev.duration_ns,
                  dict(ev.stats))
             for i, line in enumerate(host.lines) for ev in line.events
             if ev.name.startswith("repro.")]
    return out, spans


def named(spans, name):
    return [s for s in spans if s.name == name]


def test_a_mixed_solve_records_request_plan_and_polish_spans(tmp_path):
    d, e = make_family("uniform", 200, seed=1)
    _, spans = traced(tmp_path, lambda: np.asarray(eigvalsh_tridiagonal(
        d, e, leaf=8, precision="mixed")))
    (req,) = named(spans, "repro.request")
    assert req.stats == {"kind": "full", "batch": 1, "n": 200}
    for name in ("repro.plan.stage", "repro.plan.tree") + POLISH:
        assert named(spans, name), name
        assert all(req.holds(s) for s in named(spans, name)), name
    (stage,), (tree,) = (named(spans, "repro.plan.stage"),
                         named(spans, "repro.plan.tree"))
    assert stage.end <= tree.start
    certify = named(spans, "repro.polish.certify")
    assert [s.stats["round"] for s in certify] == list(range(len(certify)))
    assert tree.end <= certify[0].start
    compact = named(spans, "repro.polish.compact")
    sweeps = named(spans, "repro.polish.sweep")
    scatter = named(spans, "repro.polish.scatter")
    assert len(compact) == len(sweeps) == len(scatter) >= 1
    for c, w, s in zip(compact, sweeps, scatter):
        assert c.stats["launch"] == w.stats["launch"] == s.stats["launch"]
        assert c.end <= w.start and w.end <= s.start
        # Useful lanes never exceed the launched ones.
        assert 1 <= c.stats["live"] <= c.stats["width"]
    # The first launch carries the whole miss set of one problem.
    assert compact[0].stats["width"] <= 256


def test_a_served_flush_records_its_spans_with_one_flush_index(tmp_path):
    D, E = make_family_batch("uniform", 100, 4, seed0=10)

    def serve():
        with EigensolverClient(max_batch=8, max_wait_us=2000) as client:
            return client.solve_batch_async(
                D, E, leaf=8, precision="mixed").result()

    _, spans = traced(tmp_path, serve)
    (submit,) = named(spans, "repro.client.submit")
    assert submit.stats == {"problems": 4}
    (stage,) = named(spans, "repro.serve.stage")
    assert stage.stats["flush"] == 0 and stage.stats["problems"] == 4
    assert 0 <= stage.stats["queue_wait_us"] < 60e6
    (launch,) = named(spans, "repro.serve.launch")
    (wait,) = named(spans, "repro.serve.wait")
    (demux,) = named(spans, "repro.serve.demux")
    assert launch.stats == wait.stats == {"flush": 0}
    assert demux.stats == {"flush": 0, "requests": 1}
    # Staging, the plan and the polish all run inside the launch, on the
    # engine's thread and not the caller's; the wait and the demux follow.
    assert launch.thread != submit.thread
    for name in ("repro.serve.stage", "repro.plan.stage",
                 "repro.plan.tree") + POLISH:
        assert named(spans, name), name
        assert all(launch.holds(s) for s in named(spans, name)), name
    assert launch.end <= wait.start <= wait.end <= demux.start
    assert named(spans, "repro.serve.next_flush")
    assert not named(spans, "repro.request.ladder")
    assert not named(spans, "repro.request")


def test_a_mixed_flush_is_staged_after_the_previous_flush_polish(
        tmp_path):
    D, E = make_family_batch("uniform", 100, 8, seed0=10)

    def serve():
        with EigensolverClient(max_batch=4, max_wait_us=2000) as client:
            futs = [client.solve_batch_async(D[i:i + 4], E[i:i + 4], leaf=8,
                                             precision="mixed")
                    for i in (0, 4)]
            return [f.result() for f in futs]

    _, spans = traced(tmp_path, serve)
    stages = sorted(named(spans, "repro.serve.stage"), key=lambda s: s.start)
    assert [s.stats["flush"] for s in stages] == [0, 1]
    launch0 = next(s for s in named(spans, "repro.serve.launch")
                   if s.stats["flush"] == 0)
    polish0 = [s for s in spans if s.name.startswith("repro.polish.")
               and launch0.holds(s)]
    # No double buffering on the mixed route: the second flush is staged
    # only after the first one's launch, polish included, returned.
    assert polish0
    assert max(s.end for s in polish0) <= launch0.end <= stages[1].start
    # So it queued behind that launch, longer than the first flush did.
    assert (stages[1].stats["queue_wait_us"]
            > stages[0].stats["queue_wait_us"])


def test_an_escalation_records_its_ladder_span(tmp_path):
    d, e = make_family("uniform", 48, seed=2)
    configure_faults([FaultSpec(site="plan.output", kind="nan", times=(0,),
                                lane=0, width=1)])
    try:
        res, spans = traced(tmp_path,
                            lambda: execute_request(SolveRequest(d=d, e=e)))
    finally:
        reset_faults()
    assert res.diagnostics["escalations"]
    (ladder,) = named(spans, "repro.request.ladder")
    assert ladder.stats == {"lanes": 48}
    (req,) = named(spans, "repro.request")
    assert req.holds(ladder)


def no_spans(monkeypatch):
    """Replace the span helper, in every module that records one, by a
    context manager that records nothing."""
    class Null:
        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **_):
            pass

    for mod in (request_mod, plan_mod, bis, engine_mod, client_mod):
        monkeypatch.setattr(mod, "span", lambda name, **stats: Null())


@pytest.mark.parametrize("route", ["mixed", "native"])
def test_results_are_bitwise_those_of_a_program_without_spans(
        route, tmp_path, monkeypatch):
    D, E = make_family_batch("uniform", 130, 3, seed0=20)
    kw = {"leaf": 8, "precision": route}

    def solve():
        single = np.asarray(eigvalsh_tridiagonal(D[0], E[0], **kw))
        with EigensolverClient(max_batch=8, max_wait_us=2000) as client:
            served = np.asarray(client.solve_batch_async(
                D, E, **kw).result().eigenvalues)
        return single, served

    off = solve()
    on, _ = traced(tmp_path, solve)
    with monkeypatch.context() as m:
        no_spans(m)
        bare = solve()
    for a, b, c in zip(off, on, bare):
        np.testing.assert_array_equal(a, c)
        np.testing.assert_array_equal(b, c)


def test_span_is_a_trace_annotation_with_stats():
    s = instrument.span("repro.x", flush=3)
    assert isinstance(s, jax.profiler.TraceAnnotation)
    with s as inner:
        inner.set_metadata(problems=64)
