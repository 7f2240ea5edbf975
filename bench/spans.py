#!/usr/bin/env python3
"""Attribute device idle to the program's own host spans.

The program records its host work between device launches as spans
named ``repro.<layer>[.<step>]`` (``repro.core.instrument.span``), in the
profiler trace and on the clock the device ops share.  ``reduce`` reads
them beside ``bench/trace.py``'s reduction of the same trace, and returns
that reduction with:

- a new key, ``host_spans``: for each program span name, ``seconds``
  (summed duration, clipped to the window), ``count`` (spans that start
  in the window), ``idle_s`` (device idle named by the span),
  ``self_idle_s`` (device idle while it is the innermost program span:
  a gap shared by several spans is split at their edges) and ``stats``
  (each numeric stat of the counted spans, summed);
- ``idle_gaps`` named by the innermost (shortest) program span that
  covers a gap's middle; with none there, by ``bench/trace.py``'s own
  rule (the innermost ``host_prefix`` span, else the window).

On a trace that holds no program span, the output is ``bench/trace.py``'s
key for key, with ``host_spans`` empty.  Gaps are named by one sort and
sweep over gaps and spans, not gaps x spans.  The readers of the span
metrics (``bench/metrics/*``: ``polish_idle_ms.large``,
``polish_lane_use.large``, ``unnamed_idle_share.<cell>``,
``queue_wait_ms.slq``, ``stage_idle_ms_per_flush.slq``,
``polish_idle_ms_per_flush.slq``, ``demux_idle_ms_per_flush.slq``) read
``host_spans`` and find nothing to read in a reduction without it.

    python3 bench/spans.py --workload <name> --seed <n> [--out DIR]

runs one traced window of a cell on the chip, as ``bench/run.py --trace
1`` does, and prints one JSON line: the cell's per-layer metrics and the
span metrics, ``host_spans``, the idle named by span, how many runs of
``jit__refine_executor`` lie inside a ``repro.polish.sweep`` span (the
host and device clocks agree when all do) with the min, median and
max of their margins there and of their start less the host's launch
event (negative: the device clock leads), the cost of a span with the
profiler off, and the seconds each phase took.  With ``--out`` the trace
is kept there as ``<workload>.<seed>.xplane.pb``.  It compares no answer
with the reference: ``bench/run.py`` does that.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import heapq  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import defaultdict  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import trace  # noqa: E402

PROGRAM_PREFIX = "repro."
# The span metrics of each cell, read by bench/metrics/<name>.py.
METRICS = {
    "large.f64.uniform": ["polish_idle_ms.large", "polish_lane_use.large",
                          "unnamed_idle_share.large"],
    "slq.burst256": ["queue_wait_ms.slq", "stage_idle_ms_per_flush.slq",
                     "polish_idle_ms_per_flush.slq",
                     "demux_idle_ms_per_flush.slq",
                     "unnamed_idle_share.slq"],
}


def host_events(profile, prefixes: tuple) -> list:
    """[(name, start_ns, end_ns, stats)] of every host event whose name
    starts with one of ``prefixes``."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefixes):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def device_pass(profile, spec: dict, lo: float, hi: float,
                modules: tuple = ()) -> tuple:
    """(idle gaps, module runs) inside the window [lo, hi]: the gaps are
    the window less the union of op intervals over all device planes, as
    ``bench/trace.py`` computes them; the runs [(module, start, end)]
    are those of the named ``modules`` (the ``(id)`` suffix left off)."""
    busy, runs = [], []
    for plane in profile.planes:
        if not plane.name.startswith(spec["device_planes"]):
            continue
        for line in plane.lines:
            if line.name == spec["ops_line"]:
                for ev in line.events:
                    s, e = trace._clip(ev.start_ns,
                                       ev.start_ns + ev.duration_ns, lo, hi)
                    if e > s:
                        busy.append((s, e))
            elif line.name == spec["modules_line"] and modules:
                for ev in line.events:
                    name = trace._SUFFIX.sub("", ev.name)
                    if name in modules:
                        runs.append((name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    gaps, cursor = [], lo
    for s, e in trace._union(busy):
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if cursor < hi:
        gaps.append((cursor, hi))
    return gaps, runs


def _innermost(points: list, spans: list) -> list:
    """For each point (ascending), the name of the shortest span
    [start, end) that covers it, ties to the smaller name, or None: one
    sweep with a heap of the spans open at the point."""
    spans = sorted((s, e, n) for n, s, e in spans)
    heap, out, i = [], [], 0
    for p in points:
        while i < len(spans) and spans[i][0] <= p:
            s, e, n = spans[i]
            heapq.heappush(heap, (e - s, n, e))
            i += 1
        while heap and heap[0][2] <= p:
            heapq.heappop(heap)
        out.append(heap[0][1] if heap else None)
    return out


def name_gaps(gaps: list, program: list, bench: list, window: str) -> dict:
    """{name: idle seconds} of the gaps [(start, end)] (ascending): each
    named by the innermost ``program`` span [(name, start, end)] over its
    middle, else the innermost ``bench`` span, else ``window``."""
    mids = [(s + e) / 2 for s, e in gaps]
    idle = defaultdict(float)
    for (s, e), p, b in zip(gaps, _innermost(mids, program),
                            _innermost(mids, bench)):
        idle[p or b or window] += (e - s) * 1e-9
    return idle


def self_idle(gaps: list, program: list) -> dict:
    """{name: idle seconds} of the gaps [(start, end)] (ascending), split
    at span edges: each piece goes to the innermost ``program`` span
    [(name, start, end)] over it, and none to no span.  Unlike
    ``name_gaps`` it splits a gap that several spans share."""
    edges = sorted({t for g in gaps for t in g}
                   | {t for _, s, e in program for t in (s, e)})
    spans = sorted((s, e, n) for n, s, e in program)
    heap, out, i, g = [], defaultdict(float), 0, 0
    for a, b in zip(edges, edges[1:]):
        while g < len(gaps) and gaps[g][1] <= a:
            g += 1
        if g == len(gaps):
            break
        if a < gaps[g][0]:
            continue
        while i < len(spans) and spans[i][0] <= a:
            s, e, n = spans[i]
            heapq.heappush(heap, (e - s, n, e))
            i += 1
        while heap and heap[0][2] <= a:
            heapq.heappop(heap)
        if heap:
            out[heap[0][1]] += (b - a) * 1e-9
    return out


def span_totals(program: list, idle: dict, own: dict, lo: float,
                hi: float) -> dict:
    """``host_spans`` of the program spans [(name, start, end, stats)],
    with the idle each names (``idle``) and holds (``own``)."""
    out = {}
    for name, s, e, stats in program:
        row = out.setdefault(name, {"seconds": 0.0, "count": 0,
                                    "idle_s": idle.get(name, 0.0),
                                    "self_idle_s": own.get(name, 0.0),
                                    "stats": {}})
        cs, ce = trace._clip(s, e, lo, hi)
        if ce > cs:
            row["seconds"] += (ce - cs) * 1e-9
        if lo <= s < hi:
            row["count"] += 1
            for k, v in stats.items():
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    row["stats"][k] = row["stats"].get(k, 0) + v
    return out


def reduce(profile, spec: dict, prefix: str = PROGRAM_PREFIX,
           modules: tuple = ()) -> dict:
    """``bench/trace.py``'s reduction of ``profile`` with the program's
    spans attributed (``attribute``)."""
    return attribute(trace.reduce(profile, spec), profile, spec, prefix,
                     modules)


def attribute(out: dict, profile, spec: dict,
              prefix: str = PROGRAM_PREFIX, modules: tuple = ()) -> dict:
    """``out``, ``bench/trace.py``'s reduction of ``profile``, with
    ``host_spans`` added and ``idle_gaps`` named by program spans first
    (see the module docstring); with ``modules``, also ``module_runs``:
    the runs [(module, start, end)] of the named modules in the window."""
    events = host_events(profile, (prefix, spec["host_prefix"]))
    (lo, hi), = [(s, e) for n, s, e, _ in events if n == spec["window"]]
    program = [ev for ev in events if ev[0].startswith(prefix)]
    named = [ev[:3] for ev in program]
    bench = [(n, s, e) for n, s, e, _ in events
             if n.startswith(spec["host_prefix"]) and n != spec["window"]]
    gaps, runs = device_pass(profile, spec, lo, hi, modules)
    idle = name_gaps(gaps, named, bench, spec["window"])
    out["idle_gaps"] = [[k, v] for k, v in sorted(
        idle.items(), key=lambda kv: -kv[1])[:10]]
    out["host_spans"] = span_totals(program, idle, self_idle(gaps, named),
                                    lo, hi)
    if modules:
        out["module_runs"] = [r for r in runs if lo <= r[1] < hi]
    return out


# ---------------------------------------------------------------- readers

def spans_of(run, prefixes: tuple) -> list | None:
    """The ``host_spans`` rows whose names start with one of
    ``prefixes``, or None where the run has none to read."""
    spans = (run.trace or {}).get("host_spans") or {}
    rows = [v for k, v in spans.items() if k.startswith(prefixes)]
    return rows or None


def idle_ms(run, prefixes: tuple, per: float | None) -> float | None:
    """Device idle ms named by the spans under ``prefixes``, over
    ``per`` (solves or flushes), or None with nothing to read."""
    rows = spans_of(run, prefixes)
    if rows is None or not per:
        return None
    return sum(r["idle_s"] for r in rows) * 1e3 / per


def margins(runs: list, spans: list) -> list:
    """For each run [(module, start, end)], (run start - span start, span
    end - run end) in ns against the span [(name, start, end, ...)] that
    starts nearest to it; None where there is no span.  A run lies inside
    that span when both margins are >= 0."""
    starts = sorted((s, e) for _, s, e, *_ in spans)
    keys = [s for s, _ in starts]
    out = []
    for _, s, e in runs:
        i = bisect.bisect_left(keys, s)
        near = [j for j in (i - 1, i) if 0 <= j < len(keys)]
        if not near:
            out.append(None)
            continue
        j = min(near, key=lambda j: abs(keys[j] - s))
        out.append((s - starts[j][0], starts[j][1] - e))
    return out


def launch_offsets(profile, runs: list, spans: list,
                   launch: str = "TpuLoadedExecutable::ExecuteLaunch"):
    """For each run [(module, start, end)], its device start less the
    start of the first host ``launch`` event inside the span [(name,
    start, end, ...)] that starts nearest to it, in ns; runs with no
    such event are left out.  A run cannot start before the host
    launches it, so a negative offset is the device clock's lead on the
    host's in the trace."""
    launches = sorted(s for _, s, _, _ in host_events(profile, (launch,)))
    out = []
    for (_, s, e), m in zip(runs, margins(runs, spans)):
        if m is None:
            continue
        i = bisect.bisect_left(launches, s - m[0])
        if i < len(launches) and launches[i] < e + m[1]:
            out.append(s - launches[i])
    return out


# -------------------------------------------------------------------- CLI

def span_cost_us(n: int = 200_000) -> float:
    """Microseconds of one span entered and left, with two stats, while
    no profiler runs."""
    from repro.core.instrument import span
    t = time.perf_counter()
    for i in range(n):
        with span("repro.cost", flush=i, problems=64):
            pass
    return (time.perf_counter() - t) / n * 1e6


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--out", default=None,
                   help="directory to keep the trace in")
    args = p.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import harness
    from bench.clock import CompileClock
    cell = harness.load_cell(args.workload, ROOT)
    jax = harness.start_jax(ROOT)
    device = harness.tpu_device(jax, cell.chips,
                                f"bench/spans.py: {args.workload}", ROOT)
    if device is None:
        return 2
    clock = CompileClock(jax)
    drv = harness.driver(cell).Driver(cell, args.seed)
    drv.setup(clock)
    t_setup = time.perf_counter()
    cost = span_cost_us()

    seconds = float(cell.traffic["trace_seconds"])
    layers = harness._json(os.path.join(cell.bench_dir, "layers.json"))
    tmp = tempfile.mkdtemp(prefix="bench-spans-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        before = clock.programs
        with jax.profiler.trace(tmp, profiler_options=opts):
            t_win = time.perf_counter()
            win = drv.window(seconds)
            t_end = time.perf_counter()
        t_collected = time.perf_counter()
        win.compiles = clock.programs - before
        path = harness.find_xplane(tmp)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            path = shutil.copy(path, os.path.join(
                args.out, f"{args.workload}.{args.seed}.xplane.pb"))
        t = time.perf_counter()
        profile = trace.load(path)
        t_load = time.perf_counter()
        summary = trace.reduce(profile, layers)
        t_reduce = time.perf_counter()
        summary = attribute(summary, profile, layers,
                            modules=("jit__refine_executor",))
        t_spans = time.perf_counter()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    drv.close()

    runs = summary.pop("module_runs")
    run = harness.Run(cell=cell, setup_s=t_setup - T0, window=win,
                      peak_bytes=None, trace=summary)
    names = [m["name"] for m in cell.per_layer] + METRICS.get(cell.name, [])
    metrics = {}
    for name in names:
        value = harness.reader(cell, name)(run)
        metrics[name] = None if value is None else float(value)
    sweeps = host_events(profile, ("repro.polish.sweep",))
    edge = [m for m in margins(runs, sweeps) if m is not None]
    lead = launch_offsets(profile, runs, sweeps)

    def span_us(values):
        return ([min(values) / 1e3, statistics.median(values) / 1e3,
                 max(values) / 1e3] if values else None)

    out = {"workload": cell.name, "seed": args.seed, "device": device,
           "problems": win.problems, "window_compiles": win.compiles,
           "window_wall_s": win.seconds, "counters": win.counters,
           "metrics": metrics, "window_s": summary["window_s"],
           "busy_s": summary["busy_s"], "idle_gaps": summary["idle_gaps"],
           "host_spans": summary["host_spans"],
           "refine_runs": len(runs),
           "refine_runs_in_sweep": sum(a >= 0 and b >= 0 for a, b in edge),
           "refine_sweep_margins_us": [span_us([m[k] for m in edge])
                                       for k in (0, 1)],
           "refine_launch_offset_us": span_us(lead),
           "span_off_us": cost,
           "phase_s": {"setup": t_setup - T0, "window": t_end - t_win,
                       "collect": t_collected - t_end,
                       "load": t_load - t,
                       "trace_reduce": t_reduce - t_load,
                       "attribute": t_spans - t_reduce,
                       "total": time.perf_counter() - T0}}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
