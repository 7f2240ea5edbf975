"""The harness: finds a cell's files by name, runs it once, checks it.

Everything that belongs to one cell is found by name, so that a new cell
is a new entry of ``BENCHMARK.json`` plus new files, and no edit:

- ``BENCHMARK.json`` names the cell's configuration, traffic and chips,
  and lists the metrics, each with the cells (``workloads``) it is read in;
- the configuration's ``file`` (``bench/configs/<config>.json``) holds
  its sizes, its guarantee (``bar_eps``) and its reference drivers;
- ``bench/traffic/<traffic>.json`` holds the mix's parameters and names
  the general driver that reads them (``bench/drivers/<driver>.py``);
- ``bench/metrics/<metric>.py`` reads one metric from a finished run:
  ``read(run)`` returns a number, or None where it finds nothing to read.

A run: set-up (process start, JAX start-up, problem generation, warm-up
of the cell's own shapes) -> the measured window (a closed loop; with
``--trace 1`` under the profiler) -> device peak memory -> program state
freed -> the plain reference over every answer the window produced ->
one JSON line.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

from bench import reference
from bench import trace as trace_mod

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class CellError(ValueError):
    """The cell, or a file it names, is missing or malformed."""


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list    # BENCHMARK.json metric entries this cell reports
    per_layer: list
    root: str = ROOT    # checkout root the cell's files were found under

    @property
    def bench_dir(self) -> str:
        return os.path.join(self.root, "bench")


@dataclasses.dataclass
class Window:
    """What one measured window did, on the host clock."""
    seconds: float           # first issue -> last result on the host
    problems: int            # problems answered
    latencies_s: list        # per problem: its request's issue -> result
    answers: list            # (problem index, eigenvalues or exception)
    counters: dict           # program counters over the window
    compiles: int = 0        # programs compiled or loaded in the window


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    cell: Cell
    setup_s: float
    window: Window
    peak_bytes: int | None
    trace: dict | None       # bench.trace.reduce output (--trace 1)


def _json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as exc:
        raise CellError(f"missing file {path}") from exc


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    spec = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise CellError(f"unknown workload {name!r}; BENCHMARK.json has "
                        f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise CellError(f"workload {name!r} names unknown config "
                        f"{w['config']!r}")
    config = _json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _json(os.path.join(root, "bench", "traffic",
                                 w["traffic"] + ".json"))
    return Cell(name=name, config_name=w["config"],
                traffic_name=w["traffic"], chips=int(w["chips"]),
                config=config, traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"]
                           if _applies(m, name)],
                root=root)


def load_module(path: str, name: str):
    """Import one file of the benchmark by its path."""
    if not os.path.isfile(path):
        raise CellError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(cell: Cell):
    """The general driver module the cell's traffic names."""
    name = cell.traffic["driver"]
    return load_module(os.path.join(cell.bench_dir, "drivers", name + ".py"),
                       f"bench_driver_{name}")


def knobs(spec: dict) -> dict:
    """Solver keyword arguments from a traffic file: a ``dtype`` value is
    a numpy dtype name."""
    import numpy as np
    return {k: (np.dtype(v) if k == "dtype" else v) for k, v in spec.items()}


def reader(cell: Cell, metric: str):
    """The ``read(run)`` function of one metric: ``bench/metrics/<metric>.py``,
    else, for a metric named ``<quantity>.<part>``, the reader of the
    quantity that all its parts share, ``bench/metrics/<quantity>.py``."""
    metrics = os.path.join(cell.bench_dir, "metrics")
    path = os.path.join(metrics, metric + ".py")
    if not os.path.isfile(path) and "." in metric:
        shared = os.path.join(metrics, metric.rsplit(".", 1)[0] + ".py")
        if os.path.isfile(shared):
            path = shared
    mod = load_module(path, "bench_metric_" + metric.replace(".", "_"))
    return mod.read


def read_metrics(run: Run, specs: list) -> dict:
    """{name: {"value", "unit"}} of every metric in ``specs`` whose reader
    finds something to read."""
    out = {}
    for m in specs:
        value = reader(run.cell, m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def check(cell: Cell, problems: list, answers: list, compiles: int,
          dtype: str, refs: dict | None = None) -> tuple[dict, int]:
    """Compare every answer with the plain reference.

    problems: host (d, e) float64 as served; answers: (index, eigenvalues
    or the exception the request raised); refs: {index: reference
    eigenvalues} where the caller has computed them already.  Returns
    ({check name: {"value", "limit"}}, number of failed answers).  Every
    check passes when its value is at most its limit.
    """
    import numpy as np

    bar = float(cell.config["bar_eps"])
    if refs is None:
        used = sorted({i for i, _ in answers})
        refs = dict(zip(used, reference.spectra(
            [problems[i] for i in used], cell.config["reference"][dtype])))
    worst, failed = 0.0, 0
    for i, lam in answers:
        if isinstance(lam, BaseException):
            err = math.inf
        else:
            err = reference.error_units(lam, refs[i], *problems[i],
                                        np.dtype(dtype))
        worst = max(worst, err)
        failed += not err <= bar
    return ({"max_err_eps": {"value": worst, "limit": bar},
             "failed_answers": {"value": failed, "limit": 0},
             "window_compiles": {"value": compiles, "limit": 0}},
            failed)


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def start_jax(root: str = ROOT):
    """Import JAX for a run in the checkout at ``root``: float64 on, and
    the program's persistent compilation cache and tuning cache kept in
    ``<root>/.jax_cache``, whatever the environment names."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root,
                                                           ".jax_cache")
    # Never steer the solver from a tuning cache outside the checkout.
    os.environ["REPRO_TUNE_CACHE"] = os.path.join(root, ".jax_cache", "tune")
    import jax
    jax.config.update("jax_enable_x64", True)
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    return jax


def tpu_device(jax, chips: int, who: str, root: str = ROOT) -> dict | None:
    """The result line's ``device`` for a run on ``chips`` TPU chips, or
    None, said on standard error, when JAX finds fewer or another kind,
    or the chip has no entry in ``bench/peaks.json``."""
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < chips:
        print(f"{who} needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {dev.platform!r} device(s)", file=sys.stderr)
        return None
    peaks = _json(os.path.join(root, "bench", "peaks.json"))["devices"]
    if dev.device_kind not in peaks:
        print(f"{who}: no peaks for device kind {dev.device_kind!r} in "
              f"bench/peaks.json", file=sys.stderr)
        return None
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def find_xplane(directory: str) -> str:
    found = [os.path.join(d, f) for d, _, fs in os.walk(directory)
             for f in fs if f.endswith(".xplane.pb")]
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {directory}, "
                           f"found {len(found)}")
    return found[0]


def _window(drv, seconds: float, clock) -> Window:
    before = clock.programs
    win = drv.window(seconds)
    win.compiles = clock.programs - before
    return win


def measure(cell: Cell, drv, seconds: float, trace: bool, clock, jax):
    """The window, under the profiler when ``trace``; returns (Window,
    trace reduction or None).  A traced window lasts at most the
    traffic's ``trace_seconds``: collecting a TPU trace costs tens of
    microseconds per device op after the window, and one default float64
    solve at n = 16384 runs millions of ops."""
    if not trace:
        return _window(drv, seconds, clock), None
    seconds = min(seconds, float(cell.traffic.get("trace_seconds", seconds)))
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        # No Python tracer and no HLO protos: the trace keeps device ops,
        # module runs and the host's TraceAnnotation spans.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        with jax.profiler.trace(tmp, profiler_options=opts):
            win = _window(drv, seconds, clock)
        layers = _json(os.path.join(cell.bench_dir, "layers.json"))
        summary = trace_mod.reduce(trace_mod.load(find_xplane(tmp)), layers)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return win, summary


def peak_bytes(jax, chips: int):
    """peak_bytes_in_use of the fullest of the cell's chips."""
    peaks = [(dev.memory_stats() or {}).get("peak_bytes_in_use")
             for dev in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def say_checks(checks: dict) -> None:
    """The numbers compared, each beside its limit: the last lines on
    standard error."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *, t0,
             clock, jax, device: dict) -> dict:
    """Set up, measure, check; returns the result line's object."""
    mod = driver(cell)
    drv = mod.Driver(cell, seed)
    drv.setup(clock)
    setup_s = time.perf_counter() - t0
    win, summary = measure(cell, drv, seconds, trace, clock, jax)
    peak = peak_bytes(jax, cell.chips)
    problems, dtype = drv.problems, drv.dtype_name
    drv.close()
    run = Run(cell=cell, setup_s=setup_s, window=win, peak_bytes=peak,
              trace=summary)
    checks, failed = check(cell, problems, win.answers, win.compiles, dtype)
    device = dict(device, memory_peak_bytes=peak)
    out = {"correct": passed(checks), "attempted": win.problems,
           "failed": failed,
           "metrics": read_metrics(run, cell.per_layer if trace
                                   else cell.end_to_end),
           "device": device}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        out["breakdown"] = {"device_ops": summary["top_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    out["checks"] = checks
    return out
