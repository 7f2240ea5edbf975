"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to the benchmark's
per-layer numbers.

What is read, and how, is set by ``bench/layers.json``:

- the window is the host span named ``window`` (the harness wraps its
  measured loop in ``jax.profiler.TraceAnnotation("bench.window")``);
- device busy is the union of the op intervals on the ops line of each
  device plane, inside the window, averaged over the device planes;
- each layer sums, inside the window, either the runs of named XLA
  modules (``modules``: the jitted function's module name, with the
  ``(program id)`` suffix the trace adds left off), or the ops whose
  HLO opcode is one of ``opcodes`` or whose HLO text holds one of
  ``contains``; it counts the module runs (``launches``).  On a TPU an
  op event's name is its HLO instruction text, ``%name = type
  opcode(operands), attributes``; a Pallas kernel is a ``custom-call``
  whose ``custom_call_target`` is ``"tpu_custom_call"``;
- the breakdown's device ops are summed by instruction name with its
  ``.<number>`` suffix left off (``multiply_add_fusion``, ``sort``,
  ``secular_solve_pallas_batch``), leaving out the control-flow ops
  (``containers``) whose intervals hold other ops;
- the idle gaps (the window less the busy union) are named by the
  innermost host span whose name starts with ``host_prefix`` that covers
  the gap's middle, and summed by that name.
"""

from __future__ import annotations

import gzip
import re
from collections import defaultdict

_SUFFIX = re.compile(r"\(\d+\)$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")
_NAME = re.compile(r"%?([\w-]+?)(?:\.\d+)?$")


def op_parts(text: str) -> tuple[str, str]:
    """(instruction name without its .<number> suffix, opcode) of one op
    event's HLO text; the opcode is "" where the text is not HLO."""
    head, sep, rest = text.partition(" = ")
    m = _NAME.match(head.strip())
    name = m.group(1) if m else head
    op = _OPCODE.search(" " + rest) if sep else None
    return name, op.group(1) if op else ""


def load(path: str):
    """The trace at ``path``: an ``.xplane.pb``, or one gzipped
    (``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _union(intervals):
    """Sorted, merged [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def _host_spans(profile, prefix: str):
    """[(name, start_ns, end_ns)] of every host event named prefix*."""
    spans = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    return spans


def _op_layers(text: str, opcode: str, layers: dict) -> list:
    """Names of the op layers one device op belongs to."""
    return [name for name, rule in layers.items()
            if opcode in rule.get("opcodes", ())
            or any(c in text for c in rule.get("contains", ()))]


def reduce(profile, spec: dict) -> dict:
    """{window_s, busy_s, layers: {name: {seconds, launches}}, top_ops,
    idle_gaps} of the traced window; see the module docstring."""
    spans = _host_spans(profile, spec["host_prefix"])
    windows = [(s, e) for n, s, e in spans if n == spec["window"]]
    if len(windows) != 1:
        raise ValueError(f"expected one {spec['window']!r} span, found "
                         f"{len(windows)}")
    lo, hi = windows[0]
    op_layers = {k: v for k, v in spec["layers"].items() if "modules" not in v}
    mod_layers = {k: v for k, v in spec["layers"].items() if "modules" in v}

    planes = [p for p in profile.planes
              if p.name.startswith(spec["device_planes"])]
    parsed = {}    # HLO text -> (name, opcode, layers): few distinct texts
    busy_total, union_all = 0.0, []
    layer_s = defaultdict(float)
    launches = defaultdict(int)
    op_s = defaultdict(float)
    for plane in planes:
        busy = []
        for line in plane.lines:
            if line.name == spec["ops_line"]:
                for ev in line.events:
                    s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns,
                                 lo, hi)
                    if e <= s:
                        continue
                    busy.append((s, e))
                    text = ev.name
                    if text not in parsed:
                        name, opcode = op_parts(text)
                        parsed[text] = (
                            None if opcode in spec["containers"] else name,
                            _op_layers(text, opcode, op_layers))
                    name, layers = parsed[text]
                    if name is not None:
                        op_s[name] += (e - s) * 1e-9
                    for layer in layers:
                        layer_s[layer] += (e - s) * 1e-9
            elif line.name == spec["modules_line"]:
                for ev in line.events:
                    s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns,
                                 lo, hi)
                    if e <= s:
                        continue
                    module = _SUFFIX.sub("", ev.name)
                    for name, rule in mod_layers.items():
                        if module in rule["modules"]:
                            layer_s[name] += (e - s) * 1e-9
                            launches[name] += 1
        merged = _union(busy)
        busy_total += sum(e - s for s, e in merged) * 1e-9
        union_all.extend(merged)
    if not planes:
        raise ValueError(f"no device plane {spec['device_planes']!r}*")

    # Idle gaps: the window less the busy union of all device planes.
    gaps, cursor = [], lo
    for s, e in _union(union_all):
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if cursor < hi:
        gaps.append((cursor, hi))
    inner = [(n, s, e) for n, s, e in spans if n != spec["window"]]
    idle = defaultdict(float)
    for s, e in gaps:
        mid = (s + e) / 2
        covering = [(ee - ss, n) for n, ss, ee in inner if ss <= mid < ee]
        idle[min(covering)[1] if covering else spec["window"]] += (
            (e - s) * 1e-9)

    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:10]]

    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": busy_total / len(planes),
            "layers": {name: {"seconds": layer_s[name],
                              "launches": launches[name]}
                       for name in spec["layers"] if name in layer_s},
            "top_ops": top(op_s),
            "idle_gaps": top(idle)}
