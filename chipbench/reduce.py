"""Trace to metrics: one reduction of a JAX profile, shared by every
per-layer metric that reads the device.

``reduce_profile`` reads an ``.xplane.pb`` through
``jax.profiler.ProfileData`` and, inside the benchmark's window span
(a ``TraceAnnotation`` the benchmark puts around the traced slice),
returns:

- ``busy_s``: the union of the intervals in which an operation ran on
  the device (its ``XLA Ops`` line; averaged over the devices), and
  ``window_s``;
- ``modules``: device seconds per XLA program (module) name;
- ``top_ops``: the device operations that took the most time;
- ``idle_gaps``: the longest stretches with no device operation, each
  labelled by the benchmark span that covered its middle on the host
  and by the host runtime event running then.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional, Tuple

WINDOW_SPAN = "bench.window"
BENCH_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Reduction(NamedTuple):
    busy_s: float
    window_s: float
    modules: Dict[str, float]
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    devices: int


def find_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


def _union(intervals) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def reduce_profile(path: str, top: int = 10) -> Reduction:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host_spans: List[Tuple[str, float, float]] = []
    host_runtime: List[Tuple[str, float, float]] = []
    devices = []
    for plane in pd.planes:
        if any(line.name == OPS_LINE for line in plane.lines):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, a, b in _events(line):
                    (host_spans if name.startswith(BENCH_PREFIX)
                     else host_runtime).append((name, a, b))
    windows = [(a, b) for name, a, b in host_spans if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} span on the host")
    lo, hi = windows[0]
    if not devices:
        raise ValueError(f"{path}: no device plane in the profile")
    busy_total = 0.0
    op_time: Dict[str, float] = defaultdict(float)
    modules: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[float, float]] = []
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        intervals = []
        for name, a, b in _events(lines[OPS_LINE]):
            a, b = _clip(a, b, lo, hi)
            if b > a:
                intervals.append((a, b))
                # an op's name is its HLO instruction; keep "%name"
                op_time[name.split(" = ", 1)[0]] += (b - a) * 1e-9
        if MODULES_LINE in lines:
            for name, a, b in _events(lines[MODULES_LINE]):
                a, b = _clip(a, b, lo, hi)
                if b > a:
                    modules[name] += (b - a) * 1e-9
        busy = _union(intervals)
        busy_total += sum(b - a for a, b in busy) * 1e-9
        edge = lo
        for a, b in busy + [(hi, hi)]:
            if a > edge:
                gaps.append((edge, a))
            edge = max(edge, b)
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = [(_label(g, host_spans, host_runtime), (g[1] - g[0]) * 1e-9)
                for g in gaps[:top]]
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return Reduction(busy_s=busy_total / len(devices),
                     window_s=(hi - lo) * 1e-9, modules=dict(modules),
                     top_ops=ops, idle_gaps=labelled, devices=len(devices))


def _label(gap, host_spans, host_runtime) -> str:
    """What the host was doing in the middle of an idle gap: the
    innermost benchmark span and the innermost runtime event there."""
    mid = 0.5 * (gap[0] + gap[1])

    def innermost(events) -> Optional[str]:
        cover = [(b - a, name) for name, a, b in events
                 if a <= mid <= b and name != WINDOW_SPAN]
        return min(cover)[1] if cover else None

    span = innermost(host_spans) or "no benchmark span"
    event = innermost(host_runtime)
    return span if event is None else f"{span} / {event}"
