"""The benchmark harness: finds a cell's files by name, runs its driver,
reads its metrics, decides ``correct`` and prints the result.

Everything that belongs to one configuration, traffic mix, metric or
cell lives in a file of its own and is found by the name that
``BENCHMARK.json`` gives it:

- ``configs/<config>.json``  (the path is the config's ``file`` entry)
- ``traffic/<traffic>.json`` whose ``kind`` names ``drivers/<kind>.py``
- ``metrics/<metric>.py`` with ``read(obs) -> float | None``
- ``limits/<cell>.json``: the limit of each number ``correct`` compares

A driver module has ``run(ctx) -> Observations``.  It does the set-up,
measures the window, frees the program's state and then fills
``obs.checks`` with (name, value, limit) from the plain reference.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import pathlib
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

HERE = pathlib.Path(__file__).resolve().parent
CACHE = HERE / ".cache"


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Ctx:
    """What a driver gets: the cell's files, its seed and window."""

    root: pathlib.Path          # checkout root (holds BENCHMARK.json)
    cell: dict
    config_name: str
    config: dict
    config_bytes: bytes
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    t_start: float              # perf_counter at process start
    #: switches for the control run (never set by the benchmark's runs):
    #: e.g. {"precision": "bf16"}
    overrides: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Observations:
    """What a run saw; metric readers read only this."""

    family: str = ""
    setup_s: float = 0.0
    #: (start, end) of the measured window, on the ``now()`` clock
    window: Tuple[float, float] = (0.0, 0.0)
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    #: serving: one record per request submitted in the window
    requests: List[dict] = dataclasses.field(default_factory=list)
    #: serving: the front door's counters over the window
    stats: Dict[str, Any] = dataclasses.field(default_factory=dict)
    #: onboarding: one record per whole onboard in the window
    onboards: List[dict] = dataclasses.field(default_factory=list)
    reduction: Any = None        # reduce.Reduction of the traced slice
    #: (start, end) of the traced slice, on the ``now()`` clock
    trace_window: Tuple[float, float] = (0.0, 0.0)
    #: serving: per graph its true size and, per tier, the components
    #: it serves (from the fit's chain length); the bank's filter count
    sizes: List[int] = dataclasses.field(default_factory=list)
    components: List[Dict[str, int]] = dataclasses.field(
        default_factory=list)
    bank_filters: int = 0
    peaks: Dict[str, Any] = dataclasses.field(default_factory=dict)
    compiles_in_window: int = 0
    compile_s_in_window: float = 0.0
    plan_misses_in_window: int = 0
    memory_peak_bytes: int = 0
    checks: List[Tuple[str, float, float]] = dataclasses.field(
        default_factory=list)


# -- finding things by name -------------------------------------------------


def load_json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def import_path(path: pathlib.Path, name: str):
    """Import a module from its file (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot import {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_files(root: pathlib.Path, bench: dict, workload: str) -> dict:
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    traffic = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    return {
        "cell": cell,
        "config_path": root / config["file"],
        "traffic": traffic,
        "driver": HERE / "drivers" / f"{traffic['kind']}.py",
        "limits": load_json(HERE / "limits" / f"{workload}.json"),
    }


def cell_metrics(bench: dict, workload: str, trace: bool) -> List[dict]:
    """The metrics a run of this cell reports: its end-to-end metrics
    untraced, its per-layer metrics traced."""
    pool = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in pool if workload in m.get("workloads", [workload])]


def read_metrics(specs: List[dict], obs: Observations) -> dict:
    out = {}
    for spec in specs:
        reader = import_path(HERE / "metrics" / f"{spec['name']}.py",
                             spec["name"])
        value = reader.read(obs)
        if value is None:
            continue
        out[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
    return out


# -- device and compile bookkeeping -------------------------------------------


def device_info(jax, need: int, rehearse: bool) -> dict:
    devices = jax.devices()
    dev = devices[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}
    if not rehearse and dev.platform == "cpu":
        raise SystemExit(f"no accelerator: JAX found {len(devices)} "
                         f"{dev.platform} device(s) only")
    if len(devices) < need:
        raise SystemExit(f"the cell needs {need} chip(s), JAX found "
                         f"{len(devices)}")
    return info


class CompileCounter:
    """Counts JAX compile events (tracing, lowering, backend compiles
    and persistent-cache reads) while ``armed``."""

    def __init__(self, jax):
        self.armed = False
        self.count = 0

        self.seconds = 0.0

        def listener(event: str, duration: float, *args, **kwargs):
            if self.armed and ("/jax/core/compile" in event
                               or "compilation_cache" in event):
                self.count += 1
                self.seconds += duration

        jax.monitoring.register_event_duration_secs_listener(listener)


def tier_response(lam):
    """The tier response the benchmark's requests ask the service for,
    h(lam) = 1 / (1 + |lam|) on the device (``reference.tier_response``
    is its float64 twin)."""
    import jax.numpy as jnp
    return 1.0 / (1.0 + jnp.abs(lam))


def plan_misses() -> int:
    from repro.kernels.plan import plan_cache_stats
    return plan_cache_stats()["misses"]


def peak_memory(jax) -> int:
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def annotate(trace: bool) -> Callable[[str], Any]:
    """TraceAnnotation in a traced run, a no-op otherwise."""
    if trace:
        from jax.profiler import TraceAnnotation
        return TraceAnnotation
    return lambda name: contextlib.nullcontext()


def start_trace(logdir: str):
    """Start the profiler without the Python function tracer (which
    would slow every host thread it watches) and without HLO protos."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(logdir, profiler_options=opts)


def reduce_trace(ctx, logdir: str):
    """The traced slice's reduction; a rehearsal on the CPU has no
    device plane and gets none."""
    import reduce as red
    try:
        return red.reduce_profile(red.find_xplane(logdir))
    except ValueError as exc:
        if not ctx.rehearse:
            raise
        log(f"rehearsal: no device reduction ({exc})")
        return None


def load_peaks(kind: str, rehearse: bool) -> dict:
    table = load_json(HERE / "peaks.json")
    if kind not in table:
        if rehearse:
            return {}
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


# -- the run ------------------------------------------------------------------


def run(root: pathlib.Path, workload: str, seed: int, seconds: float,
        trace: bool, rehearse: bool, t_start: float,
        overrides: Optional[dict] = None) -> Tuple[dict, Observations]:
    """Run one cell once; returns (result line, observations)."""
    bench = load_json(root / "BENCHMARK.json")
    files = cell_files(root, bench, workload)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    device = device_info(jax, int(files["cell"]["chips"]), rehearse)
    log(f"device platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}")
    ctx = Ctx(root=root, cell=files["cell"],
              config_name=files["cell"]["config"],
              config=load_json(files["config_path"]),
              config_bytes=files["config_path"].read_bytes(),
              traffic=files["traffic"], limits=files["limits"],
              seed=int(seed), seconds=float(seconds), trace=trace,
              rehearse=rehearse, t_start=t_start,
              overrides=dict(overrides or {}))
    driver = import_path(files["driver"], files["traffic"]["kind"])
    obs = driver.run(ctx)
    obs.peaks = load_peaks(device["kind"], rehearse)
    log(f"compiles inside the window: {obs.compiles_in_window} events, "
        f"{obs.compile_s_in_window:.3f}s "
        f"(plan-cache misses {obs.plan_misses_in_window})")
    specs = cell_metrics(bench, workload, trace)
    metrics = read_metrics(specs, obs)
    device["memory_peak_bytes"] = int(obs.memory_peak_bytes)
    correct = bool(obs.checks) and all(
        math.isfinite(v) and v <= lim for _, v, lim in obs.checks)
    result = {"correct": correct, "attempted": int(obs.attempted),
              "failed": int(obs.failed), "metrics": metrics,
              "device": device}
    if trace and obs.reduction is not None:
        device["busy_s"] = obs.reduction.busy_s
        device["window_s"] = obs.reduction.window_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in obs.reduction.top_ops],
            "idle_gaps": [[n, s] for n, s in obs.reduction.idle_gaps]}
    result["checks"] = {name: {"value": finite(value), "limit": limit}
                        for name, value, limit in obs.checks}
    return result, obs


def print_checks(checks):
    for name, value, limit in checks:
        ok = math.isfinite(value) and value <= limit
        print(f"[bench] check {name} = {value!r} limit {limit!r} "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr, flush=True)


def finite(value: float) -> float:
    """A number printed in the result line: inf becomes 1e30."""
    return value if math.isfinite(value) else 1e30


def now() -> float:
    return time.perf_counter()
