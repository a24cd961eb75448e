"""Unified observability layer: metrics registry + span tracing +
text reporting (DESIGN.md §15).

Stdlib-only by design — ``repro.obs`` imports nothing from the rest of
the package, so the lowest layers (``kernels/plan.py``,
``checkpoint/store.py``) can instrument themselves without import
cycles.  Everything records into two process-wide singletons — the
default ``MetricsRegistry`` and the default ``Tracer`` — and
``configure(enabled=...)`` flips BOTH off in one call (the fig15
traced-vs-untraced QPS gate measures exactly that toggle).

Artifact helpers: ``export_metrics(dir)`` writes ``metrics.json`` +
``metrics.prom`` (merging into an existing ``metrics.json`` so
per-process CI benchmark runs accumulate), ``export_trace(path)``
writes the Chrome trace.  ``METRICS_DIR_ENV`` names the env var CI
sets to collect both next to the ``BENCH_*.json`` artifacts.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Callable, Optional

from repro.obs.metrics import (Counter, Gauge, Histogram,
                               MetricsRegistry, bucket_counts, counter,
                               default_registry, gauge, geometric_edges,
                               histogram, merge_histograms,
                               merge_snapshots, recording_enabled,
                               set_enabled, to_json, to_prometheus_text)
from repro.obs.report import format_slo, format_snapshot
from repro.obs.trace import Tracer, default_tracer, new_trace_id

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "Tracer",
    "bucket_counts", "configure", "counter", "default_registry",
    "default_tracer", "export_metrics", "export_trace", "format_slo",
    "format_snapshot", "gauge", "geometric_edges", "histogram",
    "merge_histograms", "merge_snapshots", "new_trace_id",
    "recording_enabled", "set_enabled", "to_json", "to_prometheus_text",
    "METRICS_DIR_ENV",
]

#: CI sets this to a directory; benchmark runs drop metrics.json /
#: metrics.prom / trace_<bench>.json there (next to BENCH_*.json)
METRICS_DIR_ENV = "REPRO_METRICS_DIR"


def configure(enabled: Optional[bool] = None,
              trace_clock: Optional[Callable[[], float]] = None,
              annotation: Optional[Callable[..., Any]] = None) -> None:
    """One switch for the whole layer: ``enabled`` toggles metric
    recording AND the default tracer; ``trace_clock`` swaps the default
    tracer's clock (tests inject a fake); ``annotation`` installs the
    default tracer's profiler mirror (``kernels/plan.py`` installs
    ``jax.profiler.TraceAnnotation``; this package never imports jax)."""
    if enabled is not None:
        set_enabled(enabled)
        default_tracer().enabled = bool(enabled)
    if trace_clock is not None:
        default_tracer().clock = trace_clock
    if annotation is not None:
        default_tracer().annotation = annotation


def export_metrics(directory, merge: bool = True) -> dict:
    """Write ``metrics.json`` + ``metrics.prom`` snapshots of the
    default registry into ``directory``.  With ``merge`` (default) an
    existing ``metrics.json`` is folded in via ``merge_snapshots`` —
    counters add across runs, which is how CI's one-process-per-
    benchmark loop accumulates a single file."""
    directory = Path(directory)
    directory.mkdir(parents=True, exist_ok=True)
    snap = default_registry().collect()
    json_path = directory / "metrics.json"
    if merge and json_path.exists():
        snap = merge_snapshots(json.loads(json_path.read_text()), snap)
    json_path.write_text(to_json(snap))
    (directory / "metrics.prom").write_text(to_prometheus_text(snap))
    return {"json": json_path, "prom": directory / "metrics.prom",
            "snapshot": snap}


def export_trace(path) -> Path:
    """Write the default tracer's ring as a Chrome trace at ``path``."""
    return default_tracer().export_chrome_trace(path)
