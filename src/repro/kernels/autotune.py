"""Persisted tile autotuner for the Pallas execution plans.

Two tuned axes (DESIGN.md §13):

  * ``block_b`` — signals per Pallas grid step (the static ``block_b``
    every kernel entry point takes; the lanes of the transposed signal
    tile).  The stage tables stream through SMEM once per tile, so the
    dial is how many signals share one pass over the tables.
  * stage chunking — the cut-ladder granularity the packers schedule
    against (``core/staging.py::default_cut_ladder``): more chunks mean
    finer anytime tiers but deeper schedules, so the best granularity is
    a measured depth-overhead trade, not a constant.

Choices persist in ONE JSON cache so they survive the process:

    {"version": 1,
     "entries": {"<key>": {"block_b": 128, "source": "measured",
                           "timings_us": {"64": 12.3, ...}},
                 "chunks/sym/n64": {"num_chunks": 4, "source": "prior",
                                    "depth_overhead": {...}}}}

Plan keys are ``<device kind>/<family>/<mode>/<batched|single>/n<width>``:
a tile measured on one device kind (the CPU's interpreter, say) is never
applied on another.  They carry no backend: only the Pallas path
consults ``block_b``.  The cache lives at
``$REPRO_AUTOTUNE_CACHE`` (or ``~/.cache/repro/autotune.json``); CI
points it into the bench artifact dir so tile choices ride along with
the benchmark JSON and ``benchmarks/_diff.py`` can warn when a choice
flips between runs.

Seeding: ``benchmarks/roofline.py`` writes analytic ``source="prior"``
entries (``prior_block_b`` — the largest candidate whose working set
fits the VMEM budget — plus the packing depth-overhead scan);
``autotune_block_b`` refines them to ``source="measured"`` by timing
the actual compiled plans.  A prior never overwrites a measurement.

Staleness rule: ``ApplyPlan.program()`` resolves ``block_b=None``
through this cache AT COMPILE TIME, so entries recorded after a plan
first compiled take effect only after ``plan.clear_plan_cache()``.
"""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import time
from typing import Optional, Sequence

import jax

from repro import obs

CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
CACHE_VERSION = 1

_OBS_AUTOTUNE = obs.counter("autotune_measurements_total",
                            "measured tile-size autotune passes")
BLOCK_B_CANDIDATES = (32, 64, 128, 256)
CHUNK_CANDIDATES = (1, 2, 4, 8)

#: usable VMEM budget for the prior: ~16 MiB/core on current TPUs
#: (pallas guide), kept at 3/4 to leave headroom for spills/semaphores.
VMEM_BUDGET_BYTES = 12 * 1024 * 1024


def cache_path() -> pathlib.Path:
    env = os.environ.get(CACHE_ENV)
    if env:
        return pathlib.Path(env).expanduser()
    return pathlib.Path("~/.cache/repro/autotune.json").expanduser()


def load_cache(path=None) -> dict:
    """The cache dict ({"version", "entries"}); empty/corrupt files load
    as a fresh cache (the tuner must never be able to brick an apply)."""
    p = pathlib.Path(path) if path else cache_path()
    try:
        data = json.loads(p.read_text())
        if (isinstance(data, dict)
                and data.get("version") == CACHE_VERSION
                and isinstance(data.get("entries"), dict)):
            return data
    except (OSError, ValueError):
        pass
    return {"version": CACHE_VERSION, "entries": {}}


def save_cache(cache: dict, path=None) -> pathlib.Path:
    """Atomic write (tmp + rename): concurrent benchmark processes may
    share one cache file."""
    p = pathlib.Path(path) if path else cache_path()
    p.parent.mkdir(parents=True, exist_ok=True)
    tmp = p.with_name(p.name + f".tmp{os.getpid()}")
    tmp.write_text(json.dumps(cache, indent=1, sort_keys=True))
    tmp.replace(p)
    return p


def device_tag() -> str:
    """The default device's kind as a key segment ("cpu", "TPU_v5_lite")."""
    return jax.devices()[0].device_kind.replace(" ", "_")


def plan_key(plan) -> str:
    return (f"{device_tag()}/{plan.family}/{plan.mode}/"
            f"{'batched' if plan.batched else 'single'}/n{plan.n}")


def chunk_key(family: str, n: int) -> str:
    return f"chunks/{family}/n{n}"


def cached_block_b(plan, path=None) -> Optional[int]:
    """The persisted tile choice for ``plan``, or None (caller falls
    back to ``plan.DEFAULT_BLOCK_B``)."""
    entry = load_cache(path)["entries"].get(plan_key(plan))
    if entry and isinstance(entry.get("block_b"), int):
        return entry["block_b"]
    return None


def cached_num_chunks(family: str, n: int, default: Optional[int] = None,
                      path=None) -> Optional[int]:
    """The persisted cut-ladder granularity for (family, n) packs."""
    entry = load_cache(path)["entries"].get(chunk_key(family, n))
    if entry and isinstance(entry.get("num_chunks"), int):
        return entry["num_chunks"]
    return default


def record(key: str, path=None, source: str = "measured",
           **fields) -> dict:
    """Merge one entry into the cache.  A ``source="prior"`` record
    never clobbers an existing measurement; everything else last-wins."""
    cache = load_cache(path)
    old = cache["entries"].get(key)
    if (source == "prior" and old is not None
            and old.get("source") == "measured"):
        return old
    entry = {"source": source, **fields}
    cache["entries"][key] = entry
    save_cache(cache, path)
    return entry


def prior_block_b(n: int, num_stages: int, width: int,
                  value_bytes: int = 4, values: int = 3, legs: int = 2,
                  candidates: Sequence[int] = BLOCK_B_CANDIDATES,
                  vmem_bytes: int = VMEM_BUDGET_BYTES) -> int:
    """Roofline-analytic tile prior: the LARGEST candidate whose working
    set — ``legs`` staged tables of ``num_stages x width`` entries
    (2 int32 index tables + ``values`` value tables per entry, the
    ``benchmarks/roofline.py`` accounting; ``values=3`` for G, 2 for T)
    plus the in/out signal tiles at f32 — fits the VMEM budget.  More
    rows per grid step amortize the table residency; the measurement
    pass only has to walk down from here when scheduling overheads
    bite."""
    per_entry = 2 * 4 + values * value_bytes
    table_bytes = legs * num_stages * width * per_entry + 4 * n
    best = candidates[0]
    for cand in sorted(candidates):
        tile_bytes = 2 * cand * (n + 1) * 4
        if table_bytes + tile_bytes <= vmem_bytes:
            best = cand
    return best


def _median_time(fn, args, repeats: int = 5, warmup: int = 2) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def autotune_block_b(plan, args: tuple,
                     candidates: Sequence[int] = BLOCK_B_CANDIDATES,
                     repeats: int = 5, path=None) -> int:
    """Measure ``plan`` at each candidate tile size on ``args`` (the
    compiled program's argument tuple — prepared tables + arrays), pick
    the fastest, persist it as ``source="measured"``, and return it.
    Candidates are capped at the signal-row count (a tile taller than
    the block just pads)."""
    x = args[-1]
    denom = plan.n * (x.shape[0] if plan.batched else 1)
    rows = max(x.size // max(denom, 1), 1)
    grid = sorted({min(int(c), max(_pow2_floor(rows), 1))
                   for c in candidates})
    timings = {}
    tracer = obs.default_tracer()
    t_start = tracer.now()
    for cand in grid:
        prog = dataclasses.replace(plan, block_b=cand).program()
        timings[str(cand)] = _median_time(prog, args, repeats=repeats)
    best = int(min(timings, key=timings.get))
    timings_us = {k: round(v * 1e6, 2) for k, v in timings.items()}
    record(plan_key(plan), path=path, source="measured", block_b=best,
           timings_us=timings_us)
    _OBS_AUTOTUNE.inc()
    tracer.add_span("autotune_measure", t_start, tracer.now(),
                    cat="autotune",
                    args={"key": plan_key(plan), "block_b": best,
                          "timings_us": timings_us})
    return best


def _pow2_floor(v: int) -> int:
    p = 1
    while 2 * p <= v:
        p *= 2
    return p
