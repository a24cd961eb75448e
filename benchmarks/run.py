"""Benchmark harness: one module per paper table/figure + micro/roofline.

Benchmarks are DISCOVERED, not hand-registered: every ``*.py`` module in
this package (except this runner and ``common.py``) that exposes a
``run(fast: bool)`` callable is picked up automatically, so a new
``figN_*.py`` is runnable the moment the file exists.  ``fig*`` modules
are addressable by their short prefix (``--only fig8``) or full stem.

  PYTHONPATH=src python -m benchmarks.run --all [--fast]
  PYTHONPATH=src python -m benchmarks.run --only fig1,fig8 --fast
  PYTHONPATH=src python -m benchmarks.run --list

``--json-dir DIR`` additionally writes one ``BENCH_<name>.json`` per
benchmark (rows, elapsed seconds, pass/fail) so CI can upload the results
as workflow artifacts and performance trajectories survive the run.
"""
import argparse
import importlib
import json
import math
import os
import pathlib
import sys
import time
import traceback

_SKIP = {"run", "common", "__init__"}


def discover():
    """Returns (benches, aliases).

    ``benches``: full module stem -> run callable, for every benchmark
    module in the package.  ``aliases``: short ``figN`` prefix -> full
    stem, registered only when the prefix is unambiguous and is not
    itself a module name (a real ``fig9.py`` always wins over an alias).
    """
    benches = {}
    here = pathlib.Path(__file__).parent
    for path in sorted(here.glob("*.py")):
        stem = path.stem
        if stem in _SKIP or stem.startswith("_"):
            continue
        mod = importlib.import_module(f".{stem}", __package__)
        fn = getattr(mod, "run", None)
        if not callable(fn):
            raise RuntimeError(
                f"benchmark module {stem}.py has no run(fast) entry point")
        benches[stem] = fn
    aliases = {}
    for stem in benches:
        short = stem.split("_")[0]
        if stem.startswith("fig") and short != stem and short not in benches:
            # ambiguous prefixes (two figN_* modules) get no alias
            aliases[short] = None if short in aliases else stem
    return benches, {k: v for k, v in aliases.items() if v is not None}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced sizes/seeds for smoke runs")
    ap.add_argument("--all", action="store_true",
                    help="run every discovered benchmark")
    ap.add_argument("--only", default="",
                    help="comma-separated subset (short fig aliases ok)")
    ap.add_argument("--list", action="store_true",
                    help="print discovered benchmarks and exit")
    ap.add_argument("--json-dir", default=None,
                    help="write BENCH_<name>.json result files here "
                         "(created if missing)")
    args = ap.parse_args(argv)
    benches, aliases = discover()
    if args.list:
        for name in sorted(benches):
            print(name)
        return 0
    selected = set()
    for token in filter(None, args.only.split(",")):
        if token in benches:
            selected.add(token)
        elif token in aliases:
            selected.add(aliases[token])
        else:
            ap.error(f"unknown benchmark {token!r}; discovered: "
                     f"{sorted(benches)} (aliases: {sorted(aliases)})")
    if not selected and not args.all:
        ap.error("pass --all to run every benchmark, or --only <names>")
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    json_dir = None
    if args.json_dir:
        json_dir = pathlib.Path(args.json_dir)
        json_dir.mkdir(parents=True, exist_ok=True)
    failures = 0
    for name in sorted(benches):
        if selected and name not in selected:
            continue
        t0 = time.time()
        record = {"benchmark": name, "fast": bool(args.fast)}
        try:
            rows = benches[name](fast=args.fast)
            from . import common
            record.update(status="pass", rows=_jsonable(rows),
                          columns=common.LAST_HEADERS.get(name))
            print(f"[{name} done in {time.time() - t0:.1f}s]")
        except Exception as exc:  # noqa: BLE001 — report all benches
            failures += 1
            # a failed gate is exactly when the measured rows matter most;
            # benchmarks attach them to the raised error (gate_assert)
            record.update(status="fail", error=repr(exc),
                          rows=_jsonable(getattr(exc, "bench_rows", None)))
            print(f"[{name} FAILED]")
            traceback.print_exc()
        record["elapsed_s"] = round(time.time() - t0, 3)
        if json_dir is not None:
            (json_dir / f"BENCH_{name}.json").write_text(
                json.dumps(record, indent=1))
        _export_trace(name)
    _export_metrics()
    return 1 if failures else 0


def _obs_dir():
    out = os.environ.get("REPRO_METRICS_DIR")
    return pathlib.Path(out) if out else None


def _export_trace(name):
    """When ``$REPRO_METRICS_DIR`` is set (CI), drop ``trace_<bench>.json``
    next to the BENCH artifacts; the tracer is cleared after each export
    so every file holds exactly one benchmark's spans."""
    out = _obs_dir()
    if out is None:
        return
    from repro import obs
    obs.export_trace(out / f"trace_{name}.json")
    obs.default_tracer().clear()


def _export_metrics():
    """One merged ``metrics.json``/``metrics.prom`` per PROCESS (the
    registry is cumulative, so a per-benchmark merge inside one process
    would double-count); CI's one-process-per-benchmark loop accumulates
    the file across processes via the merge."""
    out = _obs_dir()
    if out is None:
        return
    from repro import obs
    obs.export_metrics(out, merge=True)


def gate_assert(cond, msg, rows=None):
    """Benchmark gate: like assert, but a failure carries the measured
    rows so the BENCH_*.json artifact records them (see main())."""
    if not cond:
        err = AssertionError(msg)
        err.bench_rows = rows
        raise err


def _jsonable(obj):
    """Coerce benchmark return values (numpy scalars/arrays, tuples) into
    strict JSON: non-finite floats become None (json.dumps would emit the
    non-standard NaN/Infinity tokens), non-coercible values their repr."""
    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if hasattr(obj, "item") and getattr(obj, "ndim", None) == 0:
        return _jsonable(obj.item())   # numpy scalar: re-check finiteness
    if hasattr(obj, "tolist"):
        return _jsonable(obj.tolist())
    return repr(obj)


if __name__ == "__main__":
    sys.exit(main())
