#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout that holds ``BENCHMARK.json``,
``chipbench/`` and the program under ``src/``.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``), ``device`` and, traced,
``breakdown``; the numbers ``correct`` compared, each with its limit,
come last in it and on the last lines of standard error.

Without an accelerator, with fewer chips than the cell needs, or
without the program, it exits non-zero and prints no result.
``--rehearse`` runs the cell at tiny sizes on any device (the CPU
included) and prints no result line: control flow only.

JAX's persistent compilation cache lives in ``chipbench/.cache/jax`` and
fitted fleets in ``chipbench/.cache/fleets``, both inside the checkout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = pathlib.Path.cwd()


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any device; prints no result line")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if not (ROOT / "BENCHMARK.json").is_file():
        print(f"[bench] FAIL: no BENCHMARK.json in {ROOT}", file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"[bench] FAIL: {ROOT} holds no program (src/repro)",
              file=sys.stderr)
        return 2
    # the compile cache is fixed inside the checkout, set before jax loads
    cache = HERE / ".cache" / "jax"
    cache.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import harness
    try:
        result, _ = harness.run(ROOT, args.workload, args.seed,
                                args.seconds, bool(args.trace),
                                args.rehearse, T_START)
    except SystemExit as exc:
        print(f"[bench] FAIL: {exc}", file=sys.stderr)
        return 2
    except Exception as exc:  # noqa: BLE001 — a failed run prints no result
        import traceback
        traceback.print_exc()
        print(f"[bench] FAIL: {type(exc).__name__}: {exc}", file=sys.stderr)
        return 1
    checks = [(k, v["value"], v["limit"])
              for k, v in result["checks"].items()]
    if args.rehearse:
        harness.log(f"rehearsal on {result['device']['platform']}: "
                    f"metrics {json.dumps(result['metrics'])}; "
                    f"correct={result['correct']}; no result line")
        harness.print_checks(checks)
        return 0 if result["correct"] else 1
    print(json.dumps(result), flush=True)
    harness.print_checks(checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
