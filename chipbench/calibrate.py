#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 4,5,6 --seconds 5 [--rehearse]

Runs one cell through the harness once per seed, in one process, and
prints every number ``correct`` compares, one JSON line per seed, then
the largest reading of each for the program and the smallest for the
control.  The control is the program's own lower-precision path: the
engines' bfloat16 tables (``precision="bf16"``) in place of the float32
the configuration states.  For an onboarding cell it also prints the
relative objective of the identity chain on each seed's first churned
graph: a fit that returns its state unchanged, which needs no run.

The benchmark's own runs never run this; keep it for the limits.
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
CONTROL = {"precision": "bf16"}


def identity_objective(driver, config: dict, traffic: dict, seed: int,
                       rehearse: bool) -> float:
    """||L - diag(L)||_F^2 / ||L||_F^2 for the first churned graph a
    window of this seed onboards (the chain that leaves L unchanged)."""
    import numpy as np

    import graphs
    pos = config["graphs"].index(traffic["graph"])
    adj0 = graphs.config_graphs(config, rehearse=rehearse)[pos]
    edges = int(np.count_nonzero(np.triu(adj0, 1)))
    num = max(int(round(float(traffic["churn"]) * edges)), 1)
    lap = graphs.laplacian(graphs.churn(
        adj0, num, [seed, driver.SEED_CHURN, driver.WARM + 1]))
    lap = np.asarray(lap, np.float64)
    total = float((lap * lap).sum())
    return (total - float((np.diag(lap) ** 2).sum())) / total


def calibrate(workload: str, seeds, seconds: float, control: bool,
              rehearse: bool, t_start: float) -> dict:
    """{check: [reading per seed]} for the program or its control."""
    import harness
    readings = {}
    for seed in seeds:
        result, _ = harness.run(ROOT, workload, seed, seconds, False,
                                rehearse, t_start,
                                overrides=CONTROL if control else None)
        row = {name: c["value"] for name, c in result["checks"].items()}
        files = harness.cell_files(ROOT, harness.load_json(
            ROOT / "BENCHMARK.json"), workload)
        if control and files["traffic"]["kind"] == "onboard":
            row["fit_objective.identity"] = identity_objective(
                harness.import_path(files["driver"], "onboard"),
                harness.load_json(files["config_path"]), files["traffic"],
                seed, rehearse)
        print(json.dumps({"workload": workload, "seed": seed,
                          "control": control, "correct": result["correct"],
                          "readings": row}), flush=True)
        for name, value in row.items():
            readings.setdefault(name, []).append(value)
        t_start = time.perf_counter()
    return readings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="",
                    help="comma-separated seeds of the program")
    ap.add_argument("--control-seeds", default="",
                    help="comma-separated seeds of the control")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cache = HERE / ".cache" / "jax"
    cache.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    t_start = T_START
    for control, spec in ((False, args.seeds), (True, args.control_seeds)):
        seeds = [int(s) for s in spec.split(",") if s]
        if not seeds:
            continue
        readings = calibrate(args.workload, seeds, args.seconds, control,
                             args.rehearse, t_start)
        pick = min if control else max
        print(json.dumps({"workload": args.workload, "control": control,
                          "seeds": seeds, "summary": {
                              k: pick(v) for k, v in readings.items()}}),
              flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
