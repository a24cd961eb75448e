"""Tests of the benchmark itself, on the CPU at tiny sizes:
``python -m pytest chipbench/tests`` from the repository root."""
import json
import os
import pathlib
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(scope="session")
def bench_root(tmp_path_factory):
    """A checkout whose BENCHMARK.json also lists the cells that are
    built and rehearsed but not yet measured on the chip
    (``data/pending_cells.json``: the entries a later benchmark PR adds)."""
    root = tmp_path_factory.mktemp("checkout")
    (root / "src").symlink_to(ROOT / "src")
    (root / "chipbench").symlink_to(BENCH)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    pending = json.loads((HERE / "data" / "pending_cells.json").read_text())
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        spec[key].extend(pending[key])
    for metric in spec["end_to_end"] + spec["per_layer"]:
        metric.get("workloads", []).extend(
            pending["also_in"].get(metric["name"], []))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
