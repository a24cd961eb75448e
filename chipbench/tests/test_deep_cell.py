"""The directed fleet at g = w (configuration ``dir-standins-deep``, cell
``dir-deep-bulk``): its files agree with the shallow configuration it
deepens, the cell reports the serving cells' metrics plus the stage
metric, and a CPU rehearsal of it runs to its end."""
import json
import os
import subprocess
import sys

import harness

ROOT = harness.HERE.parent
CELL, CONFIG = "dir-deep-bulk", "dir-standins-deep"


def test_config_deepens_only_the_chain():
    deep = harness.load_json(harness.HERE / "configs" / f"{CONFIG}.json")
    shallow = harness.load_json(harness.HERE / "configs" /
                                "dir-standins.json")
    assert deep["num_transforms"] == 4096
    assert set(deep["reduced_from"]) == {"num_transforms"}
    skip = {"num_transforms", "reduced_from", "deployment"}
    assert {k: v for k, v in deep.items() if k not in skip} == \
        {k: v for k, v in shallow.items() if k not in skip}
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    (entry,) = [c for c in spec["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["num_transforms"]
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"


def test_cell_reports_what_sym_bulk_reports_and_the_stage_metric():
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    files = harness.cell_files(ROOT, spec, CELL)
    assert files["cell"]["config"] == CONFIG
    assert files["cell"]["chips"] == 1
    assert files["limits"]["unanswered"] == 0
    assert 0 < files["limits"]["answer_gap"] < 1e-3
    for trace in (False, True):
        want = [m["name"] for m in harness.cell_metrics(spec, "sym-bulk",
                                                        trace)]
        got = [m["name"] for m in harness.cell_metrics(spec, CELL, trace)]
        assert got == want
    assert "device_us_per_stage" in got and "serve_roofline" in got
    assert len(got) == 9


def test_cpu_rehearsal_exits_zero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL, "--seed",
         "1", "--seconds", "1", "--rehearse"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "check answer_gap" in proc.stderr
    # a rehearsal prints no result line
    assert not any(line.startswith("{") and "correct" in json.loads(line)
                   for line in proc.stdout.splitlines()
                   if line.startswith("{"))
