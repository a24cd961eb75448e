"""The harness is driven by data: a configuration, a traffic mix, a
metric and a cell added as new files (and BENCHMARK.json entries) in a
copy of the checkout are picked up without editing any file already
there.  The run is a CPU rehearsal at tiny sizes: it prints the metrics
and checks on standard error and no result line on standard output."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import harness

ROOT = pathlib.Path(harness.HERE).parent


def _checkout(tmp: pathlib.Path) -> pathlib.Path:
    dst = tmp / "checkout"
    ignore = shutil.ignore_patterns("__pycache__", ".cache", "tests")
    shutil.copytree(ROOT / "chipbench", dst / "chipbench", ignore=ignore)
    shutil.copytree(ROOT / "src", dst / "src", ignore=ignore)
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    return dst


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=900)


def test_new_files_alone_add_a_cell(tmp_path):
    dst = _checkout(tmp_path)
    before = {p: p.read_bytes() for p in dst.rglob("*") if p.is_file()}
    bench = dst / "chipbench"
    config = json.loads((bench / "configs" / "sym-standins.json").read_text())
    config.update(graphs=["email", "facebook"], rehearse_sizes=[10, 18],
                  num_transforms=64, n_iter=1,
                  tiers={"half": 0.5, "full": 1.0})
    (bench / "configs" / "tiny-pair.json").write_text(json.dumps(config))
    (bench / "traffic" / "solo.json").write_text(json.dumps({
        "kind": "closed_loop", "clients": 2, "rows": 3,
        "graphs": {"email": 1, "facebook": 2}, "tiers": {"half": 1},
        "pool_rows": 64, "sample_every": 2, "warm_seconds": 0.2,
        "trace_seconds": 0.2}))
    (bench / "metrics" / "rows_seen.py").write_text(
        "def read(obs):\n"
        "    return float(sum(r['rows'] for r in obs.requests))\n")
    (bench / "limits" / "tiny-solo.json").write_text(
        json.dumps({"answer_gap": 1e-4, "unanswered": 0}))
    spec = json.loads((dst / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-pair", "source": "test",
                            "file": "chipbench/configs/tiny-pair.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-solo", "config": "tiny-pair",
                              "traffic": "solo", "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "rows_seen", "unit": "rows",
                               "better": "higher", "bound": 0.1,
                               "source": "host_clock",
                               "workloads": ["tiny-solo"]})
    (dst / "BENCHMARK.json").write_text(json.dumps(spec))

    out = _run(dst, "--workload", "tiny-solo", "--seed", "4294967311",
               "--seconds", "1", "--trace", "0", "--rehearse")
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip() == "", "a rehearsal prints no result line"
    line = next(ln for ln in out.stderr.splitlines()
                if "rehearsal on cpu" in ln)
    assert '"rows_seen"' in line and '"setup_s"' in line
    assert '"signals_per_s"' not in line, "listed for other cells only"
    assert "check answer_gap" in out.stderr
    changed = [p for p, data in before.items()
               if p.name != "BENCHMARK.json" and p.read_bytes() != data]
    assert changed == []


def test_without_a_chip_or_the_program_there_is_no_result(tmp_path):
    dst = _checkout(tmp_path)
    out = _run(dst, "--workload", "sym-bulk", "--seed", "1",
               "--seconds", "1", "--trace", "0")
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "no accelerator" in out.stderr
    shutil.rmtree(dst / "src")
    out = _run(dst, "--workload", "sym-bulk", "--seed", "1",
               "--seconds", "1", "--trace", "0")
    assert out.returncode != 0 and out.stdout.strip() == ""
