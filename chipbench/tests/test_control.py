"""The control of every cell comes out not correct: the program's own
bfloat16 table path in place of the float32 its configuration states
(and, for onboarding, the chain that leaves the graph unchanged), at a
size a test run can hold.  On the chip the same readings come from
``python3 chipbench/calibrate.py --control`` at the cell's own size."""
import time

import pytest

import calibrate
import harness

CELLS = ["sym-bulk", "dir-bulk", "sym-onboard"]
SEEDS = [3, 2 ** 31 + 7]


@pytest.fixture(autouse=True)
def _at_root(monkeypatch, bench_root):
    monkeypatch.setattr(calibrate, "ROOT", bench_root)


def _limits(workload):
    return harness.load_json(harness.HERE / "limits" / f"{workload}.json")


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_a_limit_and_the_program_passes(workload):
    limits = _limits(workload)
    sound = calibrate.calibrate(workload, SEEDS, 1.5, False, True,
                                time.perf_counter())
    for name, values in sound.items():
        assert max(values) <= limits[name], (name, values)
    control = calibrate.calibrate(workload, SEEDS, 1.5, True, True,
                                  time.perf_counter())
    for k in range(len(SEEDS)):
        assert control["answer_gap"][k] > limits["answer_gap"], control
    if "fit_objective.identity" in control:
        assert min(control["fit_objective.identity"]) > \
            limits["fit_objective"], control
