"""The graph stack imports one way: tables -> kernels -> core -> launch,
and none of it loads the LM stack.  Each case imports its module first,
in a fresh interpreter, so an import cycle or a stray LM import cannot
hide behind whatever an earlier test imported."""
import pytest

from conftest import run_in_mesh_subprocess

LM_PACKAGES = ("repro.models", "repro.configs", "repro.optim", "repro.data")


@pytest.mark.parametrize("module", [
    "repro.core", "repro.kernels.plan", "repro.launch.serve",
    "repro.launch.service"])
def test_graph_module_imports_first_without_the_lm_stack(module):
    loaded = run_in_mesh_subprocess(f"""
        import json, sys
        import {module}
        print(json.dumps(sorted(sys.modules)))
        """, devices=1, timeout=300)
    lm = [m for m in loaded
          if any(m == p or m.startswith(p + ".") for p in LM_PACKAGES)]
    assert not lm, f"importing {module} loaded the LM stack: {lm}"
