import json
import os
import subprocess
import sys
import textwrap
import threading

# Tests must see ONE CPU device (the dry-run's 512-device forcing is local
# to repro.launch.dryrun, never global).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

_REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def run_in_mesh_subprocess(script: str, devices: int = 8,
                           timeout: float = 600.0) -> dict:
    """Run ``script`` in a fresh interpreter with ``devices`` forced host
    CPU devices; return its LAST stdout line parsed as JSON.

    XLA fixes the device count at first ``import jax``, so multi-device
    tests cannot run in the main pytest process (conftest pins it to one
    CPU device).  The shared idiom: prepend the XLA_FLAGS forcing BEFORE
    any import the script does, launch with a minimal env, and let the
    script print one JSON result line (anything it prints earlier is
    ignored, so debug prints don't break parsing).  Raises AssertionError
    with the subprocess stderr tail on a non-zero exit."""
    body = textwrap.dedent(script)
    prelude = ("import os\n"
               f'os.environ["XLA_FLAGS"] = '
               f'"--xla_force_host_platform_device_count={int(devices)}"\n')
    out = subprocess.run(
        [sys.executable, "-c", prelude + body], capture_output=True,
        text=True, timeout=timeout,
        env={"PYTHONPATH": "src", "PATH": os.environ.get(
                 "PATH", "/usr/bin:/bin"),
             "HOME": os.environ.get("HOME", "/root"),
             "JAX_PLATFORMS": "cpu"},
        cwd=_REPO_ROOT)
    assert out.returncode == 0, (
        f"mesh subprocess failed (exit {out.returncode}):\n"
        f"{out.stderr[-3000:]}")
    lines = out.stdout.strip().splitlines()
    assert lines, f"mesh subprocess printed nothing:\n{out.stderr[-2000:]}"
    return json.loads(lines[-1])


# ---------------------------------------------------------------------------
# Thread-leak guard: a test that leaves a non-daemon thread running (an
# unclosed AsyncFGFTService dispatcher/maintainer, a forgotten worker)
# would hang the interpreter at exit and poison every later test's
# concurrency assertions — fail THAT test, by name, instead.
# ---------------------------------------------------------------------------


def _non_daemon_threads():
    return {t for t in threading.enumerate()
            if t.is_alive() and not t.daemon}


@pytest.fixture(autouse=True)
def _thread_leak_guard():
    before = _non_daemon_threads()
    yield
    leaked = [t for t in _non_daemon_threads() if t not in before]
    # bounded grace for threads mid-exit (a close() racing the teardown),
    # then best-effort reap so the interpreter can still shut down — but
    # a thread that needed reaping still fails the test that leaked it
    for t in leaked:
        t.join(2.0)
    leaked = [t for t in leaked if t.is_alive()]
    if not leaked:
        return
    names = sorted(t.name for t in leaked)
    from repro.launch.service import shutdown_all_services
    shutdown_all_services()
    for t in leaked:
        t.join(2.0)
    pytest.fail(f"test leaked non-daemon thread(s): {names} — join every "
                f"worker and close every AsyncFGFTService before the test "
                f"returns", pytrace=False)


# ---------------------------------------------------------------------------
# Session-scoped fitted bases: greedy fits (and their jit compiles) are the
# dominant cost of this suite, so parity/semantics tests that only need
# SOME fitted basis share one fit instead of each paying for their own.
# Tests that assert properties of specific fit hyperparameters still fit
# locally.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="session")
def sym_batch48():
    """(mats, basis): one batched symmetric fit (B=3, n=16, g=48,
    n_iter=1) shared across batched-engine parity tests."""
    import numpy as np
    import jax.numpy as jnp
    from repro.core import ApproxEigenbasis
    x = np.random.default_rng(1).standard_normal((3, 16, 16)).astype(
        np.float32)
    mats = jnp.asarray(x + np.swapaxes(x, 1, 2))
    return mats, ApproxEigenbasis.fit(mats, 48, n_iter=1)


@pytest.fixture(scope="session")
def ragged_sym_fit():
    """(fleet, basis): a mixed-size symmetric fleet (sizes 10/16/9/16)
    and its masked bucket fit (g=16, n_iter=1), shared across the ragged
    parity/pad-semantics/persistence tests."""
    import numpy as np
    from repro.core import ApproxEigenbasis

    def s(n, seed):
        x = np.random.default_rng(seed).standard_normal((n, n)).astype(
            np.float32)
        return x + x.T

    fleet = [s(10, 0), s(16, 1), s(9, 2), s(16, 3)]
    return fleet, ApproxEigenbasis.fit(fleet, 16, n_iter=1)


# ---------------------------------------------------------------------------
# Gated answers: hold the service's dispatch pipeline at a known state
# ---------------------------------------------------------------------------


class GatedAnswers:
    """Wraps engines' steps so that each launch's answer is held in the
    completion stage's wait for the device until the test opens that
    launch (numbered from 0 in launch order); the pull of a launch in
    ``fail`` raises.  So a test decides which dispatches are unreplied
    when the next one launches, with no timing in its assertions."""

    def __init__(self, monkeypatch, engines, fail=()):
        self.cond = threading.Condition()
        self.launched = 0
        self.opened = set()
        self.all_open = False
        self.fail = set(fail)
        for eng in engines:
            for name in ("step_versioned", "step_bank_versioned"):
                monkeypatch.setattr(eng, name,
                                    self._wrap(getattr(eng, name)))

    def _wrap(self, step):
        def gated(*args, **kwargs):
            y, version = step(*args, **kwargs)
            with self.cond:
                i = self.launched
                self.launched += 1
                self.cond.notify_all()
            return _GatedAnswer(self, i, y), version
        return gated

    def wait_launched(self, n, timeout=30.0):
        with self.cond:
            assert self.cond.wait_for(lambda: self.launched >= n, timeout), \
                f"{self.launched} of {n} launches after {timeout}s"

    def open(self, *launches):
        with self.cond:
            self.opened.update(launches)
            self.cond.notify_all()

    def open_all(self):
        with self.cond:
            self.all_open = True
            self.cond.notify_all()


class _GatedAnswer:
    """A device answer that the completion stage waits for only once
    its launch is opened."""

    def __init__(self, gate, i, y):
        self.gate, self.i, self.y = gate, i, y

    def copy_to_host_async(self):
        self.y.copy_to_host_async()

    def block_until_ready(self):
        gate = self.gate
        with gate.cond:
            assert gate.cond.wait_for(
                lambda: gate.all_open or self.i in gate.opened, 30.0)
        self.y.block_until_ready()
        return self

    def __array__(self, dtype=None, copy=None):
        if self.i in self.gate.fail:
            raise RuntimeError(f"pull of launch {self.i} failed")
        return np.asarray(self.y, dtype=dtype)


@pytest.fixture
def gate(monkeypatch):
    """``gate(engines, fail=())`` -> a ``GatedAnswers`` over them."""
    return lambda engines, fail=(): GatedAnswers(monkeypatch, engines, fail)
