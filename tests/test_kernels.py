"""Pallas kernels (interpret mode) vs the pure-jnp oracle: shape/dtype
sweeps as required for every kernel."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (approximate_symmetric, approximate_general,
                        pack_g, pack_g_adjoint, pack_t, pack_t_inverse)
from repro.kernels import ref
from repro.kernels import butterfly as bf
from repro.kernels import shear as sh
from repro.kernels.plan import ApplyPlan


def _staged_g(n, g, seed=0):
    x = np.random.default_rng(seed).standard_normal((n, n)).astype(np.float32)
    f, sbar, _ = approximate_symmetric(jnp.asarray(x + x.T), g=g, n_iter=1)
    return pack_g(f), pack_g_adjoint(f), sbar


def _staged_t(n, m, seed=0):
    c = np.random.default_rng(seed).standard_normal((n, n)).astype(np.float32)
    f, cbar, _ = approximate_general(jnp.asarray(c), m=m, n_iter=1)
    return pack_t(f, n), pack_t_inverse(f, n), cbar


SHAPES = [(1, 16), (7, 32), (64, 48), (130, 16)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("b,n", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_butterfly_kernel_sweep(b, n, dtype):
    fwd, _, _ = _staged_g(n, 2 * n, seed=b)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((b, n)),
                    dtype)
    want = ref.staged_g_apply(fwd, x)
    got = bf.butterfly_apply(fwd, x, interpret=True)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("b,n", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.slow
def test_shear_kernel_sweep(b, n, dtype):
    fwd, _, _ = _staged_t(n, 2 * n, seed=b)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((b, n)), dtype)
    want = ref.staged_t_apply(fwd, x)
    got = sh.shear_apply(fwd, x, interpret=True)
    tol = 1e-6 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("b,n", [(4, 16), (33, 32)])
def test_fused_sym_kernel(b, n):
    fwd, adj, sbar = _staged_g(n, 3 * n, seed=7)
    x = jnp.asarray(np.random.default_rng(3).standard_normal((b, n)),
                    jnp.float32)
    want = ref.sym_operator_apply(fwd, adj, sbar, x)
    got = bf.sym_operator_apply(fwd, adj, sbar, x, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,n", [(4, 16), (33, 32)])
@pytest.mark.slow
def test_fused_gen_kernel(b, n):
    fwd, inv, cbar = _staged_t(n, 3 * n, seed=8)
    x = jnp.asarray(np.random.default_rng(4).standard_normal((b, n)),
                    jnp.float32)
    want = ref.gen_operator_apply(fwd, inv, cbar, x)
    got = sh.gen_operator_apply(fwd, inv, cbar, x, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_plan_backend_switch_and_nd_shapes():
    fwd, adj, sbar = _staged_g(16, 32, seed=9)
    x = jnp.asarray(np.random.default_rng(5).standard_normal((3, 5, 16)),
                    jnp.float32)

    def apply(backend):
        return ApplyPlan.for_staged(fwd, mode="apply",
                                    backend=backend).apply(fwd, x)

    y_x, y_p = apply("xla"), apply("pallas")
    assert y_x.shape == x.shape
    np.testing.assert_allclose(np.asarray(y_x), np.asarray(y_p), atol=1e-6)
    with pytest.raises(ValueError):
        apply("cuda")


def test_block_b_tiling_boundaries():
    """Batch not divisible by block_b exercises the grid edge."""
    fwd, _, _ = _staged_g(16, 32, seed=10)
    x = jnp.asarray(np.random.default_rng(6).standard_normal((130, 16)),
                    jnp.float32)
    got = bf.butterfly_apply(fwd, x, block_b=64, interpret=True)
    want = ref.staged_g_apply(fwd, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


# ---------------------------------------------------------------------------
# Anytime prefix parity (DESIGN.md §9): the Pallas kernels must match the
# XLA oracle at EVERY exact tier boundary, for both table orientations.
# ---------------------------------------------------------------------------


def _tier_boundaries(staged):
    """All exact (num_stages,) boundaries except the trivial empty cut."""
    return [int(s) for s, k in np.asarray(staged.cuts) if k > 0]


@pytest.mark.slow
def test_butterfly_prefix_parity_all_tiers():
    fwd, adj, _ = _staged_g(24, 60, seed=11)
    x = jnp.asarray(np.random.default_rng(7).standard_normal((9, 24)),
                    jnp.float32)
    for s in _tier_boundaries(fwd):
        for staged, keep in ((fwd, "tail"), (adj, "head")):
            want = ref.staged_g_apply(staged, x, num_stages=s, keep=keep)
            got = bf.butterfly_apply(staged, x, interpret=True,
                                     num_stages=s, keep=keep)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-6, atol=1e-6)


@pytest.mark.slow
def test_shear_prefix_parity_all_tiers():
    fwd, inv, _ = _staged_t(20, 40, seed=12)
    x = jnp.asarray(np.random.default_rng(8).standard_normal((6, 20)),
                    jnp.float32)
    for s in _tier_boundaries(fwd):
        for staged, keep in ((fwd, "head"), (inv, "tail")):
            want = ref.staged_t_apply(staged, x, num_stages=s, keep=keep)
            got = sh.shear_apply(staged, x, interpret=True,
                                 num_stages=s, keep=keep)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_fused_prefix_parity_all_tiers():
    fwd, adj, sbar = _staged_g(16, 48, seed=13)
    x = jnp.asarray(np.random.default_rng(9).standard_normal((5, 16)),
                    jnp.float32)
    for s in _tier_boundaries(fwd):
        want = ref.sym_operator_apply(fwd, adj, sbar, x, num_stages=s)
        got = bf.sym_operator_apply(fwd, adj, sbar, x, interpret=True,
                                    num_stages=s)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    tfwd, tinv, cbar = _staged_t(16, 48, seed=14)
    for s in _tier_boundaries(tfwd):
        want = ref.gen_operator_apply(tfwd, tinv, cbar, x, num_stages=s)
        got = sh.gen_operator_apply(tfwd, tinv, cbar, x, interpret=True,
                                    num_stages=s)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_plan_prefix_backend_parity_and_bank():
    """plan-level switch: xla and pallas agree at a mid-ladder boundary
    for the plain, fused and filter-bank paths."""
    from repro.core.staging import select_cut
    fwd, adj, sbar = _staged_g(16, 32, seed=15)
    s, _ = select_cut(fwd, fraction=0.5)
    x = jnp.asarray(np.random.default_rng(10).standard_normal((2, 3, 16)),
                    jnp.float32)

    def plan(mode, backend, keep="head"):
        return ApplyPlan.for_staged(fwd, mode=mode, backend=backend,
                                    num_stages=s, keep=keep)

    y_x = plan("apply", "xla", keep="tail").apply(fwd, x)
    y_p = plan("apply", "pallas", keep="tail").apply(fwd, x)
    np.testing.assert_allclose(np.asarray(y_x), np.asarray(y_p), atol=1e-6)
    o_x = plan("operator", "xla").operator(fwd, adj, sbar, x)
    o_p = plan("operator", "pallas").operator(fwd, adj, sbar, x)
    np.testing.assert_allclose(np.asarray(o_x), np.asarray(o_p), atol=1e-5)
    gains = jnp.asarray(np.random.default_rng(11).standard_normal(
        (3, 16)), jnp.float32)
    b_x = plan("bank", "xla").bank(fwd, adj, gains, x[0])
    b_p = plan("bank", "pallas").bank(fwd, adj, gains, x[0])
    np.testing.assert_allclose(np.asarray(b_x), np.asarray(b_p), atol=1e-5)


def test_interpret_mode_only_on_the_cpu(monkeypatch):
    """Kernels interpret exactly when the backend is the CPU; asking for
    the interpreter anywhere else is an error, never a silent fallback."""
    assert bf.resolve_interpret(None) is True
    assert bf.resolve_interpret(True) is True
    monkeypatch.setattr(bf.jax, "default_backend", lambda: "tpu")
    assert bf.resolve_interpret(None) is False
    assert bf.resolve_interpret(False) is False
    with pytest.raises(ValueError, match="interpret"):
        bf.resolve_interpret(True)
