"""Dense lowering of the serving plans (kernels/plan.py; DESIGN.md §13):
the operator and bank served as f32 matmuls against dense legs equal
the staged walk at every ladder cut, whole-bucket, per graph and
unbatched, on ragged pad columns too; every matmul runs at HIGHEST
precision; and ``choose_lowering`` picks the walk off the TPU, for bf16
tables and for legs over the free memory."""
import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import ApproxEigenbasis, pad_ragged
from repro.core.fgft import laplacian
from repro.graphs import community_graph, directed_variant
from repro.kernels import plan as plan_mod
from repro.kernels.plan import (DENSE_LEG_COPIES, ApplyPlan,
                                choose_lowering, dense_legs)
from repro.runtime.sharding import BucketPlacement


def _laps(family, sizes, seed=0):
    graphs = [community_graph(n, seed=seed + k) for k, n in enumerate(sizes)]
    if family == "general":
        graphs = [directed_variant(a, seed=k) for k, a in enumerate(graphs)]
    return [laplacian(a) for a in graphs]


@pytest.fixture(scope="module")
def bases():
    """family -> (batched basis of three n = 16 graphs, a one-graph
    basis of the first)."""
    out = {}
    for family in ("sym", "general"):
        laps = np.stack(_laps(family, [16] * 3))
        kind = "general" if family == "general" else "auto"
        out[family] = (
            ApproxEigenbasis.fit(jnp.asarray(laps), 64, n_iter=1,
                                 kind=kind),
            ApproxEigenbasis.fit(jnp.asarray(laps[0]), 64, n_iter=1,
                                 kind=kind))
    return out


def _scale(spectrum, mode):
    """A diagonal (operator) or three filters' gains (bank) on the
    spectrum's last axis."""
    if mode == "operator":
        return 1.0 / (1.0 + jnp.abs(spectrum))
    return jnp.stack([1.0 / (1.0 + jnp.abs(spectrum)),
                      jnp.exp(-jnp.abs(spectrum)),
                      jnp.ones_like(spectrum)], axis=-2)


def _close(got, want):
    """Within 1e-5 of the walk, relative to the answer's largest entry."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * np.abs(want).max())


def _ladder(basis):
    return sorted({int(k) for k in np.asarray(basis.fwd.cuts)[:, 0]}) + [
        None]


@pytest.mark.parametrize("mode", ["operator", "bank"])
@pytest.mark.parametrize("family", ["sym", "general"])
def test_dense_matches_walk_every_cut(bases, family, mode):
    """Whole-bucket and per-graph (``row``) programs, every exact cut."""
    basis, _ = bases[family]
    scale = _scale(basis.spectrum, mode)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (3, 5, basis.n)).astype(np.float32))
    ladder = _ladder(basis)
    legs = dense_legs(basis.fwd, basis.bwd, ladder)
    assert set(legs) == set(ladder)
    for k in ladder:
        walk = ApplyPlan(family=family, mode=mode, n=basis.n, batched=True,
                         num_stages=k)
        dense = dataclasses.replace(walk, lowering="dense")
        want = getattr(walk, mode)(basis.fwd, basis.bwd, scale, x)
        _close(dense.program()(legs[k], scale, x), want)
        row = dataclasses.replace(dense, row=True).program()
        for g in range(3):
            _close(row(legs[k], scale, x[g], jnp.int32(g)), want[g])


@pytest.mark.parametrize("mode", ["operator", "bank"])
@pytest.mark.parametrize("family", ["sym", "general"])
def test_dense_unbatched_matches_walk(bases, family, mode):
    """One graph's (n, n) legs against a (2, 3, n) signal block."""
    _, basis = bases[family]
    scale = _scale(basis.spectrum, mode)
    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        (2, 3, basis.n)).astype(np.float32))
    for k in _ladder(basis):
        walk = ApplyPlan(family=family, mode=mode, n=basis.n, num_stages=k)
        dense = dataclasses.replace(walk, lowering="dense")
        legs = dense.legs(dense.prepare(basis.fwd), dense.prepare(basis.bwd),
                          [k])[k]
        _close(dense.program()(legs, scale, x),
               getattr(walk, mode)(basis.fwd, basis.bwd, scale, x))


@pytest.mark.parametrize("family", ["sym", "general"])
def test_dense_ragged_pad_columns_stay_zero(family):
    """A masked fit of graphs of 10 and 14 nodes in a 16 bucket: with
    pad-masked gains the dense answer is zero on the pad columns, as
    the walk's, and equals it on the rest."""
    stack, sizes = pad_ragged(_laps(family, [10, 14]), width=16)
    basis = ApproxEigenbasis.fit(
        jnp.asarray(stack), 48, n_iter=1, sizes=sizes,
        kind="general" if family == "general" else "auto")
    valid = jnp.asarray(np.arange(16)[None, :] < np.asarray(sizes)[:, None])
    x = np.zeros((2, 4, 16), np.float32)
    rng = np.random.default_rng(3)
    for i, s in enumerate(sizes):
        x[i, :, :s] = rng.standard_normal((4, s))
    x = jnp.asarray(x)
    for mode in ("operator", "bank"):
        scale = _scale(basis.spectrum, mode)
        scale = jnp.where(valid[:, None] if mode == "bank" else valid,
                          scale, 0.0)
        walk = ApplyPlan(family=family, mode=mode, n=16, batched=True)
        dense = dataclasses.replace(walk, lowering="dense")
        legs = dense_legs(basis.fwd, basis.bwd, [None])[None]
        got = np.asarray(dense.program()(legs, scale, x))
        _close(got, getattr(walk, mode)(basis.fwd, basis.bwd, scale, x))
        for i, s in enumerate(sizes):
            np.testing.assert_array_equal(got[i, ..., s:], 0.0)


@pytest.mark.parametrize("row", [False, True])
@pytest.mark.parametrize("mode", ["operator", "bank"])
@pytest.mark.parametrize("family", ["sym", "general"])
def test_dense_program_dots_run_at_highest_precision(family, mode, row):
    """Every matmul of a dense program keeps f32 inputs (a default
    precision f32 matmul on a TPU rounds them to bf16); sym contracts
    its one leg both ways, general reads two."""
    n, b = 8, 2
    plan = ApplyPlan(family=family, mode=mode, n=n, batched=True,
                     lowering="dense", row=row)
    legs = tuple(jnp.ones((b, n, n), jnp.float32)
                 for _ in range(1 if family == "sym" else 2))
    scale = jnp.ones((b, n) if mode == "operator" else (b, 3, n))
    x = jnp.ones((4, n) if row else (b, 4, n), jnp.float32)
    args = (legs, scale, x) + ((jnp.int32(1),) if row else ())
    text = plan.program().lower(*args).as_text()
    dots = [line for line in text.splitlines()
            if "stablehlo.dot_general" in line]
    assert len(dots) == 2
    assert all("precision = [HIGHEST, HIGHEST]" in d for d in dots)
    assert f"module @jit_{plan.program_name} " in text
    assert plan.program_name.endswith("_dense")


GIB = 1 << 30


@pytest.mark.parametrize("platform, precision, leg_bytes, free, want", [
    ("cpu", "f32", 1, 16 * GIB, "walk"),
    ("tpu", "f32", 4 * GIB, 12 * GIB, "dense"),
    ("tpu", "f32", 4 * GIB + 1, 12 * GIB, "walk"),
    ("tpu", "bf16", 1, 16 * GIB, "walk"),
    ("tpu", "f32", 1, None, "walk"),
    ("gpu", "f32", 1, 16 * GIB, "walk"),
])
def test_choose_lowering(platform, precision, leg_bytes, free, want):
    assert DENSE_LEG_COPIES == 3
    assert choose_lowering(platform, precision, leg_bytes, free) == want


def test_dense_plan_validation():
    ok = dict(family="sym", n=8, lowering="dense")
    ApplyPlan(mode="operator", **ok)
    ApplyPlan(mode="bank", batched=True, row=True, **ok)
    ApplyPlan(mode="operator", batched=True,
              placement=BucketPlacement(device_ids=(0,), batch=2), **ok)
    with pytest.raises(ValueError, match="lowering must be"):
        ApplyPlan(family="sym", mode="operator", n=8, lowering="matmul")
    for bad in (dict(mode="apply"), dict(mode="operator", precision="bf16"),
                dict(mode="operator", fused=False)):
        with pytest.raises(ValueError, match="dense lowering"):
            ApplyPlan(**ok, **bad)


@pytest.mark.parametrize("mode", ["operator", "bank"])
def test_dense_plan_refuses_per_call_tables(bases, mode):
    """A dense plan's one-shot ``operator``/``bank`` would rebuild the
    legs on every call: it refuses the tables and names ``legs``."""
    basis, _ = bases["sym"]
    plan = ApplyPlan(family="sym", mode=mode, n=basis.n, batched=True,
                     lowering="dense")
    with pytest.raises(ValueError, match="legs"):
        getattr(plan, mode)(basis.fwd, basis.bwd,
                            _scale(basis.spectrum, mode),
                            jnp.zeros((3, 2, basis.n), jnp.float32))


def test_dense_legs_compile_once_per_ladder(bases):
    """A second basis of the same shapes and ladder (a hot swap) builds
    its legs with the same compiled program; the sym one-pass snapshots
    equal a walk of each head prefix alone."""
    basis, _ = bases["sym"]
    ladder = _ladder(basis)
    before = plan_mod._legs_program.cache_info()
    legs = dense_legs(basis.fwd, basis.bwd, ladder)
    again = dense_legs(basis.fwd, basis.bwd, list(reversed(ladder)))
    after = plan_mod._legs_program.cache_info()
    assert after.currsize - before.currsize <= 1
    assert after.hits >= before.hits + 1
    for k in ladder:
        alone = dense_legs(basis.fwd, basis.bwd, [k])[k]
        np.testing.assert_array_equal(np.asarray(legs[k][0]),
                                      np.asarray(again[k][0]))
        np.testing.assert_allclose(np.asarray(legs[k][0]),
                                   np.asarray(alone[0]), atol=1e-6)
