"""Batched eigenspace engine (core/eigenbasis.py): batched-vs-loop
equivalence, save/load round-trips, and batched Pallas-vs-ref parity."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (ApproxEigenbasis, approximate_general,
                        approximate_symmetric)
from repro.core.staging import pack_g_pair
from repro.kernels.plan import ApplyPlan


def _sym_batch(b, n, seed=0):
    x = np.random.default_rng(seed).standard_normal((b, n, n)).astype(
        np.float32)
    return jnp.asarray(x + np.swapaxes(x, 1, 2))


def _gen_batch(b, n, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (b, n, n)).astype(np.float32))


def test_batched_sym_fit_matches_single_runs():
    """Acceptance: B=8 matrices in one jit == 8 single gtransform runs
    (per-matrix relative Frobenius errors, atol 1e-5)."""
    b, n, g = 8, 24, 64
    mats = _sym_batch(b, n)
    basis = ApproxEigenbasis.fit(mats, g, n_iter=2)
    assert basis.kind == "sym" and basis.batched
    norms = np.asarray(jnp.sum(mats * mats, axis=(1, 2)))
    rel_batched = np.asarray(basis.objective) / norms
    for i in range(b):
        _, _, info = approximate_symmetric(mats[i], g=g, n_iter=2)
        rel_single = float(info["objective"]) / norms[i]
        np.testing.assert_allclose(rel_batched[i], rel_single, atol=1e-5)


@pytest.mark.parametrize("shards", [1, 2])
def test_accelerator_batch_form_matches_lockstep(shards):
    """A batched fit runs each device's matrices in turn (eigenbasis.py::
    _over_batch); it must return what a lockstep vmap of the same core
    returns, split over one or two device shards."""
    from repro.core import eigenbasis as eb
    mats = _sym_batch(4, 16, seed=3)
    sbar0 = jnp.zeros((4, 16), jnp.float32)

    def one(s, sb):
        return eb.gt._approx_sym_core(s, sb, 48, 2, True,
                                      jnp.asarray(1e-3, s.dtype), "gamma")

    want = jax.jit(jax.vmap(one))(mats, sbar0)
    got = jax.jit(eb._over_batch(one, shards))(mats, sbar0)
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert w.shape == g.shape
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[2]),
                               rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(got[0].i), np.asarray(want[0].i))


@pytest.mark.slow
def test_batched_gen_fit_matches_single_runs():
    b, n, m = 4, 16, 40
    mats = _gen_batch(b, n)
    basis = ApproxEigenbasis.fit(mats, m, n_iter=2)
    assert basis.kind == "general" and basis.batched
    norms = np.asarray(jnp.sum(mats * mats, axis=(1, 2)))
    rel_batched = np.asarray(basis.objective) / norms
    for i in range(b):
        _, _, info = approximate_general(mats[i], m=m, n_iter=2)
        rel_single = float(info["objective"]) / norms[i]
        np.testing.assert_allclose(rel_batched[i], rel_single, atol=1e-5)


def test_batched_objective_matches_dense_reconstruction(sym_batch48):
    mats, basis = sym_batch48
    np.testing.assert_allclose(np.asarray(basis.frobenius_error(mats)),
                               np.asarray(basis.objective),
                               rtol=1e-3, atol=1e-3)


def test_batched_to_dense_orthonormal(sym_batch48):
    _, basis = sym_batch48
    u = np.asarray(basis.to_dense())
    eye = np.broadcast_to(np.eye(16, dtype=np.float32), u.shape)
    np.testing.assert_allclose(u @ np.swapaxes(u, 1, 2), eye, atol=1e-5)


@pytest.mark.parametrize("kind,make", [
    ("sym", _sym_batch),
    pytest.param("general", _gen_batch, marks=pytest.mark.slow)])
def test_batched_pallas_matches_ref(kind, make):
    """Batched fused Pallas kernels == vmapped ref.py oracle."""
    b, n, g = 5, 20, 60
    mats = make(b, n, seed=3)
    basis = ApproxEigenbasis.fit(mats, g, n_iter=1)
    assert basis.kind == kind
    x = jnp.asarray(np.random.default_rng(4).standard_normal(
        (b, 9, n)).astype(np.float32))
    want = basis.project(x, backend="xla")
    got = basis.project(x, backend="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_batched_apply_matches_per_matrix_staged_apply():
    """The padded/stacked (B, S, P) tables apply exactly like each
    matrix's own (S, P) staging of the SAME factor chain (greedy fits of
    different jit programs may legitimately tie-break differently, so the
    comparison shares one set of factors)."""
    from repro.core.staging import _gfactors_slice
    b, n, g = 4, 16, 40
    mats = _sym_batch(b, n, seed=5)
    basis = ApproxEigenbasis.fit(mats, g, n_iter=1)
    x = jnp.asarray(np.random.default_rng(6).standard_normal(
        (b, 3, n)).astype(np.float32))
    got = np.asarray(basis.project(x))
    for i in range(b):
        fwd, adj = pack_g_pair(_gfactors_slice(basis.factors, i))
        plan = ApplyPlan.for_staged(fwd, mode="operator")
        want = np.asarray(plan.operator(fwd, adj, basis.spectrum[i],
                                        x[i]))
        np.testing.assert_allclose(got[i], want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("make", [
    _sym_batch, pytest.param(_gen_batch, marks=pytest.mark.slow)])
def test_save_load_roundtrip(make, tmp_path):
    b, n, g = 3, 16, 32
    mats = make(b, n, seed=7)
    basis = ApproxEigenbasis.fit(mats, g, n_iter=1)
    basis.save(tmp_path, step=5)
    loaded = ApproxEigenbasis.load(tmp_path)
    assert loaded.kind == basis.kind
    assert loaded.batched and loaded.n == n
    x = jnp.asarray(np.random.default_rng(8).standard_normal(
        (b, 4, n)).astype(np.float32))
    np.testing.assert_array_equal(np.asarray(basis.spectrum),
                                  np.asarray(loaded.spectrum))
    np.testing.assert_array_equal(np.asarray(basis.project(x)),
                                  np.asarray(loaded.project(x)))


def test_save_load_roundtrip_single(tmp_path):
    mats = _sym_batch(1, 16, seed=9)[0]
    basis = ApproxEigenbasis.fit(mats, 32, n_iter=1)
    assert not basis.batched
    basis.save(tmp_path)
    loaded = ApproxEigenbasis.load(tmp_path)
    x = jnp.asarray(np.random.default_rng(10).standard_normal(
        (4, 16)).astype(np.float32))
    np.testing.assert_array_equal(np.asarray(basis.project(x)),
                                  np.asarray(loaded.project(x)))


def test_fit_with_mesh_shards_batch():
    from repro.launch.mesh import make_local_mesh
    mesh = make_local_mesh()
    mats = _sym_batch(3, 16, seed=11)
    basis = ApproxEigenbasis.fit(mats, 32, n_iter=1, mesh=mesh).shard(mesh)
    x = jnp.asarray(np.random.default_rng(12).standard_normal(
        (3, 2, 16)).astype(np.float32))
    assert basis.project(x).shape == (3, 2, 16)


def test_kind_validation_and_auto():
    # same shape/hyperparams as test_fit_and_extend_reject_score_for_
    # general_family -> the gen fit program is compiled once for both
    mats = _gen_batch(2, 10, seed=13)
    basis = ApproxEigenbasis.fit(mats, 12, n_iter=0)
    assert basis.kind == "general"
    with pytest.raises(ValueError):
        ApproxEigenbasis.fit(jnp.zeros((3, 4, 5)), 8)
    with pytest.raises(ValueError):
        ApproxEigenbasis.fit(jnp.zeros((4, 4)), 8, kind="bogus")


@pytest.mark.slow
def test_fgft_serve_engine_smoke():
    from repro.launch.serve import serve_fgft, parse_args
    args = parse_args(["--graphs", "3", "--graph-n", "24",
                       "--transforms", "96", "--filter-steps", "2",
                       "--signals", "4"])
    out = serve_fgft(args)
    assert out["rel_error"].shape == (3,)
    assert np.all(out["rel_error"] < 0.5)
    assert out["transforms_per_s"] > 0


# ---------------------------------------------------------------------------
# Anytime subsystem (DESIGN.md §9): warm-start extension, auto-kind hint,
# tiered serving, prefix metadata persistence.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("make,n_iter", [
    (_sym_batch, 0),
    pytest.param(_sym_batch, 2, marks=pytest.mark.slow),
    pytest.param(_gen_batch, 0, marks=pytest.mark.slow),
    pytest.param(_gen_batch, 2, marks=pytest.mark.slow)])
def test_extend_never_increases_objective(make, n_iter):
    mats = make(3, 16, seed=21)
    base = ApproxEigenbasis.fit(mats, 24, n_iter=n_iter)
    grown = base.extend(mats, 48, n_iter=n_iter)
    assert grown.num_transforms == 48
    obj0 = np.asarray(base.objective)
    obj1 = np.asarray(grown.objective)
    assert np.all(obj1 <= obj0 * (1 + 1e-5) + 1e-5), (obj0, obj1)
    # the extension is consistent: reported objective == dense residual
    np.testing.assert_allclose(np.asarray(grown.frobenius_error(mats)),
                               obj1, rtol=1e-3, atol=1e-3)


@pytest.mark.slow
def test_extend_continues_the_greedy_exactly():
    """With no polish sweeps the greedy is sequential, so extending a
    g1-component init to g2 must reproduce the from-scratch g2 init
    bit-for-bit (same discovery sequence) — the strongest correctness
    check on the warm start."""
    mats = _sym_batch(2, 16, seed=22)
    a = ApproxEigenbasis.fit(mats, 20, n_iter=0).extend(mats, 40, n_iter=0)
    b = ApproxEigenbasis.fit(mats, 40, n_iter=0)
    for fa, fb in zip(a.factors, b.factors):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))
    np.testing.assert_allclose(np.asarray(a.objective),
                               np.asarray(b.objective), rtol=1e-6)


def test_extend_validates_arguments(sym_batch48):
    mats, base = sym_batch48
    with pytest.raises(ValueError):
        base.extend(mats, 48)          # must grow
    with pytest.raises(ValueError):
        base.extend(mats[0], 64)       # batched fit needs batched mats
    with pytest.raises(ValueError):
        base.extend(_sym_batch(3, 20, seed=24), 64)  # wrong n


@pytest.mark.slow
def test_fit_auto_warns_when_overriding_hint():
    mats = _sym_batch(2, 12, seed=25)   # numerically symmetric
    with pytest.warns(UserWarning, match="overriding the caller hint"):
        basis = ApproxEigenbasis.fit(mats, 16, n_iter=0, hint="general")
    assert basis.kind == "sym"
    # an explicit kind is honored silently — the hint only guards "auto"
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        forced = ApproxEigenbasis.fit(mats, 16, n_iter=0, kind="general")
    assert forced.kind == "general"
    # non-canonical hints are caught at the call site, not half-warned
    with pytest.raises(ValueError, match="unknown hint"):
        ApproxEigenbasis.fit(mats, 16, n_iter=0, hint="symmetric")


def test_select_tier_and_prefix_project_matches_prefix_basis(sym_batch48):
    _, basis = sym_batch48
    num_stages, k = basis.select_tier(fraction=0.5)
    assert 0 < k < 48
    x = jnp.asarray(np.random.default_rng(27).standard_normal(
        (3, 4, 16)).astype(np.float32))
    got = basis.apply(x, num_stages=num_stages)
    # reference: per-matrix staged apply of the significance-prefix chain
    from repro.core.staging import _gfactors_slice
    from repro.core.types import GFactors
    for i in range(3):
        f = _gfactors_slice(basis.factors, i)
        pre = GFactors(*(arr[48 - k:] for arr in f))
        fwd, _ = pack_g_pair(pre)
        want = ApplyPlan.for_staged(fwd, mode="apply").apply(fwd, x[i])
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_save_load_preserves_stage_cuts(sym_batch48, tmp_path):
    _, basis = sym_batch48
    basis.save(tmp_path, step=1)
    loaded = ApproxEigenbasis.load(tmp_path)
    np.testing.assert_array_equal(np.asarray(basis.stage_cuts),
                                  np.asarray(loaded.stage_cuts))
    import json
    import pathlib
    manifest = json.loads((pathlib.Path(tmp_path) / "step_000000001" /
                           "manifest.json").read_text())
    meta = manifest["metadata"]["eigenbasis"]
    assert meta["stage_cuts"] == np.asarray(basis.stage_cuts).tolist()
    assert meta["num_stages"] == int(basis.fwd.num_stages)


def test_fgft_serve_engine_tiers():
    from repro.launch.serve import serve_fgft, parse_args
    args = parse_args(["--graphs", "2", "--graph-n", "16",
                       "--transforms", "64", "--filter-steps", "2",
                       "--signals", "4",
                       "--tiers", "full:1.0,draft:0.25"])
    out = serve_fgft(args)
    assert set(out["tiers"]) == {"full", "draft"}
    assert out["tiers"]["draft"]["num_transforms"] < 64
    # warmup/compile is excluded: counters match the timed filter-steps
    assert out["stats"]["steps"] == {"full": 2, "draft": 2}
    # draft tier must run strictly fewer stages
    assert (out["tiers"]["draft"]["num_stages"]
            < out["tiers"]["full"]["num_stages"])


@pytest.mark.slow
def test_fgft_serve_engine_directed_kind():
    """--directed must reach the T-transform family (the kind= plumbing
    this PR adds; the service used to silently auto-route)."""
    from repro.launch.serve import serve_fgft, parse_args
    args = parse_args(["--directed", "--graphs", "2",
                       "--graph-n", "12", "--transforms", "24",
                       "--filter-steps", "1", "--signals", "2",
                       "--tiers", "full:1.0"])
    out = serve_fgft(args)
    assert out["kind"] == "general"
    assert np.all(np.isfinite(out["rel_error"]))
    assert out["transforms_per_s"] > 0


def test_serve_step_defaults_to_best_tier_and_rejects_dup_tiers():
    """step() must not assume a tier literally named "full" exists; the
    default is the highest-quality tier in the map.  Duplicate tier names
    are rejected (silent last-wins would redefine the speedup baseline)."""
    from repro.launch.serve import FGFTServeEngine, parse_tiers
    import pytest as _pytest
    from repro.core.fgft import laplacian
    from repro.graphs import community_graph
    laps = np.stack([laplacian(community_graph(12, seed=s))
                     for s in range(2)])
    engine = FGFTServeEngine(jnp.asarray(laps), 24, n_iter=0,
                             tiers={"hq": 1.0, "draft": 0.25})
    assert engine.default_tier == "hq"
    x = jnp.ones((2, 3, 12), jnp.float32)
    y = engine.step(x)                     # no KeyError without "full"
    assert y.shape == x.shape
    assert engine.stats["steps"]["hq"] == 1
    with _pytest.raises(ValueError, match="duplicate tier"):
        parse_tiers("full:1.0,full:0.25")
    with _pytest.raises(ValueError, match="empty name"):
        parse_tiers("full:1.0,:0.25")


def test_select_tier_never_picks_the_empty_cut():
    """Regression: a small positive fraction must snap to the smallest
    REAL cut, not to (0, 0) — a zero-component tier silently serves
    diag-only results."""
    mats = _sym_batch(2, 16, seed=31)
    basis = ApproxEigenbasis.fit(mats, 32, n_iter=0)
    ns, k = basis.select_tier(fraction=0.05)
    assert k > 0 and ns > 0


@pytest.mark.slow
def test_extend_keeps_original_g_as_a_tier():
    """Regression: the extended tables' ladder must contain the original
    g even when it is not on the new default quarters ladder, so the
    pre-extension basis stays selectable (README's tier claim)."""
    mats = _sym_batch(2, 16, seed=32)
    base = ApproxEigenbasis.fit(mats, 20, n_iter=0)
    grown = base.extend(mats, 56, n_iter=0)      # quarters of 56 miss 20
    ns, k = grown.select_tier(num_transforms=20)
    assert k == 20
    x = jnp.asarray(np.random.default_rng(33).standard_normal(
        (2, 3, 16)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(grown.apply(x, num_stages=ns)),
        np.asarray(base.apply(x)), rtol=1e-5, atol=1e-5)


def test_save_load_extend_preserves_score_and_objective(tmp_path):
    """Regression (confirmed bug): load() used to drop info["score"] and
    objective, so extend() after a restore silently switched the greedy
    criterion from "paper" to "gamma".  The manifest now records both."""
    mats = _sym_batch(2, 12, seed=40)
    lam = jnp.asarray(np.linalg.eigvalsh(np.asarray(mats)))
    base = ApproxEigenbasis.fit(mats, 12, n_iter=0, spectrum=lam)
    assert base.info["score"] == "paper"
    base.save(tmp_path, step=1)
    loaded = ApproxEigenbasis.load(tmp_path)
    assert loaded.info["score"] == "paper"
    np.testing.assert_allclose(np.asarray(loaded.objective),
                               np.asarray(base.objective), rtol=1e-6)
    grown = loaded.extend(mats, 24, n_iter=0)
    assert grown.info["score"] == "paper"   # was "gamma" before the fix


@pytest.mark.slow
def test_save_load_general_records_no_score(tmp_path):
    """General-family fits have no score; the restored info stays clean
    (and the objective still round-trips)."""
    gmats = _gen_batch(2, 10, seed=41)
    gen = ApproxEigenbasis.fit(gmats, 12, n_iter=0)
    gen.save(tmp_path / "gen", step=1)
    gloaded = ApproxEigenbasis.load(tmp_path / "gen")
    assert "score" not in gloaded.info
    np.testing.assert_allclose(np.asarray(gloaded.objective),
                               np.asarray(gen.objective), rtol=1e-6)


def test_fit_and_extend_reject_score_for_general_family():
    """Regression: score= used to be silently dropped for the T family."""
    mats = _gen_batch(2, 10, seed=42)
    with pytest.raises(ValueError, match="symmetric .*family only"):
        ApproxEigenbasis.fit(mats, 12, score="gamma")
    base = ApproxEigenbasis.fit(mats, 12, n_iter=0)
    with pytest.raises(ValueError, match="symmetric .*family only"):
        base.extend(mats, 24, score="paper")


def test_fit_rejects_spectrum_shape_mismatch():
    mats = _sym_batch(2, 12, seed=43)
    with pytest.raises(ValueError, match="spectrum shape"):
        ApproxEigenbasis.fit(mats, 12, spectrum=jnp.zeros((12,)))
    with pytest.raises(ValueError, match="spectrum shape"):
        ApproxEigenbasis.fit(mats[0], 12, spectrum=jnp.zeros((2, 12)))
    # matching shapes still pass
    ok = ApproxEigenbasis.fit(mats, 12, n_iter=0,
                              spectrum=jnp.ones((2, 12)))
    assert ok.info["score"] == "paper"


def test_serve_tier_stats_speedup_vs_best():
    """Regression: the tier stat was named speedup_vs_full but computed
    against the default (best) tier whatever its name.  It is now
    speedup_vs_best; the old key survives only as a deprecated alias and
    only when a tier named "full" actually exists."""
    from repro.launch.serve import serve_fgft, parse_args
    args = parse_args(["--graphs", "2", "--graph-n", "16",
                       "--transforms", "64", "--filter-steps", "1",
                       "--signals", "2", "--tiers", "full:1.0,draft:0.25"])
    out = serve_fgft(args)
    for ts in out["tiers"].values():
        assert "speedup_vs_best" in ts
        assert ts["speedup_vs_full"] == ts["speedup_vs_best"]
    assert out["tiers"]["full"]["speedup_vs_best"] == pytest.approx(1.0)
    args = parse_args(["--graphs", "2", "--graph-n", "16",
                       "--transforms", "64", "--filter-steps", "1",
                       "--signals", "2", "--tiers", "hq:1.0,draft:0.25"])
    out = serve_fgft(args)
    for ts in out["tiers"].values():
        assert "speedup_vs_best" in ts
        assert "speedup_vs_full" not in ts   # no tier named "full"
    assert out["tiers"]["hq"]["speedup_vs_best"] == pytest.approx(1.0)


def test_extend_reuses_the_fit_score():
    """Regression: extend must continue the greedy with the score the
    fit resolved (paper-score fits extend with the paper score; bit-
    exact continuation only holds for the spectrum-free gamma score,
    since a paper-score extension pairs by the REFIT spectrum)."""
    mats = _sym_batch(2, 12, seed=34)
    lam = jnp.asarray(np.linalg.eigvalsh(np.asarray(mats)))
    base = ApproxEigenbasis.fit(mats, 12, n_iter=0, spectrum=lam)
    assert base.info["score"] == "paper"
    grown = base.extend(mats, 24, n_iter=0)
    assert np.all(np.asarray(grown.objective)
                  <= np.asarray(base.objective) * (1 + 1e-5) + 1e-5)
    default = ApproxEigenbasis.fit(mats, 12, n_iter=0)
    assert default.info["score"] == "gamma"
