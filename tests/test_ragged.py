"""Heterogeneous (ragged) fleets: masked fits, pad semantics, the
size-bucketed serving router, and the spectral-bank gain masking
(DESIGN.md §10).

Most tests share the session-scoped ``ragged_sym_fit`` fixture
(conftest.py) — one masked bucket fit covers parity, pad semantics,
persistence, extension and the bank; only family-specific tests fit
their own."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (ApproxEigenbasis, approximate_general,
                        approximate_symmetric, pad_ragged)


def _sym(n, seed):
    x = np.random.default_rng(seed).standard_normal((n, n)).astype(
        np.float32)
    return x + x.T


def _gen(n, seed):
    return np.random.default_rng(seed).standard_normal((n, n)).astype(
        np.float32)


# ---------------------------------------------------------------------------
# Masked fit parity: the padded bucket fit IS the own-size fit
# ---------------------------------------------------------------------------


def test_ragged_sym_fit_matches_single_runs(ragged_sym_fit):
    """Acceptance: each graph's rel error through the padded masked fit
    matches its own-size single fit within 1e-5 (f32)."""
    fleet, basis = ragged_sym_fit
    assert basis.kind == "sym" and basis.batched
    assert basis.n == 16 and list(np.asarray(basis.sizes)) == [10, 16, 9,
                                                               16]
    for i, m in enumerate(fleet):
        _, _, info = approximate_symmetric(jnp.asarray(m), g=16, n_iter=1)
        denom = float((m * m).sum())
        np.testing.assert_allclose(
            float(np.asarray(basis.objective)[i]) / denom,
            float(info["objective"]) / denom, atol=1e-5)


@pytest.mark.slow
def test_ragged_gen_fit_matches_single_runs():
    fleet = [_gen(10, 1), _gen(14, 2)]
    basis = ApproxEigenbasis.fit(fleet, 12, n_iter=1)
    assert basis.kind == "general" and basis.batched
    for i, m in enumerate(fleet):
        _, _, info = approximate_general(jnp.asarray(m), m=12, n_iter=1)
        denom = float((m * m).sum())
        np.testing.assert_allclose(
            float(np.asarray(basis.objective)[i]) / denom,
            float(info["objective"]) / denom, atol=1e-5)


def test_pad_ragged_layout_and_validation():
    stack, sizes = pad_ragged([_sym(6, 0), _sym(9, 1)], width=12)
    assert stack.shape == (2, 12, 12) and list(sizes) == [6, 9]
    assert float(jnp.abs(stack[0, 6:, :]).max()) == 0.0
    assert float(jnp.abs(stack[0, :, 6:]).max()) == 0.0
    with pytest.raises(ValueError, match="square"):
        pad_ragged([np.zeros((3, 4), np.float32)])
    with pytest.raises(ValueError, match="bucket width"):
        pad_ragged([_sym(9, 1)], width=8)
    with pytest.raises(ValueError, match="empty"):
        pad_ragged([])
    with pytest.raises(ValueError, match="sizes"):
        ApproxEigenbasis.fit([_sym(6, 0)], 8, sizes=[6])
    with pytest.raises(ValueError, match="sizes must lie"):
        ApproxEigenbasis.fit(stack, 8, sizes=[6, 13])
    with pytest.raises(ValueError, match="sizes must be"):
        ApproxEigenbasis.fit(stack, 8, sizes=[6])


def test_fit_enforces_zero_pad_block(ragged_sym_fit):
    """A caller-padded stack with GARBAGE in the pad block must fit
    identically to the zero-padded one: fit() zeroes coordinates >= the
    true size instead of assuming the documented precondition."""
    fleet, basis = ragged_sym_fit
    stack, sizes = pad_ragged(fleet)
    dirty = np.asarray(stack).copy()
    rng = np.random.default_rng(99)
    for b, s in enumerate(sizes):
        dirty[b, s:, :] = rng.standard_normal((16 - s, 16))
        dirty[b, :, s:] = rng.standard_normal((16, 16 - s))
    redo = ApproxEigenbasis.fit(jnp.asarray(dirty), 16, n_iter=1,
                                sizes=sizes)
    np.testing.assert_allclose(np.asarray(redo.objective),
                               np.asarray(basis.objective), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(redo.factors.i),
                                  np.asarray(basis.factors.i))


# ---------------------------------------------------------------------------
# Pad semantics through the kernel stack
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_apply_identity_and_project_zero_on_padding(ragged_sym_fit,
                                                    backend):
    _, basis = ragged_sym_fit
    x = np.random.default_rng(9).standard_normal((4, 3, 16)).astype(
        np.float32)
    y = np.asarray(basis.apply(jnp.asarray(x), backend=backend))
    p = np.asarray(basis.project(jnp.asarray(x), backend=backend))
    # h(0) != 0 responses must not leak pad columns either: project masks
    # its gains at the padding coordinates (regression — only h=None used
    # to be covered, and tikhonov-style h(0)=1 passed pads through)
    ph = np.asarray(basis.project(jnp.asarray(x),
                                  h=lambda lam: 1.0 / (1.0 + lam),
                                  backend=backend))
    for b, s in enumerate(np.asarray(basis.sizes)):
        np.testing.assert_array_equal(y[b, :, s:], x[b, :, s:])
        assert p[b, :, s:].size == 0 or np.abs(p[b, :, s:]).max() == 0.0
        assert ph[b, :, s:].size == 0 or np.abs(ph[b, :, s:]).max() == 0.0


def test_masked_bank_gains_zero_on_padding(ragged_sym_fit):
    from repro.spectral import SpectralFilterBank, named_responses
    _, basis = ragged_sym_fit
    bank = SpectralFilterBank(basis, named_responses("heat,tikhonov"))
    gains = np.asarray(bank.gains())                    # (B, F, n)
    x = np.random.default_rng(21).standard_normal((4, 2, 16)).astype(
        np.float32)
    out = np.asarray(bank.apply(jnp.asarray(x)))
    for b, s in enumerate(np.asarray(basis.sizes)):
        assert np.abs(gains[b, :, s:]).max(initial=0.0) == 0.0
        assert np.abs(out[b, :, :, s:]).max(initial=0.0) == 0.0
    # fused bank == per-filter composition on the ragged basis
    per = np.asarray(bank.apply(jnp.asarray(x), fused=False))
    np.testing.assert_allclose(out, per, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Ragged persistence + warm-start extension keep the masking
# ---------------------------------------------------------------------------


def test_ragged_save_load_roundtrip(ragged_sym_fit, tmp_path):
    _, basis = ragged_sym_fit
    basis.save(tmp_path, step=3)
    loaded = ApproxEigenbasis.load(tmp_path)
    np.testing.assert_array_equal(np.asarray(loaded.sizes),
                                  np.asarray(basis.sizes))
    x = jnp.asarray(np.random.default_rng(11).standard_normal(
        (4, 2, 16)).astype(np.float32))
    np.testing.assert_array_equal(np.asarray(basis.project(x)),
                                  np.asarray(loaded.project(x)))


def test_ragged_extend_stays_masked(ragged_sym_fit):
    fleet, base = ragged_sym_fit
    stack, _ = pad_ragged(fleet)
    grown = base.extend(stack, 24, n_iter=0)
    assert grown.num_transforms == 24
    np.testing.assert_array_equal(np.asarray(grown.sizes),
                                  np.asarray(base.sizes))
    fi, fj = np.asarray(grown.factors.i), np.asarray(grown.factors.j)
    for b, s in enumerate(np.asarray(grown.sizes)):
        assert fi[b].max() < s and fj[b].max() < s
    assert np.all(np.asarray(grown.objective)
                  <= np.asarray(base.objective) * (1 + 1e-5) + 1e-5)


# ---------------------------------------------------------------------------
# Size-bucketed serving router
# ---------------------------------------------------------------------------


def test_bucket_width_powers_of_two():
    from repro.launch.serve import bucket_width
    assert bucket_width(5) == 8 and bucket_width(8) == 8
    assert bucket_width(9) == 16 and bucket_width(33) == 64
    assert bucket_width(3, min_width=4) == 4
    with pytest.raises(ValueError):
        bucket_width(1)


@pytest.fixture(scope="module")
def router():
    from repro.core import laplacian
    from repro.graphs import community_graph
    from repro.launch.serve import RaggedFGFTServeEngine
    sizes = [10, 16, 24]
    laps = [laplacian(community_graph(n, seed=i))
            for i, n in enumerate(sizes)]
    return sizes, laps, RaggedFGFTServeEngine(
        laps, 48, n_iter=1, tiers={"full": 1.0, "draft": 0.25})


def test_ragged_router_end_to_end(router):
    sizes, laps, eng = router
    assert eng.num_buckets == 2 and sorted(eng.engines) == [16, 32]
    rng = np.random.default_rng(0)
    signals = [rng.standard_normal((3, n)).astype(np.float32)
               for n in sizes]
    outs = eng.step(signals, lambda lam: 1.0 / (1.0 + lam))
    assert [o.shape for o in outs] == [(3, n) for n in sizes]
    rel = eng.rel_errors()
    assert rel.shape == (len(sizes),) and np.all(rel < 0.5)
    # draft tier serves through the same router
    outs_draft = eng.step(signals, lambda lam: 1.0 / (1.0 + lam),
                          tier="draft")
    assert [o.shape for o in outs_draft] == [(3, n) for n in sizes]
    with pytest.raises(ValueError, match="signal blocks"):
        eng.step(signals[:-1])


def test_ragged_router_matches_single_graph_serving(router):
    """Bucketed dispatch == single-graph engine serving (same h, same
    tier) up to f32: routing/padding must not change any result."""
    from repro.launch.serve import FGFTServeEngine
    sizes, laps, eng = router
    h = lambda lam: 1.0 / (1.0 + lam)  # noqa: E731
    rng = np.random.default_rng(1)
    signals = [rng.standard_normal((2, n)).astype(np.float32)
               for n in sizes]
    outs = eng.step(signals, h)
    i = 0                                 # one representative is enough
    g = eng.engines[eng.widths[i]].basis.num_transforms
    single = FGFTServeEngine(jnp.asarray(laps[i])[None], g, n_iter=1,
                             tiers={"full": 1.0})
    want = np.asarray(single.step(jnp.asarray(signals[i])[None], h))[0]
    np.testing.assert_allclose(outs[i], want, rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_serve_fgft_ragged_smoke():
    from repro.launch.serve import parse_args, serve_fgft
    args = parse_args(["--ragged", "--graphs", "4",
                       "--graph-sizes", "10,16", "--transforms", "48",
                       "--filter-steps", "2", "--signals", "3"])
    out = serve_fgft(args)
    assert out["sizes"] == [10, 16, 10, 16]
    assert out["buckets"] == [16]
    assert out["rel_error"].shape == (4,)
    assert np.all(np.isfinite(out["rel_error"]))
    assert out["transforms_per_s"] > 0
    # warmup/compile is excluded from the per-tier counters: every tier
    # is timed, and each counts exactly its timed steps in every bucket
    assert set(out["tiers"]) == {"full", "balanced", "draft"}
    for bucket_stats in out["stats"].values():
        assert bucket_stats["steps"] == {"full": 2, "balanced": 2,
                                         "draft": 2}


@pytest.mark.slow
def test_ragged_router_filter_bank():
    """--filter + --ragged must actually serve the named bank (it used to
    be silently dropped): per-graph (F, R, n_i) blocks, pads never leak
    through h(0) != 0 responses."""
    from repro.launch.serve import parse_args, serve_fgft
    args = parse_args(["--ragged", "--graphs", "2", "--graph-sizes",
                       "10,16", "--transforms", "32", "--filter-steps",
                       "2", "--signals", "3", "--filter",
                       "heat,tikhonov"])
    out = serve_fgft(args)
    assert out["responses_per_s"] > 0
    assert out["sizes"] == [10, 16]
    # direct router path: shapes and request order
    from repro.core import laplacian
    from repro.graphs import community_graph
    from repro.launch.serve import RaggedFGFTServeEngine
    sizes = [10, 16]
    laps = [laplacian(community_graph(n, seed=i))
            for i, n in enumerate(sizes)]
    router = RaggedFGFTServeEngine(laps, 32, n_iter=0,
                                   filters="heat,tikhonov",
                                   tiers={"full": 1.0})
    rng = np.random.default_rng(3)
    sig = [rng.standard_normal((3, n)).astype(np.float32) for n in sizes]
    ys = router.step_bank(sig)
    assert [y.shape for y in ys] == [(2, 3, n) for n in sizes]


@pytest.mark.slow
def test_speedup_vs_full_alias_uses_the_full_tier():
    """The deprecated alias must be computed against the tier literally
    named "full", not the best tier — when "full" is NOT the best tier
    the two baselines differ."""
    from repro.launch.serve import parse_args, serve_fgft
    args = parse_args(["--graphs", "2", "--graph-n", "16",
                       "--transforms", "64", "--filter-steps", "1",
                       "--signals", "2", "--tiers", "full:0.5,hq:1.0"])
    out = serve_fgft(args)
    ts = out["tiers"]
    assert ts["full"]["speedup_vs_full"] == pytest.approx(1.0)
    assert ts["hq"]["speedup_vs_best"] == pytest.approx(1.0)
    want = (ts["hq"]["transforms_per_s"]
            / ts["full"]["transforms_per_s"])
    assert ts["hq"]["speedup_vs_full"] == pytest.approx(want)
