"""ApplyPlan execution layer (kernels/plan.py; DESIGN.md §13): parity
of every (family, mode, batched, backend) plan against the oracle at
every ladder cut, fused-vs-three-pass equivalence, the bf16 precision
policy bounds, plan-cache identity, and the persisted autotuner."""
import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (ApproxEigenbasis, approximate_general,
                        approximate_symmetric, pad_ragged)
from repro.core.fgft import laplacian
from repro.core.staging import (pack_g_pair, pack_t_pair, with_precision)
from repro.graphs import community_graph, directed_variant
from repro.kernels import autotune, ref
from repro.kernels.plan import (ApplyPlan, leg_orientation,
                                clear_plan_cache, plan_cache_size)


def _pair(family, n, g, seed=0):
    """(fwd, bwd, spectrum) staged pair of one fitted chain."""
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n)).astype(np.float32)
    if family == "sym":
        f, spec, _ = approximate_symmetric(jnp.asarray(a + a.T), g=g,
                                           n_iter=1)
        fwd, bwd = pack_g_pair(f)
    else:
        f, spec, _ = approximate_general(jnp.asarray(a), m=g, n_iter=1)
        fwd, bwd = pack_t_pair(f, n)
    return fwd, bwd, spec


def _batched_basis(family, n=16, b=2, seed=0):
    laps = np.stack([laplacian(community_graph(n, seed=seed + s))
                     for s in range(b)])
    if family == "general":
        laps = np.stack([laplacian(directed_variant(
            community_graph(n, seed=seed + s), seed=s)) for s in range(b)])
    kind = "general" if family == "general" else "auto"
    return ApproxEigenbasis.fit(jnp.asarray(laps), 4 * n, n_iter=1,
                                kind=kind), laps


def _cuts(staged, backend):
    """Every exact ladder cut; pallas kernels cannot slice the empty
    k == 0 tables (pre-existing), so that rung is oracle-only."""
    ks = sorted({int(k) for k in np.asarray(staged.cuts)[:, 0]})
    return [k for k in ks if k > 0 or backend == "xla"]


# -- apply-mode parity at every ladder cut ------------------------------

@pytest.mark.parametrize("family", ["sym", "general"])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_apply_parity_every_cut(family, backend):
    n = 16
    fwd, bwd, _ = _pair(family, n, 2 * n)
    oracle = (ref.staged_g_apply if family == "sym"
              else ref.staged_t_apply)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (5, n)).astype(np.float32))
    for keep_idx, staged in ((0, bwd), (1, fwd)):
        keep = leg_orientation(family)[keep_idx]
        for k in _cuts(staged, backend) + [None]:
            plan = ApplyPlan.for_staged(staged, backend=backend,
                                        num_stages=k, keep=keep)
            got = np.asarray(plan.apply(staged, x))
            want = np.asarray(oracle(staged, x, k, keep))
            np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("family", ["sym", "general"])
def test_batched_apply_parity(family):
    basis, _ = _batched_basis(family)
    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        (2, 3, basis.n)).astype(np.float32))
    oracle = (ref.batched_g_apply if family == "sym"
              else ref.batched_t_apply)
    for backend in ("xla", "pallas"):
        for k in _cuts(basis.fwd, backend) + [None]:
            keep = leg_orientation(family)[1]
            plan = ApplyPlan.for_staged(basis.fwd, backend=backend,
                                        num_stages=k, keep=keep)
            np.testing.assert_allclose(
                np.asarray(plan.apply(basis.fwd, x)),
                np.asarray(oracle(basis.fwd, x, k, keep)),
                atol=2e-5, rtol=2e-5)


# -- operator/bank: fused vs three-pass, every cut, both backends -------

@pytest.mark.parametrize("family", ["sym", "general"])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_operator_fused_vs_three_pass_every_cut(family, backend):
    n = 16
    fwd, bwd, spec = _pair(family, n, 2 * n)
    d = 1.0 / (1.0 + jnp.abs(spec))
    x = jnp.asarray(np.random.default_rng(3).standard_normal(
        (4, n)).astype(np.float32))
    for k in _cuts(fwd, backend) + [None]:
        kw = dict(family=family, mode="operator", n=n, backend=backend,
                  num_stages=k)
        fused = ApplyPlan(**kw).operator(fwd, bwd, d, x)
        staged = ApplyPlan(fused=False, **kw).operator(fwd, bwd, d, x)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(staged),
                                   atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("family", ["sym", "general"])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_bank_fused_vs_three_pass(family, backend):
    n = 16
    fwd, bwd, spec = _pair(family, n, 2 * n)
    gains = jnp.stack([1.0 / (1.0 + jnp.abs(spec)),
                       jnp.exp(-jnp.abs(spec)),
                       jnp.ones_like(spec)])
    x = jnp.asarray(np.random.default_rng(4).standard_normal(
        (4, n)).astype(np.float32))
    cuts = _cuts(fwd, backend)
    for k in [cuts[len(cuts) // 2], None]:      # truncated prefix + full
        kw = dict(family=family, mode="bank", n=n, backend=backend,
                  num_stages=k)
        fused = ApplyPlan(**kw).bank(fwd, bwd, gains, x)
        staged = ApplyPlan(fused=False, **kw).bank(fwd, bwd, gains, x)
        assert fused.shape == (gains.shape[0],) + x.shape
        np.testing.assert_allclose(np.asarray(fused), np.asarray(staged),
                                   atol=3e-5, rtol=3e-5)


def test_batched_operator_backend_parity():
    basis, _ = _batched_basis("sym")
    d = 1.0 / (1.0 + basis.spectrum)
    x = jnp.asarray(np.random.default_rng(5).standard_normal(
        (2, 3, basis.n)).astype(np.float32))
    outs = {}
    for backend in ("xla", "pallas"):
        plan = ApplyPlan(family="sym", mode="operator", n=basis.n,
                         batched=True, backend=backend)
        outs[backend] = np.asarray(plan.operator(basis.fwd, basis.bwd,
                                                 d, x))
    np.testing.assert_allclose(outs["xla"], outs["pallas"],
                               atol=2e-5, rtol=2e-5)


# -- bf16 precision policy ----------------------------------------------

def test_with_precision_casts_values_only():
    fwd, _, _ = _pair("sym", 16, 32)
    lo = with_precision(fwd, "bf16")
    assert lo.idx_i.dtype == jnp.int32 and lo.idx_j.dtype == jnp.int32
    assert lo.c.dtype == jnp.bfloat16 and lo.sigma.dtype == jnp.bfloat16
    assert with_precision(lo, "bf16") is lo        # idempotent
    back = with_precision(lo, "f32")
    assert back.c.dtype == jnp.float32
    with pytest.raises(ValueError):
        with_precision(fwd, "f16")


@pytest.mark.parametrize("family", ["sym", "general"])
def test_bf16_operator_tracks_f32(family):
    """bf16 tables + f32 accumulation stay within the operator
    perturbation the table rounding implies: rel deviation from the f32
    path is bounded by twice the dense-operator rel Frobenius delta."""
    n = 16
    fwd, bwd, spec = _pair(family, n, 2 * n)
    d = 1.0 / (1.0 + jnp.abs(spec))
    eye = jnp.eye(n, dtype=jnp.float32)
    ops = {}
    for precision in ("f32", "bf16"):
        plan = ApplyPlan(family=family, mode="operator", n=n,
                         precision=precision)
        ops[precision] = np.asarray(plan.operator(fwd, bwd, d, eye))
    delta = (np.linalg.norm(ops["bf16"] - ops["f32"])
             / max(np.linalg.norm(ops["f32"]), 1e-12))
    assert delta < 0.03                       # ~bf16 epsilon, accumulated
    x = np.random.default_rng(6).standard_normal((8, n)).astype(
        np.float32)
    y = {p: np.asarray(ApplyPlan(family=family, mode="operator", n=n,
                                 precision=p).operator(fwd, bwd, d,
                                                       jnp.asarray(x)))
         for p in ("f32", "bf16")}
    dev = (np.linalg.norm(y["bf16"] - y["f32"])
           / max(np.linalg.norm(y["f32"]), 1e-12))
    assert dev <= 2.0 * delta + 1e-3


@pytest.mark.parametrize("family", ["sym", "general"])
def test_bf16_batched_and_backend_consistent(family):
    basis, _ = _batched_basis(family)
    d = 1.0 / (1.0 + jnp.abs(basis.spectrum))
    x = jnp.asarray(np.random.default_rng(7).standard_normal(
        (2, 4, basis.n)).astype(np.float32))
    outs = {}
    for backend in ("xla", "pallas"):
        plan = ApplyPlan(family=basis.kind, mode="operator", n=basis.n,
                         batched=True, backend=backend, precision="bf16")
        outs[backend] = np.asarray(plan.operator(basis.fwd, basis.bwd,
                                                 d, x))
    # f32 accumulation is backend-independent: both backends run the
    # SAME bf16 tables against an f32 signal
    np.testing.assert_allclose(outs["xla"], outs["pallas"],
                               atol=2e-5, rtol=2e-5)
    f32 = np.asarray(ApplyPlan(family=basis.kind, mode="operator",
                               n=basis.n, batched=True).operator(
                                   basis.fwd, basis.bwd, d, x))
    dev = np.linalg.norm(outs["xla"] - f32) / max(np.linalg.norm(f32),
                                                  1e-12)
    assert dev < 0.03


def test_bf16_ragged_masked_fleet():
    """Masked (ragged) fits keep their pad-identity property under bf16
    tables: pad coordinates of the output stay exactly zero when the
    gains are pad-masked, and real coordinates track the f32 path."""
    fleet = [laplacian(community_graph(s, seed=s)) for s in (10, 14)]
    stack, sizes = pad_ragged(fleet, width=16)
    basis = ApproxEigenbasis.fit(jnp.asarray(stack), 48, n_iter=1,
                                 sizes=sizes)
    valid = np.arange(basis.n)[None, :] < np.asarray(sizes)[:, None]
    d = jnp.where(jnp.asarray(valid),
                  1.0 / (1.0 + jnp.abs(basis.spectrum)), 0.0)
    x = np.zeros((2, 4, basis.n), np.float32)
    rng = np.random.default_rng(8)
    for i, s in enumerate(sizes):
        x[i, :, :s] = rng.standard_normal((4, s))
    y = {}
    for precision in ("f32", "bf16"):
        plan = ApplyPlan(family=basis.kind, mode="operator", n=basis.n,
                         batched=True, precision=precision)
        y[precision] = np.asarray(plan.operator(basis.fwd, basis.bwd, d,
                                                jnp.asarray(x)))
    for i, s in enumerate(sizes):
        np.testing.assert_array_equal(y["bf16"][i, :, s:], 0.0)
    dev = (np.linalg.norm(y["bf16"] - y["f32"])
           / max(np.linalg.norm(y["f32"]), 1e-12))
    assert dev < 0.03


def test_bf16_filter_within_lipschitz_bound():
    """End-to-end fig8/fig13 bound: a bf16 spectral filter stays within
    2 * Lip(h) * delta of dense eigh filtering (the f32 bar)."""
    from repro.spectral import response_lipschitz
    n = 32
    lap = laplacian(community_graph(n, seed=0))
    # a deliberately coarse budget (g = n log2 n/2): the bound is only a
    # meaningful gate when the basis error dominates bf16 rounding noise
    basis = ApproxEigenbasis.fit(jnp.asarray(lap),
                                 int(n * np.log2(n) / 2), n_iter=1)
    delta = float(np.sqrt(basis.frobenius_error(lap)
                          / (lap * lap).sum()))
    lam, u = np.linalg.eigh(lap)
    h = lambda v: 1.0 / (1.0 + v)                         # noqa: E731
    lip = max(response_lipschitz(h), 1.0)
    x = np.random.default_rng(9).standard_normal((8, n)).astype(
        np.float32)
    dense = x @ (u * np.asarray(h(jnp.asarray(lam)))[None, :]) @ u.T
    scale = max(float(np.linalg.norm(dense)), 1e-12)
    for precision in ("f32", "bf16"):
        plan = ApplyPlan(family="sym", mode="operator", n=n,
                         precision=precision)
        y = np.asarray(plan.operator(basis.fwd, basis.bwd,
                                     h(basis.spectrum), jnp.asarray(x)))
        err = float(np.linalg.norm(y - dense)) / scale
        assert err <= 2.0 * lip * delta + 5e-3, (precision, err)


# -- plan cache ----------------------------------------------------------

def test_plan_cache_identity_and_canonicalization():
    fwd, bwd, spec = _pair("sym", 16, 32)
    plan = ApplyPlan(family="sym", mode="operator", n=16)
    assert plan.program() is plan.program()
    assert ApplyPlan(family="sym", mode="operator", n=16).program() \
        is plan.program()
    # operator/bank ignore keep: equivalent plans share one entry
    assert ApplyPlan(family="sym", mode="operator", n=16,
                     keep="tail") == plan
    assert ApplyPlan(family="sym", mode="apply", n=16,
                     keep="tail") != ApplyPlan(family="sym",
                                               mode="apply", n=16)
    size = plan_cache_size()
    d = 1.0 / (1.0 + spec)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (3, 16)).astype(np.float32))
    for _ in range(3):                        # hot swaps: same shapes
        plan.operator(fwd, bwd, d, x)
    assert plan_cache_size() == size


def test_plan_validation():
    with pytest.raises(ValueError):
        ApplyPlan(family="nope", mode="apply", n=8)
    with pytest.raises(ValueError):
        ApplyPlan(family="sym", mode="nope", n=8)
    with pytest.raises(ValueError):
        ApplyPlan(family="sym", mode="apply", n=8, backend="tpu")
    with pytest.raises(ValueError):
        ApplyPlan(family="sym", mode="apply", n=8, precision="f64")
    with pytest.raises(ValueError):
        ApplyPlan(family="sym", mode="apply", n=8, keep="middle")
    with pytest.raises(ValueError):
        ApplyPlan(family="sym", mode="apply", n=0)
    with pytest.raises(ValueError):
        ApplyPlan(family="sym", mode="apply", n=8, block_b=0)


# -- persisted autotuner -------------------------------------------------

def test_autotune_cache_roundtrip(tmp_path):
    path = tmp_path / "autotune.json"
    plan = ApplyPlan(family="sym", mode="operator", n=32, batched=True)
    assert autotune.cached_block_b(plan, path) is None
    autotune.record(autotune.plan_key(plan), path=path, source="prior",
                    block_b=64)
    assert autotune.cached_block_b(plan, path) == 64
    # a measurement overwrites a prior...
    autotune.record(autotune.plan_key(plan), path=path,
                    source="measured", block_b=128)
    assert autotune.cached_block_b(plan, path) == 128
    # ...but a later prior never clobbers the measurement
    autotune.record(autotune.plan_key(plan), path=path, source="prior",
                    block_b=32)
    assert autotune.cached_block_b(plan, path) == 128
    autotune.record(autotune.chunk_key("sym", 32), path=path,
                    source="prior", num_chunks=4)
    assert autotune.cached_num_chunks("sym", 32, path=path) == 4
    assert autotune.cached_num_chunks("general", 64, default=2,
                                      path=path) == 2


def test_autotune_keys_carry_the_device_kind(tmp_path):
    """A tile recorded for another device kind is never applied here."""
    path = tmp_path / "autotune.json"
    plan = ApplyPlan(family="sym", mode="operator", n=32, batched=True)
    key = autotune.plan_key(plan)
    assert key == f"{autotune.device_tag()}/sym/operator/batched/n32"
    assert " " not in key
    other = "cpu" if autotune.device_tag() != "cpu" else "TPU_v5_lite"
    autotune.record(key.replace(autotune.device_tag(), other, 1),
                    path=path, source="measured", block_b=256)
    assert autotune.cached_block_b(plan, path) is None
    autotune.record(key, path=path, source="measured", block_b=64)
    assert autotune.cached_block_b(plan, path) == 64


def test_autotune_corrupt_cache_is_fresh(tmp_path):
    path = tmp_path / "autotune.json"
    path.write_text("{not json")
    cache = autotune.load_cache(path)
    assert cache == {"version": autotune.CACHE_VERSION, "entries": {}}
    path.write_text('{"version": 99, "entries": {"k": {}}}')
    assert autotune.load_cache(path)["entries"] == {}


def test_prior_block_b_shrinks_with_working_set():
    small = autotune.prior_block_b(16, 10, 8)
    big = autotune.prior_block_b(4096, 4000, 2048)
    assert small == max(autotune.BLOCK_B_CANDIDATES)
    assert big <= small
    assert small in autotune.BLOCK_B_CANDIDATES
    assert big in autotune.BLOCK_B_CANDIDATES


def test_plan_resolves_persisted_block_b(tmp_path, monkeypatch):
    path = tmp_path / "autotune.json"
    monkeypatch.setenv(autotune.CACHE_ENV, str(path))
    plan = ApplyPlan(family="sym", mode="apply", n=16, backend="pallas")
    from repro.kernels.plan import DEFAULT_BLOCK_B
    assert plan._resolved_block_b() == DEFAULT_BLOCK_B
    autotune.record(autotune.plan_key(plan), source="measured",
                    block_b=32)
    assert plan._resolved_block_b() == 32
    # explicit block_b always wins
    assert dataclasses.replace(plan, block_b=8)._resolved_block_b() == 8


def test_autotune_measured_pass(tmp_path):
    path = tmp_path / "autotune.json"
    fwd, bwd, spec = _pair("sym", 16, 32)
    plan = ApplyPlan(family="sym", mode="operator", n=16,
                     backend="pallas")
    d = 1.0 / (1.0 + spec)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (32, 16)).astype(np.float32))
    best = autotune.autotune_block_b(
        plan, (plan.prepare(fwd), plan.prepare(bwd), d, x),
        candidates=(8, 16), repeats=1, path=path)
    assert best in (8, 16)
    entry = autotune.load_cache(path)["entries"][autotune.plan_key(plan)]
    assert entry["source"] == "measured"
    assert set(entry["timings_us"]) == {"8", "16"}


def test_clear_plan_cache():
    plan = ApplyPlan(family="sym", mode="apply", n=16)
    plan.program()
    assert plan_cache_size() > 0
    clear_plan_cache()
    assert plan_cache_size() == 0
    plan.program()                            # recompiles cleanly
