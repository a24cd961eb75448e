"""Mesh-partitioned serving fleet (DESIGN.md §14), single-device tier:
placement allocator logic, BucketPlacement semantics, placed plan/engine
parity, shard-aware checkpoints, placement-manifest validation.  The
multi-device behavior (device ownership, zero collectives, overlapped
maintenance) lives in tests/test_fleet_mesh.py (slow, subprocess)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.fgft import laplacian
from repro.graphs import community_graph
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import FGFTServeEngine, RaggedFGFTServeEngine
from repro.runtime.sharding import (BucketPlacement, assign_buckets,
                                    fleet_placement,
                                    single_bucket_placement)


# ---------------------------------------------------------------------------
# assign_buckets: the pure allocator
# ---------------------------------------------------------------------------


def test_assign_buckets_proportional_and_disjoint():
    a = assign_buckets(8, {2: 4, 4: 4}, weights={2: 8.0, 4: 24.0})
    assert set(a) == {2, 4}
    ids = [i for ids in a.values() for i in ids]
    assert sorted(ids) == sorted(set(ids))          # disjoint
    # the heavier bucket gets at least as many devices
    assert len(a[4]) >= len(a[2])
    assert all(len(ids) >= 1 for ids in a.values())


def test_assign_buckets_caps_at_batch():
    a = assign_buckets(8, {4: 2})
    assert len(a[4]) <= 2                            # never > batch rows


def test_assign_buckets_round_robin_when_crowded():
    a = assign_buckets(2, {1: 3, 2: 3, 4: 3})
    assert all(len(ids) == 1 for ids in a.values())
    assert {ids[0] for ids in a.values()} == {0, 1}  # both devices used


def test_assign_buckets_validation():
    with pytest.raises(ValueError):
        assign_buckets(0, {2: 1})
    with pytest.raises(ValueError):
        assign_buckets(4, {2: 0})                    # zero-graph bucket
    assert assign_buckets(4, {}) == {}


# ---------------------------------------------------------------------------
# BucketPlacement semantics
# ---------------------------------------------------------------------------


def test_bucket_placement_pad_and_place():
    mesh = make_local_mesh()
    pl = single_bucket_placement(mesh, 3)
    assert pl.batch == 3
    assert pl.batch_padded % pl.num_devices == 0
    x = np.ones((3, 2, 4), np.float32)
    y = np.asarray(pl.place(x))
    assert y.shape == (pl.batch_padded, 2, 4)
    np.testing.assert_array_equal(y[:3], x)
    np.testing.assert_array_equal(y[3:], 0.0)        # zero pad rows


def test_bucket_placement_validation():
    with pytest.raises(ValueError):
        BucketPlacement(device_ids=(), batch=2)
    with pytest.raises(ValueError):
        BucketPlacement(device_ids=(0,), batch=0)
    missing = BucketPlacement(device_ids=(10_000,), batch=1)
    with pytest.raises(ValueError, match="fleet_placement"):
        missing.mesh()


def test_fleet_placement_manifest_roundtrip():
    mesh = make_local_mesh()
    fp = fleet_placement(mesh, {16: 3, 32: 2}, weights={16: 1.0, 32: 4.0})
    man = fp.manifest()
    assert man["num_devices"] >= 1
    assert set(man["buckets"]) == {"16", "32"}
    for k, batch in (("16", 3), ("32", 2)):
        assert man["buckets"][k]["batch"] == batch
        assert len(man["buckets"][k]["device_ids"]) >= 1


# ---------------------------------------------------------------------------
# make_local_mesh validation (was a bare assert)
# ---------------------------------------------------------------------------


def test_make_local_mesh_bad_model_axis_message():
    n = len(jax.devices())
    with pytest.raises(ValueError) as ei:
        make_local_mesh(model_axis=n + 1)
    msg = str(ei.value)
    assert str(n) in msg and str(n + 1) in msg       # names both numbers
    with pytest.raises(ValueError):
        make_local_mesh(model_axis=0)


# ---------------------------------------------------------------------------
# placed engine == unplaced engine on the SAME basis (the serving path
# itself must not change results; fit-under-different-mesh differences
# are covered by the fig14 tolerance gate)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def placed_pair():
    mesh = make_local_mesh()
    b, n = 3, 16
    laps = np.stack([laplacian(community_graph(n, seed=s))
                     for s in range(b)])
    pl = single_bucket_placement(mesh, b)
    placed = FGFTServeEngine(jnp.asarray(laps), 64, n_iter=1, mesh=mesh,
                             filters="heat", placement=pl)
    plain = FGFTServeEngine(jnp.asarray(laps), 64, n_iter=1, mesh=mesh,
                            filters="heat")
    return placed, plain, b, n


def test_placed_step_bitwise_matches(placed_pair):
    placed, plain, b, n = placed_pair
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(b, 2, n)).astype(np.float32))
    np.testing.assert_array_equal(np.asarray(placed.step(x)),
                                  np.asarray(plain.step(x)))
    np.testing.assert_array_equal(np.asarray(placed.step_bank(x)),
                                  np.asarray(plain.step_bank(x)))


def test_placed_step_with_response_map(placed_pair):
    placed, plain, b, n = placed_pair
    x = jnp.asarray(np.random.default_rng(1).normal(
        size=(b, 2, n)).astype(np.float32))
    h = lambda lam: jnp.exp(-2.0 * lam)              # noqa: E731
    np.testing.assert_array_equal(np.asarray(placed.step(x, h)),
                                  np.asarray(plain.step(x, h)))


def test_placement_requires_batched_stack():
    mesh = make_local_mesh()
    lap = laplacian(community_graph(16, seed=0))
    pl = single_bucket_placement(mesh, 1)
    with pytest.raises(ValueError, match="batched"):
        FGFTServeEngine(jnp.asarray(lap), 48, placement=pl)


def test_placement_batch_mismatch_raises():
    mesh = make_local_mesh()
    laps = np.stack([laplacian(community_graph(16, seed=s))
                     for s in range(3)])
    pl = single_bucket_placement(mesh, 5)
    with pytest.raises(ValueError, match="placement.batch"):
        FGFTServeEngine(jnp.asarray(laps), 48, placement=pl)


# ---------------------------------------------------------------------------
# placed ragged router: auto-placement, save/load, manifest corruption
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def placed_router():
    mesh = make_local_mesh()
    sizes = [10, 16, 24, 12]
    laps = [laplacian(community_graph(s, seed=s)) for s in sizes]
    router = RaggedFGFTServeEngine(laps, n_iter=1, mesh=mesh,
                                   placement="auto", dynamic=True)
    return router, sizes


def _signals(sizes, seed=0):
    return [np.random.default_rng(seed + i).normal(
        size=(2, s)).astype(np.float32) for i, s in enumerate(sizes)]


def test_router_auto_placement_covers_buckets(placed_router):
    router, _ = placed_router
    man = router.placement.manifest()
    assert set(man["buckets"]) == {str(w) for w in router.engines}
    for w, eng in router.engines.items():
        assert eng.placement is router.placement[w]


def test_router_placed_save_load_bit_identical(placed_router, tmp_path):
    router, sizes = placed_router
    router.save(tmp_path, step=1)
    assert (tmp_path / "placement.json").exists()
    loaded = RaggedFGFTServeEngine.load(tmp_path)
    assert loaded.placement is not None
    sig = _signals(sizes)
    for a, b in zip(router.step(sig), loaded.step(sig)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and an explicitly UNPLACED load also serves identically
    flat = RaggedFGFTServeEngine.load(tmp_path, placement=False)
    assert flat.placement is None
    for a, b in zip(router.step(sig), flat.step(sig)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_load_serves_saved_tier_spectra(tmp_path, monkeypatch):
    """A restored engine serves the cut-tier spectra it was saved with
    (a reader whose devices batch the Lemma-1 refit differently must not
    serve other bits); caller-supplied Laplacians refit them instead."""
    from repro.dynamic import refit
    laps = np.stack([laplacian(community_graph(16, seed=s))
                     for s in range(3)])
    eng = FGFTServeEngine(jnp.asarray(laps), 96, n_iter=1,
                          tiers={"draft": 0.25, "half": 0.5, "full": 1.0})
    eng.save(tmp_path, step=1)
    calls = []
    real = refit.prefix_spectrum
    monkeypatch.setattr(refit, "prefix_spectrum",
                        lambda *a: calls.append(a[2]) or real(*a))
    loaded = FGFTServeEngine.load(tmp_path)
    assert calls == []
    for name in ("draft", "half", "full"):
        np.testing.assert_array_equal(
            np.asarray(loaded.tiers[name]["spectrum"]),
            np.asarray(eng.tiers[name]["spectrum"]))
    FGFTServeEngine.load(tmp_path, laps=jnp.asarray(laps))
    assert sorted(calls) == sorted(
        t["num_stages"] for name, t in eng.tiers.items() if name != "full")


def test_corrupt_placement_manifest_raises(placed_router, tmp_path):
    router, _ = placed_router
    router.save(tmp_path, step=1)
    (tmp_path / "placement.json").write_text('{"buckets": {}}')
    with pytest.raises(ValueError, match="corrupt placement manifest"):
        RaggedFGFTServeEngine.load(tmp_path)
    (tmp_path / "placement.json").write_text("not json at all")
    with pytest.raises(ValueError, match="corrupt placement manifest"):
        RaggedFGFTServeEngine.load(tmp_path)


def test_maintain_dirty_only_skips_clean_buckets(placed_router):
    router, sizes = placed_router
    assert router.maintain(dirty_only=True) == {}    # nothing dirty
    router.apply_updates(2, np.eye(sizes[2], dtype=np.float32) * 0.01)
    w_dirty = router.widths[2]
    res = router.maintain(dirty_only=True)
    assert list(res) == [w_dirty]                    # only the dirty bucket


# ---------------------------------------------------------------------------
# shard-aware checkpoint store (checkpoint/store.py)
# ---------------------------------------------------------------------------


def test_sharded_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import restore_checkpoint, save_checkpoint
    state = {"big": np.arange(24, dtype=np.float32).reshape(6, 4),
             "tiny": np.arange(3, dtype=np.float32),
             "scalar": np.float32(7.0)}
    save_checkpoint(tmp_path, 5, state, shards=4)
    files = sorted(p.name for p in (tmp_path / "step_000000005").iterdir()
                   if p.name.startswith("leaves_"))
    assert files == [f"leaves_{s:03d}.npz" for s in range(4)]
    like = {k: jnp.zeros_like(np.asarray(v)) for k, v in state.items()}
    got, step, _ = restore_checkpoint(tmp_path, like)
    assert step == 5
    for k in state:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(state[k]))


def test_sharded_checkpoint_small_leaves_land_in_shard_zero(tmp_path):
    from repro.checkpoint import save_checkpoint
    state = {"tiny": np.arange(3, dtype=np.float32)}     # 3 rows < 4 shards
    save_checkpoint(tmp_path, 1, state, shards=4)
    files = [p.name for p in (tmp_path / "step_000000001").iterdir()
             if p.name.startswith("leaves_")]
    assert files == ["leaves_000.npz"]                   # no empty files


def test_checkpoint_shards_validation(tmp_path):
    from repro.checkpoint import save_checkpoint
    with pytest.raises(ValueError):
        save_checkpoint(tmp_path, 0, {"a": np.zeros(2)}, shards=0)
