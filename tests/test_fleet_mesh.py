"""Mesh-partitioned fleet, multi-device tier (DESIGN.md §14): device
ownership, collective-free steady state, device-overlapped maintenance
and cross-mesh-shape checkpoint restores — all on forced host CPU
devices in subprocesses (conftest.run_in_mesh_subprocess)."""
import numpy as np
import pytest

from conftest import run_in_mesh_subprocess

pytestmark = pytest.mark.slow

_SIZES = [10, 16, 24, 24, 12, 30, 9, 24]

_FLEET_PRELUDE = """
    import json
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core.fgft import laplacian
    from repro.graphs import community_graph
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import RaggedFGFTServeEngine

    SIZES = %r
    def fleet():
        return [laplacian(community_graph(s, seed=s)) for s in SIZES]
    def signals():
        return [np.random.default_rng(100 + i).normal(
            size=(2, s)).astype(np.float32) for i, s in enumerate(SIZES)]
""" % (_SIZES,)


def test_placed_fleet_owns_devices_and_serves_collective_free():
    """Every bucket's live tables live ONLY on that bucket's devices, and
    the lowered steady-state step program contains ZERO collectives."""
    res = run_in_mesh_subprocess(_FLEET_PRELUDE + """
    from repro.runtime import hlo_analysis as hlo

    mesh = make_local_mesh()
    r = RaggedFGFTServeEngine(fleet(), n_iter=1, mesh=mesh,
                              placement="auto", dynamic=True)
    ownership, collectives = {}, {}
    for w, eng in r.engines.items():
        want = set(eng.placement.device_ids)
        got = set()
        for leaf in eng._live.fwd:
            got |= {d.id for d in leaf.sharding.device_set}
        ownership[str(w)] = [sorted(want), sorted(got)]
        live = eng._live
        tier = eng.default_tier
        xp = eng.placement.place(jnp.zeros(
            (eng.placement.batch, 2, eng.basis.n), jnp.float32))
        txt = live.fns[tier].lower(
            live.fwd, live.bwd, live.tiers[tier]["spectrum"],
            xp).compile().as_text()
        collectives[str(w)] = sum(
            hlo.collective_bytes(txt)["counts"].values())
    print(json.dumps({
        "num_devices": len(jax.devices()),
        "buckets": sorted(r.engines),
        "ownership": ownership,
        "collectives": collectives,
        "all_devices_used": sorted(
            {i for w, eng in r.engines.items()
             for i in eng.placement.device_ids})}))
    """, devices=8)
    assert res["num_devices"] == 8
    for w, (want, got) in res["ownership"].items():
        assert got == want, f"bucket {w} tables leaked off its devices"
    assert all(c == 0 for c in res["collectives"].values()), res
    # both buckets present, devices partitioned over them
    assert len(res["buckets"]) >= 2
    assert res["all_devices_used"] == list(range(8))


def test_placed_router_dispatches_one_whole_bucket_launch():
    """Through the async front door a placed bucket keeps its one
    whole-bucket launch a dispatch (its batch axis is split over
    devices, so no row program may select a graph), and the answers are
    the router's own ``step``."""
    res = run_in_mesh_subprocess(_FLEET_PRELUDE + """
    from repro.launch.service import AsyncFGFTService

    mesh = make_local_mesh()
    r = RaggedFGFTServeEngine(fleet(), n_iter=1, mesh=mesh,
                              placement="auto")
    sig = signals()
    want = r.step(sig)
    w = r.widths[0]
    eng = r.engines[w]
    graphs = r.bucket_of[w][:2]
    steps0 = eng.stats["steps"]["full"]
    svc = AsyncFGFTService(r, auto_start=False)
    futs = [svc.submit(g, sig[g]) for g in graphs]
    served = svc.drain_once()
    st = svc.stats()
    svc.close()
    diff = max(float(np.abs(f.result(timeout=0).y - want[g]).max())
               for f, g in zip(futs, graphs))
    print(json.dumps({
        "served": served, "dispatches": st["dispatches"],
        "graph_blocks": st["graph_blocks"],
        "launches": eng.stats["steps"]["full"] - steps0,
        "bucket_graphs": len(r.bucket_of[w]),
        "placed": eng.placement is not None, "row_steps": eng.row_steps,
        "diff": diff}))
    """, devices=4)
    assert res["placed"] and not res["row_steps"]
    assert res["served"] == 2 and res["dispatches"] == 1
    assert res["launches"] == 1
    # the whole bucket is walked, not only the two graphs asked about
    assert res["graph_blocks"] == res["bucket_graphs"] > 2
    assert res["diff"] == 0.0, res


def test_overlapped_maintenance_touches_only_dirty_bucket():
    """A dirty bucket's refit bumps ONLY that bucket's serving version;
    clean buckets keep serving their version untouched (and the placed
    refit shards over the bucket's own sub-mesh)."""
    res = run_in_mesh_subprocess(_FLEET_PRELUDE + """
    mesh = make_local_mesh()
    r = RaggedFGFTServeEngine(fleet(), n_iter=1, mesh=mesh,
                              placement="auto", dynamic=True)
    before = {str(w): e._live.version for w, e in r.engines.items()}
    empty = r.maintain(dirty_only=True)
    dirty_graph = 2
    w_dirty = r.widths[dirty_graph]
    r.apply_updates(dirty_graph, np.eye(
        SIZES[dirty_graph], dtype=np.float32) * 0.05)
    ticked = sorted(str(w) for w in r.maintain(dirty_only=True))
    after = {str(w): e._live.version for w, e in r.engines.items()}
    sub_mesh_devices = sorted(
        d.id for d in r.engines[w_dirty].mesh.devices.ravel())
    print(json.dumps({
        "empty_tick": sorted(empty), "ticked": ticked,
        "w_dirty": str(w_dirty), "before": before, "after": after,
        "sub_mesh_devices": sub_mesh_devices,
        "owned": sorted(r.placement[w_dirty].device_ids)}))
    """, devices=8)
    assert res["empty_tick"] == []
    assert res["ticked"] == [res["w_dirty"]]
    for w, v0 in res["before"].items():
        if w == res["w_dirty"]:
            assert res["after"][w] >= v0           # monotone, may bump
        else:
            assert res["after"][w] == v0           # untouched
    # the dirty bucket's refit mesh IS its owned device subset
    assert res["sub_mesh_devices"] == res["owned"]


def _save_script(ckpt_dir):
    return _FLEET_PRELUDE + f"""
    import pathlib
    mesh = make_local_mesh()
    r = RaggedFGFTServeEngine(fleet(), n_iter=1, mesh=mesh,
                              placement="auto")
    r.save({str(ckpt_dir)!r}, step=3)
    outs = r.step(signals())
    for i, y in enumerate(outs):
        np.save(pathlib.Path({str(ckpt_dir)!r}) / f"out_{{i}}.npy",
                np.asarray(y))
    shard_files = sorted(
        p.name for p in pathlib.Path({str(ckpt_dir)!r}).rglob(
            "leaves_*.npz"))
    print(json.dumps({{"devices": len(jax.devices()),
                       "n_shard_files": len(shard_files)}}))
    """


def _load_script(ckpt_dir):
    return _FLEET_PRELUDE + f"""
    import pathlib
    r = RaggedFGFTServeEngine.load({str(ckpt_dir)!r})
    outs = r.step(signals())
    diffs = []
    for i, y in enumerate(outs):
        want = np.load(pathlib.Path({str(ckpt_dir)!r}) / f"out_{{i}}.npy")
        diffs.append(float(np.abs(np.asarray(y) - want).max()))
    print(json.dumps({{"devices": len(jax.devices()),
                       "placed": r.placement is not None,
                       "max_diff": max(diffs)}}))
    """


def test_shard_checkpoint_restores_across_mesh_shapes(tmp_path):
    """Save a placed fleet on a 4-device mesh (one table shard per owning
    device), then load on 1- and 8-device meshes: the load RE-PLACES onto
    the reader's devices and serves bit-identical sym outputs."""
    saved = run_in_mesh_subprocess(_save_script(tmp_path), devices=4)
    assert saved["devices"] == 4
    # one shard file per owning device, summed over both buckets
    assert saved["n_shard_files"] == 4
    for devices in (1, 8):
        res = run_in_mesh_subprocess(_load_script(tmp_path),
                                     devices=devices)
        assert res["devices"] == devices
        assert res["placed"] is True                 # re-placed, not flat
        assert res["max_diff"] == 0.0, (devices, res)   # sym: bitwise


def test_placed_matches_unplaced_from_same_checkpoint(tmp_path):
    """The placement layer must not change serving math: a placed load
    and an unplaced load of the SAME checkpoint agree bitwise."""
    run_in_mesh_subprocess(_save_script(tmp_path), devices=4)
    res = run_in_mesh_subprocess(_FLEET_PRELUDE + f"""
    r_placed = RaggedFGFTServeEngine.load({str(tmp_path)!r})
    r_flat = RaggedFGFTServeEngine.load({str(tmp_path)!r},
                                        placement=False)
    sig = signals()
    a, b = r_placed.step(sig), r_flat.step(sig)
    diff = max(float(np.abs(np.asarray(x) - np.asarray(y)).max())
               for x, y in zip(a, b))
    print(json.dumps({{"diff": diff,
                       "placed": r_placed.placement is not None,
                       "flat": r_flat.placement is None}}))
    """, devices=8)
    assert res["placed"] and res["flat"]
    assert res["diff"] == 0.0, res
    out = np.load(tmp_path / "out_0.npy")            # saved by the writer
    assert out.shape == (2, _SIZES[0])


def test_placed_dense_legs_own_devices_and_match_one_device():
    """On a TPU (its facts faked here) a placed fleet serves dense too:
    each bucket's legs live only on its devices, the lowered dense step
    holds zero collectives, and every tier and the bank answer as the
    one-device dense router from the same checkpoint does, bitwise."""
    res = run_in_mesh_subprocess(_FLEET_PRELUDE + """
    import tempfile
    import repro.launch.serve as serve_mod
    from repro.runtime import hlo_analysis as hlo

    serve_mod._device_facts = lambda devices: ("tpu", 16 << 30)
    mesh = make_local_mesh()
    tiers = {"full": 1.0, "draft": 0.5}
    with tempfile.TemporaryDirectory() as d:
        RaggedFGFTServeEngine(fleet(), n_iter=1, tiers=tiers,
                              filters="heat,lowpass").save(d)
        placed = RaggedFGFTServeEngine.load(d, placement="auto", mesh=mesh)
        flat = RaggedFGFTServeEngine.load(d, placement=False)
    ownership, collectives, lowering = {}, {}, {}
    for w, eng in placed.engines.items():
        live, tier = eng._live, eng.default_tier
        got = set()
        for legs in live.legs.values():
            for leaf in legs:
                got |= {dev.id for dev in leaf.sharding.device_set}
        ownership[str(w)] = [sorted(eng.placement.device_ids), sorted(got)]
        lowering[str(w)] = [eng.lowering, flat.engines[w].lowering]
        xp = eng.placement.place(jnp.zeros(
            (eng.placement.batch, 2, eng.basis.n), jnp.float32))
        txt = live.fns[tier].lower(
            *live.operands(tier), live.tiers[tier]["spectrum"],
            xp).compile().as_text()
        collectives[str(w)] = sum(
            hlo.collective_bytes(txt)["counts"].values())
    sig = signals()
    diff = 0.0
    for tier in tiers:
        for a, b in zip(placed.step(sig, tier=tier),
                        flat.step(sig, tier=tier)):
            diff = max(diff, float(np.abs(np.asarray(a) - np.asarray(b)).max()
                                   / max(np.abs(np.asarray(b)).max(), 1e-30)))
    for a, b in zip(placed.step_bank(sig), flat.step_bank(sig)):
        diff = max(diff, float(np.abs(np.asarray(a) - np.asarray(b)).max()
                               / max(np.abs(np.asarray(b)).max(), 1e-30)))
    print(json.dumps({"ownership": ownership, "collectives": collectives,
                      "lowering": lowering, "diff": diff}))
    """, devices=4)
    assert all(v == ["dense", "dense"] for v in res["lowering"].values()), res
    for w, (want, got) in res["ownership"].items():
        assert got == want, f"bucket {w} legs leaked off its devices"
    assert all(c == 0 for c in res["collectives"].values()), res
    assert res["diff"] == 0.0, res
