"""Integration tests: train driver (with checkpoint/restart), graph
generators, and the dry-run's step builder on one device."""
import numpy as np
import pytest
import jax

from repro.graphs import (community_graph, erdos_renyi, sensor_graph,
                          directed_variant, real_graph_standin)


@pytest.mark.slow
def test_train_driver_runs_and_resumes(tmp_path):
    from repro.launch import train as train_mod
    ckpt = str(tmp_path / "ckpt")
    loss1 = train_mod.main([
        "--arch", "qwen2-1.5b", "--smoke", "--steps", "6",
        "--seq-len", "32", "--global-batch", "4", "--ckpt-every", "3",
        "--ckpt-dir", ckpt, "--log-every", "3"])
    assert np.isfinite(loss1)
    # resume continues from step 6 (runs 4 more)
    loss2 = train_mod.main([
        "--arch", "qwen2-1.5b", "--smoke", "--steps", "10",
        "--seq-len", "32", "--global-batch", "4", "--ckpt-every", "5",
        "--ckpt-dir", ckpt, "--resume", "auto", "--log-every", "2"])
    assert np.isfinite(loss2)


@pytest.mark.slow
def test_train_driver_grad_compression(tmp_path):
    from repro.launch import train as train_mod
    loss = train_mod.main([
        "--arch", "qwen2-1.5b", "--smoke", "--steps", "4",
        "--seq-len", "32", "--global-batch", "4",
        "--grad-compress-ratio", "0.25",
        "--ckpt-dir", str(tmp_path / "c"), "--log-every", "2"])
    assert np.isfinite(loss)


def test_graph_generators_shapes():
    for gen in (community_graph, erdos_renyi, sensor_graph):
        a = gen(48, seed=1)
        assert a.shape == (48, 48)
        np.testing.assert_allclose(a, a.T)
        assert np.all(np.diag(a) == 0)
        assert a.sum() > 0


def test_directed_variant_orients_edges():
    a = erdos_renyi(32, seed=2)
    d = directed_variant(a, seed=2)
    # every undirected edge appears exactly once in one direction
    np.testing.assert_allclose(d + d.T, a)
    assert (d * d.T).sum() == 0


def test_real_graph_standins_match_specs():
    specs = {"minnesota": (2642, 3304), "email": (1133, 5451)}
    for name, (n, m) in specs.items():
        a = real_graph_standin(name)
        assert a.shape == (n, n)
        assert int(np.triu(a, 1).sum()) == m


@pytest.mark.slow
def test_dryrun_runs_tiny_cell_on_one_device():
    """Exercise the step-builder + roofline analysis path on the local
    1-device mesh (the 512-device path is covered by launch/dryrun.py)."""
    from repro.configs import get_config
    from repro.runtime import steps as steps_lib
    from repro.runtime import hlo_analysis as hlo
    cfg = get_config("qwen2-1.5b", smoke=True)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with mesh:
        bundle = steps_lib.make_train_step(cfg, mesh, seq_len=32,
                                           global_batch=2)
        compiled = bundle.fn.lower(bundle.abstract_state,
                                   bundle.abstract_batch).compile()
        terms = hlo.roofline_terms(compiled)
    assert terms["compute_s"] > 0
    assert np.isfinite(terms["memory_s"])
