"""Ahead-of-time compiles for a described TPU v5e (no chip needed).

The TPU compiler is installed with jaxlib and compiles for a topology
that is only described, so what Mosaic or XLA:TPU would refuse fails
here, before any chip time: the fit programs the ragged router runs at
the paper's bucket widths, and the batched serving programs (operator
and filter bank, both families, both backends) at n=4096.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and under pytest-xdist every
worker imports this file.  These tests compile in their own process for
the same reason.
"""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.core import eigenbasis as eb
from repro.kernels import butterfly as bf
from repro.kernels.plan import ApplyPlan, clear_plan_cache


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def native_pallas(monkeypatch):
    """The default backend here is the CPU, where kernels interpret; a
    compile for the TPU needs them lowered through Mosaic instead."""
    monkeypatch.setattr(bf, "resolve_interpret", lambda interpret: False)
    clear_plan_cache()
    yield
    clear_plan_cache()


def _spec(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _g_log_g(n: int) -> int:
    return int(2 * n * np.log2(n))


@pytest.mark.parametrize("family,n", [("sym", 2048), ("general", 1024)])
def test_router_fit_program_compiles(one_chip, family, n):
    """The masked batched fit a one-graph bucket runs (n_iter=3, the
    router's default g = 2 n log2 n)."""
    g = _g_log_g(n)
    if family == "sym":
        prog = eb._sym_fit_program(g, 3, True, 1e-3, "gamma", True, True)
    else:
        prog = eb._gen_fit_program(g, 3, True, 1e-3, True, True)
    compiled = prog.lower(_spec(one_chip, (1, n, n)),
                          _spec(one_chip, (1, n)),
                          _spec(one_chip, (1,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16e9


def test_fit_split_over_four_chips_gathers_nothing(topo):
    """A batch sharded over a 2x2 mesh fits each chip's matrices on that
    chip: no all-gather, permute or all-to-all in the compiled program
    (the one all-reduce is the convergence test of the sweep loop)."""
    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",))
    batch = NamedSharding(mesh, PartitionSpec("data"))
    n = 1024
    prog = eb._sym_fit_program(_g_log_g(n), 3, True, 1e-3, "gamma", True,
                               True, 4)
    text = prog.lower(
        jax.ShapeDtypeStruct((4, n, n), jnp.float32, sharding=batch),
        jax.ShapeDtypeStruct((4, n), jnp.float32, sharding=batch),
        jax.ShapeDtypeStruct((4,), jnp.int32, sharding=batch),
    ).compile().as_text()
    for op in ("all-gather", "collective-permute", "all-to-all"):
        assert f" {op}(" not in text and f" {op}-start(" not in text, op


def _tables(one_chip, family, b, stages, width):
    ints = 2
    vals = 3 if family == "sym" else 2
    return tuple(_spec(one_chip, (b, stages, width), jnp.int32)
                 for _ in range(ints)) + tuple(
        _spec(one_chip, (b, stages, width)) for _ in range(vals))


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("mode", ["operator", "bank"])
@pytest.mark.parametrize("family", ["sym", "general"])
def test_serving_program_compiles_at_4096(one_chip, native_pallas, family,
                                          mode, backend):
    """A 4096 bucket of three graphs, 8 signals each: the tier operator
    and the five-filter bank as the router serves them."""
    n, b, rows, filters = 4096, 3, 8, 5
    width = n // 2 if family == "sym" else n
    plan = ApplyPlan(family=family, mode=mode, n=n, batched=True,
                     backend=backend, block_b=bf.DEFAULT_BLOCK_B)
    tables = _tables(one_chip, family, b, 64, width)
    diag = (_spec(one_chip, (b, n)) if mode == "operator"
            else _spec(one_chip, (b, filters, n)))
    compiled = plan.program().lower(
        tables, tables, diag, _spec(one_chip, (b, rows, n))).compile()
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == (backend == "pallas")


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("mode", ["operator", "bank"])
@pytest.mark.parametrize("family", ["sym", "general"])
def test_row_program_compiles_at_4096(one_chip, native_pallas, family,
                                      mode, backend):
    """The front door's per-graph program on the same bucket: one
    graph's 8 signals against the three graphs' tables, spectra or
    gains, the graph's row an argument."""
    n, b, rows, filters = 4096, 3, 8, 5
    width = n // 2 if family == "sym" else n
    plan = ApplyPlan(family=family, mode=mode, n=n, batched=True,
                     backend=backend, block_b=bf.DEFAULT_BLOCK_B, row=True)
    tables = _tables(one_chip, family, b, 64, width)
    diag = (_spec(one_chip, (b, n)) if mode == "operator"
            else _spec(one_chip, (b, filters, n)))
    compiled = plan.program().lower(
        tables, tables, diag, _spec(one_chip, (rows, n)),
        _spec(one_chip, (), jnp.int32)).compile()
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == (backend == "pallas")
