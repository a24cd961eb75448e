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
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.core import eigenbasis as eb
from repro.kernels import butterfly as bf
from repro.kernels.plan import (ApplyPlan, _legs_program, clear_plan_cache,
                                dense_leg_count)


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


def _entry(text: str) -> list:
    """The instructions of a compiled module's entry computation."""
    body = text[text.index("\nENTRY "):]
    return body[:body.index("\n}")].splitlines()[1:]


@pytest.mark.parametrize("row", [False, True])
@pytest.mark.parametrize("mode", ["operator", "bank"])
@pytest.mark.parametrize("family", ["sym", "general"])
def test_dense_program_compiles_at_4096(one_chip, family, mode, row):
    """The dense lowering of the same bucket, whole and per graph: the
    matmuls read the (3, 4096, 4096) legs in place (a row's slice fuses
    into them), so the entry computation materializes no leg."""
    n, b, rows, filters = 4096, 3, 8, 5
    plan = ApplyPlan(family=family, mode=mode, n=n, batched=True,
                     lowering="dense", row=row)
    legs = tuple(_spec(one_chip, (b, n, n))
                 for _ in range(dense_leg_count(family)))
    scale = (_spec(one_chip, (b, n)) if mode == "operator"
             else _spec(one_chip, (b, filters, n)))
    x = _spec(one_chip, (rows, n) if row else (b, rows, n))
    args = (legs, scale, x) + ((_spec(one_chip, (), jnp.int32),) if row
                               else ())
    text = plan.program().lower(*args).compile().as_text()
    assert "tpu_custom_call" not in text
    made = [line for line in _entry(text)
            if re.search(r"= f32\[(\d+,)?4096,4096\]", line)
            and " parameter(" not in line]
    assert made == []


@pytest.mark.parametrize("family, stages, cuts", [
    ("sym", 1206, (300, 600, None)),
    ("general", 98, (26, 39, None)),
])
def test_dense_leg_build_compiles_at_4096(one_chip, family, stages, cuts):
    """The swap-time leg build for a bucket of three graphs at the
    fleets' full depths and three tier cuts fits the chip."""
    n, b = 4096, 3
    width = n // 2 if family == "sym" else n
    tables = _tables(one_chip, family, b, stages, width)
    compiled = _legs_program(family, n, True, cuts).lower(
        tables, tables).compile()
    mem = compiled.memory_analysis()
    legs = len(cuts) * dense_leg_count(family) * b * n * n * 4
    # the legs, and the output tuple's few index bytes
    assert legs <= mem.output_size_in_bytes < legs + (1 << 20)
    assert mem.temp_size_in_bytes + legs < 4e9


@pytest.mark.parametrize("mode", ["operator", "bank"])
@pytest.mark.parametrize("family", ["sym", "general"])
def test_placed_dense_program_splits_over_four_chips(topo, family, mode):
    """A placed bucket of four graphs at n = 4096, one a chip: the dense
    step and the leg build that feeds it, both on batch-split operands,
    compile for the 2x2 mesh with no collective."""
    from repro.runtime import hlo_analysis as hlo
    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",))
    n, b, rows, filters = 4096, 4, 8, 5

    def split(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(
            mesh, PartitionSpec("data", *(None,) * (len(shape) - 1))))

    plan = ApplyPlan(family=family, mode=mode, n=n, batched=True,
                     lowering="dense")
    legs = tuple(split((b, n, n)) for _ in range(dense_leg_count(family)))
    scale = (split((b, n)) if mode == "operator"
             else split((b, filters, n)))
    step = plan.program().lower(legs, scale,
                                split((b, rows, n))).compile().as_text()
    assert sum(hlo.collective_bytes(step)["counts"].values()) == 0
    stages, width = (1206, n // 2) if family == "sym" else (98, n)
    tables = (tuple(split((b, stages, width), jnp.int32) for _ in range(2))
              + tuple(split((b, stages, width))
                      for _ in range(3 if family == "sym" else 2)))
    build = _legs_program(family, n, True, (None,)).lower(
        tables, tables).compile().as_text()
    assert sum(hlo.collective_bytes(build)["counts"].values()) == 0
