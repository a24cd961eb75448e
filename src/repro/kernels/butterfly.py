"""Pallas TPU kernel: staged G-transform (butterfly) application.

TPU mapping (DESIGN.md §4).  A signal block is held TRANSPOSED in VMEM:
graph coordinates on the sublane axis, signals on the 128-wide lane axis,
so one coordinate of every signal in the tile is one (1, lanes) row.  A
2x2 transform on coordinates (i, j) then loads rows i and j at scalar
offsets, does its 6 flops per lane on the VPU and stores the two rows
back — no data-dependent lane gather or scatter, which Mosaic cannot
lower.  The stage tables are streamed through SMEM in (8, chunk) blocks
(eight rows is the least Mosaic accepts; a row is up to 1024 entries of
one stage), so their size is bounded by HBM, not by SMEM or VMEM.  The
2x2 transforms are deliberately NOT mapped to the MXU: a stage is a
block-diagonal orthonormal matrix whose dense form would waste n^2/(3n)
of the systolic array.

``chain_call`` is the one launch behind every kernel entry point of this
package (shear.py and spectral.py reuse it): an optional analysis leg,
then per filter a diagonal scale and a synthesis leg, all inside one
``pallas_call`` whose last grid axis walks the table blocks.  The signal
tile never leaves VMEM between legs (one HBM read, one write per filter
and tile).  Plain applies are the synthesis leg alone; the fused
operator ``Ubar diag(d) Ubar^T`` is a one-filter bank.

Padding entries of the stage tables carry the out-of-bounds index n; the
block holds a zero dummy row there (rows are padded to a multiple of 8),
so pads read and write only that row.  The kernels compute in f32; a
narrower signal dtype is rounded back after every product and sum, table
values are first cast to the signal dtype, and the diagonal is too — the
rounding points of the ``kernels/ref.py`` oracles, which the tests hold
them to.

Kernels run compiled on a TPU and through the Pallas interpreter only on
the CPU, where Mosaic cannot lower (``interpret=None`` picks by backend).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.staging import StagedG, table_arrays, truncate_staged

DEFAULT_BLOCK_B = 128

_LANES = 128
#: table rows per grid step (an (8, chunk) SMEM block per table); a row
#: holds up to _MAX_CHUNK entries of one stage, so the double-buffered
#: blocks of four or five tables stay well inside the 1 MiB of SMEM
_BLOCK_ROWS = 8
_MAX_CHUNK = 1024
#: scoped-VMEM request bounds (v5e has 128 MiB of VMEM per core)
_VMEM_FLOOR = 16 * 1024 * 1024
_VMEM_CAP = 96 * 1024 * 1024

#: identity entry (index slots take the width n): c=1, s=0, sigma=1
G_NOOP = (1.0, 0.0, 1.0)


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Interpret mode exactly when the default backend is the CPU.

    ``None`` picks by backend; an explicit ``True`` is accepted on the
    CPU only, so no path can run the interpreter on an accelerator."""
    on_cpu = jax.default_backend() == "cpu"
    if interpret is None:
        return on_cpu
    if interpret and not on_cpu:
        raise ValueError(f"Pallas interpret mode is for the CPU backend; "
                         f"this process runs on {jax.default_backend()!r}")
    return bool(interpret)


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def _g_pair(buf, rnd, i, j, c, s, sg):
    xi = buf[pl.ds(i, 1), :]
    xj = buf[pl.ds(j, 1), :]
    buf[pl.ds(i, 1), :] = rnd(rnd(c * xi) + rnd(s * xj))
    buf[pl.ds(j, 1), :] = rnd(sg * rnd(rnd(-s * xi) + rnd(c * xj)))


def _chain_kernel(*refs, pair, num_tables, chunk, rows_a, rows_s, k_a,
                  k_s, has_gains, rnd):
    """Grid cell (b, i, k): k < k_a walks analysis table block k over the
    coefficient scratch; from k_a on, block (k - k_a) % k_s of the
    synthesis tables runs on filter (k - k_a) // k_s's output tile, which
    each filter seeds from the coefficients (times its gains)."""
    tabs = refs[:num_tables]
    rest = refs[num_tables:]
    g_ref = rest[0] if has_gains else None
    x_ref, o_ref = rest[int(has_gains)], rest[int(has_gains) + 1]
    coeff = rest[int(has_gains) + 2] if k_a else x_ref
    k = pl.program_id(2)
    q_blk = jnp.maximum(k - k_a, 0) % k_s

    def run(buf, first_row, num_rows):
        for r in range(_BLOCK_ROWS):
            @pl.when(first_row + r < num_rows)
            def _():
                def body(p, carry):
                    pair(buf, rnd, *(t[r, p] for t in tabs))
                    return carry
                lax.fori_loop(0, chunk, body, 0)

    if k_a:
        @pl.when(k == 0)
        def _():
            coeff[...] = x_ref[...]

        @pl.when(k < k_a)
        def _():
            run(coeff, k * _BLOCK_ROWS, rows_a)

    @pl.when(jnp.logical_and(k >= k_a, q_blk == 0))
    def _():
        seed = coeff[...]
        o_ref[...] = rnd(seed * g_ref[...]) if has_gains else seed

    @pl.when(k >= k_a)
    def _():
        run(o_ref, q_blk * _BLOCK_ROWS, rows_s)


def _leg_rows(leg: Sequence[jnp.ndarray], noop, n: int, width: int,
              chunk: int, dtype) -> tuple:
    """(B, S, P) tables -> (B, rows_pad, chunk) in execution order (row =
    one chunk of one stage; a stage's pairs are disjoint, so its chunks
    may run in sequence), plus the count of real rows.  Index tables stay
    int32; value tables are rounded through the signal dtype to f32 (SMEM
    is 32-bit); added entries are identity transforms at the dummy index
    n."""
    b, s, p = leg[0].shape
    rows = s * (width // chunk)
    rows_pad = max(_round_up(rows, _BLOCK_ROWS), _BLOCK_ROWS)
    out = []
    for k, t in enumerate(leg):
        if k < 2:
            t, fill = t.astype(jnp.int32), n
        else:
            t, fill = t.astype(dtype).astype(jnp.float32), noop[k - 2]
        t = jnp.pad(t, ((0, 0), (0, 0), (0, width - p)),
                    constant_values=fill).reshape(b, rows, chunk)
        out.append(jnp.pad(t, ((0, 0), (0, rows_pad - rows), (0, 0))))
    return out, rows


def chain_call(pair: Callable, noop, analysis, synthesis,
               gains: Optional[jnp.ndarray], x: jnp.ndarray,
               block_b: int = DEFAULT_BLOCK_B,
               interpret: Optional[bool] = None) -> jnp.ndarray:
    """Staged chains over a batch of signal blocks in ONE pallas_call.

    ``analysis``/``synthesis``: table tuples ((B, S, P) arrays, the two
    index tables first; ``analysis`` may be None).  ``gains``: (B, F, n)
    per-filter diagonals, or None (one filter, no scale).  ``x``:
    (B, R, n).  Returns (B, F, R, n): per filter f, synthesis applied to
    diag(gains_f) (analysis x).  ``block_b`` caps the signals per grid
    step; a tile that splits the signals is a multiple of 128 lanes."""
    interpret = resolve_interpret(interpret)
    b, r, n = x.shape
    dt = x.dtype
    width = max(leg[0].shape[-1] for leg in (analysis, synthesis)
                if leg is not None)
    chunk = min(width, _MAX_CHUNK)
    width = _round_up(width, chunk)
    tables, rows_s = _leg_rows(synthesis, noop, n, width, chunk, dt)
    rows_a = k_a = 0
    if analysis is not None:
        ana, rows_a = _leg_rows(analysis, noop, n, width, chunk, dt)
        k_a = ana[0].shape[1] // _BLOCK_ROWS
        tables = [jnp.concatenate([a, s], axis=1)
                  for a, s in zip(ana, tables)]
    k_tot = tables[0].shape[1] // _BLOCK_ROWS
    k_s = k_tot - k_a
    tables = [t.reshape(b * k_tot * _BLOCK_ROWS, chunk) for t in tables]

    rows = _round_up(n + 1, 8)                       # + the dummy row n
    bb = r if r <= block_b else _round_up(block_b, _LANES)
    r_pad = _round_up(r, bb)
    xt = jnp.swapaxes(x.astype(jnp.float32), 1, 2)   # (B, n, R)
    xt = jnp.pad(xt, ((0, 0), (0, rows - n), (0, r_pad - r)))
    num_filters = 1 if gains is None else gains.shape[1]
    operands = list(tables)
    if gains is not None:
        gt = gains.astype(dt).astype(jnp.float32)
        gt = jnp.pad(gt, ((0, 0), (0, 0), (0, rows - n)))[..., None]
        operands.append(gt)
    operands.append(xt)

    def table_block(bm, i, k):
        blk = jnp.where(k < k_a, k, k_a + jnp.maximum(k - k_a, 0) % k_s)
        return (bm * k_tot + blk, 0)

    def filt(k):
        return jnp.maximum(k - k_a, 0) // k_s

    in_specs = [pl.BlockSpec((_BLOCK_ROWS, chunk), table_block,
                             memory_space=pltpu.SMEM)] * len(tables)
    if gains is not None:
        in_specs.append(pl.BlockSpec((None, None, rows, 1),
                                     lambda bm, i, k: (bm, filt(k), 0, 0)))
    in_specs.append(pl.BlockSpec((None, rows, bb),
                                 lambda bm, i, k: (bm, 0, i)))
    out_spec = pl.BlockSpec((None, None, rows, bb),
                            lambda bm, i, k: (bm, filt(k), 0, i))
    scratch = [pltpu.VMEM((rows, bb), jnp.float32)] if k_a else []
    tile = rows * _round_up(bb, _LANES) * 4
    vmem = min(max(2 * (6 * tile + 2 * rows * _LANES * 4), _VMEM_FLOOR),
               _VMEM_CAP)
    rnd = ((lambda v: v) if dt == jnp.float32
           else (lambda v: v.astype(dt).astype(jnp.float32)))
    kernel = functools.partial(
        _chain_kernel, pair=pair, num_tables=len(tables), chunk=chunk,
        rows_a=rows_a, rows_s=rows_s, k_a=k_a, k_s=k_s,
        has_gains=gains is not None, rnd=rnd)
    out = pl.pallas_call(
        kernel,
        grid=(b, r_pad // bb, k_a + num_filters * k_s),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((b, num_filters, rows, r_pad),
                                       jnp.float32),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
    )(*operands)
    return jnp.swapaxes(out[:, :, :n, :r], 2, 3).astype(dt)


def batch_of_one(staged):
    """A single-matrix StagedG/StagedT as a batch of one."""
    return staged._replace(**{f: a[None] for f, a in zip(
        staged._fields, table_arrays(staged))})


_STATIC = ("block_b", "interpret", "num_stages")


@functools.partial(jax.jit, static_argnames=_STATIC + ("keep",))
def batched_butterfly_apply(staged: StagedG, x: jnp.ndarray,
                            block_b: int = DEFAULT_BLOCK_B,
                            interpret: Optional[bool] = None,
                            num_stages: int | None = None,
                            keep: str = "head") -> jnp.ndarray:
    """y[b] = Ubar_b x[b]: tables (B, S, P), x (B, R, n) -> (B, R, n).
    Static ``num_stages`` cuts the tables at a prefix boundary (DESIGN.md
    §9) — the kernel then walks exactly that many stages."""
    staged = truncate_staged(staged, num_stages, keep)
    return chain_call(_g_pair, G_NOOP, None, table_arrays(staged), None,
                      x, block_b, interpret)[:, 0]


@functools.partial(jax.jit, static_argnames=_STATIC + ("keep",))
def butterfly_apply(staged: StagedG, x: jnp.ndarray,
                    block_b: int = DEFAULT_BLOCK_B,
                    interpret: Optional[bool] = None,
                    num_stages: int | None = None,
                    keep: str = "head") -> jnp.ndarray:
    """y = Ubar @ x for x of shape (R, n) (vectors in rows)."""
    return batched_butterfly_apply(batch_of_one(staged), x[None], block_b,
                                   interpret, num_stages, keep)[0]


@functools.partial(jax.jit, static_argnames=_STATIC)
def batched_sym_operator_apply(fwd: StagedG, adj: StagedG,
                               diag: jnp.ndarray, x: jnp.ndarray,
                               block_b: int = DEFAULT_BLOCK_B,
                               interpret: Optional[bool] = None,
                               num_stages: int | None = None
                               ) -> jnp.ndarray:
    """y[b] = Ubar_b diag(d_b) Ubar_b^T x[b] for a batch of factorizations
    in one fused launch: tables (B, S, P), diag (B, n), x (B, R, n).
    Static ``num_stages`` cuts both legs to the same component prefix
    (adj head / fwd tail)."""
    adj = truncate_staged(adj, num_stages, "head")
    fwd = truncate_staged(fwd, num_stages, "tail")
    return chain_call(_g_pair, G_NOOP, table_arrays(adj), table_arrays(fwd),
                      diag[:, None], x, block_b, interpret)[:, 0]


@functools.partial(jax.jit, static_argnames=_STATIC)
def sym_operator_apply(fwd: StagedG, adj: StagedG, diag: jnp.ndarray,
                       x: jnp.ndarray, block_b: int = DEFAULT_BLOCK_B,
                       interpret: Optional[bool] = None,
                       num_stages: int | None = None) -> jnp.ndarray:
    """y = Ubar diag(d) Ubar^T x, fused: one VMEM round trip."""
    return batched_sym_operator_apply(
        batch_of_one(fwd), batch_of_one(adj), diag[None], x[None], block_b,
        interpret, num_stages)[0]
