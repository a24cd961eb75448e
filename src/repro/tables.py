"""The staged-table format: what the fit writes and the kernels read.

A fitted chain of 2x2 transforms is packed (core/staging.py) into
*stages* of pairwise-disjoint pairs; ``StagedG``/``StagedT`` hold those
stages as (S, P) tables, or (B, S, P) for a batch of graphs.  Every
apply path takes them: the Pallas kernels and the XLA reference walk
(kernels/), the serving plans (kernels/plan.py), the drift scorer
(dynamic/drift.py).

This module imports neither ``repro.core`` nor ``repro.kernels``, so
both layers take the format from here: the fit above, the kernels
below.  Pad entries are structural no-ops at the out-of-bounds index
``n``; ``cuts`` lists the stage counts at which a table set is exactly
cuttable (the anytime prefixes, DESIGN.md §9).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import numpy as np
import jax.numpy as jnp


class StagedG(NamedTuple):
    """G-transforms packed into conflict-free stages (padded to width P).

    ``idx_*``/``c``/``s``/``sigma`` are (S, P) tables — (B, S, P) when
    batched.  ``cuts`` is host metadata: an (C, 2) int array of
    (num_stages, num_components) pairs at which truncating the stage axis
    is exact (core/staging.py's docstring gives the head/tail orientation).
    Padding entries use the out-of-bounds index ``n`` with (c=1, s=0,
    sigma=1): an exact no-op under y_i = c x_i + s x_j;
    y_j = sigma (-s x_i + c x_j).
    """

    idx_i: jnp.ndarray   # (S, P) int32
    idx_j: jnp.ndarray   # (S, P) int32
    c: jnp.ndarray       # (S, P)
    s: jnp.ndarray       # (S, P)
    sigma: jnp.ndarray   # (S, P)
    cuts: Optional[np.ndarray]  # (C, 2) int64 host: (num_stages, num_comp)
    n: int

    @property
    def num_stages(self) -> int:
        return self.idx_i.shape[-2]


class StagedT(NamedTuple):
    """T-transforms packed into stages.  Unified per-pair action
    y_i = alpha x_i + beta x_j with (alpha, beta) = (1, a) for shears and
    (a, 0) for scalings.  Padding: (alpha=1, beta=0) at index ``n``.
    ``cuts`` carries the exact (num_stages, num_components) prefix
    ladder."""

    idx_i: jnp.ndarray   # (S, P) int32 (written coordinate)
    idx_j: jnp.ndarray   # (S, P) int32 (read coordinate)
    alpha: jnp.ndarray   # (S, P)
    beta: jnp.ndarray    # (S, P)
    cuts: Optional[np.ndarray]  # (C, 2) int64 host
    n: int

    @property
    def num_stages(self) -> int:
        return self.idx_i.shape[-2]


_G_TABLE_FIELDS = ("idx_i", "idx_j", "c", "s", "sigma")
_T_TABLE_FIELDS = ("idx_i", "idx_j", "alpha", "beta")


def _table_fields(staged) -> Tuple[str, ...]:
    return _G_TABLE_FIELDS if isinstance(staged, StagedG) else _T_TABLE_FIELDS


def table_arrays(staged) -> Tuple:
    """The device table arrays of a StagedG/StagedT, WITHOUT the host
    metadata tail (cuts ladder + width) — the canonical split used by
    programs that take staged tables as jit arguments (drift scoring,
    the serving tier programs)."""
    return tuple(staged[:len(_table_fields(staged))])


TABLE_PRECISIONS = ("f32", "bf16")


def with_precision(staged, precision: str):
    """Staged tables under a storage-precision policy.

    ``"f32"`` is the packing default (returned unchanged).  ``"bf16"``
    casts the VALUE tables (c/s/sigma for G, alpha/beta for T) to
    bfloat16 — index tables stay int32, and the ``cuts``/``n`` metadata
    is untouched, so every cut ladder and program cache key survives the
    cast.  Accumulation stays f32: the apply kernels cast table entries
    to the SIGNAL dtype at compute time (kernels/ref.py, butterfly.py,
    shear.py), so an f32 signal against bf16 tables upcasts each entry
    and accumulates in f32 — bf16 is purely a storage/bandwidth policy
    (half the table VMEM footprint; DESIGN.md §13).  The accuracy cost
    is bounded by the same ``2 Lip(h) delta`` accounting as the
    factorization error itself (tests/test_plan.py, fig13)."""
    if precision not in TABLE_PRECISIONS:
        raise ValueError(f"precision must be one of {TABLE_PRECISIONS}, "
                         f"got {precision!r}")
    dtype = jnp.float32 if precision == "f32" else jnp.bfloat16
    values = _table_fields(staged)[2:]          # skip idx_i / idx_j
    if all(getattr(staged, f).dtype == dtype for f in values):
        return staged
    return staged._replace(**{f: getattr(staged, f).astype(dtype)
                              for f in values})


_G_PAD_VALUES = (None, None, 1.0, 0.0, 1.0)   # idx fields use n
_T_PAD_VALUES = (None, None, 1.0, 0.0)


def pad_batch(staged, quantum: int):
    """Pad the leading batch axis of (B, S, P) tables up to a multiple of
    ``quantum`` with whole no-op rows (per-device batch quanta,
    DESIGN.md §14).

    A mesh placement splits the batch axis over a bucket's devices, which
    needs B divisible by the device count; rather than reshard, the batch
    pads with rows whose every entry is the structural no-op (out-of-bounds
    index ``n`` + identity values) — a pad row applies as the identity on
    its signal row, so padded tables on padded signals equal the original
    tables on the original signals (rows past B are untouched/zero).  The
    ``cuts`` ladder and ``n`` are batch-independent and survive unchanged.
    """
    if quantum < 1:
        raise ValueError(f"pad_batch: quantum must be >= 1, got {quantum}")
    tables = table_arrays(staged)
    if tables[0].ndim != 3:
        raise ValueError("pad_batch expects batched (B, S, P) tables, got "
                         f"ndim={tables[0].ndim}")
    b = tables[0].shape[0]
    b_pad = -(-b // quantum) * quantum
    if b_pad == b:
        return staged
    pads = (_G_PAD_VALUES if isinstance(staged, StagedG) else _T_PAD_VALUES)
    upd = {}
    for field, pad_val in zip(_table_fields(staged), pads):
        arr = getattr(staged, field)
        fill = staged.n if pad_val is None else pad_val
        pad_block = jnp.full((b_pad - b,) + arr.shape[1:], fill, arr.dtype)
        upd[field] = jnp.concatenate([arr, pad_block], axis=0)
    return staged._replace(**upd)


def truncate_staged(staged, num_stages: Optional[int], keep: str = "head"):
    """Cut staged tables at a stage boundary: keep the first (``head``) or
    last (``tail``) ``num_stages`` stages.  Exact (equals the operator of
    the corresponding component prefix) whenever ``num_stages`` is one of
    ``staged.cuts``; core/staging.py's docstring says which direction
    each family/table set uses.  Works on (S, P) and batched (B, S, P) tables
    and on traced (jit) values."""
    if num_stages is None:
        return staged
    s_tot = staged.idx_i.shape[-2]
    if not 0 <= num_stages <= s_tot:
        raise ValueError(f"num_stages {num_stages} not in [0, {s_tot}]")
    if num_stages == s_tot:
        return staged
    if keep == "head":
        sl = slice(0, num_stages)
    elif keep == "tail":
        sl = slice(s_tot - num_stages, s_tot)
    else:
        raise ValueError(f"keep must be 'head' or 'tail', got {keep!r}")
    upd = {f: getattr(staged, f)[..., sl, :] for f in _table_fields(staged)}
    if isinstance(staged.cuts, np.ndarray):
        # host metadata only; under jit the leaf is a tracer — leave it
        upd["cuts"] = staged.cuts[staged.cuts[:, 0] <= num_stages]
    return staged._replace(**upd)
