"""Conflict-free stage packing: the TPU adaptation of sequential 2x2 chains.

The paper applies its g transforms sequentially (6 flops each on CPU).  On a
TPU that is the worst possible shape.  Disjoint 2x2 transforms commute, so the
ordered factor list can be packed greedily (ASAP list scheduling) into
*stages* whose transforms touch pairwise-disjoint coordinates; each stage then
applies as one vectorized gather -> 2xFMA -> scatter step.  Packing preserves
the exact operator: the relative order of any two *conflicting* transforms is
never changed.

For Theorem-1-initialized factor chains with g = alpha * n log2 n the greedy
packing empirically produces ~2 alpha log2 n stages of ~n/2 pairs (see
tests/test_staging.py), turning an O(g)-deep dependency chain into an
O(log n)-deep one.

Anytime prefixes (DESIGN.md §9).  The number of fundamental components is
the paper's accuracy/latency dial, so the staged tables must be cuttable:
packing is *chunked* along the greedy **discovery order** (the order the
solver found the components — the paper's significance order).  Within a
chunk, scheduling is plain ASAP (full depth efficiency); chunk boundaries
are barriers, so every chunk boundary is a stage boundary at which cutting
the (S, P) tables yields EXACTLY the operator of the leading k components.
The valid (num_stages, num_components) pairs are recorded in the ``cuts``
metadata carried by ``StagedG``/``StagedT``.  Adjoint/inverse tables are
built as stage-mirrors of the forward tables (same stages, reversed order,
per-entry adjoint/inverse values), so one ``num_stages`` selects consistent
cuts of both directions:

  * G-family: discovery order is the REVERSE of application order
    (core/types.py), so the significant stages sit at the TAIL of the
    forward (synthesis) tables and at the HEAD of the adjoint (analysis)
    tables.
  * T-family: discovery order == application order, so the significant
    stages sit at the HEAD of the forward tables and the TAIL of the
    inverse tables.

Ragged embedding (DESIGN.md §10).  Padding entries carry the
OUT-OF-BOUNDS index ``n`` (see ``_pad_layout``), which is also what makes
heterogeneous fleets work: an n'-node matrix fitted inside an n-wide
bucket (masked greedy, core/eigenbasis.py) produces factors that touch
only coordinates < n', so its staged tables act as the identity on
coordinates >= n' — the same structural no-op mechanism, just wider.
Pass the bucket width explicitly (``pack_g(..., n=...)`` / ``pack_t(...,
n)``) so the tables — and their pad index — match the bucket, not the
chain's own coordinate range.

Packing happens on the host (numpy, once per factorization); the staged
arrays are then consumed by jit code (kernels/ or the XLA reference path).
The table format itself (``StagedG``/``StagedT``, cutting, padding,
precision) lives in repro/tables.py, below both this package and
kernels/.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np
import jax.numpy as jnp

from repro.tables import StagedG, StagedT
from .types import GFactors, SCALE, TFactors

DEFAULT_NUM_CHUNKS = 4


# ---------------------------------------------------------------------------
# Prefix metadata helpers
# ---------------------------------------------------------------------------

def default_cut_ladder(num_transforms: int,
                       num_chunks: int = DEFAULT_NUM_CHUNKS) -> np.ndarray:
    """Component counts at which the staged tables are exactly cuttable.

    Evenly spaced (including 0 and ``num_transforms``); scheduling treats
    each consecutive pair as a barrier-separated chunk.  More cut points
    mean finer anytime tiers but deeper schedules (each barrier forfeits
    a little cross-chunk packing: ~4% depth at the default 4 chunks, ~10%
    at 8, on Theorem-1 chains — and batched tables additionally pad each
    chunk to the batch max).  The default quarters ladder exactly covers
    the stock full/balanced/draft serving tiers."""
    ks = {round(num_transforms * c / num_chunks)
          for c in range(num_chunks + 1)}
    return np.asarray(sorted(ks | {0, num_transforms}), np.int64)


def select_cut(staged, num_transforms: Optional[int] = None,
               fraction: Optional[float] = None) -> Tuple[int, int]:
    """Pick the exact cut nearest a component target.

    Give either ``num_transforms`` (absolute component count) or
    ``fraction`` (of the full chain).  Returns ``(num_stages,
    num_components)`` — the ladder entry whose component count is closest
    to the target (ties resolve to the larger, i.e. more accurate, cut)."""
    if staged.cuts is None:
        raise ValueError("staged tables carry no cut metadata "
                         "(built outside pack_g/pack_t?)")
    cuts = np.asarray(staged.cuts)
    total = int(cuts[:, 1].max())
    if fraction is not None:
        if num_transforms is not None:
            raise ValueError("pass num_transforms or fraction, not both")
        num_transforms = fraction * total
    if num_transforms is None:
        raise ValueError("pass num_transforms or fraction")
    if num_transforms > 0:
        # a positive target must never snap to the empty (0, 0) cut — a
        # zero-component "transform" serves diag-only results silently
        pos = cuts[cuts[:, 1] > 0]
        if len(pos):
            cuts = pos
    dist = np.abs(cuts[:, 1].astype(np.float64) - float(num_transforms))
    best = int(np.lexsort((-cuts[:, 1], dist))[0])
    return int(cuts[best, 0]), int(cuts[best, 1])


def _chunk_bounds(g: int, cuts: Optional[Sequence[int]],
                  significance_tail: bool) -> np.ndarray:
    """Factor-index barriers (application order) for a significance ladder.

    ``cuts`` lists significance-prefix sizes (component counts).  For the
    G family significance order is reversed application order
    (``significance_tail=True``): a significance prefix of k components is
    the application suffix [g-k, g)."""
    ladder = (default_cut_ladder(g) if cuts is None
              else np.asarray(sorted({0, g} | {int(k) for k in cuts
                                               if 0 <= int(k) <= g}),
                              np.int64))
    if significance_tail:
        return g - ladder[::-1]
    return ladder


def _chunked_schedule(touch_sets, bounds) -> Tuple[np.ndarray, int,
                                                   np.ndarray]:
    """ASAP list scheduling with barriers at ``bounds``.

    ``touch_sets``: per-factor coordinate tuples (application order);
    ``bounds``: ascending factor indices (incl. 0 and len) at which a
    fresh stage must start.  Returns (stage per factor, num_stages, stage
    index of every barrier)."""
    stage_of = np.zeros(len(touch_sets), dtype=np.int64)
    stage_bounds = np.zeros(len(bounds), dtype=np.int64)
    base = 0
    for c, (a, b) in enumerate(zip(bounds[:-1], bounds[1:])):
        busy = {}
        depth = 0
        for k in range(a, b):
            st = 0
            for coord in touch_sets[k]:
                st = max(st, busy.get(int(coord), 0))
            stage_of[k] = base + st
            for coord in touch_sets[k]:
                busy[int(coord)] = st + 1
            depth = max(depth, st + 1)
        base += depth
        stage_bounds[c + 1] = base
    return stage_of, base, stage_bounds


def _pad_layout(stage_of, n_stages):
    """Common padded (S, P) layout: returns (slots, P).

    Padding entries use the OUT-OF-BOUNDS index ``n``: the apply functions
    scatter with mode="drop", so pads are structural no-ops.  (An in-range
    "identity write at an unused index" is unsound: a stage that touches
    all n coordinates has no unused index, and a duplicate scatter index
    clobbers a real factor's write — found by hypothesis.)"""
    counts = np.bincount(stage_of, minlength=max(n_stages, 1))
    width = max(int(counts.max(initial=1)), 1)
    slot = np.zeros_like(stage_of)
    seen = np.zeros(max(n_stages, 1), dtype=np.int64)
    for k, st in enumerate(stage_of):
        slot[k] = seen[st]
        seen[st] += 1
    return slot, width


def _cut_table(stage_bounds: np.ndarray, bounds: np.ndarray, g: int,
               n_stages: int, significance_tail: bool) -> np.ndarray:
    """(num_stages, num_components) rows for every exact barrier."""
    if significance_tail:
        # barrier i leaves application factors [bounds[i], g) — the k =
        # g - bounds[i] most significant components — in the LAST
        # n_stages - stage_bounds[i] stages
        rows = [(n_stages - int(sb), g - int(fb))
                for sb, fb in zip(stage_bounds, bounds)]
    else:
        rows = [(int(sb), int(fb))
                for sb, fb in zip(stage_bounds, bounds)]
    uniq = sorted(set(rows))
    return np.asarray(uniq, np.int64).reshape(-1, 2)


# ---------------------------------------------------------------------------
# Host-side (numpy) packers; mirrors build adjoint/inverse tables with the
# SAME stage structure so one num_stages cuts both directions consistently
# ---------------------------------------------------------------------------

def _pack_g_np(factors: GFactors, n: int, cuts: Optional[Sequence[int]]):
    fi = np.asarray(factors.i)
    fj = np.asarray(factors.j)
    fc = np.asarray(factors.c)
    fs = np.asarray(factors.s)
    fsg = np.asarray(factors.sigma)
    g = fi.shape[0]
    pairs = [(int(a), int(b)) for a, b in zip(fi, fj)]
    bounds = _chunk_bounds(g, cuts, significance_tail=True)
    stage_of, n_stages, stage_bounds = _chunked_schedule(pairs, bounds)
    slot, width = _pad_layout(stage_of, n_stages)
    n_stages = max(n_stages, 1)

    ii = np.full((n_stages, width), n, dtype=np.int32)
    jj = ii.copy()
    cc = np.ones((n_stages, width), fc.dtype)
    ss = np.zeros((n_stages, width), fs.dtype)
    sg = np.ones((n_stages, width), fsg.dtype)
    ii[stage_of, slot] = fi
    jj[stage_of, slot] = fj
    cc[stage_of, slot] = fc
    ss[stage_of, slot] = fs
    sg[stage_of, slot] = fsg
    cut = _cut_table(stage_bounds, bounds, g, n_stages,
                     significance_tail=True)
    return (ii, jj, cc, ss, sg), cut, stage_bounds


def _mirror_g_np(tables):
    """Stage-mirror of forward G tables: Ubar^T (reverse stage order;
    rotations flip s, reflections are symmetric).  Padding entries
    (c=1, s=0, sigma=1) are fixed points."""
    ii, jj, cc, ss, sg = tables
    s_adj = np.where(sg > 0, -ss, ss)
    return (ii[::-1].copy(), jj[::-1].copy(), cc[::-1].copy(),
            s_adj[::-1].copy(), sg[::-1].copy())


def _pack_t_np(factors: TFactors, n: int, cuts: Optional[Sequence[int]]):
    fk = np.asarray(factors.kind)
    fi = np.asarray(factors.i)
    fj = np.asarray(factors.j)
    fa = np.asarray(factors.a)
    m = fk.shape[0]
    touch = []
    for k in range(m):
        if fk[k] == SCALE:
            touch.append((int(fi[k]),))
        else:
            touch.append((int(fi[k]), int(fj[k])))
    bounds = _chunk_bounds(m, cuts, significance_tail=False)
    stage_of, n_stages, stage_bounds = _chunked_schedule(touch, bounds)
    slot, width = _pad_layout(stage_of, n_stages)
    n_stages = max(n_stages, 1)

    ii = np.full((n_stages, width), n, dtype=np.int32)
    jj = ii.copy()
    al = np.ones((n_stages, width), fa.dtype)
    be = np.zeros((n_stages, width), fa.dtype)
    is_scale = fk == SCALE
    ii[stage_of, slot] = fi
    jj[stage_of, slot] = np.where(is_scale, fi, fj)
    al[stage_of, slot] = np.where(is_scale, fa, 1.0)
    be[stage_of, slot] = np.where(is_scale, 0.0, fa)
    cut = _cut_table(stage_bounds, bounds, m, n_stages,
                     significance_tail=False)
    return (ii, jj, al, be), cut, stage_bounds


def _mirror_t_np(tables):
    """Stage-mirror of forward T tables: Tbar^{-1} (reverse stage order;
    per entry (alpha, beta) -> (1/alpha, -beta/alpha), which inverts
    shears (alpha=1: beta -> -beta), scalings (beta=0: alpha -> 1/alpha)
    and fixes padding (1, 0))."""
    ii, jj, al, be = tables
    inv_al = 1.0 / al
    inv_be = -be / al
    return (ii[::-1].copy(), jj[::-1].copy(), inv_al[::-1].copy(),
            inv_be[::-1].copy())


# ---------------------------------------------------------------------------
# Public single-matrix packers
# ---------------------------------------------------------------------------

def _infer_n_g(factors: GFactors, n: Optional[int] = None) -> int:
    """Matrix side for a G-chain: the caller's ``n`` when given (a ragged
    chain embedded in a wider bucket touches only its leading coordinates,
    so inferring from the indices would shrink the table width AND plant
    the structural no-op pad index inside the signal), else max index + 1.
    """
    fi = np.asarray(factors.i)
    fj = np.asarray(factors.j)
    inferred = int(max(fi.max(initial=0), fj.max(initial=0))) + 1
    if n is None:
        return inferred
    if n < inferred:
        raise ValueError(f"explicit n={n} smaller than the largest factor "
                         f"coordinate ({inferred - 1})")
    return int(n)


def pack_g(factors: GFactors,
           cuts: Optional[Sequence[int]] = None,
           n: Optional[int] = None) -> "StagedG":
    """Stage a G-chain (synthesis direction, Ubar).  ``cuts`` lists
    component counts that must be exactly cuttable (default: the quarters
    ladder); significant components land in the TAIL stages.  ``n`` pins
    the table width (required for ragged chains embedded in a wider
    bucket; default: inferred from the factor indices)."""
    n = _infer_n_g(factors, n)
    tables, cut, _ = _pack_g_np(factors, n, cuts)
    return StagedG(*map(jnp.asarray, tables), cut, n)


def pack_g_adjoint(factors: GFactors,
                   cuts: Optional[Sequence[int]] = None,
                   n: Optional[int] = None) -> "StagedG":
    """Staged form of Ubar^T: the stage-MIRROR of ``pack_g(factors)``
    (same stages, reversed order, rotations flip s), so the cut ladder of
    both directions aligns: the k most significant components are the
    first ``num_stages`` stages here and the last ``num_stages`` stages of
    the forward tables."""
    n = _infer_n_g(factors, n)
    tables, cut, _ = _pack_g_np(factors, n, cuts)
    return StagedG(*map(jnp.asarray, _mirror_g_np(tables)), cut, n)


def pack_g_pair(factors: GFactors,
                cuts: Optional[Sequence[int]] = None,
                n: Optional[int] = None) -> Tuple["StagedG", "StagedG"]:
    """(forward, adjoint) staged forms from ONE scheduling pass — the
    adjoint is a mirror of the forward tables, so packing both directions
    separately would run the host scheduler twice for the same chain."""
    n = _infer_n_g(factors, n)
    tables, cut, _ = _pack_g_np(factors, n, cuts)
    return (StagedG(*map(jnp.asarray, tables), cut, n),
            StagedG(*map(jnp.asarray, _mirror_g_np(tables)), cut, n))


def pack_t(factors: TFactors, n: int,
           cuts: Optional[Sequence[int]] = None) -> "StagedT":
    """Stage a T-chain (forward direction, Tbar); significant components
    land in the HEAD stages."""
    tables, cut, _ = _pack_t_np(factors, n, cuts)
    return StagedT(*map(jnp.asarray, tables), cut, n)


def pack_t_inverse(factors: TFactors, n: int,
                   cuts: Optional[Sequence[int]] = None) -> "StagedT":
    """Staged form of Tbar^{-1}: the stage-mirror of ``pack_t(factors)``
    (reverse order; shear a -> -a, scale a -> 1/a), cut-aligned with the
    forward tables (significant components in the TAIL stages here)."""
    tables, cut, _ = _pack_t_np(factors, n, cuts)
    return StagedT(*map(jnp.asarray, _mirror_t_np(tables)), cut, n)


def pack_t_pair(factors: TFactors, n: int,
                cuts: Optional[Sequence[int]] = None
                ) -> Tuple["StagedT", "StagedT"]:
    """(forward, inverse) staged forms from one scheduling pass."""
    tables, cut, _ = _pack_t_np(factors, n, cuts)
    return (StagedT(*map(jnp.asarray, tables), cut, n),
            StagedT(*map(jnp.asarray, _mirror_t_np(tables)), cut, n))


# ---------------------------------------------------------------------------
# Batched packers: (B, S, P) tables with chunk-uniform padding
# ---------------------------------------------------------------------------

def _gfactors_slice(factors: GFactors, b: int) -> GFactors:
    return GFactors(*(jnp.asarray(np.asarray(f)[b]) for f in factors))


def _tfactors_slice(factors: TFactors, b: int) -> TFactors:
    return TFactors(*(jnp.asarray(np.asarray(f)[b]) for f in factors))


def _stack_chunked(per_matrix, stage_bounds_list, pad_values, n,
                   pad: Optional[Tuple[int, int]] = None):
    """Stack per-matrix staged tables into (B, S, P), padding each CHUNK
    to the batch-max chunk depth (and each stage to the batch-max width).

    Chunk-uniform padding keeps every cut boundary at the SAME stage index
    for all B matrices, so one static ``num_stages`` cuts the whole batch
    exactly (DESIGN.md §9).  Pads are structural no-ops (out-of-bounds
    index ``n`` + identity values).

    ``pad``: optional (depth_quantum, width_quantum) SHAPE QUANTIZATION
    (DESIGN.md §11): each chunk's depth rounds up to a multiple of
    ``depth_quantum`` and the stage width to a multiple of
    ``width_quantum``.  The greedy packing depth is content-dependent, so
    two refits of the SAME (B, n, g) problem can produce tables one stage
    apart — which would retrace every jitted program holding the tables
    as arguments.  Quantized shapes make steady-state refits land on the
    compiled-program cache instead, at the cost of a few no-op pad
    stages."""
    num_chunks = len(stage_bounds_list[0]) - 1
    depths = np.zeros(num_chunks, np.int64)
    for sb in stage_bounds_list:
        depths = np.maximum(depths, np.diff(sb))
    qd, qw = pad if pad is not None else (1, 1)
    if qd < 1 or qw < 1:
        raise ValueError(f"pad quanta must be >= 1, got {(qd, qw)}")
    depths = -(-depths // qd) * qd
    offs = np.concatenate([[0], np.cumsum(depths)])
    s_max = int(offs[-1]) if offs[-1] > 0 else 1
    p_max = max(t[0].shape[1] for t in per_matrix)
    p_max = int(-(-p_max // qw) * qw)
    batch = len(per_matrix)
    stacked = []
    for f, pad_val in enumerate(pad_values):
        arr = np.full((batch, s_max, p_max), pad_val,
                      per_matrix[0][f].dtype)
        for b, tables in enumerate(per_matrix):
            sb = stage_bounds_list[b]
            src = tables[f]
            for c in range(num_chunks):
                lo, hi = int(sb[c]), int(sb[c + 1])
                arr[b, int(offs[c]):int(offs[c]) + (hi - lo),
                    :src.shape[1]] = src[lo:hi]
        stacked.append(arr)
    return stacked, offs


def _batch_cut_table(offs, bounds, g, significance_tail):
    n_stages = int(offs[-1]) if offs[-1] > 0 else 1
    return _cut_table(offs, bounds, g, n_stages, significance_tail)


def _pack_g_batch_np(factors: GFactors, n: int,
                     cuts: Optional[Sequence[int]],
                     pad: Optional[Tuple[int, int]] = None):
    fi = np.asarray(factors.i)
    batch, g = fi.shape
    n = max(n, int(max(fi.max(initial=0),
                       np.asarray(factors.j).max(initial=0))) + 1)
    per, sbs = [], []
    for b in range(batch):
        tables, _, sb = _pack_g_np(_gfactors_slice(factors, b), n, cuts)
        per.append(tables)
        sbs.append(sb)
    pads = (np.int32(n), np.int32(n), 1.0, 0.0, 1.0)
    stacked, offs = _stack_chunked(per, sbs, pads, n, pad)
    bounds = _chunk_bounds(g, cuts, significance_tail=True)
    cut = _batch_cut_table(offs, bounds, g, significance_tail=True)
    return stacked, cut, n


def _mirror_g_batch_np(stacked):
    """Batched stage-mirror (Ubar^T per matrix): flip the stage axis and
    adjoint each entry; chunk-uniform padding keeps cut boundaries
    aligned under the flip."""
    out = [np.ascontiguousarray(a[:, ::-1]) for a in stacked]
    sg = out[4]
    out[3] = np.where(sg > 0, -out[3], out[3])
    return out


def pack_g_batch(factors: GFactors, n: int, adjoint: bool = False,
                 cuts: Optional[Sequence[int]] = None,
                 pad: Optional[Tuple[int, int]] = None) -> "StagedG":
    """Pack a batch of G-factor chains (leading (B, g) arrays) into one
    StagedG whose tables carry a leading batch dim: (B, S, P).  All B
    chains share one cut ladder; chunk-uniform padding keeps the ladder's
    stage boundaries aligned across the batch.  ``pad``: optional
    (depth, width) shape quanta (see ``_stack_chunked``)."""
    stacked, cut, n = _pack_g_batch_np(factors, n, cuts, pad)
    if adjoint:
        stacked = _mirror_g_batch_np(stacked)
    return StagedG(*map(jnp.asarray, stacked), cut, n)


def pack_g_batch_pair(factors: GFactors, n: int,
                      cuts: Optional[Sequence[int]] = None,
                      pad: Optional[Tuple[int, int]] = None
                      ) -> Tuple["StagedG", "StagedG"]:
    """(forward, adjoint) batched staged forms from ONE scheduling +
    stacking pass (the O(B·g) host scheduler is the packing cost)."""
    stacked, cut, n = _pack_g_batch_np(factors, n, cuts, pad)
    return (StagedG(*map(jnp.asarray, stacked), cut, n),
            StagedG(*map(jnp.asarray, _mirror_g_batch_np(stacked)),
                    cut, n))


def _pack_t_batch_np(factors: TFactors, n: int,
                     cuts: Optional[Sequence[int]],
                     pad: Optional[Tuple[int, int]] = None):
    batch, m = np.asarray(factors.kind).shape
    per, sbs = [], []
    for b in range(batch):
        f = _tfactors_slice(factors, b)
        tables, _, sb = _pack_t_np(f, n, cuts)
        per.append(tables)
        sbs.append(sb)
    pads = (np.int32(n), np.int32(n), 1.0, 0.0)
    stacked, offs = _stack_chunked(per, sbs, pads, n, pad)
    bounds = _chunk_bounds(m, cuts, significance_tail=False)
    cut = _batch_cut_table(offs, bounds, m, significance_tail=False)
    return stacked, cut


def _mirror_t_batch_np(stacked):
    """Batched stage-mirror (Tbar^{-1} per matrix)."""
    al, be = stacked[2], stacked[3]
    out = [stacked[0], stacked[1], 1.0 / al, -be / al]
    return [np.ascontiguousarray(a[:, ::-1]) for a in out]


def pack_t_batch(factors: TFactors, n: int, inverse: bool = False,
                 cuts: Optional[Sequence[int]] = None,
                 pad: Optional[Tuple[int, int]] = None) -> "StagedT":
    """Pack a batch of T-factor chains into one StagedT with (B, S, P)
    tables (``inverse=True`` mirrors the stages into Tbar^{-1} per
    matrix), cut-aligned across the batch like ``pack_g_batch``."""
    stacked, cut = _pack_t_batch_np(factors, n, cuts, pad)
    if inverse:
        stacked = _mirror_t_batch_np(stacked)
    return StagedT(*map(jnp.asarray, stacked), cut, n)


def pack_t_batch_pair(factors: TFactors, n: int,
                      cuts: Optional[Sequence[int]] = None,
                      pad: Optional[Tuple[int, int]] = None
                      ) -> Tuple["StagedT", "StagedT"]:
    """(forward, inverse) batched staged forms from one packing pass."""
    stacked, cut = _pack_t_batch_np(factors, n, cuts, pad)
    return (StagedT(*map(jnp.asarray, stacked), cut, n),
            StagedT(*map(jnp.asarray, _mirror_t_batch_np(stacked)),
                    cut, n))
