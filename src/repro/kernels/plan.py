"""Declarative execution plans: ONE way to run every staged-table apply.

An ``ApplyPlan`` names a computation over staged tables — family (G or
T), mode (plain transform apply / fused ``Ubar diag(d) Ubar^T`` operator
/ spectral filter bank), batching, anytime ladder cut, backend, tile
size and storage-precision policy — and ``program()`` compiles it to
exactly ONE cached jitted program.  Everything serving-shaped in the
repo routes through this module: the serve engines' tier/bank programs
(launch/serve.py), the drift scorer's operator leg (dynamic/drift.py)
and the core apply paths (core/fgft.py, core/eigenbasis.py) all
construct plans instead of hand-wiring kernel dispatch, so the
"same-shape swaps recompile
nothing" invariant (DESIGN.md §11) holds by construction: programs take
the staged tables as ARGUMENTS and are cached on the plan alone.

Program signatures (``tables`` = ``repro/tables.py::table_arrays``
tuples, i.e. the device arrays without the host ``cuts``/``n`` tail —
``ApplyPlan.prepare`` produces them under the plan's precision policy):

  * mode "apply":     ``program(tables, x)``
  * mode "operator":  ``program(fwd_tables, bwd_tables, diag, x)``
  * mode "bank":      ``program(fwd_tables, bwd_tables, gains, x)``

A ``row`` plan (batched tables, no placement) takes one more argument,
the batch row, last: ``program(..., x, row)``.  It walks that one
graph's (R, n) block against the whole (B, S, P) tables and the (B, ...)
spectrum or gains, each indexed at ``row`` inside the program, so one
compiled program serves every graph of a bucket and the tables are
never copied per graph.

Precision policy (DESIGN.md §13): ``precision="bf16"`` stores the value
tables in bfloat16 (``prepare`` casts them; indices stay int32) while
ACCUMULATING in f32 — the compiled program upcasts the signal to f32
for the staged walk and casts the result back to the caller's dtype,
and the kernels cast each table entry to the signal dtype at compute
time, so bf16 never touches the accumulator.  ``precision="f32"`` is
bit-identical to the pre-plan dispatch.

Fusion policy: ``fused=True`` (default) compiles operator/bank modes to
the single-program fused path (one Pallas kernel per dispatch — the
coefficients never leave VMEM; one XLA program on the oracle backend).
``fused=False`` is the faithful three-pass staged baseline — analysis,
diagonal scale and synthesis each cross the dispatch boundary (and a
bank re-runs its analysis per filter) — kept as a first-class plan so
parity tests and the fig13 speedup gate exercise the exact path the
fused programs replace.

Lowering policy: ``lowering="walk"`` (default) runs the staged tables
stage by stage.  ``lowering="dense"`` (fused f32 operator and bank
plans) serves each leg as ONE f32 matmul on the MXU against the leg's
dense w x w matrix, built once per basis version by ``dense_legs`` (the
identity walked through the same tables; ``ApplyPlan.legs`` builds them
from prepared tables and places them like those), so the cost no longer
grows with the stage count.  Dense programs take the legs in place of
the tables:

  * mode "operator":  ``program(legs, diag, x)``
  * mode "bank":      ``program(legs, gains, x)``

with ``legs`` a tuple: ``(U,)`` for sym (analysis ``x @ U``, synthesis
the same array contracted transposed, ``c @ U^T``, with no second
copy) and ``(A, S)`` for general (``A = Tbar^-T``, ``S = Tbar^T``), each
(w, w) or (B, w, w) when batched.  Every matmul runs at
``Precision.HIGHEST`` with f32 accumulation: a default-precision f32
matmul on a TPU rounds its inputs to bf16.  ``choose_lowering`` picks
the lowering from what the caller observes (platform, precision,
memory).

Ragged fleets need no extra plan state: masked fits emit tables that
act as the identity on padding coordinates (core/staging.py), and
callers mask bank/filter gains where ``h(0) != 0``.  The dense legs
inherit that: a pad coordinate's row and column are the identity's.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Optional

import jax
import jax.numpy as jnp

from jax import lax

from repro import obs
from repro.tables import (StagedG, StagedT, TABLE_PRECISIONS, pad_batch,
                          table_arrays, with_precision)
from repro.runtime.sharding import BucketPlacement
from . import butterfly as _bf
from . import ref as _ref
from . import shear as _sh
from . import spectral as _sp

# the serving path's spans (plan compiles here, the front door's
# dispatch stages in launch/service.py) also land in the JAX profile,
# on the device trace's clock, when a profiled slice is running
obs.configure(annotation=jax.profiler.TraceAnnotation)

PLAN_FAMILIES = ("sym", "general")
PLAN_MODES = ("apply", "operator", "bank")
PLAN_BACKENDS = ("xla", "pallas")
PLAN_LOWERINGS = ("walk", "dense")

#: free device memory a version's dense legs need, in copies of those
#: legs: the live version's, the next version's built beside them
#: during a hot swap, and the identity walk that builds them
DENSE_LEG_COPIES = 3

#: rows-per-grid-step default shared by every Pallas kernel; a persisted
#: autotune entry (kernels/autotune.py) overrides it per plan key.
DEFAULT_BLOCK_B = _bf.DEFAULT_BLOCK_B


def leg_orientation(family: str) -> tuple:
    """(analysis_keep, synthesis_keep) cut orientation of a family's
    operator legs (core/staging.py module docstring): the significant
    stages sit at the HEAD of G-adjoint / T-forward tables and the TAIL
    of G-forward / T-inverse tables, so an operator cut keeps
    analysis="head"/synthesis="tail" for G and the reverse for T."""
    return ("head", "tail") if family == "sym" else ("tail", "head")


@dataclass(frozen=True)
class ApplyPlan:
    """One declarative execution plan (hashable: it IS the cache key).

    ``family``: "sym" (G transforms) | "general" (T transforms).
    ``mode``: "apply" | "operator" | "bank".  ``n``: table width (the
    bucket width for ragged fleets).  ``num_stages``: anytime ladder cut
    (both operator legs are cut consistently; "apply" mode also takes
    ``keep`` — see ``leg_orientation``).  ``block_b``: Pallas tile
    signals (None = the persisted autotune choice, falling back to
    ``DEFAULT_BLOCK_B``).  ``precision``/``fused``: see module
    docstring.  Pallas plans run compiled on a TPU and interpreted on
    the CPU (kernels/butterfly.py::resolve_interpret)."""

    family: str
    mode: str
    n: int
    batched: bool = False
    backend: str = "xla"
    num_stages: Optional[int] = None
    keep: str = "head"
    precision: str = "f32"
    fused: bool = True
    block_b: Optional[int] = None
    #: optional mesh placement (runtime/sharding.py::BucketPlacement):
    #: ``prepare`` pads the batch axis to the per-device quantum and pins
    #: the tables onto the bucket's devices as sharded jit arguments.
    #: Frozen + hashable, so placed plans are ordinary cache keys — a hot
    #: swap that keeps shapes AND placement recompiles nothing (the jit
    #: argument layout is unchanged).
    placement: Optional[BucketPlacement] = None
    #: walk one graph of the batched tables, its row an argument (see
    #: the module docstring): the serving front door's per-graph blocks
    row: bool = False
    #: "walk" the staged tables, or serve "dense" legs as matmuls (see
    #: the module docstring)
    lowering: str = "walk"

    def __post_init__(self):
        if self.family not in PLAN_FAMILIES:
            raise ValueError(f"family must be one of {PLAN_FAMILIES}, "
                             f"got {self.family!r}")
        if self.mode not in PLAN_MODES:
            raise ValueError(f"mode must be one of {PLAN_MODES}, "
                             f"got {self.mode!r}")
        if self.backend not in PLAN_BACKENDS:
            raise ValueError(f"backend must be one of {PLAN_BACKENDS}, "
                             f"got {self.backend!r}")
        if self.precision not in TABLE_PRECISIONS:
            raise ValueError(f"precision must be one of "
                             f"{TABLE_PRECISIONS}, got {self.precision!r}")
        if self.keep not in ("head", "tail"):
            raise ValueError(f"keep must be 'head' or 'tail', "
                             f"got {self.keep!r}")
        if self.n <= 0:
            raise ValueError(f"n must be positive, got {self.n}")
        if self.block_b is not None and self.block_b <= 0:
            raise ValueError(f"block_b must be positive, "
                             f"got {self.block_b}")
        if self.placement is not None and not self.batched:
            raise ValueError("placement requires batched=True (the batch "
                             "axis is what partitions over the bucket's "
                             "devices)")
        if self.row and (not self.batched or self.placement is not None):
            raise ValueError("row requires batched=True and no placement "
                             "(a row index must not cross devices)")
        if self.lowering not in PLAN_LOWERINGS:
            raise ValueError(f"lowering must be one of {PLAN_LOWERINGS}, "
                             f"got {self.lowering!r}")
        if self.lowering == "dense" and (
                self.mode == "apply" or self.precision != "f32"
                or not self.fused):
            raise ValueError("the dense lowering serves fused f32 operator "
                             "and bank plans")
        if self.mode != "apply" and self.keep != "head":
            # operator/bank legs derive their own orientation; canonical
            # keep="head" keeps equivalent plans on one cache entry
            object.__setattr__(self, "keep", "head")

    @classmethod
    def for_staged(cls, staged, mode: str = "apply", **kwargs) -> ApplyPlan:
        """Infer family / batching / width from a StagedG/StagedT."""
        return cls(family="sym" if isinstance(staged, StagedG)
                   else "general",
                   mode=mode, n=staged.n,
                   batched=staged.idx_i.ndim == 3, **kwargs)

    @property
    def staged_cls(self):
        return StagedG if self.family == "sym" else StagedT

    # -- table preparation -------------------------------------------------

    def prepare(self, staged) -> tuple:
        """Device table tuple of ``staged`` under the plan's precision
        policy — what the compiled program takes as its table arguments
        (prepare once per basis version, off the hot path).

        With a ``placement``, the batch axis first pads to the per-device
        quantum with structural no-op rows (tables.pad_batch) and every
        leaf is device_put onto the bucket's sub-mesh, batch-split — the
        compiled program then runs collective-free, each device owning
        its graphs end-to-end."""
        staged = with_precision(staged, self.precision)
        if self.placement is not None:
            staged = pad_batch(staged, self.placement.batch_padded)
            return tuple(self.placement.place_leaf(a)
                         for a in table_arrays(staged))
        return table_arrays(staged)

    def legs(self, fwd: tuple, bwd: tuple, cuts) -> dict:
        """``dense_legs`` of the table tuples ``prepare`` returned, at
        each cut: built where those tables live and, with a
        ``placement``, pinned batch-split onto the bucket's devices like
        them (a pad graph's legs are the identity)."""
        legs = dense_legs(self._staged(fwd), self._staged(bwd), cuts)
        if self.placement is None:
            return legs
        return {cut: tuple(map(self.placement.place_leaf, leg))
                for cut, leg in legs.items()}

    def place(self, arr):
        """Pad (zeros) + device_put a per-graph operand (diag spectrum,
        bank gains, signal batch) to match placed tables; identity when
        the plan carries no placement."""
        if self.placement is None:
            return arr
        return self.placement.place(arr)

    def crop(self, y):
        """Undo the batch padding on a program output (identity when
        unplaced or the batch already divides the device count)."""
        if self.placement is None or self.placement.batch_padded == \
                self.placement.batch:
            return y
        return y[:self.placement.batch]

    # -- compilation -------------------------------------------------------

    def program(self):
        """The plan's compiled program — ONE process-wide cache entry
        per plan (two equal plans return the identical program object,
        so a hot swap with unchanged table shapes recompiles nothing)."""
        before = _compile.cache_info().misses
        prog = _compile(self)
        # the miss counter increments inside _compile (the only place a
        # compile actually happens); a lookup that left `misses`
        # untouched was a hit
        if _compile.cache_info().misses == before:
            _PLAN_HITS.inc(**self._obs_labels())
        return prog

    @property
    def program_name(self) -> str:
        """Stable name of the compiled program (its XLA module is
        ``jit_<name>``), so a profile attributes device time to the
        plan: family, mode, width, ladder cut and row selection."""
        cut = "" if self.num_stages is None else f"_k{self.num_stages}"
        row = "_row" if self.row else ""
        dense = "_dense" if self.lowering == "dense" else ""
        return f"plan_{self.family}_{self.mode}_n{self.n}{cut}{row}{dense}"

    def _obs_labels(self) -> dict:
        return {"family": self.family, "mode": self.mode,
                "backend": self.backend, "n": self.n}

    def table_op(self):
        """The plan's computation over raw table tuples, UNJITTED — for
        embedding inside LARGER jitted programs (the Hutchinson drift
        scorer wraps the operator leg this way) without nesting a second
        dispatch cache."""
        op = self._dense() if self.lowering == "dense" else self._dispatch()
        if self.precision != "f32":
            op = _accumulate_f32(op)
        return _select_row(op) if self.row else op

    # -- one-shot conveniences (prepare + program + call) ------------------

    def apply(self, staged, x: jnp.ndarray) -> jnp.ndarray:
        return self.crop(self.program()(self.prepare(staged),
                                        self.place(x)))

    def operator(self, fwd, bwd, diag: jnp.ndarray,
                 x: jnp.ndarray) -> jnp.ndarray:
        return self.crop(self.program()(*self._tables(fwd, bwd),
                                        self.place(diag), self.place(x)))

    def bank(self, fwd, bwd, gains: jnp.ndarray,
             x: jnp.ndarray) -> jnp.ndarray:
        return self.crop(self.program()(*self._tables(fwd, bwd),
                                        self.place(gains), self.place(x)))

    def _tables(self, fwd, bwd) -> tuple:
        """Both prepared table sets: a walked program's leading
        arguments.  A dense program takes legs built once per version
        (``legs``), never per call."""
        if self.lowering == "dense":
            raise ValueError("a dense plan's program takes its legs: build "
                             "them once with legs() and call program()")
        return self.prepare(fwd), self.prepare(bwd)

    # -- dispatch ----------------------------------------------------------

    def _resolved_block_b(self) -> int:
        if self.block_b is not None:
            return self.block_b
        from . import autotune
        return autotune.cached_block_b(self) or DEFAULT_BLOCK_B

    def _staged(self, tables: tuple):
        """Rebuild a StagedG/StagedT from a table tuple (jit argument
        form): cuts metadata is host-only and programs cut statically."""
        return self.staged_cls(*tables, None, self.n)

    def _dispatch(self):
        """tables -> arrays map implementing the plan (the ONE place the
        kernel entry points, reshape conventions and cut orientations
        are wired; every engine and apply path inherits it)."""
        cut, keep, n = self.num_stages, self.keep, self.n
        # a row plan walks one graph: the single-matrix entry points on
        # the row's tables (``_select_row`` indexes them)
        batched = self.batched and not self.row
        if self.mode == "apply":
            if self.backend == "xla":
                fns = {("sym", False): _ref.staged_g_apply,
                       ("sym", True): _ref.batched_g_apply,
                       ("general", False): _ref.staged_t_apply,
                       ("general", True): _ref.batched_t_apply}
                fn = fns[self.family, batched]
                return lambda t, x: fn(self._staged(t), x, cut, keep)
            fns = {("sym", False): _bf.butterfly_apply,
                   ("sym", True): _bf.batched_butterfly_apply,
                   ("general", False): _sh.shear_apply,
                   ("general", True): _sh.batched_shear_apply}
            fn = fns[self.family, batched]
            kw = dict(block_b=self._resolved_block_b(), num_stages=cut,
                      keep=keep)
            if batched:
                return lambda t, x: fn(
                    self._staged(t), x.reshape(x.shape[0], -1, n),
                    **kw).reshape(x.shape)
            return lambda t, x: fn(self._staged(t), x.reshape(-1, n),
                                   **kw).reshape(x.shape)
        if self.mode == "operator":
            if self.backend == "xla":
                fns = {("sym", False): _ref.sym_operator_apply,
                       ("sym", True): _ref.batched_sym_operator_apply,
                       ("general", False): _ref.gen_operator_apply,
                       ("general", True): _ref.batched_gen_operator_apply}
                fn = fns[self.family, batched]
                return lambda ft, bt, d, x: fn(
                    self._staged(ft), self._staged(bt), d, x, cut)
            fns = {("sym", False): _bf.sym_operator_apply,
                   ("sym", True): _bf.batched_sym_operator_apply,
                   ("general", False): _sh.gen_operator_apply,
                   ("general", True): _sh.batched_gen_operator_apply}
            fn = fns[self.family, batched]
            kw = dict(block_b=self._resolved_block_b(), num_stages=cut)
            if batched:
                return lambda ft, bt, d, x: fn(
                    self._staged(ft), self._staged(bt), d,
                    x.reshape(x.shape[0], -1, n), **kw).reshape(x.shape)
            return lambda ft, bt, d, x: fn(
                self._staged(ft), self._staged(bt), d,
                x.reshape(-1, n), **kw).reshape(x.shape)
        # mode == "bank": gains (F, n) -> (F, ..., n), or batched
        # (B, F, n) -> (B, F, ..., n)
        if self.backend == "xla":
            fns = {("sym", False): _ref.sym_filter_bank_apply,
                   ("sym", True): _ref.batched_sym_filter_bank_apply,
                   ("general", False): _ref.gen_filter_bank_apply,
                   ("general", True): _ref.batched_gen_filter_bank_apply}
            fn = fns[self.family, batched]
            return lambda ft, bt, g, x: fn(
                self._staged(ft), self._staged(bt), g, x, cut)
        fns = {("sym", False): _sp.sym_filter_bank_apply,
               ("sym", True): _sp.batched_sym_filter_bank_apply,
               ("general", False): _sp.gen_filter_bank_apply,
               ("general", True): _sp.batched_gen_filter_bank_apply}
        fn = fns[self.family, batched]
        kw = dict(block_b=self._resolved_block_b(), num_stages=cut)

        if batched:
            def bank_op(ft, bt, g, x):
                out = fn(self._staged(ft), self._staged(bt), g,
                         x.reshape(x.shape[0], -1, n), **kw)
                return out.reshape((x.shape[0], g.shape[1]) + x.shape[1:])
            return bank_op

        def bank_op(ft, bt, g, x):
            out = fn(self._staged(ft), self._staged(bt), g,
                     x.reshape(-1, n), **kw)
            return out.reshape((g.shape[0],) + x.shape)
        return bank_op

    def _dense(self):
        """legs -> arrays map of a dense plan: the operator or bank as
        f32 matmuls against the legs (module docstring).  Batched legs
        and operands carry the batch on axis 0, which every matmul
        keeps as a batch dimension; a row plan's legs are the row's
        (``_select_row``)."""
        n, sym = self.n, self.family == "sym"
        batched = self.batched and not self.row
        lead = 1 if batched else 0              # contraction axis offset
        batch = ((0,), (0,)) if batched else ((), ())

        def mm(a, b, b_axis):
            # a (..., R, n) against b (..., n, n) on b's axis ``b_axis``
            return lax.dot_general(
                a, b, (((lead + 1,), (lead + b_axis,)), batch),
                precision=lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)

        def legs_op(legs, scale, x):
            # scale: (..., n) diagonal or (..., F, n) gains; the answer
            # is (..., R, n) or (..., F, R, n)
            shape = x.shape
            x = x.reshape((shape[0], -1, n) if batched else (-1, n))
            c = mm(x, legs[0], 0)
            if self.mode == "operator":
                c = c * jnp.expand_dims(scale, -2)
            else:
                # every filter in one matmul over the stacked (F R, n)
                c = jnp.expand_dims(scale, -2) * jnp.expand_dims(c, -3)
                c = c.reshape(c.shape[:-3] + (-1, n))
            y = mm(c, legs[0], 1) if sym else mm(c, legs[1], 0)
            if self.mode == "operator":
                return y.reshape(shape)
            f = scale.shape[-2]
            head = (shape[0], f) if batched else (f,)
            return y.reshape(head + (shape[1:] if batched else shape))
        return legs_op

    def _three_pass(self):
        """The UNFUSED baseline program: analysis, diagonal scale and
        synthesis as separate dispatches through cached "apply" plans (a
        bank re-runs its analysis per filter) — the exact pre-fusion
        execution shape, kept callable so fused-vs-three-pass parity and
        speedup stay measurable through one API (fig13).  A row plan's
        legs are row "apply" plans, and its diagonal or gains are the
        row's."""
        a_keep, s_keep = leg_orientation(self.family)
        analysis = replace(self, mode="apply", keep=a_keep,
                           fused=True).program()
        synthesis = replace(self, mode="apply", keep=s_keep,
                            fused=True).program()
        batched = self.batched and not self.row
        scale = _scale_program(batched)
        if self.mode == "operator":
            def three_pass(fwd_t, bwd_t, d, x, *row):
                d = d[row[0]] if row else d
                return synthesis(fwd_t, scale(d, analysis(bwd_t, x, *row)),
                                 *row)
            return three_pass

        def three_pass_bank(fwd_t, bwd_t, gains, x, *row):
            gains = gains[row[0]] if row else gains
            num_filters = gains.shape[1 if batched else 0]
            outs = [synthesis(fwd_t, scale(gains[:, f] if batched
                                           else gains[f],
                                           analysis(bwd_t, x, *row)), *row)
                    for f in range(num_filters)]
            return jnp.stack(outs, axis=1 if batched else 0)
        return three_pass_bank


def _accumulate_f32(op):
    """bf16 policy: tables are stored bf16 but the staged walk runs on
    an f32 signal (the kernels cast entries to the signal dtype), so
    accumulation never drops below f32.  ``x`` is the last argument."""
    def accumulate_f32(*args):
        x = args[-1]
        y = op(*args[:-1], x.astype(jnp.float32))
        return y.astype(x.dtype)
    return accumulate_f32


def _select_row(op):
    """``op`` over one graph's operands -> ``(*operands, x, row)`` over
    the batched ones: every table leaf and the diagonal or gains are
    indexed at ``row`` inside the program."""
    def row_op(*args):
        *operands, x, row = args
        pick = functools.partial(jax.lax.dynamic_index_in_dim, index=row,
                                 axis=0, keepdims=False)
        return op(*jax.tree.map(pick, operands), x)
    return row_op


def choose_lowering(platform: str, precision: str, leg_bytes: int,
                    free_bytes: Optional[int]) -> str:
    """"dense" where the dense legs pay (a TPU's MXU), keep the served
    precision (f32: bf16 tables keep the walk) and ``DENSE_LEG_COPIES``
    copies of them fit in ``free_bytes``: the device's free memory
    (``bytes_limit - bytes_in_use``) plus the legs of the version they
    replace; "walk" otherwise, the CPU included."""
    if platform != "tpu" or precision != "f32" or free_bytes is None:
        return "walk"
    return ("dense" if DENSE_LEG_COPIES * leg_bytes <= free_bytes
            else "walk")


def dense_leg_count(family: str) -> int:
    """Dense (w, w) matrices one cut of a family takes: sym contracts
    one array both ways; general keeps analysis and synthesis."""
    return 1 if family == "sym" else 2


def dense_legs(fwd, bwd, cuts) -> dict:
    """Dense legs of each operator cut (``None`` = the full chain) of a
    staged pair, by walking the identity through its tables once per
    basis version: ``{cut: legs}`` in the dense programs' form (module
    docstring).  sym walks the adjoint tables ONCE, keeping the state
    at each cut (the cuts are head prefixes); general walks the forward
    head once the same way and each cut's inverse tail on its own."""
    cuts = tuple(sorted(set(cuts),
                        key=lambda k: float("inf") if k is None else k))
    build = _legs_program("sym" if isinstance(fwd, StagedG) else "general",
                          fwd.n, fwd.idx_i.ndim == 3, cuts)
    return dict(zip(cuts, build(table_arrays(fwd), table_arrays(bwd))))


@functools.lru_cache(maxsize=None)
def _legs_program(family: str, n: int, batched: bool, cuts: tuple):
    """Jitted ``dense_legs`` body for one (family, width, cuts): a hot
    swap with unchanged shapes and ladder rebuilds its legs without a
    compile."""
    cls = StagedG if family == "sym" else StagedT
    walk = {("sym", False): _ref.staged_g_apply,
            ("sym", True): _ref.batched_g_apply,
            ("general", False): _ref.staged_t_apply,
            ("general", True): _ref.batched_t_apply}[family, batched]

    def stages(tables, lo, hi):
        return cls(*(a[..., lo:hi, :] for a in tables), None, n)

    def build(fwd_t, bwd_t):
        total = fwd_t[0].shape[-2]
        ks = [total if k is None else k for k in cuts]
        eye = jnp.eye(n, dtype=jnp.float32)
        if batched:
            eye = jnp.broadcast_to(eye, fwd_t[0].shape[:1] + (n, n))
        # the head prefixes: sym's adjoint, general's forward tables
        heads, state, done = [], eye, 0
        for k in ks:
            state = walk(stages(bwd_t if family == "sym" else fwd_t,
                                done, k), state)
            heads.append(state)
            done = k
        if family == "sym":
            return tuple((u,) for u in heads)
        return tuple((walk(stages(bwd_t, total - k, total), eye), s)
                     for k, s in zip(ks, heads))
    build.__name__ = build.__qualname__ = f"dense_legs_{family}_n{n}"
    return jax.jit(build)


#: per-plan cache telemetry (DESIGN.md §15): misses increment INSIDE
#: the lru-cached ``_compile`` body — the only code path where a staged
#: program is actually built — so the compile-event count in the trace
#: equals the plan-cache miss delta by construction (fig15 gates the
#: equality exactly)
_PLAN_HITS = obs.counter(
    "plan_cache_hits_total",
    "plan-cache lookups served by an already-compiled program",
    ("family", "mode", "backend", "n"))
_PLAN_MISSES = obs.counter(
    "plan_cache_misses_total",
    "staged-program compilations (plan-cache misses)",
    ("family", "mode", "backend", "n"))


@functools.lru_cache(maxsize=None)
def _compile(plan: ApplyPlan):
    """THE plan cache: every tier/bank/drift/core program in the process
    lives here, keyed by its plan (one cache, one eviction story —
    ``clear_plan_cache`` drops all compiled programs at once)."""
    labels = plan._obs_labels()
    _PLAN_MISSES.inc(**labels)
    with obs.default_tracer().span(
            "plan_compile", cat="compile",
            args={**labels, "fused": plan.fused,
                  "num_stages": plan.num_stages,
                  "precision": plan.precision}):
        if plan.mode != "apply" and not plan.fused:
            return plan._three_pass()
        op = plan.table_op()

        def program(*args):
            return op(*args)
        program.__name__ = program.__qualname__ = plan.program_name
        return jax.jit(program)


@functools.lru_cache(maxsize=None)
def _scale_program(batched: bool):
    """Jitted diagonal scale of the three-pass path: its own dispatch,
    exactly as the pre-fusion composition paid for it."""
    def scale(d, xh):
        if batched:                       # d (B, n) against xh (B, ..., n)
            d = d.reshape(d.shape[:1] + (1,) * (xh.ndim - 2)
                          + d.shape[-1:])
        return xh * d.astype(xh.dtype)
    return jax.jit(scale)


def plan_cache_size() -> int:
    """Number of compiled plan programs resident in the process."""
    return int(_compile.cache_info().currsize)


def plan_cache_stats() -> dict:
    """Hit/miss/size counters of THE plan cache — the structural facts
    the fig7/fig13/fig14 compile-count gates assert.  ``clear_plan_cache``
    resets all three to zero (functools semantics), so gates bracket a
    region with ``clear_plan_cache(); ...; plan_cache_stats()`` and read
    deltas from a clean origin."""
    info = _compile.cache_info()
    return {"hits": int(info.hits), "misses": int(info.misses),
            "currsize": int(info.currsize)}


def clear_plan_cache() -> None:
    """Drop every compiled plan program (tests / autotune refresh: a
    persisted tile choice recorded after a plan compiled only takes
    effect for that plan after a clear)."""
    _compile.cache_clear()
    _scale_program.cache_clear()
    _legs_program.cache_clear()
