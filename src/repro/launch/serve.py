"""Graph-filter serving: the engines, the ragged router, and the CLI.

``FGFTServeEngine`` factorizes a whole fleet of graph Laplacians in ONE
jitted fit (core/eigenbasis.py) and then serves spectral-filter requests
for all graphs per step through the batched fused ``Ubar diag(d) Ubar^T``
operator — B graph Fourier transforms per dispatch instead of one.  Named
quality TIERS map to anytime prefixes of the staged tables: each tier is
its own jitted program over the cut tables (fewer stages -> proportionally
less work), selectable per step, with per-tier counts in the serve stats.
``RaggedFGFTServeEngine`` routes a fleet of mixed sizes through
power-of-two buckets, one engine each.

The CLI (``serve_fgft``) builds one fleet and one engine whatever the
mode, then serves it.  CPU smokes:

  # many graphs per step (DESIGN.md §7), every tier timed (§9)
  python -m repro.launch.serve --graphs 8 --graph-n 64 \
      --tiers full:1.0,balanced:0.5,draft:0.25 --filter-steps 20
  # the spectral filter bank through the fused path (§8); add
  # --directed anywhere for the T-transform family
  python -m repro.launch.serve --filter heat,tikhonov,wavelets:4 \
      --graphs 8 --graph-n 64 --filter-steps 20
  # a fleet of mixed sizes in power-of-two buckets (§10)
  python -m repro.launch.serve --ragged --graphs 9 \
      --graph-sizes 24,48,64 --filter-steps 20
  # an evolving fleet: edge churn, drift-triggered refits, hot swaps (§11)
  python -m repro.launch.serve --dynamic --graphs 4 --graph-n 48 \
      --update-rounds 4 --churn 0.02 --filter-steps 10
  # the async front door under a closed-loop load (§12)
  python -m repro.launch.serve --serve-async --graphs 4 --graph-n 32 \
      --load-requests 64 --load-workers 4
"""
from __future__ import annotations

import argparse
import pathlib
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.launch.mesh import auto_mesh, make_local_mesh
from repro.launch.service import (AsyncFGFTService, ShedError,
                                  closed_loop_load, open_loop_load)
from repro.runtime.sharding import (FleetPlacement, fleet_placement,
                                    matrix_batch_sharding)
from repro.tables import TABLE_PRECISIONS, table_arrays, with_precision

DEFAULT_TIERS = {"full": 1.0, "balanced": 0.5, "draft": 0.25}

# -- serving-engine telemetry (DESIGN.md §15) -------------------------------
_OBS_SWAPS = obs.counter("serve_swaps_total",
                         "versioned hot swaps installed (version > 0)",
                         ("family",))
_OBS_VERSION = obs.gauge("serve_version", "live serving version",
                         ("family",))
_OBS_STEPS = obs.counter("serve_steps_total", "engine steps served",
                         ("tier",))
_OBS_DRIFT = obs.gauge("serve_drift_score",
                       "per-graph drift score after the last maintain "
                       "tick", ("graph",))
_OBS_MAINTAIN = obs.counter("maintain_actions_total",
                            "maintenance controller decisions",
                            ("action",))


# ---------------------------------------------------------------------------
# Serving programs come from the plan cache (kernels/plan.py; DESIGN.md
# §13).  Staged tables + spectrum are ARGUMENTS, not closure constants: a
# hot-swapped basis version with unchanged table shapes reuses the
# compiled program, so the steady-state step path never recompiles across
# dynamic refreshes (fig11 asserts the compile count).  One cache entry
# per ApplyPlan serves every engine and every version in the process —
# the plan cache is the ONE program cache (the pre-plan `_tier_program`/
# `_bank_program` lru caches collapsed onto it).
# ---------------------------------------------------------------------------

def _tables(staged, precision: str = "f32") -> tuple:
    """Device table arrays of a StagedG/StagedT at the serving precision
    (``precision="bf16"`` casts the value tables ONCE per swap, matching
    ``ApplyPlan.prepare``)."""
    return table_arrays(with_precision(staged, precision))


@dataclass(frozen=True)
class _LiveVersion:
    """One immutable serving version: everything ``step``/``step_bank``
    read, bundled so the hot swap is a single attribute store (readers
    grab ``self._live`` once and never see a half-updated engine)."""

    basis: Any
    fwd: tuple
    bwd: tuple
    tiers: Dict[str, dict]
    fns: Dict[str, Any]
    bank: Any
    bank_gains: Any
    bank_fn: Any
    version: int
    #: per-graph programs (``ApplyPlan.row``): tier -> program, and the
    #: bank's; empty / None where the engine serves no row steps
    row_fns: Dict[str, Any]
    bank_row_fn: Any
    #: tier -> (h, gains h(spectrum) with pad columns zeroed), the last
    #: response each tier served: a dispatch's per-graph launches, and
    #: the dispatches after it, filter the spectrum once
    gains: Dict[str, tuple]
    #: tier (and ``None`` for the bank) -> the cut its programs serve
    #: (``None``: the full chain)
    cuts: Dict[Optional[str], Optional[int]]
    #: cut -> dense legs (kernels/plan.py), built at swap time, when the
    #: programs take legs in place of the staged tables; None when they
    #: walk the tables
    legs: Optional[Dict[Optional[int], tuple]]

    @property
    def lowering(self) -> str:
        return "walk" if self.legs is None else "dense"

    def operands(self, key: Optional[str]) -> tuple:
        """The leading arguments of tier ``key``'s programs (``None``:
        the bank's): both table sets walked, the cut's legs dense."""
        if self.legs is None:
            return self.fwd, self.bwd
        return (self.legs[self.cuts[key]],)


def _device_facts(devices) -> tuple:
    """(platform, least free memory ``bytes_limit - bytes_in_use``, or
    None where a device reports none) of the devices an engine serves
    on: what ``plan.choose_lowering`` decides from."""
    free = []
    for dev in devices:
        stats = dev.memory_stats()
        if not stats or "bytes_limit" not in stats:
            return devices[0].platform, None
        free.append(stats["bytes_limit"] - stats.get("bytes_in_use", 0))
    return devices[0].platform, min(free)


def parse_tiers(spec: str) -> Dict[str, float]:
    """'full:1.0,balanced:0.5,draft:0.25' -> {name: component fraction}."""
    tiers = {}
    for token in filter(None, spec.split(",")):
        name, _, frac = token.partition(":")
        if not frac:
            raise ValueError(f"tier {token!r} needs name:fraction")
        f = float(frac)
        if not 0.0 < f <= 1.0:
            raise ValueError(f"tier fraction must be in (0, 1], got {f}")
        name = name.strip()
        if not name:
            raise ValueError(f"tier {token!r} has an empty name")
        if name in tiers:
            # silent last-wins would quietly redefine the speedup baseline
            raise ValueError(f"duplicate tier name {name!r}")
        tiers[name] = f
    if not tiers:
        raise ValueError("empty tier spec")
    return tiers


def parse_args(argv=None):
    ap = argparse.ArgumentParser(
        description="Fit a fleet of graphs and serve filters over it.")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--graphs", type=int, default=8,
                    help="number of graphs served per step (B)")
    ap.add_argument("--graph-n", type=int, default=64)
    ap.add_argument("--ragged", action="store_true",
                    help="serve a HETEROGENEOUS fleet: graphs of mixed "
                         "sizes (--graph-sizes) are grouped into "
                         "power-of-two buckets, each bucket fitted in one "
                         "masked jit and served through its own "
                         "jitted tier programs (DESIGN.md §10)")
    ap.add_argument("--graph-sizes", default="24,48,64",
                    help="comma-separated graph sizes cycled over "
                         "--graphs when --ragged is given")
    ap.add_argument("--transforms", type=int, default=0,
                    help="g (0 -> 2 n log2 n)")
    ap.add_argument("--filter-steps", type=int, default=20)
    ap.add_argument("--signals", type=int, default=32,
                    help="signal rows filtered per graph per step")
    ap.add_argument("--backend", choices=("xla", "pallas"), default="xla")
    ap.add_argument("--precision", choices=("f32", "bf16"),
                    default="f32",
                    help="staged-table storage precision for serving: "
                         "bf16 halves the value-table bytes per version "
                         "while keeping f32 accumulation (the filter "
                         "error stays within the 2*Lip(h)*delta bound; "
                         "DESIGN.md §13)")
    ap.add_argument("--fused", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve through the fused single-program "
                         "operator path (default); --no-fused runs the "
                         "three-pass analysis->scale->synthesis staged "
                         "baseline (parity / benchmarking)")
    ap.add_argument("--directed", action="store_true",
                    help="serve DIRECTED graph Laplacians through the "
                         "T-transform family (kind='general'); without "
                         "this flag symmetric inputs route through the "
                         "G path")
    ap.add_argument("--tiers", default=None,
                    help="named anytime quality tiers as "
                         "'name:fraction,...' of the fundamental "
                         "components, e.g. 'full:1.0,balanced:0.5,"
                         "draft:0.25' (default).  Each tier compiles one "
                         "jitted program over the prefix-cut staged "
                         "tables (DESIGN.md §9)")
    ap.add_argument("--filter", default=None,
                    help="serve a spectral filter BANK through the fused "
                         "analysis->scale->synthesis path; "
                         "comma-separated responses, e.g. "
                         "'heat:3.0,tikhonov,lowpass,wavelets:4' "
                         "(repro/spectral/filters.py::named_responses)")
    # dynamic (evolving-graph) serving, DESIGN.md §11
    ap.add_argument("--dynamic", action="store_true",
                    help="serve an EVOLVING fleet: per "
                         "round, stream edge-update batches into the "
                         "engine (apply_updates), run the drift-triggered "
                         "refit controller (maintain) off the hot path, "
                         "and keep serving through versioned hot swaps")
    ap.add_argument("--update-rounds", type=int, default=5,
                    help="update/serve rounds in --dynamic mode")
    ap.add_argument("--churn", type=float, default=0.02,
                    help="fraction of each graph's edge slots perturbed "
                         "per round in --dynamic mode")
    ap.add_argument("--drift-thresholds", default=None,
                    help="refit-policy thresholds as "
                         "'refresh,extend,refit' drift scores "
                         "(default: the RefitPolicy defaults)")
    # async serving front-end (DESIGN.md §12)
    ap.add_argument("--serve-async", action="store_true",
                    help="serve through the ASYNC front-end: "
                         "bounded request queue with load "
                         "shedding, cross-tenant micro-batching into "
                         "fused dispatches, background maintenance, "
                         "per-tier SLO stats (launch/service.py)")
    ap.add_argument("--load-requests", type=int, default=64,
                    help="requests generated by the --serve-async load")
    ap.add_argument("--load-workers", type=int, default=4,
                    help="closed-loop tenant threads in --serve-async")
    ap.add_argument("--qps", type=float, default=0.0,
                    help="open-loop arrival rate for --serve-async "
                         "(0 = closed loop driven by --load-workers)")
    ap.add_argument("--max-queue", type=int, default=128,
                    help="admission-control queue bound (requests past "
                         "it are shed with a typed rejection)")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="max requests coalesced into one fused dispatch")
    ap.add_argument("--maintain-interval", type=float, default=0.05,
                    help="background maintenance period in seconds "
                         "(--serve-async --dynamic)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON of the run's "
                         "spans/events to PATH on exit (loads in "
                         "chrome://tracing and Perfetto; DESIGN.md §15)")
    ap.add_argument("--metrics-dir", default=None, metavar="DIR",
                    help="write metrics.json + metrics.prom snapshots "
                         "of the obs registry into DIR on exit")
    args = ap.parse_args(argv)
    args.policy = None
    if args.drift_thresholds:
        try:
            lo, mid, hi = (float(t) for t in
                           args.drift_thresholds.split(","))
        except ValueError:
            ap.error("--drift-thresholds must be three comma-separated "
                     "floats: refresh,extend,refit")
        from repro.dynamic.refit import RefitPolicy
        args.policy = RefitPolicy(refresh=lo, extend=mid, refit=hi)
    args.tier_map = (parse_tiers(args.tiers) if args.tiers
                     else dict(DEFAULT_TIERS))
    try:
        args.size_list = [int(s) for s in
                          filter(None, args.graph_sizes.split(","))]
    except ValueError:
        ap.error(f"--graph-sizes must be comma-separated ints, got "
                 f"{args.graph_sizes!r}")
    if args.ragged and (not args.size_list
                        or any(s < 2 for s in args.size_list)):
        ap.error("--graph-sizes needs at least one size >= 2")
    return args


class FGFTServeEngine:
    """Batched spectral-filter serving over a fleet of graphs, with
    anytime quality tiers and (optionally) streaming updates.

    One ``ApproxEigenbasis.fit`` factorizes all B Laplacians inside a
    single jit; every ``step`` then filters a (B, R, n) signal block with
    one batched fused-kernel dispatch (DESIGN.md §7).  ``tiers`` maps tier
    names to component fractions; each resolves to the nearest exact stage
    cut of the staged tables and binds ONE cached jitted program over the
    truncated (B, S', P) tables, so a draft-tier step costs proportionally
    fewer stages (DESIGN.md §9).  Symmetric fits refit the spectrum per
    tier (Lemma 1 on the prefix basis); general fits reuse the full-fit
    spectrum (a per-tier Lemma-2 refit needs a dense solve per graph).

    ``kind`` is forwarded to the fit ("auto" detects symmetry; pass
    "general" to force the T-transform family for directed Laplacians);
    ``hint`` keeps auto-detection but warns when it overrides the caller's
    expectation.  ``sizes`` ((B,) true graph sides) marks a zero-padded
    ragged bucket: the fit is masked to each graph's real coordinates and
    a step's padded signal columns come back zeroed (DESIGN.md §10) —
    that is how ``RaggedFGFTServeEngine`` builds its per-bucket engines.

    DYNAMIC mode (DESIGN.md §11): with ``dynamic=True`` the engine tracks
    the current Laplacians, accepts streaming deltas via
    ``apply_updates(graph_id, delta)``, and ``maintain()`` runs the
    drift-triggered refit controller (dynamic/refit.py) OFF the hot path:
    it scores drift (Hutchinson, dynamic/drift.py), picks the cheapest
    restoring action (reuse / Lemma-1 spectrum refresh / warm-start
    extend / full refit), rebuilds a complete serving version (tier
    spectra, tier program bindings, filter-bank gains) and swaps it in
    ATOMICALLY — ``step`` reads ``self._live`` once, so queries always
    see one consistent version.  Tier/bank programs take the staged
    tables as arguments, so a swap with unchanged shapes (reuse/refresh)
    triggers ZERO recompilation.  Per-graph basis versions + drift/refit
    counters are surfaced in ``stats["dynamic"]`` and persisted through
    ``save``/``load``."""

    def __init__(self, laps: jnp.ndarray, num_transforms: int = 0,
                 n_iter: int = 3, backend: str = "xla", mesh=None,
                 filters: Optional[str] = None, kind: str = "auto",
                 hint: Optional[str] = None,
                 tiers: Optional[Dict[str, float]] = None,
                 sizes=None, dynamic: bool = False, policy=None,
                 basis=None, drift_baseline=None,
                 precision: str = "f32", fused: bool = True,
                 block_b: Optional[int] = None, placement=None,
                 tier_spectra: Optional[Dict[tuple, Any]] = None):
        # deferred import: repro.core builds jnp constants at import time,
        # and launch modules must not touch jax state before mesh setup
        from repro.core import ApproxEigenbasis
        if precision not in TABLE_PRECISIONS:
            raise ValueError(f"precision must be one of "
                             f"{TABLE_PRECISIONS}, got {precision!r}")
        self.backend = backend
        # mesh placement (DESIGN.md §14): a BucketPlacement pins this
        # engine's graphs onto its OWN device subset — serving tables,
        # tier spectra and signals partition along the batch axis over the
        # bucket sub-mesh, so the steady-state step HLO is collective-free
        # AND maintenance (drift scoring, refits) runs on the bucket's
        # devices only, never stalling other buckets' hot paths.
        self.placement = placement
        if placement is not None:
            if np.asarray(laps).ndim != 3:
                raise ValueError("placement requires a batched (B, n, n) "
                                 "Laplacian stack")
            nb = np.asarray(laps).shape[0]
            if placement.batch != nb:
                raise ValueError(f"placement.batch={placement.batch} != "
                                 f"fleet batch {nb}")
            # placement OVERRIDES mesh: fits/refits shard over the
            # bucket's own sub-mesh — the structural half of
            # device-overlapped maintenance (a whole-mesh refit would
            # stall every other bucket's hot path)
            mesh = placement.mesh()
        self.mesh = mesh
        #: whether ``step_versioned(..., row=g)`` walks graph g alone: the
        #: batch sits on one device (unplaced, a mesh of at most one
        #: device), so a row index never crosses devices
        self.row_steps = placement is None and (mesh is None
                                                or mesh.size == 1)
        self._filters = filters
        self._tier_spec = dict(tiers or {"full": 1.0})
        self._n_iter = n_iter
        # serving precision/fusion policy (DESIGN.md §13): bf16 stores
        # the swap's value tables in bfloat16 (the plan program upcasts
        # the signal, so accumulation stays f32); fused=False serves the
        # three-pass staged baseline (parity / benchmarking)
        self._precision = precision
        self._fused = bool(fused)
        self._block_b = block_b
        laps = jnp.asarray(laps, jnp.float32)
        # dynamic engines quantize staged-table shapes so steady-state
        # refits land on the compiled-program caches (core/staging.py)
        self._stage_pad = (4, 8) if dynamic and laps.ndim == 3 else None
        fitted_here = basis is None
        if basis is None:
            if num_transforms <= 0:
                raise ValueError("num_transforms must be positive when "
                                 "no prefit basis is given")
            basis = ApproxEigenbasis.fit(
                laps, num_transforms, n_iter=n_iter, mesh=mesh, kind=kind,
                hint=hint, sizes=sizes, stage_pad=self._stage_pad)
        if mesh is not None:
            basis = basis.shard(mesh)
        self._g0 = basis.num_transforms
        self._kind = basis.kind
        if basis.sizes is None:
            self._pad_valid = None
        else:
            self._pad_valid = jnp.asarray(
                np.arange(basis.n) < np.asarray(basis.sizes)[..., None])
            if self.placement is not None and self._pad_valid.ndim == 2:
                # pad rows get all-False gains masks (their signals are
                # zero anyway; the mask just keeps the invariant obvious)
                self._pad_valid = self.placement.place(self._pad_valid)
        self.stats: Dict[str, Any] = {"steps": {}}
        self.dynamic = bool(dynamic)
        self._live = None
        if self.dynamic and basis.batched:
            pinned = basis.info.get("stage_pad")
            if fitted_here or not pinned:
                # pin the shape quantization to THIS fit's depth: refit
                # chains vary with graph content, so a fixed per-chunk
                # quantum + structural-max width makes every subsequent
                # refit land on the SAME (B, S, P) tables — the whole
                # maintenance/serving program suite stays compiled
                # across swaps.  A basis that already carries a pin (the
                # load path) keeps it: re-deriving the quantum from its
                # PADDED depth would inflate the tables ~1.5x per
                # save/load cycle
                basis = self._repin(basis)
            else:
                self._stage_pad = tuple(int(q) for q in pinned)
        self._install(basis, laps, tier_spectra)
        # the row programs' index argument, one device scalar per graph
        self._row_ids = (tuple(jnp.asarray(i, jnp.int32) for i in
                               range(np.shape(basis.spectrum)[0]))
                         if self.row_steps and basis.batched else ())
        # tracked Laplacians: the update/refit substrate in dynamic mode,
        # and what save() persists so load() can rebuild tier spectra
        # without refitting (small next to the staged tables)
        self._laps_host = np.array(laps, np.float32)
        if self.dynamic:
            from repro.dynamic.refit import RefitController, RefitPolicy
            self.controller = RefitController(policy or RefitPolicy())
            nb = laps.shape[0] if basis.batched else 1
            self.versions = np.zeros(nb, np.int64)
            self._dirty = np.zeros(nb, bool)
            self._updates = 0
            # drift scores are cached per update revision: idle ticks
            # with pending-but-unchanged updates reuse the last probe
            # pass instead of recomputing an identical estimate
            self._update_rev = 0
            self._scored_rev = -1
            self._last_drift = np.zeros(nb)
            if drift_baseline is not None:
                # a restored engine hands its persisted baseline straight
                # through — estimating one here would be thrown away
                self._baseline = np.atleast_1d(
                    np.asarray(drift_baseline, np.float64))
            elif basis.objective is not None:
                from repro.dynamic.drift import relative_objective
                self._baseline = relative_objective(basis.objective,
                                                    laps)
            else:
                # a refresh-swapped basis carries no exact objective;
                # anchor the baseline stochastically instead
                from repro.dynamic.drift import estimate_rel_residual
                p = self.controller.policy
                self._baseline = np.atleast_1d(estimate_rel_residual(
                    basis, self._laps_host, num_probes=p.num_probes,
                    seed=p.seed))
            self._refresh_dynamic_stats(np.zeros(nb))

    # -- the versioned hot swap (DESIGN.md §11) ----------------------------

    def _repin(self, basis):
        """Repack a batched basis with a depth quantum pinned to its own
        staged depth (see __init__); idempotent when already pinned."""
        from dataclasses import replace as _replace
        from repro.core.staging import (DEFAULT_NUM_CHUNKS,
                                        pack_g_batch_pair,
                                        pack_t_batch_pair)
        s0 = int(basis.fwd.num_stages)
        # depth pin: 1.5x the observed per-chunk depth — refit chains
        # vary tens of percent with graph content (most under topology
        # churn); a chunk overflowing the pin costs one recompile.
        # width pin: the STRUCTURAL maximum (disjoint pairs bound a
        # G-stage at n/2 entries, a T-stage at n), so the width can
        # never overflow and every refit lands on identical tables.
        q = max(-(-3 * s0 // (2 * DEFAULT_NUM_CHUNKS)), 1)
        w_max = basis.n // 2 if basis.kind == "sym" else basis.n
        pad = (q, max(8 * -(-w_max // 8), 8))
        if self._stage_pad == pad:
            return basis
        self._stage_pad = pad
        cuts = (sorted(set(np.asarray(basis.fwd.cuts)[:, 1].tolist()))
                if basis.fwd.cuts is not None else None)
        if basis.kind == "sym":
            fwd, bwd = pack_g_batch_pair(basis.factors, basis.n,
                                         cuts=cuts, pad=pad)
        else:
            fwd, bwd = pack_t_batch_pair(basis.factors, basis.n,
                                         cuts=cuts, pad=pad)
        return _replace(basis, fwd=fwd, bwd=bwd,
                        info={**basis.info, "stage_pad": pad})

    def warmup(self, signals: jnp.ndarray):
        """Compile the full serving + maintenance program suite up front
        (tier programs, bank, their per-graph programs on ``row_steps``
        engines at the block's row count, drift scorer, Lemma-1
        refresh), so the first real update round runs at steady-state
        cost."""
        for name in self._live.tiers:
            y = self.step(signals, tier=name)
            self.stats["steps"][name] -= 1      # warmup doesn't count
            if self._row_ids:
                y = self.step_versioned(signals[0], tier=name, row=0)[0]
                self.stats["steps"][name] -= 1
        if self._live.bank is not None:
            y = self.step_bank(signals)
            if self._row_ids:
                y = self.step_bank_versioned(signals[0], row=0)[0]
        if self.dynamic:
            self.drift()
            if self._kind == "sym":
                from repro.dynamic.refit import lemma1_refresh
                jax.block_until_ready(lemma1_refresh(
                    self._live.basis, jnp.asarray(self._laps_host)))
        return jax.block_until_ready(y)

    def _install(self, basis, laps, tier_spectra=None):
        """Build a COMPLETE serving version (per-tier refit spectra,
        cached program bindings, filter-bank gains) and swap it in with a
        single attribute store.  ``laps``: the Laplacians the tier
        spectra refit against — the fit stack at construction, the
        updated stack on a dynamic swap.  ``tier_spectra`` ({(num_stages,
        num_transforms): spectrum}) supplies cut-tier spectra computed
        earlier against the same basis and ``laps`` (a checkpoint's);
        cuts it lacks are refit here."""
        from repro.kernels.plan import ApplyPlan

        full_stages = int(basis.fwd.num_stages)
        ladder = {name: basis.select_tier(fraction=frac)
                  for name, frac in self._tier_spec.items()}
        # each tier's program cut (None: the full chain), and the bank's
        served = {name: None if k >= full_stages else k
                  for name, (k, _) in ladder.items()}
        if self._filters:
            served[None] = None
        old = self._live
        # a Lemma-1 refresh keeps the chain (its factors and staging),
        # so the legs too: only the spectrum changes
        keep_legs = (old is not None and old.legs is not None
                     and basis.factors is old.basis.factors
                     and full_stages == int(old.basis.fwd.num_stages)
                     and set(served.values()) == set(old.legs))
        lowering = ("dense" if keep_legs
                    else self._lowering(basis, len(set(served.values()))))

        def _plan(mode, num_stages=None, row=False):
            return ApplyPlan(family=basis.kind, mode=mode, n=basis.n,
                             batched=basis.batched, backend=self.backend,
                             num_stages=num_stages,
                             precision=self._precision,
                             fused=self._fused, block_b=self._block_b,
                             placement=self.placement, row=row,
                             lowering=lowering)

        def _place(arr):
            # per-graph operands (tier spectra, bank gains) pad with zero
            # rows to the per-device batch quantum and pin onto the
            # bucket's devices, matching the placed tables; identity when
            # the engine is unplaced
            if self.placement is None or arr is None:
                return arr
            return self.placement.place(arr)

        rows = self.row_steps and basis.batched
        tiers: Dict[str, dict] = {}
        fns: Dict[str, Any] = {}
        row_fns: Dict[str, Any] = {}
        for name, (n_stages, n_comp) in ladder.items():
            cut = served[name]
            if cut is None or basis.kind != "sym":
                spec = basis.spectrum
            elif tier_spectra and (cut, n_comp) in tier_spectra:
                spec = jnp.asarray(tier_spectra[(cut, n_comp)], jnp.float32)
            else:
                from repro.dynamic.refit import prefix_spectrum
                spec = prefix_spectrum(basis, laps, cut)
            tiers[name] = {"num_stages": n_stages,
                           "num_transforms": n_comp,
                           "spectrum": _place(spec)}
            fns[name] = _plan("operator", cut).program()
            if rows:
                row_fns[name] = _plan("operator", cut, row=True).program()
        bank = bank_gains = bank_fn = bank_row_fn = None
        if self._filters:
            from repro.spectral import SpectralFilterBank, named_responses
            # gains are recomputed from the (possibly refreshed) spectrum
            # on every swap; the serving program itself is shape-cached
            bank = SpectralFilterBank(basis, named_responses(self._filters))
            bank_gains = _place(bank.gains())
            bank_fn = _plan("bank").program()
            if rows:
                bank_row_fn = _plan("bank", row=True).program()
        version = 0 if self._live is None else self._live.version + 1
        # placed engines build their table arguments through the plan's
        # prepare (batch-padded + NamedSharding-pinned); unplaced engines
        # keep the plain host->device tables
        if self.placement is not None:
            prep = _plan("operator")
            fwd_t, bwd_t = prep.prepare(basis.fwd), prep.prepare(basis.bwd)
        else:
            fwd_t = _tables(basis.fwd, self._precision)
            bwd_t = _tables(basis.bwd, self._precision)
        legs = None
        if keep_legs:
            legs = old.legs
        elif lowering == "dense":
            # the programs take the legs of their cut in place of the
            # tables, built once for the version where the tables live
            with obs.default_tracer().span(
                    "serve.densify", cat="serve",
                    args={"family": basis.kind, "w": basis.n,
                          "cuts": len(set(served.values()))}):
                legs = _plan("operator").legs(fwd_t, bwd_t,
                                              served.values())
                jax.block_until_ready(list(legs.values()))
        self._live = _LiveVersion(
            basis=basis, fwd=fwd_t, bwd=bwd_t, tiers=tiers,
            fns=fns, bank=bank, bank_gains=bank_gains, bank_fn=bank_fn,
            version=version, row_fns=row_fns, bank_row_fn=bank_row_fn,
            gains={}, cuts=served, legs=legs)
        _OBS_VERSION.set(version, family=basis.kind)
        if version > 0:
            _OBS_SWAPS.inc(family=basis.kind)
        obs.default_tracer().event(
            "serve_swap", cat="serve",
            args={"version": version, "family": basis.kind,
                  "num_stages": full_stages,
                  "tiers": sorted(tiers)})
        # default tier = highest quality in the map, whatever its name
        self.default_tier = max(
            tiers, key=lambda k: tiers[k]["num_transforms"])
        for name in tiers:
            self.stats["steps"].setdefault(name, 0)
        self.stats["tiers"] = {name: {k: t[k] for k in
                                      ("num_stages", "num_transforms")}
                               for name, t in tiers.items()}

    def _lowering(self, basis, num_cuts: int) -> str:
        """How this version's operator and bank programs run: dense legs
        on a TPU for a fused f32 xla engine, unplaced on one device or
        placed over a bucket's, where its legs (one or two w x w
        matrices per graph a device holds, for each of the ``num_cuts``
        distinct cuts served) fit the free memory of those devices as
        ``plan.choose_lowering`` counts it, the legs of the version they
        replace included; the walk otherwise (the CPU, bf16 tables, the
        Pallas kernels, the three-pass baseline, mesh-sharded engines
        without a placement)."""
        from repro.kernels.plan import choose_lowering, dense_leg_count
        if not (self.backend == "xla" and self._fused
                and (self.placement is not None or self.mesh is None
                     or self.mesh.size == 1)):
            return "walk"
        if self.placement is not None:
            devices = list(self.placement.mesh().devices.flat)
            per_device = self.placement.batch_padded // len(devices)
        else:
            devices = jax.devices()[:1]
            per_device = int(np.shape(basis.spectrum)[0]) \
                if basis.batched else 1
        leg_bytes = (num_cuts * dense_leg_count(basis.kind) * per_device
                     * basis.n ** 2 * 4)
        platform, free = _device_facts(devices)
        old = self._live
        if free is not None and old is not None and old.legs is not None:
            # the legs this version replaces are freed once it is live
            free += sum(leg.nbytes for legs in old.legs.values()
                        for leg in legs) // len(devices)
        return choose_lowering(platform, self._precision, leg_bytes, free)

    @property
    def lowering(self) -> str:
        """The live version's lowering: "dense" or "walk"."""
        return self._live.lowering

    @property
    def basis(self):
        """The currently served basis (read-only snapshot)."""
        return self._live.basis

    @property
    def tiers(self) -> Dict[str, dict]:
        """Tier geometry + served spectra of the live version."""
        return self._live.tiers

    @property
    def bank(self):
        return self._live.bank

    # -- serving hot path --------------------------------------------------

    def _row_id(self, row: int):
        """Batch row ``row`` as the row programs' index argument."""
        if not self._row_ids:
            raise ValueError("row steps need an unplaced batched engine "
                             "on one device")
        if not 0 <= row < len(self._row_ids):
            raise ValueError(f"row {row} not in a batch of "
                             f"{len(self._row_ids)}")
        return self._row_ids[row]

    def _gains(self, live: _LiveVersion, tier: str, h):
        """``h`` of the tier's spectrum in ``live``, pad columns zeroed.
        ``h`` is a pure map of the frequencies, so the gains are kept on
        ``live`` for the last ``h`` the tier saw and computed again only
        for another ``h`` or another version."""
        hit = live.gains.get(tier)
        if hit is not None and hit[0] is h:
            return hit[1]
        d = h(live.tiers[tier]["spectrum"])
        if self._pad_valid is not None:
            # h(0) need not be 0 (heat/Tikhonov map 0 -> 1): unmasked
            # gains would leak pad columns of x into the output
            d = jnp.where(self._pad_valid, d, 0.0)
        live.gains[tier] = (h, d)
        return d

    def _step_on(self, live: _LiveVersion, signals: jnp.ndarray, h,
                 tier: Optional[str], row: Optional[int] = None
                 ) -> jnp.ndarray:
        """Tier dispatch against ONE live-version snapshot: tables, tier
        spectra and program binding all come from ``live``, so a
        concurrent ``maintain()`` swap can never mix versions inside a
        single response (the async front-end relies on this).  With
        ``row``, ``signals`` is graph ``row``'s (R, n) block and only
        that graph is walked."""
        rid = None if row is None else self._row_id(row)
        tier = tier if tier is not None else self.default_tier
        d = (live.tiers[tier]["spectrum"] if h is None
             else self._gains(live, tier, h))
        self.stats["steps"][tier] += 1
        _OBS_STEPS.inc(tier=tier)
        ops = live.operands(tier)
        if rid is not None:
            return live.row_fns[tier](*ops, d, signals, rid)
        if self.placement is not None:
            # callers hand true-B blocks; pad rows are zero signals on
            # identity pad tables, so the padded rows compute zeros that
            # the crop discards — per-device work, no collectives
            y = live.fns[tier](*ops, d, self.placement.place(signals))
            return y[:self.placement.batch]
        return live.fns[tier](*ops, d, signals)

    def step(self, signals: jnp.ndarray, h=None,
             tier: Optional[str] = None) -> jnp.ndarray:
        """Filter one (B, R, n) signal block on every graph at once, at
        the requested quality tier (default: the highest-quality tier in
        the map, whatever its name).  ``h`` maps the tier's (refit) graph
        frequencies to gains."""
        return self._step_on(self._live, signals, h, tier)

    def step_versioned(self, signals: jnp.ndarray, h=None,
                       tier: Optional[str] = None,
                       row: Optional[int] = None) -> tuple:
        """``step`` that also returns the serving version that produced
        the answer, both read from a SINGLE atomic ``_live`` snapshot
        (DESIGN.md §12: per-response version accounting for the async
        service).  ``row=g`` filters graph g's (R, n) block alone
        (``row_steps`` engines)."""
        live = self._live
        return self._step_on(live, signals, h, tier, row), live.version

    def walk_stages(self, tier: Optional[str]) -> int:
        """Stages one launch walks on one graph block of the live
        version, over both legs: the tier's ``num_stages`` for its
        analysis and again for its synthesis; for ``tier=None``, the
        bank, the full tables once each way (fused) or once each way
        per filter (three-pass).  0 under the dense lowering, which
        walks no stage."""
        live = self._live
        if live.lowering == "dense":
            return 0
        if tier is None:
            legs = 2 if self._fused else 2 * len(live.bank)
            return legs * int(live.basis.fwd.num_stages)
        return 2 * int(live.tiers[tier]["num_stages"])

    def step_bank(self, signals: jnp.ndarray) -> jnp.ndarray:
        """All F bank responses on every graph: (B, R, n) ->
        (B, F, R, n), one fused dispatch (full tier; DESIGN.md §8)."""
        return self.step_bank_versioned(signals)[0]

    def step_bank_versioned(self, signals: jnp.ndarray,
                            row: Optional[int] = None) -> tuple:
        """``step_bank`` plus the serving version, from one atomic
        ``_live`` snapshot (DESIGN.md §12); ``row=g``: graph g's (R, n)
        block -> (F, R, n)."""
        live = self._live
        if live.bank is None:
            raise ValueError("engine was built without --filter responses")
        _OBS_STEPS.inc(tier="bank")
        ops = live.operands(None)
        if row is not None:
            return (live.bank_row_fn(*ops, live.bank_gains, signals,
                                     self._row_id(row)),
                    live.version)
        if self.placement is not None:
            y = live.bank_fn(*ops, live.bank_gains,
                             self.placement.place(signals))
            return y[:self.placement.batch], live.version
        return (live.bank_fn(*ops, live.bank_gains, signals),
                live.version)

    # -- streaming updates + drift-triggered refits (DESIGN.md §11) --------

    def _require_dynamic(self):
        if not self.dynamic:
            raise ValueError("engine was built without dynamic=True")

    def _graph_size(self, graph_id: int) -> int:
        basis = self._live.basis
        if basis.sizes is None:
            return basis.n
        sizes = np.asarray(basis.sizes)
        return int(sizes[graph_id]) if basis.batched else int(sizes)

    def apply_updates(self, graph_id: int, delta):
        """Absorb one update batch for graph ``graph_id`` into the
        tracked Laplacian.  ``delta``: an ``UpdateBatch`` (edge
        insert/delete/reweight list, dynamic/stream.py) or a dense
        Laplacian delta ((n_i, n_i) arrays from a smaller ragged graph
        are embedded at the leading block).  The SERVED basis is
        untouched until the next ``maintain()`` decides an action — the
        hot path never pays for refit work."""
        self._require_dynamic()
        from repro.dynamic.stream import UpdateBatch, laplacian_delta
        basis = self._live.basis
        n = basis.n
        size = self._graph_size(graph_id)
        if isinstance(delta, UpdateBatch):
            dl = laplacian_delta(delta, size)   # bounds-checked at size
        else:
            dl = np.asarray(delta, np.float32)
            if dl.shape[0] > size:
                raise ValueError(f"delta side {dl.shape[0]} exceeds graph "
                                 f"{graph_id}'s size {size}")
        if dl.shape[0] < n:                     # embed into the bucket
            pad = np.zeros((n, n), np.float32)
            pad[:dl.shape[0], :dl.shape[1]] = dl
            dl = pad
        if basis.batched:
            self._laps_host[graph_id] += dl
        else:
            if graph_id != 0:
                raise ValueError("unbatched engine serves graph 0 only")
            self._laps_host += dl
        self._dirty[graph_id] = True
        self._updates += 1
        self._update_rev += 1

    def drift(self) -> np.ndarray:
        """Per-graph drift scores of the LIVE version on the tracked
        (updated) Laplacians: Hutchinson relative residual minus the
        baseline recorded at the last structural (re)fit, floored at 0."""
        self._require_dynamic()
        from repro.dynamic.drift import estimate_rel_residual
        p = self.controller.policy
        est = estimate_rel_residual(self._live.basis, self._laps_host,
                                    num_probes=p.num_probes, seed=p.seed)
        return np.maximum(np.atleast_1d(est) - self._baseline, 0.0)

    def maintain(self) -> dict:
        """One OFF-hot-path controller tick: score drift, pick the
        cheapest restoring action, execute it as a cached compiled
        program, and atomically swap the new serving version.  Returns
        {action, drift, post_drift, versions, swap_version}."""
        self._require_dynamic()
        from repro.dynamic.refit import Action
        if not self._dirty.any():
            zero = np.zeros_like(self._baseline)
            self.controller.record(Action.REUSE, zero,  # idle tick counts
                                   drift=zero)
            self._refresh_dynamic_stats(zero)
            self._obs_maintain(Action.REUSE.value, zero, zero)
            return {"action": Action.REUSE.value, "drift": zero,
                    "post_drift": zero,
                    "versions": self.versions.copy(),
                    "swap_version": self._live.version}
        if self._scored_rev != self._update_rev:
            self._last_drift = self.drift()
            self._scored_rev = self._update_rev
        drift = self._last_drift
        # the general family has no cheap spectrum refresh (Lemma 2 needs
        # a dense solve per graph) — the controller escalates for it
        action = self.controller.decide(
            drift, can_refresh=self._kind == "sym")
        post = drift
        if action is not Action.REUSE:
            self._execute(action)
            bump = self._dirty.copy()
            if action in (Action.EXTEND, Action.REFIT):
                bump[:] = True      # every chain in the batch was regrown
            self.versions[bump] += 1
            self._dirty[:] = False
            post = self.drift()
            self._last_drift = post
            self._scored_rev = self._update_rev
        self.controller.record(action, post, drift=drift)
        self._refresh_dynamic_stats(post)
        self._obs_maintain(action.value, drift, post)
        return {"action": action.value, "drift": drift,
                "post_drift": post, "versions": self.versions.copy(),
                "swap_version": self._live.version}

    def _obs_maintain(self, action: str, drift, post):
        """Record one maintain decision in the obs layer: the action
        counter, per-graph drift gauges (post-action scores), and one
        queryable trace event mirroring the controller's timeline entry
        (dynamic/refit.py)."""
        _OBS_MAINTAIN.inc(action=action)
        post = np.atleast_1d(np.asarray(post, np.float64))
        for gid, d in enumerate(post):
            _OBS_DRIFT.set(float(d), graph=gid)
        obs.default_tracer().event(
            "maintain", cat="maintain",
            args={"action": action,
                  "drift_max": float(np.max(np.atleast_1d(drift))),
                  "post_drift_max": float(np.max(post)),
                  "swap_version": self._live.version})

    def _execute(self, action):
        """Run one refit action through its cached compiled program and
        install the resulting serving version."""
        from dataclasses import replace as _replace
        from repro.core import ApproxEigenbasis
        from repro.dynamic.refit import Action, lemma1_refresh
        basis = self._live.basis
        laps = jnp.asarray(self._laps_host)
        if self.mesh is not None and basis.batched:
            laps = jax.device_put(
                laps, matrix_batch_sharding(self.mesh, laps.ndim,
                                            batch=laps.shape[0]))
        if action is Action.REFRESH:
            # spectrum-only: the factor chain (and its staged tables, and
            # the baseline anchored at the last structural fit) survive
            new_spec = lemma1_refresh(basis, laps)
            basis = _replace(basis, spectrum=new_spec, objective=None)
        elif action is Action.EXTEND:
            p = self.controller.policy
            extra = max(int(round(p.extend_fraction * self._g0)), 1)
            basis = basis.extend(laps, basis.num_transforms + extra,
                                 n_iter=0, mesh=self.mesh)
        elif action is Action.REFIT:
            # keep the fit's RESOLVED greedy criterion: refitting under
            # the default score would silently switch the criterion
            # mid-stream (the bug class the score persistence in
            # core/eigenbasis.py save/load exists to prevent)
            score = (basis.info.get("score") if self._kind == "sym"
                     else None)
            basis = ApproxEigenbasis.fit(
                laps, self._g0, n_iter=self._n_iter, kind=self._kind,
                score=score, sizes=basis.sizes, mesh=self.mesh,
                stage_pad=self._stage_pad)
        else:
            raise ValueError(f"not an executable action: {action}")
        if self.mesh is not None:
            basis = basis.shard(self.mesh)
        if action in (Action.EXTEND, Action.REFIT):
            # re-baseline at the new structural fit (exact objective)
            from repro.dynamic.drift import relative_objective
            self._baseline = relative_objective(basis.objective, laps)
        self._install(basis, laps)

    def _refresh_dynamic_stats(self, last_drift):
        self.stats["dynamic"] = {
            "updates": int(self._updates) if hasattr(self, "_updates")
            else 0,
            "versions": self.versions.tolist(),
            "swap_version": self._live.version,
            "actions": dict(self.controller.counts),
            "last_drift": np.asarray(last_drift).tolist(),
        }

    # -- persistence (checkpoint/store.py; DESIGN.md §6/§11) ---------------

    def save(self, directory, step: int = 0, extra_metadata=None,
             shards: Optional[int] = None):
        """Persist the live basis + serving state through the atomic
        checkpoint store: the tracked Laplacians ride as an extra state
        leaf, per-graph versions and drift/refit counters as metadata,
        and the engine swap counter as the basis version.
        ``extra_metadata`` merges additional top-level metadata keys (the
        async service persists its SLO counters this way).  ``shards``
        controls the checkpoint's table-file split (checkpoint/store.py);
        a placed engine defaults to one shard per owning device so each
        file holds one device's rows."""
        from dataclasses import replace as _replace
        live = self._live
        basis = _replace(live.basis,
                         info={**live.basis.info,
                               "version": int(live.version)})
        if shards is None:
            shards = (self.placement.num_devices
                      if self.placement is not None else 1)
        extra_meta: Dict[str, Any] = {
            "serve": {"tier_spec": self._tier_spec,
                      "filters": self._filters,
                      "n_iter": self._n_iter,
                      "num_transforms": int(self._g0),
                      "precision": self._precision,
                      "fused": self._fused}}
        if self.placement is not None:
            extra_meta["serve"]["placement"] = {
                "device_ids": list(self.placement.device_ids),
                "batch": int(self.placement.batch)}
        if extra_metadata:
            overlap = {"serve", "dynamic"} & set(extra_metadata)
            if overlap:
                raise ValueError(f"extra_metadata may not override the "
                                 f"engine's own keys: {sorted(overlap)}")
            extra_meta.update(extra_metadata)
        extra_state = {"laps": jnp.asarray(self._laps_host)}
        # the cut tiers' served spectra: a restored engine serves these
        # instead of refitting them, so its answers do not depend on how
        # the reader's devices would batch the Lemma-1 refit (placed
        # serving is bitwise the saving engine's; DESIGN.md §14)
        nb = live.basis.spectrum.shape[0] if live.basis.batched else None
        cuts = []
        for t in live.tiers.values():
            cut = [int(t["num_stages"]), int(t["num_transforms"])]
            if (live.basis.kind == "sym"
                    and cut[0] < live.basis.fwd.num_stages
                    and cut not in cuts):
                cuts.append(cut)
                extra_state["tier_spectrum_{}_{}".format(*cut)] = (
                    jnp.asarray(np.asarray(t["spectrum"])[:nb]))
        extra_meta["serve"]["tier_spectrum_cuts"] = sorted(cuts)
        if self.dynamic:
            extra_meta["dynamic"] = {
                "versions": self.versions.tolist(),
                "updates": int(self._updates),
                "baseline": np.asarray(self._baseline).tolist(),
                "controller": self.controller.state_dict(),
                # pending-maintenance flags: a restored engine must not
                # silently serve a basis whose updates were never scored
                "dirty": self._dirty.tolist(),
            }
        return basis.save(directory, step, extra_state=extra_state,
                          extra_metadata=extra_meta, shards=shards)

    @classmethod
    def load(cls, directory, step: Optional[int] = None, *,
             laps=None, backend: str = "xla", mesh=None,
             filters: Optional[str] = None,
             tiers: Optional[Dict[str, float]] = None,
             dynamic: Optional[bool] = None, policy=None,
             precision: Optional[str] = None,
             fused: Optional[bool] = None,
             block_b: Optional[int] = None,
             placement=None) -> "FGFTServeEngine":
        """Rebuild a serving engine from a checkpoint WITHOUT refitting.

        ``placement`` pins the restored engine onto a BucketPlacement.
        The checkpoint holds full (reassembled) arrays whatever shard
        count wrote it, so loading a 4-device checkpoint onto a 1- or
        8-device placement just re-places — it never crashes on a mesh
        shape mismatch (DESIGN.md §14).

        Dynamic engines restore their tracked Laplacians, per-graph
        versions, baselines and controller counters; checkpoints written
        before the dynamic subsystem (or by plain ``ApproxEigenbasis.
        save``) restore with every version at 0 and fresh counters —
        loading them must not raise.  ``laps`` overrides the tracked
        Laplacians (required for pre-dynamic checkpoints, which carry
        none)."""
        from repro.checkpoint import (latest_step, read_metadata,
                                      restore_checkpoint)
        from repro.core import ApproxEigenbasis
        if step is None:
            step = latest_step(directory)
            if step is None:
                raise FileNotFoundError(
                    f"no committed checkpoint in {directory}")
        basis = ApproxEigenbasis.load(directory, step)
        meta = read_metadata(directory, step)
        serve_meta = meta.get("serve", {})
        dyn_meta = meta.get("dynamic")
        if dynamic is None:
            dynamic = dyn_meta is not None
        # saved cut-tier spectra were refit against the saved Laplacians:
        # caller-supplied ``laps`` refit them instead
        tier_spectra = None
        if laps is None:
            shape = ((int(basis.spectrum.shape[0]), basis.n, basis.n)
                     if basis.batched else (basis.n, basis.n))
            try:
                state, _, _ = restore_checkpoint(
                    directory, {"laps": jnp.zeros(shape, jnp.float32)},
                    step=step)
            except KeyError as exc:
                raise ValueError(
                    "checkpoint carries no tracked Laplacians (written "
                    "by plain ApproxEigenbasis.save, not engine.save); "
                    "pass laps= explicitly") from exc
            laps = state["laps"]
            names = {(int(s), int(c)): f"tier_spectrum_{s}_{c}"
                     for s, c in serve_meta.get("tier_spectrum_cuts", [])}
            if names:
                like = {name: jnp.zeros(basis.spectrum.shape, jnp.float32)
                        for name in names.values()}
                state, _, _ = restore_checkpoint(directory, like, step=step)
                tier_spectra = {key: state[name]
                                for key, name in names.items()}
        engine = cls(laps, n_iter=serve_meta.get("n_iter", 3),
                     backend=backend, mesh=mesh,
                     filters=filters if filters is not None
                     else serve_meta.get("filters"),
                     tiers=tiers if tiers is not None
                     else serve_meta.get("tier_spec"),
                     dynamic=dynamic, policy=policy, basis=basis,
                     drift_baseline=(dyn_meta or {}).get("baseline"),
                     precision=precision if precision is not None
                     else serve_meta.get("precision", "f32"),
                     fused=fused if fused is not None
                     else serve_meta.get("fused", True),
                     block_b=block_b, placement=placement,
                     tier_spectra=tier_spectra)
        from dataclasses import replace as _replace
        engine._live = _replace(
            engine._live, version=int(basis.info.get("version", 0)))
        # the ORIGINAL fitted budget, not the (possibly extended) current
        # component count: REFIT clamps back to it and EXTEND budgets are
        # fractions of it — re-anchoring at the grown count would let
        # chains grow without bound across save/load cycles
        engine._g0 = int(serve_meta.get("num_transforms", engine._g0))
        if engine.dynamic:
            dyn = dyn_meta or {}
            nb = engine.versions.shape[0]
            versions = dyn.get("versions")
            if versions is not None:
                engine.versions = np.asarray(versions, np.int64)
            else:
                engine.versions = np.zeros(nb, np.int64)
            engine._updates = int(dyn.get("updates", 0))
            if dyn.get("dirty") is not None:
                engine._dirty = np.asarray(dyn["dirty"], bool)
                if engine._dirty.any():
                    engine._update_rev += 1   # force a fresh drift pass
            engine.controller.load_state_dict(dyn.get("controller", {}))
            engine._refresh_dynamic_stats(
                np.zeros_like(engine._baseline))
        return engine


def bucket_width(n: int, min_width: int = 8) -> int:
    """Power-of-two bucket for an n-node graph (floored at ``min_width``).

    Pow-2 buckets bound the padding waste at < 2x flops while keeping the
    number of distinct compiled programs logarithmic in the size range —
    every graph in [w/2+1, w] shares one jitted fit and one jitted tier
    program set (DESIGN.md §10)."""
    if n < 2:
        raise ValueError(f"graph size must be >= 2, got {n}")
    w = max(int(min_width), 2)
    while w < n:
        w *= 2
    return w


def _resolve_fleet_placement(placement, mesh, bucket_of):
    """Normalize the router's ``placement`` argument.

    ``None`` -> unplaced; ``"auto"`` -> work-weighted partition of the
    mesh's data-axis devices over the buckets (weight ~ members * w log
    w, the per-bucket apply cost); a ``FleetPlacement`` is validated
    against the router's bucket geometry so a stale manifest fails
    loudly instead of mis-routing."""
    if placement is None:
        return None
    if isinstance(placement, str):
        if placement != "auto":
            raise ValueError(f"placement must be None, 'auto' or a "
                             f"FleetPlacement, got {placement!r}")
        if mesh is None:
            raise ValueError("placement='auto' requires a mesh to "
                             "partition (pass mesh=)")
        sizes = {w: len(m) for w, m in bucket_of.items()}
        weights = {w: len(m) * w * float(np.log2(max(w, 2)))
                   for w, m in bucket_of.items()}
        return fleet_placement(mesh, sizes, weights=weights)
    if not isinstance(placement, FleetPlacement):
        raise TypeError(f"placement must be None, 'auto' or a "
                        f"FleetPlacement, got {type(placement).__name__}")
    missing = sorted(set(bucket_of) - {k for k, _ in placement.items()})
    if missing:
        raise ValueError(f"placement has no entry for bucket(s) "
                         f"{missing}")
    for w, members in bucket_of.items():
        if placement[w].batch != len(members):
            raise ValueError(
                f"placement bucket {w} sized for batch "
                f"{placement[w].batch}, fleet has {len(members)} graphs "
                f"there — re-place with fleet_placement on the current "
                f"fleet")
    return placement


def _read_placement_manifest(path, bucket_of):
    """Parse + validate a saved placement.json; None if absent.

    The manifest is advisory (readers re-place on their own mesh) but
    its SHAPE is contract: a truncated or hand-mangled file raises a
    clear ValueError instead of silently loading an unplaced fleet."""
    import json
    path = pathlib.Path(path)
    if not path.exists():
        return None
    try:
        pm = json.loads(path.read_text())
        num_devices = int(pm["num_devices"])
        buckets = {int(k): {"device_ids": [int(i) for i in
                                           v["device_ids"]],
                            "batch": int(v["batch"])}
                   for k, v in pm["buckets"].items()}
        if num_devices < 1 or not buckets:
            raise ValueError("num_devices < 1 or no buckets")
        for k, v in buckets.items():
            if not v["device_ids"] or v["batch"] < 1:
                raise ValueError(f"bucket {k} has empty device_ids or "
                                 f"non-positive batch")
    except (KeyError, TypeError, ValueError,
            json.JSONDecodeError) as exc:
        raise ValueError(
            f"corrupt placement manifest {path}: {exc} — re-save the "
            f"fleet or delete the file to load unplaced") from exc
    missing = sorted(set(bucket_of) - set(buckets))
    if missing:
        raise ValueError(
            f"placement manifest {path} missing bucket(s) {missing} "
            f"present in router.json — checkpoint is inconsistent")
    return buckets


class RaggedFGFTServeEngine:
    """Size-bucketed serving for a HETEROGENEOUS graph fleet.

    A production fleet arrives with many Laplacian sizes; one (B, n, n)
    stack cannot hold it.  The router groups graphs into power-of-two
    buckets (``bucket_width``), zero-pads each graph into its bucket and
    fits every bucket in ONE masked jit (``ApproxEigenbasis.fit``
    with ``sizes``), so per-graph accuracy matches each graph's own-size
    fit while the fleet still compiles O(log sizes) programs instead of
    O(graphs).  Fitted per-bucket engines (and their jitted tier programs)
    are cached for the lifetime of the router; ``step`` scatters a
    per-graph signal list to the right bucket dispatches and gathers the
    results back in request order (DESIGN.md §10).

    ``num_transforms``: components per graph for the LARGEST bucket;
    smaller buckets scale as w log2 w (the paper's g = alpha n log2 n
    regime keeps alpha constant across the fleet).  0 -> 2 w log2 w.

    ``placement``: ``"auto"`` partitions the mesh's data-axis devices
    over the buckets (whole buckets per device subset, work-weighted;
    ``runtime.sharding.fleet_placement``), or pass a prebuilt
    ``FleetPlacement``.  Placed routers serve each bucket on its OWN
    devices — steady-state steps run collective-free, and a dirty
    bucket's refit touches only that bucket's devices (DESIGN.md §14).
    """

    def __init__(self, laps, num_transforms: int = 0, n_iter: int = 3,
                 backend: str = "xla", mesh=None,
                 filters: Optional[str] = None, kind: str = "auto",
                 hint: Optional[str] = None,
                 tiers: Optional[Dict[str, float]] = None,
                 min_width: int = 8, dynamic: bool = False, policy=None,
                 precision: str = "f32", fused: bool = True,
                 block_b: Optional[int] = None, placement=None,
                 _engines: Optional[Dict[int, FGFTServeEngine]] = None):
        from repro.core import pad_ragged
        laps = [np.asarray(lap, np.float32) for lap in laps]
        if not laps:
            raise ValueError("empty graph fleet")
        self.sizes = [lap.shape[0] for lap in laps]
        self._denoms = np.asarray([max(float((lap * lap).sum()), 1e-30)
                                   for lap in laps])
        self.widths = [bucket_width(s, min_width) for s in self.sizes]
        self.dynamic = bool(dynamic)
        # bucket -> positions in request order (stable within a bucket)
        self.bucket_of: Dict[int, list] = {}
        for pos, w in enumerate(self.widths):
            self.bucket_of.setdefault(w, []).append(pos)
        w_max = max(self.bucket_of)
        self.placement = _resolve_fleet_placement(placement, mesh,
                                                  self.bucket_of)

        def scaled_g(w: int) -> int:
            if not num_transforms:
                return int(2 * w * np.log2(w))
            alpha = num_transforms / (w_max * np.log2(w_max))
            return max(int(round(alpha * w * np.log2(w))), 1)

        #: per-bucket onboarding seconds (fit, pack, tier spectra) of
        #: the fits this router ran; empty for a restored router
        self.onboard_seconds: Dict[int, float] = {}
        if _engines is not None:               # load() restores prefit
            self.engines = _engines
            return
        self.engines: Dict[int, FGFTServeEngine] = {}
        for w, members in sorted(self.bucket_of.items()):
            t0 = time.perf_counter()
            stack, sizes = pad_ragged([laps[p] for p in members], width=w)
            self.engines[w] = FGFTServeEngine(
                stack, scaled_g(w), n_iter=n_iter, backend=backend,
                mesh=mesh, filters=filters, kind=kind, hint=hint,
                tiers=tiers, sizes=None if np.all(sizes == w) else sizes,
                dynamic=dynamic, policy=policy, precision=precision,
                fused=fused, block_b=block_b,
                placement=(None if self.placement is None
                           else self.placement[w]))
            jax.block_until_ready([t["spectrum"] for t in
                                   self.engines[w].tiers.values()])
            self.onboard_seconds[w] = time.perf_counter() - t0

    def __len__(self) -> int:
        return len(self.sizes)

    @property
    def num_buckets(self) -> int:
        return len(self.engines)

    def rel_errors(self) -> np.ndarray:
        """Per-graph relative Frobenius error, in request order.  The
        masked fit's objective is exactly the graph's own-size objective
        (the pad block contributes zero), so this is comparable 1:1 with
        per-graph single fits."""
        out = np.zeros(len(self.sizes))
        for w, members in self.bucket_of.items():
            basis = self.engines[w].basis
            obj = np.atleast_1d(np.asarray(basis.objective))
            for row, pos in enumerate(members):
                out[pos] = obj[row] / self._denoms[pos]
        return out

    def _scatter(self, signals) -> Dict[int, jnp.ndarray]:
        """Per-graph (R, n_i) list -> zero-padded (B_w, R, w) per bucket."""
        if len(signals) != len(self.sizes):
            raise ValueError(f"expected {len(self.sizes)} signal blocks "
                             f"(one per graph), got {len(signals)}")
        blocks = {}
        for w, members in self.bucket_of.items():
            r = np.asarray(signals[members[0]]).shape[0]
            pad = np.zeros((len(members), r, w), np.float32)
            for row, pos in enumerate(members):
                x = np.asarray(signals[pos], np.float32)
                if x.shape != (r, self.sizes[pos]):
                    raise ValueError(
                        f"signal block {pos} must be ({r}, "
                        f"{self.sizes[pos]}), got {x.shape}")
                pad[row, :, :x.shape[1]] = x
            blocks[w] = jnp.asarray(pad)
        return blocks

    def step(self, signals, h=None, tier: Optional[str] = None) -> list:
        """Filter one signal block per graph (list of (R, n_i) arrays) at
        the requested tier; one jitted dispatch per bucket.  Returns the
        filtered blocks in request order, cropped to each graph's true
        size."""
        outs = [None] * len(self.sizes)
        # dispatch every bucket first (async device work overlaps), then
        # gather — a np.asarray inside the dispatch loop would serialize
        # the buckets on the serving hot path
        pending = {w: self.engines[w].step(block, h, tier=tier)
                   for w, block in self._scatter(signals).items()}
        for w, y in pending.items():
            y = np.asarray(y)
            for row, pos in enumerate(self.bucket_of[w]):
                outs[pos] = y[row, :, :self.sizes[pos]]
        return outs

    def reset_step_stats(self):
        """Zero every bucket engine's per-tier step counters (the serve
        drivers call this after warmup so compile steps don't count,
        matching the non-ragged path's convention)."""
        for eng in self.engines.values():
            eng.stats["steps"] = {name: 0 for name in eng.tiers}

    def step_bank(self, signals) -> list:
        """All F bank responses on every graph (requires ``filters=`` at
        construction): list of (R, n_i) blocks -> list of (F, R, n_i)
        blocks in request order, one fused bank dispatch per bucket (the
        per-bucket gains are zeroed at padding coordinates, so cropping
        is exact)."""
        outs = [None] * len(self.sizes)
        pending = {w: self.engines[w].step_bank(block)
                   for w, block in self._scatter(signals).items()}
        for w, y in pending.items():
            y = np.asarray(y)                       # (B_w, F, R, w)
            for row, pos in enumerate(self.bucket_of[w]):
                outs[pos] = y[row, :, :, :self.sizes[pos]]
        return outs

    @property
    def stats(self) -> dict:
        return {w: eng.stats for w, eng in self.engines.items()}

    # -- streaming updates (DESIGN.md §11): per-bucket hot swaps -----------

    def _locate(self, graph_id: int) -> tuple:
        if not 0 <= graph_id < len(self.sizes):
            raise ValueError(f"graph_id {graph_id} not in fleet of "
                             f"{len(self.sizes)}")
        w = self.widths[graph_id]
        return w, self.bucket_of[w].index(graph_id)

    def apply_updates(self, graph_id: int, delta):
        """Route one update batch to the graph's bucket engine (request-
        order ``graph_id``; the bucket keeps serving its OTHER graphs on
        the old version until its own ``maintain`` swap)."""
        w, row = self._locate(graph_id)
        self.engines[w].apply_updates(row, delta)

    def drift(self) -> np.ndarray:
        """Per-graph drift scores, request order."""
        out = np.zeros(len(self.sizes))
        for w, members in self.bucket_of.items():
            d = self.engines[w].drift()
            for row, pos in enumerate(members):
                out[pos] = d[row]
        return out

    def maintain(self, buckets=None, dirty_only: bool = False) -> dict:
        """One controller tick per bucket; buckets refit and swap
        independently (a burst of updates to small graphs never blocks
        the big bucket's serving version).

        ``buckets`` restricts the tick to those widths.  ``dirty_only``
        skips buckets with no pending updates entirely — on a placed
        router that means maintenance touches ONLY devices owning dirty
        buckets while every other device keeps serving undisturbed
        (device-overlapped maintenance, DESIGN.md §14)."""
        sel = (sorted(self.engines) if buckets is None
               else [int(w) for w in buckets])
        out = {}
        for w in sel:
            eng = self.engines[w]
            if dirty_only and not bool(
                    np.any(getattr(eng, "_dirty", False))):
                continue
            out[w] = eng.maintain()
        return out

    @property
    def versions(self) -> np.ndarray:
        """Per-graph basis versions, request order."""
        out = np.zeros(len(self.sizes), np.int64)
        for w, members in self.bucket_of.items():
            v = self.engines[w].versions
            for row, pos in enumerate(members):
                out[pos] = v[row]
        return out

    # -- persistence: one checkpoint per bucket + a router manifest --------

    def save(self, directory, step: int = 0):
        """Persist every bucket engine (basis + dynamic state) plus the
        router geometry, so ``load`` rebuilds the fleet without
        refitting."""
        import json
        import os
        directory = pathlib.Path(directory)
        directory.mkdir(parents=True, exist_ok=True)
        for w, eng in self.engines.items():
            eng.save(directory / f"bucket_{w:05d}", step)
        # atomic manifest write: the bucket checkpoints survive a crashed
        # writer (DESIGN.md §6), so the router geometry must too
        tmp = directory / "router.json.tmp"
        tmp.write_text(json.dumps(
            {"sizes": self.sizes, "widths": self.widths, "step": step}))
        os.replace(tmp, directory / "router.json")
        if self.placement is not None:
            # placement manifest (DESIGN.md §14): records which devices
            # owned which bucket at save time.  Advisory on load — a
            # reader with a different mesh re-places — but its shape is
            # validated, so corruption fails loudly
            tmp = directory / "placement.json.tmp"
            tmp.write_text(json.dumps(self.placement.manifest()))
            os.replace(tmp, directory / "placement.json")
        return directory

    @classmethod
    def load(cls, directory, step: Optional[int] = None, *,
             backend: str = "xla", mesh=None,
             filters: Optional[str] = None,
             tiers: Optional[Dict[str, float]] = None,
             dynamic: Optional[bool] = None, policy=None,
             precision: Optional[str] = None,
             fused: Optional[bool] = None,
             block_b: Optional[int] = None,
             placement=None) -> "RaggedFGFTServeEngine":
        """Rebuild a fleet router from its per-bucket checkpoints.

        ``placement``: ``None`` re-uses the saved placement manifest (if
        any) by RE-PLACING onto the current mesh/devices — a checkpoint
        written on a 4-device mesh loads fine on 1 or 8 devices, the
        manifest's device ids are provenance, not a requirement.
        ``"auto"``/``FleetPlacement`` force a placement; pass
        ``placement=False`` to load unplaced even when a manifest
        exists."""
        import json
        directory = pathlib.Path(directory)
        manifest = json.loads((directory / "router.json").read_text())
        if step is None:
            step = int(manifest["step"])
        widths = [int(w) for w in manifest["widths"]]
        bucket_of: Dict[int, list] = {}
        for pos, w in enumerate(widths):
            bucket_of.setdefault(w, []).append(pos)
        saved = _read_placement_manifest(directory / "placement.json",
                                         bucket_of)
        if placement is False:
            placement = None
        elif placement is None and saved is not None:
            # saved manifest + no override: re-place on whatever devices
            # THIS process has (shard-aware restore reassembles full
            # arrays, so any mesh shape works)
            if mesh is None:
                mesh = auto_mesh((len(jax.devices()),), ("data",))
            placement = "auto"
        fp = _resolve_fleet_placement(placement, mesh, bucket_of)
        engines: Dict[int, FGFTServeEngine] = {}
        for w in sorted(bucket_of):
            engines[w] = FGFTServeEngine.load(
                directory / f"bucket_{w:05d}", step, backend=backend,
                mesh=mesh, filters=filters, tiers=tiers, dynamic=dynamic,
                policy=policy, precision=precision, fused=fused,
                block_b=block_b,
                placement=None if fp is None else fp[w])
        # rebuild request-order geometry from the restored laps (pads are
        # zero, so per-graph denominators crop for free)
        laps = []
        for pos, w in enumerate(manifest["widths"]):
            row = [p for p in range(len(manifest["widths"]))
                   if manifest["widths"][p] == w].index(pos)
            n_i = int(manifest["sizes"][pos])
            lap = np.asarray(engines[int(w)]._laps_host[row],
                             np.float32)[:n_i, :n_i]
            laps.append(lap)
        router = cls(laps, dynamic=any(e.dynamic
                                       for e in engines.values()),
                     _engines=engines)
        # restore the PERSISTED routing geometry: the constructor
        # recomputed widths with the default min_width, which diverges
        # for routers built with a custom one
        router.widths = widths
        router.bucket_of = bucket_of
        router.placement = fp
        return router


# ---------------------------------------------------------------------------
# The CLI driver: one fleet builder, one engine constructor and one churn
# routine under every mode; then the synchronous step loop or the async
# front door under load.
# ---------------------------------------------------------------------------

def _lowpass(lam):
    return 1.0 / (1.0 + lam)


def _fleet(args):
    """(sizes, GraphStream) of the CLI's fleet: --graphs community graphs
    of side --graph-n, or of the --graph-sizes cycled under --ragged;
    --directed orients their edges at random."""
    from repro.dynamic import GraphStream
    from repro.graphs import community_graph, directed_variant
    sizes = ([args.size_list[i % len(args.size_list)]
              for i in range(args.graphs)] if args.ragged
             else [args.graph_n] * args.graphs)
    adjs = [community_graph(n, seed=s) for s, n in enumerate(sizes)]
    if args.directed:
        adjs = [directed_variant(a, seed=s) for s, a in enumerate(adjs)]
    return sizes, GraphStream(adjs, directed=args.directed)


def _engine(args, laps):
    """The fleet's engine: the ragged router under --ragged, else one
    FGFTServeEngine over the (B, n, n) stack.  --directed pins the T
    family: a numerically symmetric directed Laplacian must not reroute
    through the G path."""
    kw = dict(backend=args.backend, mesh=make_local_mesh(),
              filters=args.filter, tiers=args.tier_map,
              kind="general" if args.directed else "auto",
              dynamic=args.dynamic, policy=args.policy,
              precision=args.precision, fused=args.fused)
    if args.ragged:
        return RaggedFGFTServeEngine(laps, args.transforms, **kw)
    n = args.graph_n
    g = args.transforms or int(2 * n * np.log2(n))
    return FGFTServeEngine(jnp.asarray(np.stack(laps)), g, **kw)


def _churn(args, stream, engine, rnd: int):
    """One churn round: perturb --churn of every graph's edge slots, in
    the stream and the engine in lockstep."""
    from repro.graphs import edge_perturbation
    for gid, n in enumerate(stream.sizes):
        batch = edge_perturbation(
            stream.adjs[gid], max(int(args.churn * n * (n - 1) / 2), 1),
            seed=args.seed + 1000 * (rnd + 1) + gid,
            directed=args.directed)
        engine.apply_updates(gid, stream.apply(gid, batch))


def serve_fgft(args) -> dict:
    """Build the fleet, fit it, and serve it in the mode the flags name:
    the async front door under load (--serve-async), update rounds
    (--dynamic), the filter bank (--filter) or every quality tier."""
    sizes, stream = _fleet(args)
    laps = stream.laplacians()
    t0 = time.time()
    engine = _engine(args, laps)
    fit_s = time.time() - t0
    engines = (list(engine.engines.values()) if args.ragged
               else [engine])
    top = engines[-1]                 # the widest bucket, or the engine
    if args.ragged:
        rel = engine.rel_errors()
    else:
        denom = (np.stack(laps) ** 2).sum((1, 2))
        rel = np.asarray(engine.basis.objective) / np.maximum(denom, 1e-30)
    out = {"rel_error": rel, "kind": top.basis.kind, "sizes": sizes}
    if args.ragged:
        out["buckets"] = sorted(engine.engines)
    print(f"[fgft] fitted {args.graphs} graphs (sizes "
          f"{sorted(set(sizes))}, kind={top.basis.kind}) into "
          f"{len(engines)} bucket(s) in {fit_s:.1f}s, mean rel error "
          f"{rel.mean():.4f}")
    if args.serve_async:
        return {**out, **_serve_load(args, engine, sizes, stream)}
    rng = np.random.default_rng(args.seed)

    def signals():
        x = [rng.standard_normal((args.signals, n)).astype(np.float32)
             for n in sizes]
        return x if args.ragged else jnp.asarray(np.stack(x))

    if args.dynamic:
        return {**out, **_serve_rounds(args, engine, stream, signals)}
    x = signals()

    def timed(step) -> float:
        """Wall seconds of --filter-steps calls of ``step``."""
        t0 = time.time()
        y = None
        for _ in range(args.filter_steps):
            y = step(x)
        jax.block_until_ready(y)
        return max(time.time() - t0, 1e-9)        # --filter-steps 0 ok

    if args.filter:
        jax.block_until_ready(engine.step_bank(x))      # warmup/compile
        dt = timed(engine.step_bank)
        f = len(top.bank)
        served = args.filter_steps * args.graphs * f
        print(f"[fgft] served {served} filter responses ({f} filters x "
              f"{args.graphs} graphs x {args.filter_steps} steps, "
              f"{args.signals} signals each) in {dt:.2f}s — "
              f"{served / dt:.1f} responses/s through the fused bank "
              f"path, {len(engines)} dispatch(es) a step [{args.backend}]")
        return {**out, "responses_per_s": served / dt,
                "filters": top.bank.names}
    for name in top.tiers:
        jax.block_until_ready(engine.step(x, _lowpass, tier=name))
    for e in engines:                 # warmup/compile doesn't count
        e.stats["steps"] = dict.fromkeys(e.tiers, 0)
    tier_stats = {}
    for name, tier in top.tiers.items():
        rate = args.filter_steps * args.graphs / timed(
            lambda x: engine.step(x, _lowpass, tier=name))
        tier_stats[name] = {"transforms_per_s": rate,
                            "num_stages": tier["num_stages"],
                            "num_transforms": tier["num_transforms"]}
        print(f"[fgft]   tier {name!r}: g'={tier['num_transforms']} of "
              f"{top.tiers[top.default_tier]['num_transforms']} "
              f"({tier['num_stages']} stages) — {rate:.1f} "
              f"graph-transforms/s [{args.backend}]")
    # headline number: the highest-quality tier, whatever its name.  The
    # stat is therefore "speedup_vs_best"; the old "speedup_vs_full" key
    # claimed a baseline tier named "full" but was silently computed
    # against the default (best) tier — it survives only as a deprecated
    # alias, and only when a tier named "full" actually exists.
    base = tier_stats[top.default_tier]["transforms_per_s"]
    for ts in tier_stats.values():
        ts["speedup_vs_best"] = ts["transforms_per_s"] / base
        if "full" in tier_stats:
            # deprecated alias: honest only against the tier literally
            # named "full" (== speedup_vs_best whenever full IS the best)
            ts["speedup_vs_full"] = (ts["transforms_per_s"]
                                     / tier_stats["full"]["transforms_per_s"])
    served = args.filter_steps * args.graphs * len(tier_stats)
    print(f"[fgft] served {served} graph-filter requests across "
          f"{len(tier_stats)} tiers")
    return {**out, "transforms_per_s": base, "tiers": tier_stats,
            "stats": engine.stats}


def _serve_rounds(args, engine, stream, signals) -> dict:
    """--dynamic (DESIGN.md §11): per round, churn every graph, run the
    drift-triggered maintenance tick off the hot path, then keep
    answering filter steps through the hot-swapped versions."""
    ys = engine.step(signals(), _lowpass)        # warmup/compile
    actions = []
    t_serve = t_maintain = 0.0
    for rnd in range(args.update_rounds):
        _churn(args, stream, engine, rnd)
        t0 = time.time()
        res = engine.maintain()
        t_maintain += time.time() - t0
        ticks = list(res.values()) if args.ragged else [res]
        actions.append("+".join(sorted({r["action"] for r in ticks})))
        # maintain() already scored post-action drift; an extra fleet-
        # wide probe pass here would just distort the serve/maintain split
        drift_max = max(float(np.max(r["post_drift"])) for r in ticks)
        t0 = time.time()
        for _ in range(args.filter_steps):
            ys = engine.step(signals(), _lowpass)
        jax.block_until_ready(ys)
        t_serve += time.time() - t0
        print(f"[fgft]   round {rnd}: action={actions[-1]}, max drift "
              f"{drift_max:.4f}, versions {engine.versions.tolist()}")
    served = args.update_rounds * args.filter_steps * args.graphs
    print(f"[fgft] served {served} graph-filter requests across "
          f"{args.update_rounds} update rounds at churn {args.churn} "
          f"(serve {t_serve:.2f}s, maintain {t_maintain:.2f}s) "
          f"[{args.backend}]")
    dyn_stats = ({w: s["dynamic"] for w, s in engine.stats.items()}
                 if args.ragged else engine.stats["dynamic"])
    print(f"[fgft] dynamic stats: {dyn_stats}")
    return {"actions": actions, "versions": engine.versions.tolist(),
            "serve_s": t_serve, "maintain_s": t_maintain,
            "stats": dyn_stats}


def _serve_load(args, engine, sizes, stream) -> dict:
    """--serve-async (DESIGN.md §12): wrap the engine in the async front
    door and drive a closed- or open-loop load through it, with churn and
    background maintenance under --dynamic; print the SLO summary."""
    rng = np.random.default_rng(args.seed)
    tiers = sorted(args.tier_map)
    requests = []
    for i in range(args.load_requests):
        gid = i % args.graphs
        x = rng.standard_normal((args.signals, sizes[gid])).astype(
            np.float32)
        requests.append((gid, x, None, True) if args.filter
                        else (gid, x, tiers[i % len(tiers)], False))
    interval = args.maintain_interval if args.dynamic else None
    with AsyncFGFTService(engine, h=None if args.filter else _lowpass,
                          max_queue=args.max_queue,
                          max_batch=args.max_batch,
                          maintain_interval=interval) as service:
        # warm every (tier, shape) program before the timed load; a
        # tight --max-queue sheds mid-burst, so drain and resubmit
        # instead of crashing before the timed load starts
        warm = []
        for gid, x, tier, bank in requests[:args.graphs * len(tiers)]:
            try:
                warm.append(service.submit(gid, x, tier=tier, bank=bank))
            except ShedError:
                for f in warm:
                    f.result()
                warm = [service.submit(gid, x, tier=tier, bank=bank)]
        for f in warm:
            f.result()
        service.reset_stats()                   # compile time isn't SLO
        churn_stop = threading.Event()

        def churn():
            rnd = 0
            while not churn_stop.is_set():
                _churn(args, stream, engine, rnd)
                service.request_maintain()
                rnd += 1
                churn_stop.wait(0.05)

        churner = threading.Thread(target=churn, name="churn")
        if args.dynamic:
            churner.start()
        t0 = time.time()
        if args.qps > 0:
            results = open_loop_load(service, requests, args.qps)["results"]
        else:
            results = closed_loop_load(service, requests,
                                       workers=args.load_workers)
        elapsed = max(time.time() - t0, 1e-9)
        churn_stop.set()
        if churner.is_alive():
            churner.join()
        stats = service.stats()
    failed = stats["errors"], stats["maintain"]["errors"]
    if any(failed):
        raise RuntimeError(f"[svc] {failed[0]} request(s) and {failed[1]} "
                           f"maintenance tick(s) failed; no rate reported")
    qps = len(results) / elapsed
    print(f"[svc] {len(results)} requests in {elapsed:.2f}s -> "
          f"{qps:.1f} qps sustained "
          f"[{'open' if args.qps > 0 else 'closed'}-loop, "
          f"{args.backend}]")
    print(obs.format_slo(stats))
    return {"qps": qps, "stats": stats,
            "versions": sorted({r.version for r in results}),
            "results": len(results)}


def _export_obs(args):
    """--trace / --metrics-dir artifact export: runs on EVERY exit path
    (main wraps the drivers in try/finally) so a failed run still leaves
    its telemetry behind — exactly when the trace is most interesting."""
    if getattr(args, "trace", None):
        path = obs.export_trace(args.trace)
        print(f"[obs] chrome trace -> {path}")
    if getattr(args, "metrics_dir", None):
        out = obs.export_metrics(args.metrics_dir)
        print(f"[obs] metrics -> {out['json']} + {out['prom']}")


def main(argv=None):
    args = parse_args(argv)
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    try:
        return serve_fgft(args)
    finally:
        _export_obs(args)


if __name__ == "__main__":
    main()
