"""Fig. 10 (repo-original): heterogeneous graph fleets — size-bucketed
ragged batching vs a per-graph loop (DESIGN.md §10).

A production fleet arrives with MANY distinct Laplacian sizes; the batched
engine's (B, n, n) stack cannot hold it directly.  The router
(launch/serve.py::RaggedFGFTServeEngine) zero-pads each graph into its
power-of-two bucket, fits every bucket in ONE masked jit, and
dispatches each serving step as one fused batched operator per bucket.
This benchmark gates the two claims that make that design honest:

  * ACCURACY — the masked padded fit must match each graph's own-size fit:
    per-graph relative Frobenius error within 1e-5 of a per-matrix single
    fit (the greedy never selects padding coordinates, so the padded
    chain IS the own-size chain embedded in the bucket);
  * THROUGHPUT — onboarding AND serving the fleet through the router must
    be >= 1.5x faster end-to-end than the per-graph alternative (one
    single-graph engine per Laplacian), on BOTH backends.

The throughput race is run from COLD on purpose: a fleet of D distinct
sizes costs the per-graph path D fit programs + D serving programs (and a
production service sees an unbounded size set — every new size compiles
forever), while the router compiles O(log sizes) bucket programs and its
compile cache keeps hitting as new sizes arrive.  That program-count
collapse is the structural win of bucketing; the warm per-dispatch race
for SAME-size batches is fig7's subject (and is recorded here per step as
a report-only column).
"""
import time

import numpy as np
import jax.numpy as jnp

from repro.core import laplacian
from repro.graphs import community_graph
from repro.launch.serve import (FGFTServeEngine, RaggedFGFTServeEngine,
                                bucket_width)
from .common import emit
from .run import gate_assert

_STEPS = 5


def _lowpass(lam):
    return 1.0 / (1.0 + lam)


def _fleet(fast: bool):
    """B=9 graphs, every size DISTINCT (the regime bucketing exists for:
    a per-graph loop compiles one program pair per size)."""
    sizes = ([10, 11, 12, 14, 15, 18, 20, 22, 24] if fast
             else [24, 28, 30, 36, 42, 48, 54, 60, 63])
    laps = [laplacian(community_graph(n, seed=i))
            for i, n in enumerate(sizes)]
    return sizes, laps


def run(fast: bool = False):
    sizes, laps = _fleet(fast)
    b = len(sizes)
    n_iter = 1
    r = 8
    rng = np.random.default_rng(0)
    signals = [rng.standard_normal((r, n)).astype(np.float32)
               for n in sizes]
    # components per graph follow the router's alpha scaling (g ~ w log2 w
    # of the graph's bucket) so both sides fit the same component count;
    # fast mode halves alpha (0 -> the router's 2 w log2 w default)
    w_max = bucket_width(max(sizes))
    alpha_g = int(0.5 * w_max * np.log2(w_max)) if fast else 0

    speed, warm_step = {}, {}
    router = None
    loop_objs = None
    for backend in ("xla", "pallas"):
        # --- bucketed: cold router (per-bucket masked fits + tier
        # programs) + _STEPS serving steps ----------------------------
        t0 = time.time()
        router = RaggedFGFTServeEngine(laps, alpha_g, n_iter=n_iter,
                                       backend=backend,
                                       tiers={"full": 1.0})
        for _ in range(_STEPS):
            ys = router.step(signals, _lowpass)
        t_bucket = time.time() - t0
        t0 = time.time()
        router.step(signals, _lowpass)
        warm_bucket = time.time() - t0

        # --- per-graph loop: one cold single-graph engine per
        # Laplacian (the pre-PR serving stack for a mixed fleet) +
        # _STEPS serving steps ----------------------------------------
        gs = [router.engines[w].basis.num_transforms
              for w in router.widths]
        t0 = time.time()
        singles = [FGFTServeEngine(jnp.asarray(lap)[None], g,
                                   n_iter=n_iter, backend=backend,
                                   tiers={"full": 1.0})
                   for lap, g in zip(laps, gs)]
        for _ in range(_STEPS):
            outs = [np.asarray(e.step(jnp.asarray(x)[None], _lowpass))[0]
                    for e, x in zip(singles, signals)]
        t_loop = time.time() - t0
        t0 = time.time()
        [np.asarray(e.step(jnp.asarray(x)[None], _lowpass))[0]
         for e, x in zip(singles, signals)]
        warm_loop = time.time() - t0

        speed[backend] = t_loop / t_bucket
        warm_step[backend] = warm_loop / max(warm_bucket, 1e-9)
        loop_objs = [float(np.asarray(e.basis.objective)[0])
                     for e in singles]
        for y, x in zip(ys, signals):
            assert y.shape == x.shape
        print(f"[fig10] fleet of {b} distinct-size graphs "
              f"({router.num_buckets} buckets vs {b} per-graph "
              f"programs): onboard+{_STEPS} steps {t_bucket:.1f}s vs "
              f"{t_loop:.1f}s -> {speed[backend]:.2f}x; warm step "
              f"{warm_step[backend]:.2f}x [{backend}]")

    # --- parity: masked padded fit == per-matrix own-size fit ------------
    # (the loop engines' B=1 fits ARE the per-matrix references)
    rel_bucketed = router.rel_errors()
    denoms = np.asarray([max(float((lap * lap).sum()), 1e-30)
                         for lap in laps])
    rel_single = np.asarray(loop_objs) / denoms
    gap = np.abs(rel_bucketed - rel_single)
    print(f"[fig10] padded-vs-exact rel-error gap: max {gap.max():.2e}")

    rows = [[sizes[i], router.widths[i], rel_bucketed[i], rel_single[i],
             speed["xla"], speed["pallas"], warm_step["xla"],
             warm_step["pallas"]] for i in range(b)]
    emit("fig10_ragged", rows,
         ["graph_n", "bucket_n", "rel_error_bucketed", "rel_error_single",
          "e2e_speedup_xla", "e2e_speedup_pallas", "warm_step_xla",
          "warm_step_pallas"])

    gate_assert(gap.max() <= 1e-5,
                f"padded bucket fits must match per-matrix fits within "
                f"1e-5 rel error, worst gap {gap.max():.2e}", rows)
    gate_assert(speed["xla"] >= 1.5,
                f"bucketed fleet onboarding+serving must be >= 1.5x the "
                f"per-graph loop on xla, got {speed['xla']:.2f}x", rows)
    gate_assert(speed["pallas"] >= 1.5,
                f"bucketed fleet onboarding+serving must be >= 1.5x the "
                f"per-graph loop on pallas, got {speed['pallas']:.2f}x",
                rows)
    return rows
