"""Fig. 12 (repo-original): the async serving front-end under a
churn-while-serving load (DESIGN.md §12).

The paper's pitch is that a fixed component budget makes projecting on
approximate eigenspaces cheap enough to SERVE; PRs 1-5 built the engines
and this PR puts a front door on them.  The claim that needs gating is the
front door's, not the kernels': given many small independent requests
arriving concurrently while the fleet's graphs churn underneath, the
queue -> coalesce -> fused-dispatch pipeline with background maintenance
must beat the synchronous one-request-at-a-time loop — at the SAME tier
and the SAME maintained accuracy, with ZERO steady-state recompiles.

Both modes run through the identical ``AsyncFGFTService`` machinery (same
padding, same quantization, same maintenance policy, same churn schedule)
so the comparison isolates exactly two design points:

  * COALESCING — sync caps dispatches at one request (``max_batch=1``,
    pumped inline); async coalesces up to 8 same-tier requests into one
    fused dispatch (dispatch cost is overhead-dominated at fleet sizes,
    so occupancy is nearly free throughput);
  * MAINTENANCE PLACEMENT — sync scores drift and refits INLINE between
    requests (the synchronous CLI loop's shape); async runs the same
    controller on the maintainer thread, overlapped with serving via the
    versioned hot swap.

Gates (both backends): sustained QPS >= 2x sync, step-program compile
count FLAT across the whole churned load, final maintained rel-error
within 1.2x of the sync loop's (drift ticks may coalesce under load —
the speedup must not come from silently skipping maintenance), at least
one hot swap observed mid-load, and p99 latency reported per mode.
"""
import time

import numpy as np
import jax.numpy as jnp

from repro.dynamic import GraphStream, RefitPolicy, exact_rel_residual
from repro.graphs import edge_perturbation, erdos_renyi, weight_jitter
from repro.launch.serve import FGFTServeEngine
from repro.launch.service import AsyncFGFTService, closed_loop_load
from .common import emit
from .run import gate_assert

_ROWS = 4                 # signal rows per request


def _round_batch(stream, gid, rnd, topo_rounds):
    """Weight jitter most rounds, topology churn on the designated ones
    (the fig11 regime: refresh-absorbable drift + refit-forcing churn)."""
    n_edges = int((np.triu(stream.adjs[gid], 1) > 0).sum())
    if rnd in topo_rounds:
        return edge_perturbation(stream.adjs[gid],
                                 max(int(0.06 * n_edges), 1),
                                 seed=500 * rnd + gid)
    return weight_jitter(stream.adjs[gid], max(int(0.2 * n_edges), 1),
                         scale=0.1, seed=500 * rnd + gid)


def _make_requests(b, n, count, seed):
    rng = np.random.default_rng(seed)
    return [(i % b,
             rng.standard_normal((_ROWS, n)).astype(np.float32),
             "full", False)
            for i in range(count)]


def _warm_service(service, b, n, seed):
    """Compile every (tier, row-pad) program the load can reach: bursts
    of 1 / 4 / max_batch same-graph requests cover the quantized row
    ladder; inline drains so warming needs no threads."""
    rng = np.random.default_rng(seed)
    for burst in (1, 4, service.max_batch):
        futs = [service.submit(
            0, rng.standard_normal((_ROWS, n)).astype(np.float32),
            tier="full") for _ in range(burst)]
        while any(not f.done() for f in futs):
            if service.drain_once() == 0:
                for f in futs:          # threaded service: just wait
                    f.result()
                break
    service.reset_stats()


def _run_mode(mode, backend, adjs0, g, n_iter, policy, rounds,
              per_round, topo_rounds, workers, lowpass):
    b, n = len(adjs0), adjs0[0].shape[0]
    stream = GraphStream([a.copy() for a in adjs0])
    laps0 = np.stack(stream.laplacians())
    engine = FGFTServeEngine(jnp.asarray(laps0), g, n_iter=n_iter,
                             backend=backend, tiers={"full": 1.0},
                             dynamic=True, policy=policy)
    engine.warmup(jnp.asarray(np.zeros((b, 8, n), np.float32)))
    sync = mode == "sync"
    service = AsyncFGFTService(engine, h=lowpass,
                               max_queue=4 * per_round,
                               max_batch=1 if sync else 8,
                               auto_start=not sync,
                               maintain_interval=None,
                               name=f"fig12-{mode}")
    _warm_service(service, b, n, seed=99)
    # pre-round outside the timing: one churn + maintain tick compiles
    # the refit path for THIS engine (both modes pay it identically)
    for gid in range(b):
        engine.apply_updates(gid, stream.apply(
            gid, _round_batch(stream, gid, 0, {0})))
    service.maintain_now()
    service.reset_stats()           # pre-round swaps/compiles aren't load
    # the whole-bucket program (warmup) and the per-graph one the front
    # door launches
    live = engine._live
    progs = [live.fns["full"], live.row_fns["full"]]

    def compiles():
        return sum(prog._cache_size() for prog in progs)
    compiles0 = compiles()

    t0 = time.time()
    for rnd in range(1, rounds + 1):
        for gid in range(b):
            engine.apply_updates(gid, stream.apply(
                gid, _round_batch(stream, gid, rnd, topo_rounds)))
        requests = _make_requests(b, n, per_round, seed=1000 + rnd)
        if sync:
            # the synchronous CLI loop's shape: maintain inline, then
            # answer one request per fused dispatch, waiting on each
            service.maintain_now()
            for req in requests:
                fut = service.submit(req[0], req[1], tier=req[2])
                service.drain_once()
                fut.result()
        else:
            # churn-while-serving: the tick overlaps the round's load
            service.request_maintain()
            closed_loop_load(service, requests, workers=workers)
    service.maintain_now()                 # score the last round's churn
    elapsed = max(time.time() - t0, 1e-9)

    gate_assert(compiles() == compiles0,
                f"[{mode}/{backend}] step program recompiled during the "
                f"churned load ({compiles0} -> {compiles()} "
                f"cache entries)")
    stats = service.stats()
    err = float(np.mean(exact_rel_residual(
        engine.basis, np.asarray(engine._laps_host))))
    laps_final = np.asarray(engine._laps_host).copy()
    service.close()
    total = rounds * per_round
    lat = stats["latency"]["full/total"]
    return {"qps": total / elapsed, "elapsed": elapsed, "err": err,
            "laps": laps_final, "stats": stats,
            "p50_ms": lat["p50_s"] * 1e3, "p99_ms": lat["p99_s"] * 1e3,
            "occupancy": stats["batch"]["occupancy_mean"],
            "swaps": stats["maintain"]["swaps"]}


def run(fast: bool = False):
    b = 4
    n = 24 if fast else 32
    rounds = 3 if fast else 5
    per_round = 32 if fast else 64
    topo_rounds = {2} if fast else {2, 4}
    workers = 12
    n_iter = 2
    g = int(0.5 * n * np.log2(n))
    policy = RefitPolicy(refresh=0.0008, extend=0.008, refit=0.008,
                         num_probes=32, hysteresis=1.0, max_extends=0)
    lowpass = lambda lam: 1.0 / (1.0 + lam)  # noqa: E731

    rows = []
    speed, err_ratio = {}, {}
    for backend in ("xla", "pallas"):
        adjs0 = [erdos_renyi(n, 0.3, seed=31 * gid) for gid in range(b)]
        res = {mode: _run_mode(mode, backend, adjs0, g, n_iter, policy,
                               rounds, per_round, topo_rounds, workers,
                               lowpass)
               for mode in ("sync", "async")}
        # identical churn schedule: both modes must end on the same fleet
        np.testing.assert_allclose(res["sync"]["laps"],
                                   res["async"]["laps"], atol=1e-5)
        speed[backend] = res["async"]["qps"] / max(res["sync"]["qps"],
                                                   1e-9)
        err_ratio[backend] = (res["async"]["err"]
                              / max(res["sync"]["err"], 1e-9))
        print(f"[fig12] {rounds} rounds x {per_round} reqs (B={b}, "
              f"n={n}, g={g}): sync {res['sync']['qps']:.0f} qps "
              f"(p99 {res['sync']['p99_ms']:.1f}ms) vs async "
              f"{res['async']['qps']:.0f} qps "
              f"(p99 {res['async']['p99_ms']:.1f}ms, occupancy "
              f"{res['async']['occupancy']:.1f}, swaps "
              f"{res['async']['swaps']}) -> {speed[backend]:.1f}x; "
              f"err ratio {err_ratio[backend]:.2f} [{backend}]")
        rows.append([backend, b, n, g, rounds * per_round,
                     res["sync"]["qps"], res["async"]["qps"],
                     speed[backend],
                     res["sync"]["p50_ms"], res["sync"]["p99_ms"],
                     res["async"]["p50_ms"], res["async"]["p99_ms"],
                     res["async"]["occupancy"], res["async"]["swaps"],
                     res["sync"]["err"], res["async"]["err"],
                     err_ratio[backend]])

    emit("fig12_serving", rows,
         ["backend", "B", "n", "g", "requests", "qps_sync", "qps_async",
          "speedup", "p50_sync_ms", "p99_sync_ms", "p50_async_ms",
          "p99_async_ms", "occupancy_async", "swaps_async", "err_sync",
          "err_async", "err_ratio"])
    for backend in ("xla", "pallas"):
        gate_assert(speed[backend] >= 2.0,
                    f"async coalesced serving must sustain >= 2x the "
                    f"synchronous one-request loop's QPS under churn on "
                    f"{backend}, got {speed[backend]:.2f}x", rows)
        gate_assert(err_ratio[backend] <= 1.2,
                    f"async maintained rel-error must stay within 1.2x "
                    f"of the inline-maintained loop on {backend}, got "
                    f"{err_ratio[backend]:.2f}x", rows)
    for row in rows:
        gate_assert(row[13] >= 1,
                    f"no hot swap observed during the {row[0]} async "
                    f"load — churn-while-serving was not exercised", rows)
    return rows
