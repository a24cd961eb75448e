"""Whole onboards, one after another: a churned graph from its Laplacian
to its first served answer.

Each onboard draws a seeded churn of one fleet graph (so no fit can be
reused), builds a ``RaggedFGFTServeEngine`` for it (fit, pack, tier
spectra, install), starts an ``AsyncFGFTService`` on it and waits for
the answer to one request through ``submit``.  The window closes when
the onboard in flight at its end is done.

A new chain's staged tables take their depth from the chain, so the
serving and tier-spectrum programs of each onboard compile inside the
window: that is part of onboarding.  The persistent compile cache is
turned off for the window, so every onboard compiles what it would
compile for a graph the server has not seen, whichever shapes earlier
runs in the checkout left in the cache.

Traffic file keys: ``graph`` (a fleet graph name), ``churn`` (share of
its edges whose slots one churn batch touches), ``rows`` and ``tier`` of
the first request, ``trace_seconds`` (the traced slice of the fit) and
``trace_after`` (seconds into the window's first fit when it starts).
"""
from __future__ import annotations

import gc
import tempfile
import threading

import numpy as np

import graphs
import reference as ref
from harness import (CompileCounter, Observations, annotate, log, now,
                     peak_memory, plan_misses, reduce_trace,
                     start_trace, tier_response)

SEED_CHURN, SEED_SIGNAL = 1, 2
WARM = 0                               # churn index of the set-up onboard


def run(ctx) -> Observations:
    import jax
    from repro.launch.serve import RaggedFGFTServeEngine
    from repro.launch.service import AsyncFGFTService
    import fleet
    import reduce as red
    cfg, mix, seed = ctx.config, ctx.traffic, ctx.seed
    obs = Observations(family=cfg["family"])
    pos = cfg["graphs"].index(mix["graph"])
    adj0 = graphs.config_graphs(cfg, rehearse=ctx.rehearse)[pos]
    edges = int(np.count_nonzero(np.triu(adj0, 1)))
    num_edges = max(int(round(float(mix["churn"]) * edges)), 1)
    kwargs = fleet.fit_kwargs(cfg)
    kwargs["precision"] = ctx.overrides.get("precision",
                                            kwargs["precision"])
    svc_cfg = cfg["service"]
    ann = annotate(ctx.trace)

    def onboard(k: int) -> dict:
        lap = graphs.laplacian(graphs.churn(adj0, num_edges,
                                            [seed, SEED_CHURN, k]))
        x = np.random.default_rng([seed, SEED_SIGNAL, k]).standard_normal(
            (int(mix["rows"]), lap.shape[0]), np.float32)
        t0 = now()
        with ann("bench.construct"):
            router = RaggedFGFTServeEngine([lap], **kwargs)
        with ann("bench.first_request"):
            with AsyncFGFTService(router, h=tier_response,
                                  max_batch=svc_cfg["max_batch"],
                                  max_queue=svc_cfg["max_queue"],
                                  row_quantum=svc_cfg["row_quantum"]) as svc:
                y = svc.submit(0, x, tier=mix["tier"]).result().y
        t1 = now()
        (w, secs), = router.onboard_seconds.items()
        basis = router.engines[w].basis
        rec = {"t0": t0, "t1": t1, "lap": lap, "x": x, "y": y, "w": w,
               "fit_s": secs, "g": basis.num_transforms,
               "factors": [np.asarray(f)[0] for f in basis.factors],
               "spectrum": np.asarray(basis.spectrum, np.float64)[0]}
        del router, basis
        gc.collect()
        return rec

    t0 = now()
    onboard(WARM)
    log(f"set-up onboard (compiles the fit and serving programs) "
        f"{now() - t0:.2f}s")
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    counter = CompileCounter(jax)
    misses0 = plan_misses()
    counter.armed = True
    t_start = now()
    obs.setup_s = t_start - ctx.t_start
    tracer = None
    if ctx.trace:
        tracer = _SliceTracer(t_start + float(mix["trace_after"]),
                              float(mix["trace_seconds"]), red.WINDOW_SPAN)
        tracer.start()
    recs = []
    k = WARM + 1
    while not recs or now() < t_start + ctx.seconds:
        recs.append(onboard(k))
        k += 1
    t_end = now()
    counter.armed = False
    if tracer is not None:
        tracer.join(timeout=600)
    obs.compiles_in_window = counter.count
    obs.compile_s_in_window = counter.seconds
    obs.plan_misses_in_window = plan_misses() - misses0
    obs.window = (t_start, t_end)
    obs.window_s = t_end - t_start
    obs.memory_peak_bytes = peak_memory(jax)
    obs.onboards = [{"seconds": r["t1"] - r["t0"], "fit_s": r["fit_s"],
                     "components": r["g"]} for r in recs]
    obs.attempted = len(recs)
    if tracer is not None:
        obs.reduction = reduce_trace(ctx, tracer.logdir)
        obs.trace_window = tracer.window
        tracer.cleanup()
    log(f"window {obs.window_s:.2f}s: {len(recs)} onboard(s) of "
        f"{[round(r['t1'] - r['t0'], 3) for r in recs]} s")
    obs.checks = check(ctx, recs)
    return obs


class _SliceTracer(threading.Thread):
    """Profiles ``seconds`` from ``at`` (the fit runs on the main
    thread meanwhile)."""

    def __init__(self, at: float, seconds: float, span: str):
        super().__init__(daemon=True)
        self.at, self.seconds, self.span = at, seconds, span
        self._dir = tempfile.TemporaryDirectory(prefix="chipbench-trace-")
        self.logdir = self._dir.name

    def run(self):
        import time
        import jax
        time.sleep(max(self.at - now(), 0.0))
        start_trace(self.logdir)
        with jax.profiler.TraceAnnotation(self.span):
            t0 = now()
            time.sleep(self.seconds)
            self.window = (t0, now())
        jax.profiler.stop_trace()

    def cleanup(self):
        self._dir.cleanup()


def check(ctx, recs) -> list:
    """Each onboard's fit against its graph (the relative objective its
    chain reaches, by the reference) and its first answer against the
    chain applied densely in float64."""
    t0 = now()
    worst_obj = worst_gap = 0.0
    tier = ctx.traffic["tier"]
    for r in recs:
        factors, w, n, g = r["factors"], r["w"], r["lap"].shape[0], r["g"]
        k = ref.tier_components(ctx.config["tiers"][tier], g)
        if ctx.config["family"] == "sym":
            anas = ref.sym_legs(factors, w, {k, g})
            obj = ref.sym_objective(anas[g], r["lap"])
            synth, ana = anas[k].T, anas[k]
            lam = ref.lemma1_spectrum(ana, r["lap"])
        else:
            legs = ref.gen_legs(factors, w, {k, g})
            obj = ref.gen_objective(*legs[g], r["spectrum"], r["lap"])
            (synth, ana), lam = legs[k], r["spectrum"]
        worst_obj = max(worst_obj, obj)
        want = ref.apply_operator(synth, ana, ref.tier_response(lam),
                                  r["x"], n)[0]
        worst_gap = max(worst_gap, ref.relative_gap(r["y"], want))
    log(f"reference: {len(recs)} onboard(s) in {now() - t0:.2f}s")
    if not recs:
        worst_obj = worst_gap = float("inf")
    return [("fit_objective", worst_obj, float(ctx.limits["fit_objective"])),
            ("answer_gap", worst_gap, float(ctx.limits["answer_gap"]))]
