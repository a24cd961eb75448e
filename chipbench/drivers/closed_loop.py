"""Closed-loop serving traffic through ``AsyncFGFTService.submit``.

Traffic file keys:

- ``clients``: client threads; each sends its next request only when the
  previous one is answered (no think time);
- ``rows``: signal rows per request;
- ``graphs``: fleet graph name -> integer weight;
- ``tiers``: tier name (or ``"bank"``) -> integer weight;
- ``pool_rows``: seeded signal rows kept per graph, sliced by requests;
- ``sample_every``: about one request in this many is kept and checked
  against the reference;
- ``warm_seconds``: traffic run after the shapes are warm and before the
  window (not measured);
- ``trace_seconds``: the traced slice, in the middle of the window.

Every seed serves the same deck of (graph, tier) pairs, in its counts
of graph weight times tier weight; each client walks seeded
permutations of it.  So seeds change the order, the signals and the
rows sampled, never the amount of work.
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

SEED_POOL, SEED_CLIENT, SEED_WARM = 1, 2, 3


def _deck(mix: dict, names: list) -> list:
    pos = {name: k for k, name in enumerate(names)}
    return [(pos[gname], tier) for gname, wg in mix["graphs"].items()
            for tier, wt in mix["tiers"].items() for _ in range(wg * wt)]


class Client(threading.Thread):
    """One closed-loop tenant: submit, wait, record, repeat."""

    def __init__(self, svc, pools, deck, rows, seed_words, until, sample,
                 ann, records):
        super().__init__(daemon=True)
        self.svc, self.pools, self.deck, self.rows = svc, pools, deck, rows
        self.rng = np.random.default_rng(seed_words)
        self.until, self.sample, self.ann = until, sample, ann
        self.records = records
        self.go = threading.Event()

    def run(self):
        self.go.wait()
        order = []
        while now() < self.until:
            if not order:
                order = list(self.rng.permutation(len(self.deck)))
            gid, tier = self.deck[order.pop()]
            pool = self.pools[gid]
            off = int(self.rng.integers(0, pool.shape[0] - self.rows + 1))
            keep = self.rng.random() < self.sample
            x = pool[off:off + self.rows]
            rec = {"graph": gid, "tier": tier, "offset": off,
                   "rows": self.rows, "ok": False, "y": None}
            rec["t_submit"] = now()
            try:
                with self.ann("bench.submit"):
                    fut = (self.svc.submit(gid, x, bank=True)
                           if tier == "bank"
                           else self.svc.submit(gid, x, tier=tier))
                with self.ann("bench.wait"):
                    res = fut.result()
                rec["t_done"] = now()
                rec.update(ok=True, queue_s=res.queue_s,
                           service_s=res.service_s,
                           batch_size=res.batch_size)
                if keep:
                    rec["y"] = res.y
            except Exception as exc:  # noqa: BLE001 — a failed request is recorded
                rec["t_done"] = now()
                rec["error"] = f"{type(exc).__name__}: {exc}"
            self.records.append(rec)


def _drive(svc, pools, deck, mix, seed, tag, seconds, ann, sample):
    """Start the clients for ``seconds``; returns (clients, records,
    window start, window end)."""
    records = []
    clients = [Client(svc, pools, deck, int(mix["rows"]), [seed, tag, k],
                      0.0, sample, ann, records)
               for k in range(int(mix["clients"]))]
    for c in clients:
        c.start()
    t0 = now()
    for c in clients:
        c.until = t0 + seconds
        c.go.set()
    return clients, records, t0, t0 + seconds


def _join(clients):
    for c in clients:
        c.join(timeout=600)
    alive = [c.name for c in clients if c.is_alive()]
    if alive:
        raise RuntimeError(f"client threads did not finish: {alive}")


def run(ctx) -> Observations:
    import jax
    from repro.launch.service import AsyncFGFTService, quantize_rows
    import fleet
    import reduce as red
    cfg, mix, seed = ctx.config, ctx.traffic, ctx.seed
    obs = Observations(family=cfg["family"])
    kind = jax.devices()[0].device_kind
    adjs = graphs.config_graphs(cfg, rehearse=ctx.rehearse)
    laps = [graphs.laplacian(a) for a in adjs]
    router = fleet.serving_router(ctx, laps, kind)
    svc_cfg = cfg["service"]
    svc = AsyncFGFTService(router, h=tier_response,
                           max_batch=svc_cfg["max_batch"],
                           max_queue=svc_cfg["max_queue"],
                           row_quantum=svc_cfg["row_quantum"])
    rows = int(mix["rows"])
    pool_rows = max(int(mix["pool_rows"]), rows)
    rng = np.random.default_rng([seed, SEED_POOL])
    pools = [rng.standard_normal((pool_rows, lap.shape[0]), np.float32)
             for lap in laps]
    deck = _deck(mix, cfg["graphs"])
    try:
        # every (bucket, tier or bank, quantized row count) the window
        # can dispatch, once each
        r_pads = sorted({quantize_rows(rows * m, svc_cfg["row_quantum"])
                         for m in range(1, svc_cfg["max_batch"] + 1)})
        t0 = now()
        used = sorted({gid for gid, _ in deck})
        for w in sorted(router.engines):
            members = [g for g in used if router.widths[g] == w]
            if not members:
                continue
            gid = members[0]
            for tier in mix["tiers"]:
                for r in r_pads:
                    x = np.resize(pools[gid], (r, laps[gid].shape[0]))
                    fut = (svc.submit(gid, x, bank=True) if tier == "bank"
                           else svc.submit(gid, x, tier=tier))
                    fut.result()
        log(f"warmed {len(router.engines)} bucket(s) x "
            f"{len(mix['tiers'])} tier(s) x rows {r_pads} in "
            f"{now() - t0:.2f}s")
        clients, _, _, _ = _drive(svc, pools, deck, mix, seed, SEED_WARM,
                                  float(mix["warm_seconds"]),
                                  annotate(False), 0.0)
        _join(clients)
        stats0 = svc.stats()
        counter = CompileCounter(jax)
        misses0 = plan_misses()
        counter.armed = True
        ann = annotate(ctx.trace)
        clients, records, t_start, t_end = _drive(
            svc, pools, deck, mix, seed, SEED_CLIENT, ctx.seconds, ann,
            1.0 / float(mix["sample_every"]))
        obs.setup_s = t_start - ctx.t_start
        logdir = None
        if ctx.trace:
            logdir = tempfile.TemporaryDirectory(prefix="chipbench-trace-")
            lead = max((ctx.seconds - float(mix["trace_seconds"])) / 2, 0)
            _sleep_until(t_start + lead)
            start_trace(logdir.name)
            with jax.profiler.TraceAnnotation(red.WINDOW_SPAN):
                t_trace = now()
                _sleep_until(t_trace + float(mix["trace_seconds"]))
                obs.trace_window = (t_trace, now())
            jax.profiler.stop_trace()
        _join(clients)
        counter.armed = False
        obs.compiles_in_window = counter.count
        obs.compile_s_in_window = counter.seconds
        obs.plan_misses_in_window = plan_misses() - misses0
        stats1 = svc.stats()
    finally:
        svc.close()
    obs.stats = {k: stats1[k] - stats0[k]
                 for k in ("submitted", "served", "shed", "errors",
                           "dispatches")}
    obs.window = (t_start, t_end)
    obs.window_s = t_end - t_start
    obs.requests = records
    obs.attempted = len(records)
    obs.failed = sum(not r["ok"] for r in records)
    obs.memory_peak_bytes = peak_memory(jax)
    if logdir is not None:
        obs.reduction = reduce_trace(ctx, logdir.name)
        logdir.cleanup()
    log(f"window {obs.window_s:.2f}s: {obs.attempted} requests, "
        f"{obs.failed} failed, {obs.stats['dispatches']} dispatches")
    chains = _chains(router)
    bank = [name.strip() for name in cfg["bank"].split(",")]
    obs.sizes = [lap.shape[0] for lap in laps]
    obs.components = [
        {t: (len(f[0]) if t == "bank"
             else ref.tier_components(cfg["tiers"][t], len(f[0])))
         for t in mix["tiers"]} for _, f, _ in chains]
    obs.bank_filters = len(bank)
    del svc, router
    gc.collect()
    obs.checks = check(ctx, laps, pools, chains, records)
    return obs


def _sleep_until(t):
    import time
    while True:
        left = t - now()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


def _chains(router) -> list:
    """Per graph, in fleet order: (bucket width, factor arrays as numpy,
    spectrum) — the fit's answer the reference checks answers against."""
    out = []
    for gid, w in enumerate(router.widths):
        basis = router.engines[w].basis
        row = router.bucket_of[w].index(gid)
        out.append((w, [np.asarray(f)[row] for f in basis.factors],
                    np.asarray(basis.spectrum, np.float64)[row]))
    return out


def check(ctx, laps, pools, chains, records) -> list:
    """Every sampled answer against the dense float64 reference; every
    request of the window must have been answered."""
    cfg = ctx.config
    bank = [name.strip() for name in cfg["bank"].split(",")]
    unanswered = sum(not r["ok"] for r in records)
    wanted = {}
    for r in records:
        if r["y"] is not None:
            wanted.setdefault(r["graph"], []).append(r)
    t0 = now()
    worst = 0.0
    checked = 0
    for gid, recs in sorted(wanted.items()):
        w, factors, spectrum = chains[gid]
        g = len(factors[0])
        n = laps[gid].shape[0]
        ks = {t: (g if t == "bank"
                  else ref.tier_components(cfg["tiers"][t], g))
              for t in {r["tier"] for r in recs}}
        if cfg["family"] == "sym":
            anas = ref.sym_legs(factors, w, set(ks.values()))
            legs = {k: (a.T, a) for k, a in anas.items()}
            lams = {k: ref.lemma1_spectrum(a, laps[gid])
                    for k, a in anas.items()}
        else:
            legs = ref.gen_legs(factors, w, set(ks.values()))
            lams = {k: spectrum for k in legs}
        for r in recs:
            k = ks[r["tier"]]
            synth, ana = legs[k]
            lam = lams[g] if r["tier"] == "bank" else lams[k]
            gains = (ref.bank_gains(bank, lam) if r["tier"] == "bank"
                     else ref.tier_response(lam))
            x = pools[gid][r["offset"]:r["offset"] + r["rows"]]
            want = ref.apply_operator(synth, ana, gains, x, n)
            if r["tier"] != "bank":
                want = want[0]
            worst = max(worst, ref.relative_gap(r["y"], want))
            checked += 1
    log(f"reference: {checked} answers on {len(wanted)} graph(s) in "
        f"{now() - t0:.2f}s")
    if checked == 0:
        worst = float("inf")
    return [("answer_gap", worst, float(ctx.limits["answer_gap"])),
            ("unanswered", float(unanswered), 0.0)]
