#!/usr/bin/env python3
"""Bring-up smoke run: fit and serve the paper's four graphs on a TPU.

Drives the FGFT fit-and-serve path through the entry points a user calls
(``RaggedFGFTServeEngine`` behind ``AsyncFGFTService``) at full graph
size, and checks every answer against a dense reference:

  1. undirected fleet: the four Fig. 2 stand-ins (n = 1133, 2642, 2888,
     3133; buckets 2048 and 4096), fitted at the router's default
     g = 2 w log2 w per bucket;
  2. serve: every tier and the filter bank through the async service,
     each response against the same fitted basis as a dense matrix;
  3. maintenance: one seeded churn batch, ``maintain()``, and the swapped
     version serves;
  4. directed fleet: phases 1-2 for the directed variants (T-transforms);
     g is cut to fit the run's time limit, n never is;
  5. Pallas: the served operator and bank programs with
     ``backend="pallas"`` against ``backend="xla"``;
  6. chip against host CPU: one n=256 subgraph per family fitted on the
     TPU and on the CPU, objectives within 1%.

Usage:

  python3 chip_smoke.py             one chip, phases 1-6
  python3 chip_smoke.py --chips 4   four chips, only the placed fleets:
                                    both families (g cut, n not) with
                                    placement="auto" against the
                                    one-device router (bitwise sym,
                                    <= 1e-5 general) and zero
                                    collectives in every bucket step
  JAX_PLATFORMS=cpu python3 chip_smoke.py --rehearse [--chips 4]
                                    tiny subgraphs on the CPU (with
                                    XLA_FLAGS=--xla_force_host_platform_
                                    device_count=4 for --chips 4): control
                                    flow only, prints no result line

A passing chip run ends with one JSON line:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
Without a TPU, or outside a checkout of this repository, it exits
non-zero and prints no result.  It runs in one process and starts none.
"""
import argparse
import json
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent

GRAPHS = ("email", "minnesota", "facebook", "human_protein")
TIERS = {"draft": 0.25, "half": 0.5, "full": 1.0}
BANK = "heat,tikhonov,lowpass,highpass,bandpass"
ROWS = 4                      # signals per request
BLOCK_B = 128                 # Pallas tile (no autotune cache is read)
SEED = 0
#: directed-family components for the 4096 bucket (the 2048 bucket
#: scales as w log2 w).  A T-greedy step scores every ordered pair
#: densely (about 22 ms per graph and component at w = 4096 on a v5e,
#: three sweeps), so the default g = 2 w log2 w = 98304 would take about
#: two hours; g is cut, n is not.
DIRECTED_G = 512
#: components at the 4096 bucket for the four-chip comparison, which
#: checks placement, not fit quality: both routers serve the same bases
PLACED_G = {"sym": 2048, "general": 128}
#: components of the chip-against-CPU fits (n = 256).  Once rounding
#: flips one greedy pick the chain moves as if the input had changed: a
#: 1e-7 relative change of it moves the objective by up to 2% (sym,
#: g = 4096) and 4% (general, g = 2048) on the CPU alone.  At g = n the
#: chains stay pick for pick, so a 1% gap tests the chip's arithmetic,
#: not that chaos.
PARITY_G = 256
SYM_TOL = 1e-4                # served vs dense operator, relative
GEN_TOL = 1e-3                # (Tbar^-1 conditioning enters here)
PALLAS_TOL = 1e-5             # pallas vs xla, relative to max |y|
CPU_TOL = 0.01                # chip vs CPU objective, relative
PLACED_GEN_TOL = 1e-5         # placed vs one-device, general family
CHURN_EDGES = 20
REHEARSAL_SIZES = (12, 26, 28, 30)


def log(msg: str):
    print(f"[smoke] {msg}", flush=True)


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str):
    if not cond:
        raise SmokeFailure(msg)


class Phase:
    """Times one phase and prints it on its own line."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        log(f"phase {self.name}: start")
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        log(f"phase {self.name}: {time.perf_counter() - self.t0:.2f}s"
            + (" FAILED" if exc[0] else ""))
        return False


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the placed-fleet comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="allow the CPU and use tiny subgraphs (control "
                         "flow only; prints no result line)")
    return ap.parse_args(argv)


class Smoke:
    """Shared state of one run: the fleets, the request mix, references."""

    def __init__(self, args, jax):
        import numpy as np
        from repro.core.fgft import laplacian
        from repro.graphs import (community_graph, directed_variant,
                                  real_graph_standin)
        self.jax, self.np, self.rehearse = jax, np, args.rehearse
        self.adjs = [community_graph(m, seed=SEED + k) if args.rehearse
                     else real_graph_standin(name, seed=SEED)
                     for k, (name, m) in enumerate(zip(GRAPHS,
                                                       REHEARSAL_SIZES))]
        self.laps = [laplacian(a) for a in self.adjs]
        self.dlaps = [laplacian(directed_variant(a, seed=SEED + k))
                      for k, a in enumerate(self.adjs)]
        self.sizes = [a.shape[0] for a in self.adjs]
        w_max = max(_bucket(s) for s in self.sizes)
        self.directed_g = (DIRECTED_G if not args.rehearse
                           else 2 * w_max)
        self.placed_g = ({f: 2 * w_max for f in PLACED_G} if args.rehearse
                         else PLACED_G)
        rng = np.random.default_rng(SEED)
        # two rounds of (every tier + the bank) per graph
        self.requests = [(gid, rng.standard_normal((ROWS, n)).astype(
                              np.float32), tier, tier is None)
                         for _ in range(2)
                         for gid, n in enumerate(self.sizes)
                         for tier in list(TIERS) + [None]]

    @staticmethod
    def h(lam):
        import jax.numpy as jnp
        return 1.0 / (1.0 + jnp.abs(lam))

    # -- fleets --------------------------------------------------------------

    def fit(self, family: str, g=None, **kwargs):
        """The family's fleet through the ragged router; ``g`` at the
        largest bucket (None: the router's default for sym, the directed
        cut for general)."""
        from repro.launch.serve import RaggedFGFTServeEngine
        laps = self.laps if family == "sym" else self.dlaps
        if g is None:
            g = 0 if family == "sym" else self.directed_g
        router = RaggedFGFTServeEngine(
            laps, g, kind=family, filters=BANK, tiers=TIERS,
            block_b=BLOCK_B, **kwargs)
        rel = router.rel_errors()
        for name, n, e in zip(GRAPHS, self.sizes, rel):
            log(f"{family} {name} n={n} bucket={_bucket(n)} "
                f"rel_objective={e:.6e}")
            check(self.np.isfinite(e) and 0.0 <= e < 1.0,
                  f"{family} {name}: relative objective {e}")
        for w, secs in sorted(router.onboard_seconds.items()):
            eng = router.engines[w]
            log(f"{family} bucket {w}: {len(router.bucket_of[w])} graph(s), "
                f"g={eng.basis.num_transforms}, "
                f"stages={eng.basis.fwd.num_stages}, fit {secs:.2f}s")
        return router

    def serve(self, router, requests=None):
        """Answer the request mix through the async front door; returns
        the results in request order and the service stats."""
        from repro.launch.service import AsyncFGFTService
        requests = self.requests if requests is None else requests
        with AsyncFGFTService(router, h=self.h, max_queue=4 * len(requests),
                              max_batch=8) as svc:
            futs = [svc.submit(gid, x, tier=tier, bank=bank)
                    for gid, x, tier, bank in requests]
            results = [f.result(timeout=1200) for f in futs]
            stats = svc.stats()
        check(stats["errors"] == 0, f"service counted {stats['errors']} "
              f"failed request(s)")
        check(stats["shed"] == 0, f"service shed {stats['shed']} request(s)")
        check(stats["served"] == len(requests),
              f"served {stats['served']} of {len(requests)}")
        log(f"served {len(results)} requests in {stats['dispatches']} "
            f"dispatches: errors={stats['errors']} shed={stats['shed']}")
        return results, stats

    # -- dense references ----------------------------------------------------

    def dense_legs(self, eng, cut):
        """(B, w, w) float64 host synthesis and analysis matrices of a
        bucket's basis at a stage cut: U and U^T (sym) or Tbar and its
        inverse (general).  Only the forward tables enter: Tbar^-1 is a
        host inverse of Tbar, never the served inverse tables."""
        np = self.np
        synth = np.asarray(eng.basis.to_dense(num_stages=cut), np.float64)
        if eng.basis.kind == "sym":
            return synth, np.swapaxes(synth, -1, -2)
        return synth, np.linalg.inv(synth)

    def check_responses(self, router, results, requests=None):
        """Every response against the fitted basis applied as a dense
        operator on the host: y = ((x V^T) * d) U^T in float64."""
        np = self.np
        jnp = self.jax.numpy
        requests = self.requests if requests is None else requests
        legs = {}
        worst = {}
        for (gid, x, tier, bank), res in zip(requests, results):
            w, row = router.widths[gid], router.bucket_of[
                router.widths[gid]].index(gid)
            eng = router.engines[w]
            n = self.sizes[gid]
            t = eng.tiers["full" if bank else tier]
            cut = (None if t["num_stages"] >= eng.basis.fwd.num_stages
                   else t["num_stages"])
            if (w, cut) not in legs:
                legs[(w, cut)] = self.dense_legs(eng, cut)
            synth, ana = legs[(w, cut)]
            if bank:
                gains = eng.bank.gains()[row]                     # (F, w)
            else:
                valid = jnp.arange(w) < n
                gains = jnp.where(valid, self.h(t["spectrum"][row]),
                                  0.0)[None]
            gains = np.asarray(gains, np.float64)
            xp = np.zeros((ROWS, w))
            xp[:, :n] = x
            coeff = xp @ ana[row].T
            ref = ((coeff[None] * gains[:, None, :]) @ synth[row].T)[..., :n]
            ref = ref if bank else ref[0]
            err = float(np.linalg.norm(res.y - ref)
                        / max(np.linalg.norm(ref), 1e-30))
            key = "bank" if bank else tier
            check(np.isfinite(err), f"graph {gid} {key}: non-finite "
                  f"response or reference")
            worst[key] = max(worst.get(key, 0.0), err)
        tol = SYM_TOL if router.engines[max(router.engines)].basis.kind \
            == "sym" else GEN_TOL
        for key, err in sorted(worst.items()):
            log(f"dense parity {key}: max relative error {err:.3e} "
                f"(limit {tol:g})")
            check(err <= tol, f"{key} responses differ from the dense "
                  f"operator by {err:.3e} > {tol:g}")

    def exact_filter_error(self, results):
        """Served full-tier response of graph 0 against an exact eigh
        filter (reported, not gated; the eigh runs on the host CPU)."""
        jax, np = self.jax, self.np
        jnp, hp = jax.numpy, jax.lax.Precision.HIGHEST
        for (gid, x, tier, bank), res in zip(self.requests, results):
            if gid != 0 or tier != "full":
                continue
            with jax.default_device(jax.devices("cpu")[0]):
                lam, vec = jnp.linalg.eigh(jnp.asarray(self.laps[0]))
                op = jnp.matmul(vec * self.h(lam)[None, :], vec.T,
                                precision=hp)
                ref = np.asarray(jnp.matmul(jnp.asarray(x), op.T,
                                            precision=hp))
            err = np.linalg.norm(res.y - ref) / np.linalg.norm(ref)
            log(f"{GRAPHS[0]} full tier vs exact eigh filter: relative "
                f"error {err:.4e} (reported, not gated)")
            return


def _bucket(n: int) -> int:
    from repro.launch.serve import bucket_width
    return bucket_width(n)


# -- one chip: phases 1-6 ---------------------------------------------------


def run_one_chip(smoke: Smoke):
    jax, np = smoke.jax, smoke.np
    from repro.dynamic.refit import RefitPolicy
    from repro.graphs import edge_perturbation

    with Phase("1 undirected fleet"):
        # any drift refreshes the spectrum, so phase 3's churn swaps
        policy = RefitPolicy(refresh=1e-6, extend=0.5, refit=0.9)
        sym = smoke.fit("sym", dynamic=True, policy=policy)

    with Phase("2 serve undirected"):
        results, _ = smoke.serve(sym)
        smoke.check_responses(sym, results)
        smoke.exact_filter_error(results)

    with Phase("3 maintenance"):
        from repro.launch.service import AsyncFGFTService
        gid = 0
        batch = edge_perturbation(smoke.adjs[gid], CHURN_EDGES,
                                  seed=SEED + 1)
        sym.apply_updates(gid, batch)
        req = [r for r in smoke.requests if r[0] == gid and r[2] == "full"]
        with AsyncFGFTService(sym, h=smoke.h) as svc:
            out = svc.maintain_now(timeout=1200)
            res = svc.submit(*req[0][:2], tier="full").result(timeout=600)
            stats = svc.stats()
        w = sym.widths[gid]
        log(f"maintain: bucket {w} action={out[w]['action']} "
            f"swaps={stats['maintain']['swaps']} "
            f"errors={stats['maintain']['errors']} served version "
            f"{res.version}")
        check(stats["maintain"]["errors"] == 0, "maintenance failed")
        check(stats["errors"] == 0, "request after the swap failed")
        check(stats["maintain"]["swaps"] >= 1 and res.version >= 1,
              f"no swapped version served (action {out[w]['action']})")
        smoke.check_responses(sym, [res], req[:1])

    with Phase("4 directed fleet"):
        log(f"directed family: g cut to {smoke.directed_g} at the "
            f"largest bucket (default 2 w log2 w; n is not cut)")
        gen = smoke.fit("general")
        results, _ = smoke.serve(gen)
        smoke.check_responses(gen, results)

    with Phase("5 pallas"):
        check_pallas(smoke, sym)
        check_pallas(smoke, gen)

    with Phase("6 chip vs host CPU"):
        check_cpu_parity(smoke)


def check_pallas(smoke: Smoke, router):
    """Serve every tier and the bank of each bucket through engines built
    on the SAME fitted bases with backend="pallas"."""
    jax, np = smoke.jax, smoke.np
    from repro.launch.serve import FGFTServeEngine
    rng = np.random.default_rng(SEED + 2)
    for w, eng in sorted(router.engines.items()):
        basis = eng.basis
        kind = basis.kind
        eng_p = FGFTServeEngine(eng._laps_host, basis=basis, kind=kind,
                                backend="pallas", filters=BANK, tiers=TIERS,
                                block_b=BLOCK_B)
        b = len(router.bucket_of[w])
        x = np.zeros((b, ROWS, w), np.float32)
        for row, gid in enumerate(router.bucket_of[w]):
            x[row, :, :smoke.sizes[gid]] = rng.standard_normal(
                (ROWS, smoke.sizes[gid]))
        x = jax.numpy.asarray(x)
        pairs = [(f"tier {t}", eng.step(x, smoke.h, tier=t),
                  eng_p.step(x, smoke.h, tier=t)) for t in TIERS]
        pairs.append(("bank", eng.step_bank(x), eng_p.step_bank(x)))
        for label, y_x, y_p in pairs:
            y_x, y_p = np.asarray(y_x), np.asarray(y_p)
            err = float(np.abs(y_p - y_x).max()
                        / max(np.abs(y_x).max(), 1e-30))
            log(f"pallas/xla {kind} bucket {w} {label}: max relative "
                f"difference {err:.3e} (limit {PALLAS_TOL:g})")
            check(err <= PALLAS_TOL, f"pallas {kind} {label} at {w}: {err}")


def check_cpu_parity(smoke: Smoke):
    """One n=256 stand-in subgraph per family, fitted on the chip and on
    the host CPU; greedy chains may break ties differently, so compare
    objectives, not factor tables."""
    jax, np = smoke.jax, smoke.np
    from repro.core import ApproxEigenbasis
    from repro.core.fgft import laplacian
    from repro.graphs import directed_variant
    n = min(256, smoke.sizes[0])
    sub = smoke.adjs[0][:n, :n]
    g = min(PARITY_G, n)
    cpu = jax.devices("cpu")[0]
    for family, mat in (("sym", laplacian(sub)),
                        ("general", laplacian(directed_variant(sub,
                                                               seed=SEED)))):
        den = float((mat * mat).sum())
        objs = {}
        for where, dev in (("chip", jax.devices()[0]), ("cpu", cpu)):
            t0 = time.perf_counter()
            with jax.default_device(dev):
                basis = ApproxEigenbasis.fit(jax.numpy.asarray(mat), g,
                                             n_iter=3, kind=family)
                objs[where] = float(basis.objective) / den
            log(f"{family} n={n} g={g} on {where} ({dev.platform}): "
                f"rel_objective={objs[where]:.6e} "
                f"({time.perf_counter() - t0:.2f}s)")
        gap = abs(objs["chip"] - objs["cpu"]) / max(objs["cpu"], 1e-30)
        log(f"{family} chip/cpu objective gap {gap:.3e} "
            f"(limit {CPU_TOL:g})")
        check(gap <= CPU_TOL, f"{family}: chip and CPU objectives differ "
              f"by {gap:.3e}")


# -- four chips: placed fleets against the one-device router ----------------


def run_four_chips(smoke: Smoke):
    """Both families, each placed over the chips against the one-device
    router.  Every comparison and collective count is printed before
    the run fails, so one run shows all that differs."""
    jax, np = smoke.jax, smoke.np
    from repro.launch.mesh import auto_mesh
    from repro.launch.serve import RaggedFGFTServeEngine
    from repro.runtime import hlo_analysis as hlo
    devices = jax.devices()
    mesh = auto_mesh((len(devices),), ("data",))
    failures = []
    for family in ("sym", "general"):
        with Phase(f"placed {family} fleet"):
            log(f"{family}: g cut to {smoke.placed_g[family]} at the largest "
                f"bucket (n is not cut)")
            one = smoke.fit(family, g=smoke.placed_g[family])
            want, _ = smoke.serve(one)
            with tempfile.TemporaryDirectory(dir=ROOT,
                                             prefix=".smoke_ckpt_") as d:
                one.save(d)
                placed = RaggedFGFTServeEngine.load(
                    d, placement="auto", mesh=mesh, filters=BANK,
                    tiers=TIERS, block_b=BLOCK_B)
            for w in sorted(placed.engines):
                log(f"{family} bucket {w} -> devices "
                    f"{list(placed.placement[w].device_ids)}")
            used = {d.id for eng in placed.engines.values()
                    for d in eng._live.fwd[0].devices()}
            check(len(used) == len(devices),
                  f"placed fleet uses devices {sorted(used)} of "
                  f"{len(devices)}")
            got, _ = smoke.serve(placed)
            worst, exact = {}, {}
            for (gid, _, tier, bank), a, b in zip(smoke.requests, want, got):
                key = (placed.widths[gid], "bank" if bank else tier)
                scale = max(float(np.abs(a.y).max()), 1e-30)
                worst[key] = max(worst.get(key, 0.0),
                                 float(np.abs(a.y - b.y).max()) / scale)
                exact[key] = exact.get(key, True) and np.array_equal(a.y, b.y)
            for (w, key), err in sorted(worst.items()):
                ok = (exact[(w, key)] if family == "sym"
                      else err <= PLACED_GEN_TOL)
                log(f"{family} bucket {w} {key} placed vs one-device: max "
                    f"relative difference {err:.3e}, "
                    f"{'bitwise' if exact[(w, key)] else 'not bitwise'} "
                    f"(limit {'bitwise' if family == 'sym' else PLACED_GEN_TOL})"
                    f"{'' if ok else ' FAILED'}")
                if not ok:
                    failures.append(f"{family} bucket {w} {key}: {err:.3e}")
            for w, eng in sorted(placed.engines.items()):
                live, tier = eng._live, eng.default_tier
                xp = eng.placement.place(jax.numpy.zeros(
                    (eng.placement.batch, ROWS, w), jax.numpy.float32))
                txt = live.fns[tier].lower(
                    live.fwd, live.bwd, live.tiers[tier]["spectrum"],
                    xp).compile().as_text()
                count = sum(hlo.collective_bytes(txt)["counts"].values())
                log(f"{family} bucket {w}: {count} collective(s) in the "
                    f"lowered step")
                if count:
                    failures.append(f"{family} bucket {w}: {count} "
                                    f"collective(s)")
    check(not failures, "placed fleets differ from the one-device router: "
          + "; ".join(failures))


def main(argv=None) -> int:
    args = parse_args(argv)
    import jax
    devices = jax.devices()
    dev = devices[0]
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu" and not args.rehearse:
        print(f"[smoke] FAIL: no TPU: JAX found {dev.platform!r} devices "
              f"only; this run measures nothing off the chip",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"[smoke] FAIL: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    if not (ROOT / "src" / "repro").is_dir():
        print(f"[smoke] FAIL: {ROOT} is not a checkout of this repository "
              f"(src/repro missing)", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import configure_compile_cache
    log(f"compile cache: {configure_compile_cache()}")
    t0 = time.perf_counter()
    try:
        smoke = Smoke(args, jax)
        if args.chips == 4:
            run_four_chips(smoke)
        else:
            run_one_chip(smoke)
    except Exception as exc:  # noqa: BLE001 — any failure fails the run
        import traceback
        traceback.print_exc()
        print(f"[smoke] FAIL: {type(exc).__name__}: {exc}", file=sys.stderr)
        return 1
    log(f"all phases passed in {time.perf_counter() - t0:.2f}s")
    if args.rehearse:
        log("rehearsal only: no result line")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
