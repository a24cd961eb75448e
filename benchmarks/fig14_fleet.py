"""Fig. 14 (repo-original): the mesh-partitioned serving fleet.

The placement layer (runtime/sharding.py; DESIGN.md §14) assigns whole
ragged-router buckets — and whole graphs within a bucket, along the
batch axis — to devices of a data mesh, so the steady-state serving
path needs NO cross-device communication.  Four claims are gated, each
measured in a fresh subprocess with ``--xla_force_host_platform_
device_count`` so the fleet really runs on 1/2/4/8 devices regardless
of the hardware CI lands on:

  1. FLAT COMPILES — the number of compiled serving programs is
     identical across 1/2/4/8-device fleets (placement changes WHERE
     tables live, never the traced program set), and a same-shape hot
     swap after maintenance compiles NOTHING new.
  2. ZERO COLLECTIVES — the lowered steady-state step HLO of every
     bucket contains zero collective ops (runtime/hlo_analysis.py::
     collective_bytes), the structural proof behind claim 3.
  3. OVERLAPPED MAINTENANCE — a mid-load refit of one dirty bucket
     (running on that bucket's own sub-mesh) must not stall serving on
     the other buckets' devices: serving p99 during maintenance <= 2x
     the no-maintenance p99, with bounded re-measure retries (fig7's
     convention — one noisy timing under container load must not fail
     CI while the structural facts hold).
  4. EXACTNESS — every fleet, at every device count, serves outputs
     matching the single-device engine loaded from the SAME checkpoint:
     bitwise for the sym family, <= 1e-5 for the general (T-transform)
     family.  (Placement moves arrays; it must never change math.)

The ``scale_speedup`` column (throughput vs the 1-device fleet) is
reported for the trajectory diff (_diff.py matches it by name) but NOT
gated: forced host devices share the same physical cores, so CPU
scaling is a smoke signal, not a claim.

This is a CPU rehearsal of the placement layer's structure and
exactness, NOT a chip measurement: every fleet runs in a CPU-forced
child process, and its timings are host-CPU timings.  The chip check of
the same path is ``chip_smoke.py --chips 4`` (one process, real chips).
"""
import json
import pathlib
import subprocess
import sys
import tempfile
import textwrap

from .common import emit
from .run import gate_assert

_REPO = pathlib.Path(__file__).resolve().parents[1]
_DEVICE_COUNTS = (1, 2, 4, 8)
_RETRIES = 3


def _subprocess_json(script: str, devices: int, timeout: float = 1200.0):
    """Run ``script`` with ``devices`` forced host CPU devices; the
    script prints one JSON line last (tests/conftest.py idiom)."""
    prelude = ("import os\n"
               f'os.environ["XLA_FLAGS"] = '
               f'"--xla_force_host_platform_device_count={int(devices)}"\n')
    out = subprocess.run(
        [sys.executable, "-c", prelude + textwrap.dedent(script)],
        capture_output=True, text=True, timeout=timeout, cwd=_REPO,
        env={"PYTHONPATH": "src", "JAX_PLATFORMS": "cpu",
             "PATH": __import__("os").environ.get("PATH", "/usr/bin:/bin"),
             "HOME": __import__("os").environ.get("HOME", "/root")})
    if out.returncode != 0:
        raise RuntimeError(f"fleet subprocess ({devices} devices) failed:"
                           f"\n{out.stdout[-2000:]}\n{out.stderr[-4000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


_PRELUDE = """
    import json
    import time
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core.fgft import laplacian
    from repro.graphs import community_graph, directed_variant
    from repro.launch.serve import RaggedFGFTServeEngine

    SIZES = {sizes!r}
    CKPT = {ckpt!r}

    def fleet(directed=False):
        laps = []
        for i, s in enumerate(SIZES):
            adj = community_graph(s, seed=s)
            if directed:
                adj = directed_variant(adj, seed=i)
            laps.append(laplacian(adj))
        return laps

    def signals(r={r}):
        return [np.random.default_rng(100 + i).normal(
            size=(r, s)).astype(np.float32) for i, s in enumerate(SIZES)]

    def compile_total(router):
        return sum(fn._cache_size() for eng in router.engines.values()
                   for fn in eng._live.fns.values())

    from repro.kernels.plan import plan_cache_stats
"""


def _prelude(sizes, ckpt, r):
    return _PRELUDE.format(sizes=list(sizes), ckpt=str(ckpt), r=r)


_SETUP = """
    mesh = jax.make_mesh((1,), ("data",))
    r = RaggedFGFTServeEngine(fleet(directed={directed}), n_iter=1,
                              mesh=mesh, placement="auto",
                              kind={kind!r}, dynamic=True)
    r.save(CKPT, step=0)
    import pathlib
    for i, y in enumerate(r.step(signals())):
        np.save(pathlib.Path(CKPT) / f"out_{{i}}.npy", np.asarray(y))
    print(json.dumps({{"buckets": sorted(int(w) for w in r.engines)}}))
"""


_WORKER = """
    import pathlib
    import threading
    from repro.runtime import hlo_analysis as hlo

    r = RaggedFGFTServeEngine.load(CKPT, dynamic=True)
    sig = signals()

    # --- exactness vs the writer's single-device outputs ----------------
    max_diff = 0.0
    for i, y in enumerate(r.step(sig)):
        want = np.load(pathlib.Path(CKPT) / f"out_{i}.npy")
        max_diff = max(max_diff, float(np.abs(np.asarray(y) - want).max()))

    # --- zero steady-state collectives (lowered HLO, every bucket) ------
    collectives = 0
    for w, eng in r.engines.items():
        live, tier = eng._live, eng.default_tier
        xp = eng.placement.place(jnp.zeros(
            (eng.placement.batch, sig[0].shape[0], eng.basis.n),
            jnp.float32))
        txt = live.fns[tier].lower(
            *live.operands(tier), live.tiers[tier]["spectrum"],
            xp).compile().as_text()
        collectives += sum(hlo.collective_bytes(txt)["counts"].values())

    # --- steady-state latency + throughput ------------------------------
    def one_step():
        t0 = time.perf_counter()
        r.step(sig)                       # gathers -> blocks until ready
        return (time.perf_counter() - t0) * 1e3

    for _ in range(3):
        one_step()                        # warmup past compile
    lats = sorted(one_step() for _ in range(@STEPS@))
    p50 = lats[len(lats) // 2]
    graphs_per_s = len(SIZES) / (sum(lats) / len(lats) / 1e3)

    # --- overlapped maintenance: serve CLEAN buckets while one dirty ----
    # bucket refits on its own sub-mesh devices ------------------------
    compiles_before = compile_total(r)
    plan_misses_before = plan_cache_stats()["misses"]
    ratio = p99_base = p99_maint = None
    if @MAINT@:
        dirty_pos = 0                     # first graph -> its bucket
        w_dirty = r.widths[dirty_pos]
        clean_engs = {w: e for w, e in r.engines.items() if w != w_dirty}

        def clean_step():
            t0 = time.perf_counter()
            pend = [clean_engs[w].step(b) for w, b in
                    r._scatter(sig).items() if w != w_dirty]
            for y in pend:
                np.asarray(y)
            return (time.perf_counter() - t0) * 1e3

        def p99(vals):
            vals = sorted(vals)
            return vals[min(len(vals) - 1, int(0.99 * len(vals)))]

        ratio = float("inf")
        for attempt in range(@RETRIES@):
            for _ in range(3):
                clean_step()
            base = [clean_step() for _ in range(@STEPS@)]
            r.apply_updates(dirty_pos, np.eye(
                SIZES[dirty_pos], dtype=np.float32) * 0.05)
            maint_lats, done = [], [False]

            def maintainer():
                r.maintain(dirty_only=True)
                done[0] = True

            th = threading.Thread(target=maintainer)
            th.start()
            while not done[0] or len(maint_lats) < @STEPS@:
                maint_lats.append(clean_step())
                if len(maint_lats) >= 4 * @STEPS@:
                    break
            th.join()
            p99_base, p99_maint = p99(base), p99(maint_lats)
            ratio = min(ratio, p99_maint / p99_base)
            if ratio <= 2.0:
                break
    compiles_after = compile_total(r)

    print(json.dumps({
        "devices": len(jax.devices()),
        "placed": r.placement is not None,
        "compiles": compiles_before,
        "compiles_after_maintain": compiles_after,
        "plan_misses": plan_misses_before,
        "plan_misses_after_maintain": plan_cache_stats()["misses"],
        "collectives": collectives,
        "p50_ms": p50, "graphs_per_s": graphs_per_s,
        "p99_base_ms": p99_base, "p99_maint_ms": p99_maint,
        "maint_ratio": ratio, "max_diff": max_diff}))
"""


def _worker(steps, retries, maint):
    """The worker template carries literal braces (f-strings, dicts), so
    its knobs are @TOKEN@ substitutions, not str.format fields."""
    return (_WORKER.replace("@STEPS@", str(int(steps)))
            .replace("@RETRIES@", str(int(retries)))
            .replace("@MAINT@", repr(bool(maint))))


def run(fast: bool = False):
    sizes = ([12, 16, 24, 28, 10, 30, 14, 20] if fast
             else [12, 16, 24, 28, 10, 30, 14, 20, 48, 50, 40, 60])
    r_sig = 2 if fast else 4
    steps = 30 if fast else 60
    rows = []
    with tempfile.TemporaryDirectory() as td:
        ckpt = pathlib.Path(td) / "fleet_sym"
        pre = _prelude(sizes, ckpt, r_sig)
        setup = _subprocess_json(
            pre + _SETUP.format(directed=False, kind="auto"), devices=1)
        print(f"[fig14] sym fleet of {len(sizes)} graphs, buckets "
              f"{setup['buckets']}, checkpoint saved on 1 device")
        results = {}
        for devices in _DEVICE_COUNTS:
            worker = _worker(steps, _RETRIES,
                             maint=(devices == _DEVICE_COUNTS[-1]))
            results[devices] = _subprocess_json(pre + worker, devices)
            res = results[devices]
            print(f"[fig14] {devices} device(s): compiles "
                  f"{res['compiles']}, collectives {res['collectives']}, "
                  f"p50 {res['p50_ms']:.1f}ms, max diff "
                  f"{res['max_diff']:.2e}")

        # --- general (T-transform) family: 1 writer vs 8-device reader --
        gen_sizes = [16, 16, 16, 16] if fast else [24, 24, 24, 24]
        gen_ckpt = pathlib.Path(td) / "fleet_general"
        gen_pre = _prelude(gen_sizes, gen_ckpt, r_sig)
        _subprocess_json(
            gen_pre + _SETUP.format(directed=True, kind="general"),
            devices=1)
        gen = _subprocess_json(
            gen_pre + _worker(steps=8, retries=1, maint=False),
            devices=_DEVICE_COUNTS[-1])
        print(f"[fig14] general fleet on {gen['devices']} devices: "
              f"max diff {gen['max_diff']:.2e}, collectives "
              f"{gen['collectives']}")

    thr1 = results[1]["graphs_per_s"]
    for devices in _DEVICE_COUNTS:
        res = results[devices]
        rows.append([devices, res["compiles"],
                     res["compiles_after_maintain"], res["plan_misses"],
                     res["collectives"],
                     res["p50_ms"], res["p99_base_ms"],
                     res["p99_maint_ms"], res["maint_ratio"],
                     res["max_diff"], gen["max_diff"],
                     res["graphs_per_s"], res["graphs_per_s"] / thr1])
    emit("fig14_fleet", rows,
         ["devices", "compiled_programs", "compiled_after_maintain",
          "plan_misses", "collective_ops", "step_p50_ms", "p99_base_ms",
          "p99_maint_ms", "maint_p99_ratio", "sym_max_diff",
          "general_max_diff", "graphs_per_s", "scale_speedup"])

    # 1. flat compile counts + nothing new after a same-shape hot swap
    # (both the jit-level program counts and the plan-cache miss
    # counters of kernels/plan.py::plan_cache_stats must agree: the
    # placed plans differ in WHERE their tables live, never in how many
    # distinct programs the fleet compiles)
    compiles = {d: results[d]["compiles"] for d in _DEVICE_COUNTS}
    gate_assert(len(set(compiles.values())) == 1,
                f"compiled-program count must be flat across device "
                f"counts, got {compiles}", rows)
    plan_misses = {d: results[d]["plan_misses"] for d in _DEVICE_COUNTS}
    gate_assert(len(set(plan_misses.values())) == 1,
                f"plan-cache miss count must be flat across device "
                f"counts, got {plan_misses}", rows)
    final = _DEVICE_COUNTS[-1]
    gate_assert(results[final]["compiles_after_maintain"]
                == results[final]["compiles"],
                f"same-shape hot swap must compile nothing: "
                f"{results[final]['compiles']} -> "
                f"{results[final]['compiles_after_maintain']}", rows)
    gate_assert(results[final]["plan_misses_after_maintain"]
                == results[final]["plan_misses"],
                f"same-shape hot swap must add no plan-cache misses: "
                f"{results[final]['plan_misses']} -> "
                f"{results[final]['plan_misses_after_maintain']}", rows)
    # 2. zero steady-state collectives, every fleet
    gate_assert(all(results[d]["collectives"] == 0
                    for d in _DEVICE_COUNTS) and gen["collectives"] == 0,
                f"steady-state step must lower to ZERO collective ops, "
                f"got {[results[d]['collectives'] for d in _DEVICE_COUNTS]}"
                f" + general {gen['collectives']}", rows)
    # 3. maintenance on one bucket's devices must not stall the others
    ratio = results[final]["maint_ratio"]
    gate_assert(ratio is not None and ratio <= 2.0,
                f"serving p99 during single-bucket maintenance must stay "
                f"<= 2x the idle p99, got {ratio:.2f}x "
                f"(base {results[final]['p99_base_ms']:.1f}ms, "
                f"maint {results[final]['p99_maint_ms']:.1f}ms)", rows)
    # 4. placement never changes math
    gate_assert(all(results[d]["max_diff"] == 0.0 for d in _DEVICE_COUNTS),
                f"sym fleet outputs must be BITWISE identical to the "
                f"single-device engine at every device count, got "
                f"{ {d: results[d]['max_diff'] for d in _DEVICE_COUNTS} }",
                rows)
    gate_assert(gen["max_diff"] <= 1e-5,
                f"general fleet outputs must match the single-device "
                f"engine within 1e-5, got {gen['max_diff']:.2e}", rows)
    gate_assert(all(results[d]["placed"] for d in _DEVICE_COUNTS),
                "every reader must have re-placed the checkpointed fleet",
                rows)
    return rows
