"""Async serving front-end (launch/service.py, DESIGN.md §12): exact
SLO-stats math under a fake injectable clock (no sleeps, no wall-clock
sensitivity), admission control, coalescing-equivalence properties
(fused micro-batch == per-request loop, bitwise for the G family), and
SLO persistence next to the engine checkpoint."""
import numpy as np
import pytest

import jax.numpy as jnp

import repro.launch.service as service_mod
from repro.launch.serve import FGFTServeEngine, RaggedFGFTServeEngine
from repro.launch.service import (AsyncFGFTService, LatencyRecorder,
                                  ServiceClosed, ShedError, load_slo_stats,
                                  quantize_rows)

lowpass = lambda lam: 1.0 / (1.0 + lam)  # noqa: E731


class FakeClock:
    """Injectable monotonic clock: advances only when told to, so every
    latency figure the service reports is exact arithmetic."""

    def __init__(self, t=0.0, step=0.0):
        self.t = float(t)
        self.step = float(step)          # optional auto-advance per read

    def __call__(self):
        now = self.t
        self.t += self.step
        return now

    def advance(self, dt):
        self.t += dt


def drain_all(service):
    """Pump the queue inline until empty; returns dispatch batch sizes."""
    sizes = []
    while True:
        n = service.drain_once()
        if n == 0:
            return sizes
        sizes.append(n)


# ---------------------------------------------------------------------------
# quantize_rows
# ---------------------------------------------------------------------------


def test_quantize_rows_pow2_ladder():
    assert [quantize_rows(r) for r in (1, 7, 8, 9, 16, 17)] == \
        [8, 8, 8, 16, 16, 32]
    # non-default quantum: power-of-two MULTIPLES of the quantum
    assert [quantize_rows(r, 3) for r in (1, 3, 4, 6, 7)] == \
        [3, 3, 6, 6, 12]


def test_quantize_rows_validation():
    with pytest.raises(ValueError):
        quantize_rows(0)
    with pytest.raises(ValueError):
        quantize_rows(4, quantum=0)


# ---------------------------------------------------------------------------
# LatencyRecorder: pure arithmetic, asserted exactly
# ---------------------------------------------------------------------------


def test_recorder_nearest_rank_percentiles():
    rec = LatencyRecorder()
    for ms in range(1, 11):                       # 1..10 ms
        rec.record("t", ms * 1e-3)
    assert rec.count("t") == 10
    assert rec.percentile("t", 0.0) == pytest.approx(1e-3)
    assert rec.percentile("t", 50.0) == pytest.approx(5e-3)
    assert rec.percentile("t", 99.0) == pytest.approx(10e-3)
    assert rec.percentile("t", 100.0) == pytest.approx(10e-3)
    s = rec.summary()["t"]
    assert s["count"] == 10
    assert s["mean_s"] == pytest.approx(5.5e-3)
    assert s["p50_s"] == pytest.approx(5e-3)
    assert s["max_s"] == pytest.approx(10e-3)


def test_recorder_window_eviction_keeps_exact_globals():
    rec = LatencyRecorder(max_samples=4)
    for ms in range(10, 0, -1):                   # 10ms first, then smaller
        rec.record("t", ms * 1e-3)
    # window retains the LAST 4 samples (4,3,2,1 ms) ...
    assert rec.percentile("t", 100.0) == pytest.approx(4e-3)
    # ... but count/mean/max stay exact over ALL samples ever recorded
    s = rec.summary()["t"]
    assert s["count"] == 10
    assert s["mean_s"] == pytest.approx(5.5e-3)
    assert s["max_s"] == pytest.approx(10e-3)


def test_recorder_histogram_buckets():
    rec = LatencyRecorder()
    for s in (0.0, 1e-4, 1.5e-4, 1.0):
        rec.record("t", s)
    hist = rec.histogram("t")
    assert sum(b["count"] for b in hist) == 4
    assert hist[0] == {"le_s": 0.0, "count": 1}           # the exact zero
    assert hist[-1]["le_s"] == float("inf")
    # the bounded ladder reaches past 1.0, so a 1s sample lands in a
    # FINITE bucket (the pre-obs recorder dumped it into +inf because
    # its edge list stopped at the max retained sample)
    assert hist[-1]["count"] == 0
    # geometric edges are data-independent: origin * base^i
    assert hist[1]["le_s"] == pytest.approx(1e-4)
    assert hist[2]["le_s"] == pytest.approx(2e-4)


def test_recorder_histogram_fixed_length_merges_by_position():
    # the whole point of the bounded ladder: the edge list is a function
    # of (origin, base, bucket_count) only, NEVER of the data, so two
    # recorders with wildly different sample ranges merge positionally
    from repro.launch.service import merge_histograms
    a, b = LatencyRecorder(), LatencyRecorder()
    a.record("t", 2e-4)                     # sub-millisecond run ...
    b.record("t", 3.0)                      # ... vs a multi-second run
    b.record("t", 7.0)
    ha, hb = a.histogram("t"), b.histogram("t")
    assert len(ha) == len(hb) == 28         # bucket_count + {0, +inf}
    assert [x["le_s"] for x in ha] == [x["le_s"] for x in hb]
    merged = merge_histograms(ha, hb)
    assert sum(x["count"] for x in merged) == 3
    assert [x["le_s"] for x in merged] == [x["le_s"] for x in ha]
    # mismatched ladders are a hard error, not silent corruption
    with pytest.raises(ValueError):
        merge_histograms(ha, a.histogram("t", bucket_count=8))


def test_recorder_validation():
    rec = LatencyRecorder()
    with pytest.raises(ValueError):
        rec.record("t", -1e-3)
    with pytest.raises(ValueError):
        rec.record("t", float("nan"))
    with pytest.raises(KeyError):
        rec.percentile("missing", 50.0)
    rec.record("t", 1e-3)
    with pytest.raises(ValueError):
        rec.percentile("t", 101.0)
    with pytest.raises(ValueError):
        LatencyRecorder(max_samples=0)
    # keys with no samples simply don't appear
    assert rec.keys() == ["t"]


# ---------------------------------------------------------------------------
# Shared engines (prefit bases: fitting is the expensive part)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def sym_engine(sym_batch48):
    mats, basis = sym_batch48
    return FGFTServeEngine(mats, basis=basis,
                           tiers={"full": 1.0, "draft": 0.5},
                           filters="heat,lowpass")


@pytest.fixture(scope="module")
def gen_engine():
    mats = jnp.asarray(np.random.default_rng(7).standard_normal(
        (2, 12, 12)).astype(np.float32))
    return FGFTServeEngine(mats, 24, n_iter=1, kind="general",
                           tiers={"full": 1.0, "draft": 0.5})


@pytest.fixture(scope="module")
def ragged_engine():
    def s(n, seed):
        x = np.random.default_rng(seed).standard_normal((n, n)).astype(
            np.float32)
        return x + x.T

    # sizes 6/12/7 -> buckets {8: [0, 2], 16: [1]}: two dispatch groups
    return RaggedFGFTServeEngine([s(6, 0), s(12, 1), s(7, 2)], 16,
                                 n_iter=1, tiers={"full": 1.0})


@pytest.fixture(scope="module")
def ragged3_engine():
    def s(n, seed):
        x = np.random.default_rng(seed).standard_normal((n, n)).astype(
            np.float32)
        return x + x.T

    # sizes 5/6/7 -> one bucket of width 8 holding all three graphs
    return RaggedFGFTServeEngine([s(5, 0), s(6, 1), s(7, 2)], 12,
                                 n_iter=1, tiers={"full": 1.0, "draft": 0.5},
                                 filters="heat,lowpass")


@pytest.fixture
def per_graph(monkeypatch):
    """Every bucket of several graphs walks per-graph blocks.  The
    buckets here are far smaller than ``WHOLE_BLOCK_ELEMENTS``, which
    walks them whole by default."""
    monkeypatch.setattr(service_mod, "WHOLE_BLOCK_ELEMENTS", 0)


@pytest.fixture(params=["whole", "per-graph"])
def layout(request, monkeypatch):
    """Both dispatch layouts of a row-step engine."""
    if request.param == "per-graph":
        monkeypatch.setattr(service_mod, "WHOLE_BLOCK_ELEMENTS", 0)
    return request.param


class CompileEvents:
    """Counts JAX compile events (tracing, lowering, backend compiles)
    while armed, as the chip benchmark counts them in its window."""

    _live = None

    def __init__(self):
        import jax
        self.count = 0
        self.armed = False
        if CompileEvents._live is None:
            def listener(event, duration, *args, **kwargs):
                live = CompileEvents._live
                if live is not None and live.armed \
                        and "/jax/core/compile" in event:
                    live.count += 1
            jax.monitoring.register_event_duration_secs_listener(listener)
        CompileEvents._live = self


def signals_for(engine, gid, rows, seed):
    route_n = (engine.sizes[gid] if isinstance(engine, RaggedFGFTServeEngine)
               else engine.basis.n)
    return np.random.default_rng(seed).standard_normal(
        (rows, route_n)).astype(np.float32)


# ---------------------------------------------------------------------------
# Deterministic service behaviour: fake clock + inline drain (no threads)
# ---------------------------------------------------------------------------


def test_queue_latency_is_exact(sym_engine):
    clock = FakeClock()
    svc = AsyncFGFTService(sym_engine, clock=clock, auto_start=False)
    fut = svc.submit(0, signals_for(sym_engine, 0, 2, 0))
    clock.advance(0.25)                 # request waits a quarter second
    assert svc.drain_once() == 1
    res = fut.result(timeout=0)
    assert res.queue_s == pytest.approx(0.25)
    assert res.service_s == 0.0         # clock frozen across the dispatch
    assert res.total_s == pytest.approx(0.25)
    assert res.graph_id == 0 and res.tier == "full" and res.batch_size == 1
    assert res.version == sym_engine._live.version
    lat = svc.stats()["latency"]
    assert lat["full/queue"]["p50_s"] == pytest.approx(0.25)
    assert lat["full/total"]["count"] == 1


def test_ticking_clock_splits_queue_and_service(sym_engine):
    # every clock read advances 1s: t_submit=0, t_collect=1 (the span
    # between popping the queue and starting the dispatch), t0=2, t1=3
    svc = AsyncFGFTService(sym_engine, clock=FakeClock(step=1.0),
                           auto_start=False)
    fut = svc.submit(0, signals_for(sym_engine, 0, 1, 1))
    svc.drain_once()
    res = fut.result(timeout=0)
    assert res.queue_s == pytest.approx(2.0)
    assert res.service_s == pytest.approx(1.0)
    assert res.total_s == pytest.approx(3.0)


def test_percentiles_from_scripted_waits(sym_engine):
    clock = FakeClock()
    svc = AsyncFGFTService(sym_engine, clock=clock, max_batch=1,
                           auto_start=False)
    waits = [0.001 * k for k in range(1, 11)]     # 1..10 ms queue waits
    for w in waits:
        fut = svc.submit(1, signals_for(sym_engine, 1, 1, 2))
        clock.advance(w)
        svc.drain_once()
        assert fut.result(timeout=0).queue_s == pytest.approx(w)
    lat = svc.stats()["latency"]["full/queue"]
    assert lat["count"] == 10
    assert lat["p50_s"] == pytest.approx(0.005)   # nearest rank, exact
    assert lat["p99_s"] == pytest.approx(0.010)
    assert lat["mean_s"] == pytest.approx(0.0055)


def test_admission_control_sheds_typed(sym_engine):
    svc = AsyncFGFTService(sym_engine, max_queue=2, auto_start=False)
    x = signals_for(sym_engine, 0, 1, 3)
    svc.submit(0, x)
    svc.submit(1, x)
    with pytest.raises(ShedError) as err:
        svc.submit(2, x)
    assert err.value.queue_depth == 2
    assert err.value.max_queue == 2
    assert err.value.graph_id == 2
    st = svc.stats()
    assert st["shed"] == 1 and st["submitted"] == 2
    assert st["queue"]["depth"] == 2 and st["queue"]["peak"] == 2
    drain_all(svc)                      # the two accepted ones still serve
    assert svc.stats()["served"] == 2


def test_coalescing_groups_and_occupancy(sym_engine):
    svc = AsyncFGFTService(sym_engine, max_batch=8, auto_start=False)
    x = signals_for(sym_engine, 0, 2, 4)
    futs = [svc.submit(0, x, tier="full"), svc.submit(1, x, tier="full"),
            svc.submit(2, x, tier="draft"),       # different group
            svc.submit(0, x, tier="full")]        # same graph again
    # head group (full) coalesces 3 across the draft request; FIFO kept
    assert svc.drain_once() == 3
    assert [f.done() for f in futs] == [True, True, False, True]
    assert futs[0].result(timeout=0).batch_size == 3
    assert svc.drain_once() == 1
    st = svc.stats()
    assert st["dispatches"] == 2
    assert st["batch"]["occupancy_mean"] == pytest.approx(2.0)
    assert st["batch"]["occupancy_max"] == 3
    assert st["served"] == 4


def test_max_batch_caps_coalescing(sym_engine):
    svc = AsyncFGFTService(sym_engine, max_batch=2, auto_start=False)
    x = signals_for(sym_engine, 0, 1, 5)
    for _ in range(5):
        svc.submit(0, x)
    assert drain_all(svc) == [2, 2, 1]


def test_submit_validation(sym_engine):
    svc = AsyncFGFTService(sym_engine, auto_start=False)
    x = signals_for(sym_engine, 0, 1, 6)
    with pytest.raises(ValueError, match="not in fleet"):
        svc.submit(3, x)
    with pytest.raises(ValueError, match="not in fleet"):
        svc.submit(-1, x)
    with pytest.raises(ValueError, match="must be"):
        svc.submit(0, x[:, :5])
    with pytest.raises(ValueError, match="unknown tier"):
        svc.submit(0, x, tier="turbo")
    with pytest.raises(ValueError, match="tiered or bank"):
        svc.submit(0, x, tier="full", bank=True)
    # 1-D signals promote to one row
    fut = svc.submit(0, x[0])
    svc.drain_once()
    assert fut.result(timeout=0).y.shape == (1, sym_engine.basis.n)


def test_bank_requires_filters(gen_engine):
    svc = AsyncFGFTService(gen_engine, auto_start=False)
    with pytest.raises(ValueError, match="bank requests unavailable"):
        svc.submit(0, signals_for(gen_engine, 0, 1, 7), bank=True)


def test_closed_service_rejects_submit(sym_engine):
    svc = AsyncFGFTService(sym_engine, auto_start=False)
    svc.close()
    with pytest.raises(ServiceClosed):
        svc.submit(0, signals_for(sym_engine, 0, 1, 8))
    with pytest.raises(ServiceClosed):
        svc.start()


def test_close_drains_pending(sym_engine):
    # a STARTED service must answer every accepted future before its
    # dispatcher exits: submit a burst, close immediately, all resolve
    svc = AsyncFGFTService(sym_engine, auto_start=True)
    x = signals_for(sym_engine, 0, 2, 9)
    futs = [svc.submit(i % 3, x) for i in range(12)]
    svc.close()
    assert all(f.done() for f in futs)
    assert svc.stats()["served"] == 12


def test_reset_stats_zeroes_counters(sym_engine):
    svc = AsyncFGFTService(sym_engine, auto_start=False)
    svc.submit(0, signals_for(sym_engine, 0, 1, 10))
    svc.drain_once()
    svc.reset_stats()
    st = svc.stats()
    assert st["submitted"] == st["served"] == st["dispatches"] == 0
    assert st["latency"] == {}


def test_dispatch_error_fails_batch_not_service(sym_engine, monkeypatch):
    svc = AsyncFGFTService(sym_engine, auto_start=False)
    x = signals_for(sym_engine, 0, 1, 11)
    boom = svc.submit(0, x)
    monkeypatch.setattr(
        svc, "_fused_dispatch",
        lambda batch, lay: (_ for _ in ()).throw(
            RuntimeError("device lost")))
    svc.drain_once()
    with pytest.raises(RuntimeError, match="device lost"):
        boom.result(timeout=0)
    monkeypatch.undo()
    ok = svc.submit(0, x)               # the service itself keeps serving
    svc.drain_once()
    assert ok.result(timeout=0).y.shape == (1, sym_engine.basis.n)
    st = svc.stats()
    assert st["errors"] == 1 and st["served"] == 1


# ---------------------------------------------------------------------------
# Coalescing equivalence: fused micro-batch == per-request loop
# ---------------------------------------------------------------------------


def reference_loop(engine, requests, h=None):
    """The per-request baseline: the SAME service machinery capped at one
    request per dispatch (so padding/quantization/cropping are identical
    and any divergence is the coalescing itself)."""
    svc = AsyncFGFTService(engine, h=h, max_batch=1, auto_start=False)
    outs = []
    for gid, x, tier, bank in requests:
        fut = svc.submit(gid, x, tier=tier, bank=bank)
        svc.drain_once()
        outs.append(fut.result(timeout=0))
    return outs


def coalesced(engine, requests, h=None, max_batch=8):
    svc = AsyncFGFTService(engine, h=h, max_batch=max_batch,
                           auto_start=False)
    futs = [svc.submit(gid, x, tier=tier, bank=bank)
            for gid, x, tier, bank in requests]
    drain_all(svc)
    return [f.result(timeout=0) for f in futs]


def sym_request_mix(engine, bank=False):
    """Same-graph stacking, cross-graph rows, varying row counts, both
    tiers — every coalescing shape in one list."""
    reqs = []
    for i, (gid, rows) in enumerate(
            [(0, 1), (1, 3), (0, 2), (2, 1), (1, 1), (2, 4)]):
        tier = None if bank else ("full" if i % 2 == 0 else "draft")
        reqs.append((gid, signals_for(engine, gid, rows, 20 + i),
                     tier, bank))
    return reqs


def test_equivalence_sym_bitwise(sym_engine):
    reqs = sym_request_mix(sym_engine)
    ref = reference_loop(sym_engine, reqs, h=lowpass)
    got = coalesced(sym_engine, reqs, h=lowpass)
    for a, b in zip(got, ref):
        assert a.y.shape == b.y.shape
        assert np.array_equal(a.y, b.y)           # bitwise: G family
    # sanity: coalescing actually happened (not 1-request dispatches)
    assert max(r.batch_size for r in got) > 1


def test_equivalence_bank_bitwise(sym_engine):
    reqs = sym_request_mix(sym_engine, bank=True)
    ref = reference_loop(sym_engine, reqs)
    got = coalesced(sym_engine, reqs)
    for a, b in zip(got, ref):
        assert a.tier == "bank"
        assert np.array_equal(a.y, b.y)
    f = len(sym_engine.bank)
    assert got[1].y.shape == (f, 3, sym_engine.basis.n)


def test_equivalence_single_and_full_batch(sym_engine):
    # edge cases: a lone request, and exactly max_batch same-group ones
    lone = [(1, signals_for(sym_engine, 1, 2, 30), "full", False)]
    assert np.array_equal(coalesced(sym_engine, lone)[0].y,
                          reference_loop(sym_engine, lone)[0].y)
    full = [(i % 3, signals_for(sym_engine, i % 3, 2, 31 + i),
             "full", False) for i in range(8)]
    got = coalesced(sym_engine, full, max_batch=8)
    ref = reference_loop(sym_engine, full)
    assert got[0].batch_size == 8                 # one fused dispatch
    for a, b in zip(got, ref):
        assert np.array_equal(a.y, b.y)


def test_equivalence_general_tolerance(gen_engine):
    reqs = [(i % 2, signals_for(gen_engine, i % 2, 1 + i % 3, 40 + i),
             "full" if i % 2 == 0 else "draft", False) for i in range(6)]
    ref = reference_loop(gen_engine, reqs, h=lowpass)
    got = coalesced(gen_engine, reqs, h=lowpass)
    for a, b in zip(got, ref):
        np.testing.assert_allclose(a.y, b.y, atol=1e-5, rtol=1e-5)


def test_equivalence_ragged_buckets(ragged_engine):
    reqs = [(gid, signals_for(ragged_engine, gid, rows, 50 + gid), "full",
             False)
            for gid, rows in [(0, 2), (1, 1), (2, 3), (0, 1), (1, 2)]]
    ref = reference_loop(ragged_engine, reqs, h=lowpass)
    got = coalesced(ragged_engine, reqs, h=lowpass)
    for (gid, x, _, _), a, b in zip(reqs, got, ref):
        assert a.y.shape == (x.shape[0], ragged_engine.sizes[gid])
        assert np.array_equal(a.y, b.y)
    # different buckets never share a dispatch: the three bucket-8
    # requests (graphs 0, 2) fuse together, the two bucket-16 ones
    # (graph 1) fuse together — never across
    assert [r.batch_size for r, (gid, *_) in zip(got, reqs)
            if gid in (0, 2)] == [3, 3, 3]
    assert [r.batch_size for r, (gid, *_) in zip(got, reqs)
            if gid == 1] == [2, 2]


def mixed_queue(engine, graphs, bank=True):
    """Tiers, the bank (where the engine has one) and uneven row counts
    over every graph of one bucket, in one queue."""
    reqs = []
    for i, rows in enumerate([1, 3, 2, 5, 1, 4, 2, 3, 6, 2, 1, 3]):
        gid = graphs[i % len(graphs)]
        kind = (i // len(graphs)) % (3 if bank else 2)
        tier, is_bank = ((None, True) if kind == 2
                         else ("full" if kind == 0 else "draft", False))
        reqs.append((gid, signals_for(engine, gid, rows, 70 + i), tier,
                     is_bank))
    return reqs


def whole_bucket_rows(engine, w, requests, h):
    """Each request answered by its graph's row of the whole-bucket
    ``step``/``step_bank`` over a (B, r, w) block holding it alone."""
    eng = engine.engines[w] if isinstance(engine, RaggedFGFTServeEngine) \
        else engine
    outs = []
    for gid, x, tier, bank in requests:
        row = (engine.bucket_of[w].index(gid)
               if isinstance(engine, RaggedFGFTServeEngine) else gid)
        block = np.zeros((np.shape(eng.basis.spectrum)[0], x.shape[0],
                          eng.basis.n), np.float32)
        block[row, :, :x.shape[1]] = x
        y = (eng.step_bank(jnp.asarray(block)) if bank
             else eng.step(jnp.asarray(block), h, tier=tier))
        outs.append(np.asarray(y)[row][..., :x.shape[1]])
    return outs


def test_per_graph_blocks_match_loop_and_whole_bucket(ragged3_engine,
                                                      per_graph):
    reqs = mixed_queue(ragged3_engine, [0, 1, 2])
    svc = AsyncFGFTService(ragged3_engine, h=lowpass, auto_start=False)
    futs = [svc.submit(gid, x, tier=tier, bank=bank)
            for gid, x, tier, bank in reqs]
    drain_all(svc)
    got = [f.result(timeout=0) for f in futs]
    st = svc.stats()
    ref = reference_loop(ragged3_engine, reqs, h=lowpass)
    whole = whole_bucket_rows(ragged3_engine, 8, reqs, lowpass)
    for (gid, x, _, bank), a, b, c in zip(reqs, got, ref, whole):
        f = len(ragged3_engine.engines[8].bank)
        assert a.y.shape == ((f,) if bank else ()) + x.shape
        assert np.array_equal(a.y, b.y)           # bitwise: G family
        np.testing.assert_allclose(a.y, c, atol=1e-6, rtol=1e-6)
    # one dispatch per group (full, draft, bank), each walking the
    # three graphs' own blocks; same-graph requests stacked in them
    assert st["dispatches"] == 3 and st["graph_blocks"] == 9
    assert max(r.batch_size for r in got) == 6
    assert {r.tier for r in got} == {"full", "draft", "bank"}


def test_per_graph_blocks_general_family(gen_engine, per_graph):
    reqs = mixed_queue(gen_engine, [0, 1], bank=False)
    got = coalesced(gen_engine, reqs, h=lowpass)
    ref = reference_loop(gen_engine, reqs, h=lowpass)
    whole = whole_bucket_rows(gen_engine, None, reqs, lowpass)
    for a, b, c in zip(got, ref, whole):
        np.testing.assert_allclose(a.y, b.y, atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(a.y, c, atol=1e-5, rtol=1e-5)
    assert max(r.batch_size for r in got) > 1


@pytest.fixture(scope="module")
def ragged3_gen_engine():
    def d(n, seed):
        return np.random.default_rng(seed).standard_normal((n, n)).astype(
            np.float32)

    # directed twin of ragged3_engine: one bucket of width 8, T chains
    return RaggedFGFTServeEngine([d(5, 0), d(6, 1), d(7, 2)], 12,
                                 n_iter=1, kind="general",
                                 tiers={"full": 1.0, "draft": 0.5},
                                 filters="heat,lowpass")


@pytest.mark.parametrize("kind", ["tier", "bank"])
@pytest.mark.parametrize("family", ["sym", "general"])
def test_walk_stages_arg_sums_plan_stages_over_blocks_and_legs(
        request, family, layout, kind):
    from repro import obs
    router = request.getfixturevalue(
        "ragged3_engine" if family == "sym" else "ragged3_gen_engine")
    eng = router.engines[8]
    assert eng.basis.kind == family
    tracer = obs.default_tracer()
    tracer.clear()
    svc = AsyncFGFTService(router, h=lowpass, clock=FakeClock(),
                           auto_start=False, max_batch=8)
    bank = kind == "bank"
    tier = None if bank else "draft"
    futs = [svc.submit(gid, signals_for(router, gid, rows, gid), tier=tier,
                       bank=bank) for gid, rows in ((0, 3), (2, 2))]
    assert drain_all(svc) == [2]
    for f in futs:
        f.result(timeout=0)
    svc.close()
    (disp,) = [s for s in tracer.spans() if s["name"] == "serve.dispatch"]
    # the stages of the programs a block's launch runs: the draft cut of
    # both legs, or the bank's full tables analysed once and synthesized
    # once for every filter
    per_block = (2 * eng.basis.fwd.num_stages if bank
                 else 2 * eng.tiers["draft"]["num_stages"])
    assert 0 < eng.tiers["draft"]["num_stages"] <= eng.basis.fwd.num_stages
    assert eng.basis.fwd.num_stages == eng.basis.bwd.num_stages
    blocks = 3 if layout == "whole" else 2
    assert disp["args"]["b"] == blocks
    assert disp["args"]["walk_stages"] == blocks * per_block


def _tpu_facts(monkeypatch, free=16 << 30):
    """The lowering chooser reads the devices as a TPU with ``free``
    bytes free: the engines built or swapped meanwhile serve dense legs
    (on the CPU) where those fit."""
    import repro.launch.serve as serve_mod
    monkeypatch.setattr(serve_mod, "_device_facts",
                        lambda devices: ("tpu", free))


@pytest.fixture(scope="module")
def dense_routers(ragged3_engine, ragged3_gen_engine, tmp_path_factory):
    """family -> the ragged3 router saved and loaded as a TPU would load
    it: the same bases, served by the dense lowering."""
    out = {}
    with pytest.MonkeyPatch.context() as mp:
        _tpu_facts(mp)
        for family, router in (("sym", ragged3_engine),
                               ("general", ragged3_gen_engine)):
            path = tmp_path_factory.mktemp(f"dense-{family}")
            router.save(path)
            out[family] = RaggedFGFTServeEngine.load(path)
    return out


@pytest.mark.parametrize("kind", ["tier", "bank"])
@pytest.mark.parametrize("family", ["sym", "general"])
def test_dense_engine_dispatch_span_and_counters(request, dense_routers,
                                                 family, layout, kind):
    """A dense engine's dispatch names its lowering, walks no stage and
    counts every block it serves dense, in ``stats()`` and the registry;
    its answers equal the walking engine's.  The CPU picks the walk."""
    import jax
    from repro import obs
    from repro.launch.serve import _device_facts
    walk = request.getfixturevalue(
        "ragged3_engine" if family == "sym" else "ragged3_gen_engine")
    dense = dense_routers[family]
    assert _device_facts(jax.devices()[:1])[0] == "cpu"
    assert walk.engines[8].lowering == "walk"
    assert dense.engines[8].lowering == "dense"
    bank = kind == "bank"
    tier, label = (None, "bank") if bank else ("draft", "draft")
    reqs = [(gid, signals_for(walk, gid, rows, gid))
            for gid, rows in ((0, 3), (2, 2))]
    tracer = obs.default_tracer()
    tracer.clear()
    answers, stats = {}, {}
    for name, router in (("walk", walk), ("dense", dense)):
        svc = AsyncFGFTService(router, h=lowpass, clock=FakeClock(),
                               auto_start=False, max_batch=8,
                               name=f"{name}-{family}-{layout}-{kind}")
        futs = [svc.submit(gid, x, tier=tier, bank=bank) for gid, x in reqs]
        assert drain_all(svc) == [2]
        answers[name] = [f.result(timeout=0).y for f in futs]
        stats[name] = svc.stats()
        svc.close()
    spans = {s["args"]["lowering"]: s["args"] for s in tracer.spans()
             if s["name"] == "serve.dispatch"}
    blocks = 3 if layout == "whole" else 2
    assert spans["walk"]["walk_stages"] > 0
    assert spans["dense"]["walk_stages"] == 0
    assert spans["dense"]["b"] == blocks
    assert stats["dense"]["graph_blocks"] == blocks
    assert stats["dense"]["dense_blocks"] == blocks
    assert stats["walk"]["dense_blocks"] == 0
    assert service_mod._OBS_DENSE_BLOCKS.value(
        service=f"dense-{family}-{layout}-{kind}", tier=label) == blocks
    for a, b in zip(answers["dense"], answers["walk"]):
        np.testing.assert_allclose(a, b, rtol=1e-5,
                                   atol=1e-5 * np.abs(b).max())


@pytest.mark.parametrize("kw, free, want", [
    ({}, 16 << 30, "dense"),
    ({}, 1 << 10, "walk"),                  # three copies of the legs
    ({"precision": "bf16"}, 16 << 30, "walk"),
    ({"backend": "pallas"}, 16 << 30, "walk"),
    ({"fused": False}, 16 << 30, "walk"),
])
def test_engine_lowering_follows_the_device(sym_batch48, monkeypatch, kw,
                                            free, want):
    """Only a fused f32 xla engine whose legs fit the free memory of a
    TPU serves dense; the same engine on the CPU walks."""
    mats, basis = sym_batch48
    make = lambda: FGFTServeEngine(  # noqa: E731
        mats, basis=basis, tiers={"full": 1.0, "draft": 0.5},
        filters="heat", **kw)
    assert make().lowering == "walk"
    _tpu_facts(monkeypatch, free)
    assert make().lowering == want


def test_router_buckets_share_the_free_memory(monkeypatch):
    """Each bucket decides from the memory free when it installs, the
    legs of the buckets installed before it taken: legs that fit the
    device alone but not beside the smaller bucket's keep the walk."""
    import gc
    import jax
    import repro.launch.serve as serve_mod
    from repro.core.fgft import laplacian
    from repro.graphs import community_graph
    from repro.kernels.plan import DENSE_LEG_COPIES
    laps = [laplacian(community_graph(n, seed=n)) for n in (12, 14, 28, 30)]
    used = []

    def build(limit):
        def facts(devices):
            gc.collect()
            used.append(sum(a.nbytes for a in jax.live_arrays()))
            return "tpu", limit - used[-1]
        monkeypatch.setattr(serve_mod, "_device_facts", facts)
        used.clear()
        return RaggedFGFTServeEngine(laps, 64, n_iter=1,
                                     tiers={"full": 1.0})

    # one full-chain sym leg a graph: (2, w, w) f32 a bucket
    legs = {w: 2 * w * w * 4 for w in (16, 32)}
    probe = build(1 << 40)
    assert [e.lowering for e in probe.engines.values()] == ["dense"] * 2
    used_16, used_32 = used
    # the bucket 32 alone (its in-use memory less bucket 16's legs)
    # fits three copies of its legs, beside bucket 16's it does not
    limit = used_32 + DENSE_LEG_COPIES * legs[32] - legs[16] // 2
    assert limit - (used_32 - legs[16]) >= DENSE_LEG_COPIES * legs[32]
    del probe
    router = build(limit)
    assert limit - used[0] >= DENSE_LEG_COPIES * legs[16]
    assert router.engines[16].lowering == "dense"
    assert router.engines[32].lowering == "walk"


@pytest.mark.parametrize("action", ["refresh", "refit"])
def test_dense_legs_survive_same_shape_hot_swap(monkeypatch, action):
    """A swap on a dense engine reuses every compiled program, the leg
    build's included: a Lemma-1 refresh keeps the chain and so its legs
    (no leg build runs), a refit builds new ones.  The new version
    answers as the walk of its basis."""
    from repro.kernels import plan as plan_mod
    from repro.kernels.plan import ApplyPlan
    from repro.dynamic import RefitPolicy
    from repro.graphs import weight_jitter
    _tpu_facts(monkeypatch)
    eng = _dyn_engine()
    if action == "refit":
        eng.controller.policy = RefitPolicy(
            refresh=1e-9, extend=1e-9, refit=1e-9, num_probes=16,
            max_extends=0)
    x = jnp.asarray(np.random.default_rng(4).standard_normal(
        (2, 4, 12)).astype(np.float32))
    eng.warmup(x)
    live0 = eng._live
    assert live0.lowering == "dense"
    progs = [live0.fns["full"], live0.row_fns["full"]]
    sizes0 = [p._cache_size() for p in progs]
    builds0 = plan_mod._legs_program.cache_info().misses
    built = []
    dense_legs = plan_mod.dense_legs
    monkeypatch.setattr(plan_mod, "dense_legs",
                        lambda *a: built.append(1) or dense_legs(*a))
    y0 = np.asarray(eng.step(x, lowpass))
    adj = (np.abs(np.asarray(eng._laps_host[0])) *
           (1 - np.eye(12))).astype(np.float32)
    eng.apply_updates(0, weight_jitter(adj, 6, scale=0.2, seed=1))
    assert eng.maintain()["action"] == action
    live1 = eng._live
    assert live1.version == live0.version + 1
    assert live1.lowering == "dense"
    kept = live1.legs is live0.legs
    assert kept == (action == "refresh")
    assert len(built) == (0 if kept else 1)
    assert [live1.fns["full"], live1.row_fns["full"]] == progs
    assert [p._cache_size() for p in progs] == sizes0
    assert plan_mod._legs_program.cache_info().misses == builds0
    y1 = np.asarray(eng.step(x, lowpass))
    assert np.abs(y1 - y0).max() > 0
    basis = eng.basis
    want = ApplyPlan(family=basis.kind, mode="operator", n=12,
                     batched=True).operator(basis.fwd, basis.bwd,
                                            lowpass(basis.spectrum), x)
    np.testing.assert_allclose(y1, np.asarray(want), rtol=1e-5,
                               atol=1e-5 * np.abs(np.asarray(want)).max())


def _directed_fleet(sizes, seed=0):
    """Directed Laplacians L = D_out - A of random graphs: each edge of
    an undirected G(n, p) keeps one direction, chosen at random."""
    from repro.graphs.generators import directed_variant, erdos_renyi
    laps = []
    for k, n in enumerate(sizes):
        adj = directed_variant(erdos_renyi(n, 0.2, seed=seed + k),
                               seed=seed + k)
        laps.append((np.diag(adj.sum(axis=1)) - adj).astype(np.float32))
    return laps


def _t_legs(factors, w, k):
    """(Tbar_k, Tbar_k^{-1}) as float64 (w, w) from the first k factors
    of one T chain in application order (a shear x_i += a x_j, kind 1;
    a scaling x_i *= a, kind 0)."""
    kind, i, j, a = (np.asarray(f) for f in factors)
    a = a.astype(np.float64)
    fwd, inv_t = np.eye(w), np.eye(w)
    for t in range(k):
        p, q = i[t], j[t]
        if kind[t] == 1:
            fwd[p] += a[t] * fwd[q]
            inv_t[q] -= a[t] * inv_t[p]
        else:
            fwd[p] *= a[t]
            inv_t[p] /= a[t]
    return fwd, inv_t.T


def test_directed_fleet_at_alpha_twelfth_saved_loaded_and_served(
        tmp_path, per_graph):
    """A directed fleet at g = w log2 w / 12 in its widest bucket, fitted,
    saved, restored and served with per-graph blocks on every tier and
    the bank: each answer is the float64 dense T operator of the
    restored chain, and each chain beats the identity's objective."""
    sizes = [12, 26, 28, 30]
    laps = _directed_fleet(sizes)
    tiers = {"draft": 0.25, "half": 0.5, "full": 1.0}
    bank_names = "heat,tikhonov,lowpass,highpass,bandpass"
    fitted = RaggedFGFTServeEngine(laps, round(32 * 5 / 12), n_iter=3,
                                   kind="general", tiers=tiers,
                                   filters=bank_names)
    fitted.save(tmp_path / "fleet")
    router = RaggedFGFTServeEngine.load(tmp_path / "fleet")
    assert sorted(router.engines) == [16, 32]
    # alpha = g / (w log2 w) = 1/12 in both buckets (rounded to a factor)
    for w, g in ((16, 5), (32, 13)):
        assert router.engines[w].basis.num_transforms == g
    svc = AsyncFGFTService(router, h=lowpass, max_batch=8, auto_start=False)
    reqs = []
    for gid in range(len(sizes)):
        for k, tier in enumerate(list(tiers) + [None]):
            reqs.append((gid, tier, signals_for(router, gid, 2 + k,
                                                10 * gid + k)))
    futs = [svc.submit(gid, x, tier=tier, bank=tier is None)
            for gid, tier, x in reqs]
    drain_all(svc)
    got = [f.result(timeout=0) for f in futs]
    st = svc.stats()
    svc.close()
    assert st["served"] == len(reqs) and st["errors"] == 0
    # per-graph blocks: one block a served graph in every dispatch
    assert st["graph_blocks"] == sum(
        len({gid for gid, t, _ in reqs
             if t == tier and router.widths[gid] == w})
        for w in router.engines for tier in list(tiers) + [None])
    for (gid, tier, x), res in zip(reqs, got):
        w = router.widths[gid]
        eng = router.engines[w]
        row = router.bucket_of[w].index(gid)
        factors = [np.asarray(f)[row] for f in eng.basis.factors]
        spectrum = np.asarray(eng.basis.spectrum, np.float64)[row]
        n = sizes[gid]
        if tier is None:
            k = eng.basis.num_transforms
            gains = np.asarray(eng.bank.gains(), np.float64)[row]
        else:
            k = eng.tiers[tier]["num_transforms"]
            gains = lowpass(spectrum)[None]
        synth, ana = _t_legs(factors, w, k)
        gains = gains.copy()
        gains[:, n:] = 0.0
        xp = np.zeros((x.shape[0], w))
        xp[:, :n] = x
        want = (((xp @ ana.T)[None] * gains[:, None, :]) @ synth.T)[..., :n]
        if tier is not None:
            want = want[0]
        assert res.y.shape == want.shape
        np.testing.assert_allclose(res.y, want, atol=1e-5, rtol=1e-5)
    for gid, lap in enumerate(laps):
        w = router.widths[gid]
        eng = router.engines[w]
        row = router.bucket_of[w].index(gid)
        factors = [np.asarray(f)[row] for f in eng.basis.factors]
        spectrum = np.asarray(eng.basis.spectrum, np.float64)[row]
        synth, ana = _t_legs(factors, w, eng.basis.num_transforms)
        big = np.zeros((w, w))
        big[:sizes[gid], :sizes[gid]] = lap
        total = float((big ** 2).sum())
        fit = float((((synth * spectrum) @ ana - big) ** 2).sum()) / total
        identity = float(((big - np.diag(np.diag(big))) ** 2).sum()) / total
        assert fit < identity, (gid, fit, identity)


@pytest.mark.parametrize("requests, signal, whole, block, blocks", [
    # one request: 3 rows of n = 5 in a (3 graphs, 8 rows, w = 8) block,
    # or in graph 0's own (8 rows, w = 8) block
    ([(0, 3)], 3 * 5, 3 * 8 * 8, 8 * 8, 1),
    # two graphs of the 3-graph bucket, each in its own block
    ([(0, 3), (2, 3)], 3 * 5 + 3 * 7, 3 * 8 * 8, 8 * 8 + 8 * 8, 2),
    # two requests on one graph stack to 9 rows, quantized to 16
    ([(1, 3), (1, 6)], 9 * 6, 3 * 16 * 8, 16 * 8, 1),
    # graph 1 stacks 9 rows (16), graph 2 has 2 (8): walked alone
    ([(1, 3), (2, 2), (1, 6)], 9 * 6 + 2 * 7, 3 * 16 * 8,
     16 * 8 + 8 * 8, 2),
], ids=["one-request", "two-graphs", "one-graph-stacked", "uneven-graphs"])
def test_fill_counters_match_block_arithmetic(ragged3_engine, layout,
                                              requests, signal, whole,
                                              block, blocks):
    from repro import obs
    tracer = obs.default_tracer()
    tracer.clear()
    name = f"fill-{layout}-{len(requests)}-{requests[-1][0]}"
    svc = AsyncFGFTService(ragged3_engine, clock=FakeClock(),
                           auto_start=False, max_batch=8, name=name)
    futs = [svc.submit(gid, signals_for(ragged3_engine, gid, rows, gid))
            for gid, rows in requests]
    assert drain_all(svc) == [len(requests)]
    for f in futs:
        f.result(timeout=0)
    st = svc.stats()
    svc.close()
    per_graph = {}
    for gid, rows in requests:
        per_graph[gid] = per_graph.get(gid, 0) + rows
    r_pad = max(quantize_rows(r) for r in per_graph.values())
    if layout == "whole":
        # the bucket's three graphs at the largest quantized row count
        assert whole == 3 * r_pad * 8
        block, blocks = whole, 3
    else:
        # the graphs' own blocks: sum over the served graphs of
        # quantize_rows(the graph's rows) x w
        assert block == sum(quantize_rows(r) * 8
                            for r in per_graph.values())
        assert blocks == len(per_graph)
    assert st["signal_elements"] == signal
    assert st["block_elements"] == block
    assert st["graph_blocks"] == blocks
    (disp,) = [s for s in tracer.spans() if s["name"] == "serve.dispatch"]
    assert disp["args"]["b"] == blocks
    assert disp["args"]["r_pad"] == r_pad
    snap = obs.default_registry().collect()
    for key, want in (("service_signal_elements_total", signal),
                      ("service_block_elements_total", block),
                      ("service_graph_blocks_total", blocks)):
        mine = [s for s in snap[key]["series"]
                if s["labels"] == {"service": name, "tier": "full"}]
        assert [s["value"] for s in mine] == [want]
    svc.reset_stats()
    assert svc.stats()["signal_elements"] == 0
    assert svc.stats()["block_elements"] == 0
    assert svc.stats()["graph_blocks"] == 0


def test_layout_whole_at_or_below_limit_per_graph_above(ragged3_engine,
                                                        monkeypatch):
    """The bucket's whole block at the batch's quantized rows picks the
    layout; per-graph blocks never walk fewer rows than the smallest
    count that picks them."""
    # (3 graphs, 8 rows, w = 8) is at the limit, (3, 16, 8) above it
    monkeypatch.setattr(service_mod, "WHOLE_BLOCK_ELEMENTS", 3 * 8 * 8)
    cases = [([(0, 3), (2, 3)], 3, 3 * 8 * 8),
             # graph 2's 2 rows walk 16, the fewest above the limit
             ([(1, 9), (2, 2)], 2, 2 * 16 * 8)]
    for requests, blocks, block in cases:
        reqs = [(gid, signals_for(ragged3_engine, gid, rows, 40 + gid),
                 None, False) for gid, rows in requests]
        svc = AsyncFGFTService(ragged3_engine, h=lowpass, max_batch=8,
                               auto_start=False)
        futs = [svc.submit(gid, x) for gid, x, _, _ in reqs]
        assert drain_all(svc) == [len(reqs)]
        got = [f.result(timeout=0) for f in futs]
        st = svc.stats()
        svc.close()
        assert (st["graph_blocks"], st["block_elements"]) == (blocks,
                                                              block)
        ref = reference_loop(ragged3_engine, reqs, h=lowpass)
        for a, b in zip(got, ref):
            assert np.array_equal(a.y, b.y)


def test_single_requests_compile_every_program_the_layouts_use(
        ragged3_engine, monkeypatch):
    """One request of each quantized row count compiles every program a
    mixed dispatch then runs, on either side of the limit: nothing
    compiles inside the served load."""
    # quantum 3: rungs 3, 6, 12; (3 graphs, 6 rows, w = 8) is the limit
    monkeypatch.setattr(service_mod, "WHOLE_BLOCK_ELEMENTS", 3 * 6 * 8)
    svc = AsyncFGFTService(ragged3_engine, h=lowpass, max_batch=8,
                           row_quantum=3, auto_start=False)
    for rows in (5, 10):                # rungs 6 (whole) and 12 (per graph)
        fut = svc.submit(0, signals_for(ragged3_engine, 0, rows, rows))
        drain_all(svc)
        fut.result(timeout=0)
    svc.reset_stats()
    events = CompileEvents()
    events.armed = True
    # whole at rung 6; per graph at rung 12, graph 2 floored from 3 to 12
    for requests in ([(0, 5), (2, 1)], [(1, 10), (2, 2)]):
        futs = [svc.submit(gid, signals_for(ragged3_engine, gid, rows, 9))
                for gid, rows in requests]
        assert drain_all(svc) == [len(requests)]
        for f in futs:
            f.result(timeout=0)
    events.armed = False
    svc.close()
    assert svc.stats()["graph_blocks"] == 3 + 2
    assert events.count == 0


def test_warmed_engine_serves_without_compiles(ragged3_engine, layout):
    """``warmup`` compiles the per-graph programs too: a warmed engine's
    first dispatch through the front door compiles nothing."""
    eng = ragged3_engine.engines[8]
    # quantum 5: a row count (10) no other test compiles
    eng.warmup(jnp.zeros((3, 10, 8), jnp.float32))
    svc = AsyncFGFTService(ragged3_engine, max_batch=8, row_quantum=5,
                           auto_start=False)
    events = CompileEvents()
    events.armed = True
    futs = [svc.submit(1, signals_for(ragged3_engine, 1, 7, 1)),
            svc.submit(2, signals_for(ragged3_engine, 2, 7, 2), tier=None,
                       bank=False),
            svc.submit(0, signals_for(ragged3_engine, 0, 6, 3), bank=True)]
    drain_all(svc)
    for f in futs:
        f.result(timeout=0)
    events.armed = False
    st = svc.stats()
    svc.close()
    assert st["dispatches"] == 2
    assert st["graph_blocks"] == (3 + 3 if layout == "whole" else 2 + 1)
    assert events.count == 0


# ---------------------------------------------------------------------------
# Deterministic maintenance accounting (inline tick: no maintainer thread)
# ---------------------------------------------------------------------------


def _dyn_engine():
    from repro.core.fgft import laplacian
    from repro.dynamic import RefitPolicy
    from repro.graphs import erdos_renyi
    laps = np.stack([laplacian(erdos_renyi(12, 0.4, seed=s))
                     for s in range(2)])
    # refresh threshold ~0 so any real update forces a swap (sym family)
    return FGFTServeEngine(jnp.asarray(laps), 24, n_iter=1, dynamic=True,
                           policy=RefitPolicy(refresh=1e-9, extend=10.0,
                                              refit=10.0, num_probes=16,
                                              max_extends=0))


@pytest.fixture()
def dyn_engine():
    return _dyn_engine()


def test_maintain_now_inline_counts_swaps(dyn_engine):
    from repro.graphs import weight_jitter
    svc = AsyncFGFTService(dyn_engine, auto_start=False)
    v0 = dyn_engine._live.version
    res = svc.maintain_now()            # clean fleet: REUSE, no swap
    assert res["action"] == "reuse"
    adj = (np.abs(np.asarray(dyn_engine._laps_host[0])) *
           (1 - np.eye(12))).astype(np.float32)
    dyn_engine.apply_updates(0, weight_jitter(adj, 6, scale=0.2, seed=1))
    res = svc.maintain_now()
    assert res["action"] != "reuse"
    assert dyn_engine._live.version == v0 + 1
    st = svc.stats()["maintain"]
    assert st == {"enabled": True, "ticks": 2, "errors": 0, "swaps": 1}


def test_maintain_rejects_static_engine(sym_engine):
    svc = AsyncFGFTService(sym_engine, auto_start=False)
    with pytest.raises(ValueError, match="dynamic"):
        svc.maintain_now()
    assert svc.stats()["maintain"]["enabled"] is False


# ---------------------------------------------------------------------------
# SLO persistence next to the engine checkpoint
# ---------------------------------------------------------------------------


def test_save_slo_uniform_metadata(sym_engine, tmp_path):
    svc = AsyncFGFTService(sym_engine, auto_start=False)
    svc.submit(0, signals_for(sym_engine, 0, 1, 60))
    svc.drain_once()
    svc.save(tmp_path / "ckpt")
    slo = load_slo_stats(tmp_path / "ckpt")
    assert slo["served"] == 1 and slo["dispatches"] == 1
    assert "full/total" in slo["latency"]


def test_save_slo_ragged_sidecar(ragged_engine, tmp_path):
    svc = AsyncFGFTService(ragged_engine, auto_start=False)
    svc.submit(1, signals_for(ragged_engine, 1, 2, 61))
    svc.drain_once()
    out = svc.save(tmp_path / "router")
    assert (out / "slo.json").exists()
    slo = load_slo_stats(out)
    assert slo["served"] == 1
    assert slo["queue"]["max"] == svc.max_queue
