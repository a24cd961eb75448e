"""Observability layer (repro/obs/, DESIGN.md §15): fixed-ladder
metrics with snapshot-consistent collection and associative cross-run
merge, the bounded span tracer and its exports, and the end-to-end
integration facts the fig15 gates rely on — exact span telescoping
under a fake clock, compile spans == plan-cache misses, refit-decision
and checkpoint events landing in the default tracer."""
import json
import math
import threading

import numpy as np
import pytest

from repro import obs
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Tracer


class FakeClock:
    """The test_service.py convention: advances only when told to (or
    by ``step`` per read), so every duration is exact arithmetic."""

    def __init__(self, t=0.0, step=0.0):
        self.t = float(t)
        self.step = float(step)

    def __call__(self):
        now = self.t
        self.t += self.step
        return now

    def advance(self, dt):
        self.t += dt


@pytest.fixture
def reg():
    return MetricsRegistry()


@pytest.fixture
def recording_on():
    """Restore the global recording switch no matter what a test does
    to it — a leaked ``set_enabled(False)`` would silently blind every
    later test's integration assertions."""
    yield
    obs.configure(enabled=True)


# ---------------------------------------------------------------------------
# geometric_edges: the fixed ladder
# ---------------------------------------------------------------------------


def test_geometric_edges_length_is_data_independent():
    edges = obs.geometric_edges(origin=1e-3, base=2.0, count=5)
    # count + 2: leading 0, count geometric points, trailing +inf
    assert edges == (0.0, 1e-3, 2e-3, 4e-3, 8e-3, 16e-3, float("inf"))
    # the length depends on the PARAMETERS only — same params, same
    # ladder, which is what makes positional cross-run merge sound
    assert len(obs.geometric_edges()) == len(obs.geometric_edges())


def test_geometric_edges_validation():
    for bad in (dict(origin=0.0), dict(origin=-1.0), dict(base=1.0),
                dict(base=0.5), dict(count=0)):
        with pytest.raises(ValueError):
            obs.geometric_edges(**bad)


def test_bucket_counts_le_semantics():
    edges = (0.0, 1.0, 2.0, float("inf"))
    # le-semantics: a sample ON an edge lands in that edge's bucket
    assert obs.bucket_counts(edges, [0.0, 0.5, 1.0, 1.5, 2.0, 99.0]) \
        == [1, 2, 2, 1]


# ---------------------------------------------------------------------------
# registry: kinds, labels, bound children
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_basics(reg):
    c = reg.counter("c_total", "a counter", ("k",))
    c.inc(k="a")
    c.inc(2.5, k="a")
    c.inc(k="b")
    assert c.value(k="a") == 3.5 and c.value(k="b") == 1.0
    assert c.value(k="never") == 0.0
    with pytest.raises(ValueError):
        c.inc(-1.0, k="a")

    g = reg.gauge("g", "a gauge", ("k",))
    g.set(3.0, k="a")
    g.set(7.0, k="a")                    # last write wins
    assert g.value(k="a") == 7.0

    h = reg.histogram("h_s", "a histogram", ("k",),
                      edges=(0.0, 1.0, float("inf")))
    h.observe(0.5, k="a")
    h.observe(2.0, k="a")
    snap = reg.collect()["h_s"]["series"][0]["value"]
    assert snap["counts"] == [0, 1, 1]
    assert snap["sum"] == 2.5 and snap["count"] == 2
    with pytest.raises(ValueError):
        h.observe(float("nan"), k="a")


def test_label_validation_and_reregistration(reg):
    c = reg.counter("x_total", "x", ("a", "b"))
    with pytest.raises(ValueError):
        c.inc(a="1")                     # missing label
    with pytest.raises(ValueError):
        c.inc(a="1", b="2", c="3")       # extra label
    # idempotent re-registration returns the SAME metric
    assert reg.counter("x_total", "x", ("a", "b")) is c
    # kind or labelname drift is a schema conflict
    with pytest.raises(ValueError):
        reg.gauge("x_total", "x", ("a", "b"))
    with pytest.raises(ValueError):
        reg.counter("x_total", "x", ("a",))
    # histograms additionally validate their ladder
    with pytest.raises(ValueError):
        reg.histogram("bad_h", edges=(0.0, 1.0))      # no +inf tail
    with pytest.raises(ValueError):
        reg.histogram("bad_h2", edges=(1.0, 0.0, float("inf")))


def test_bound_children_share_series_with_kwargs_path(reg):
    c = reg.counter("c_total", "c", ("k",))
    g = reg.gauge("g", "g", ("k",))
    h = reg.histogram("h_s", "h", ("k",), edges=(0.0, 1.0, float("inf")))
    bc, bg, bh = c.labels(k="a"), g.labels(k="a"), h.labels(k="a")
    bc.inc()
    c.inc(k="a")
    assert bc.value() == c.value(k="a") == 2.0
    bg.set(5.0)
    assert g.value(k="a") == bg.value() == 5.0
    bh.observe(0.5)
    h.observe(0.5, k="a")
    assert reg.collect()["h_s"]["series"][0]["value"]["count"] == 2
    # label validation happens ONCE, at bind time
    with pytest.raises(ValueError):
        c.labels(wrong="a")


def test_observe_many_and_seq_match_repeated_observe(reg):
    h1 = reg.histogram("a_s", edges=(0.0, 1.0, 2.0, float("inf")))
    h2 = reg.histogram("b_s", edges=(0.0, 1.0, 2.0, float("inf")))
    samples = [0.25, 1.0, 1.5, 3.0, 0.25]
    for v in samples:
        h1.observe(v)
    h2.observe_seq(samples[:3])
    h2.observe_many(0.25, 1)
    h2.observe(3.0)
    h2.observe_many(0.0, 0)              # count < 1: no-op
    snap = reg.collect()
    assert snap["a_s"]["series"][0]["value"] \
        == snap["b_s"]["series"][0]["value"]
    # observe_many of k identical samples == k observes
    h3 = reg.histogram("c_s", edges=(0.0, 1.0, float("inf")))
    h3.observe_many(0.5, 4)
    v = reg.collect()["c_s"]["series"][0]["value"]
    assert v["counts"] == [0, 4, 0] and v["sum"] == 2.0 \
        and v["count"] == 4
    with pytest.raises(ValueError):
        h3.observe_seq([0.5, float("inf")])


def test_disabled_recording_early_returns(reg, recording_on):
    c = reg.counter("c_total", "c", ("k",))
    h = reg.histogram("h_s", "h", ("k",))
    bc, bh = c.labels(k="a"), h.labels(k="a")
    obs.configure(enabled=False)
    assert not obs.recording_enabled()
    c.inc(k="a")
    bc.inc()
    h.observe(0.5, k="a")
    bh.observe_seq([0.5])
    assert c.value(k="a") == 0.0
    assert "series" not in reg.collect().get("h_s", {}) \
        or reg.collect()["h_s"]["series"] == []
    obs.configure(enabled=True)
    bc.inc()
    assert c.value(k="a") == 1.0


# ---------------------------------------------------------------------------
# merges: associative by construction
# ---------------------------------------------------------------------------


def _hist(edges, counts):
    return [{"le_s": e, "count": c} for e, c in zip(edges, counts)]


def test_merge_histograms_associative_and_commutative():
    edges = [0.0, 1.0, float("inf")]
    a, b, c = (_hist(edges, [1, 0, 2]), _hist(edges, [0, 3, 1]),
               _hist(edges, [2, 2, 0]))
    left = obs.merge_histograms(obs.merge_histograms(a, b), c)
    right = obs.merge_histograms(a, obs.merge_histograms(b, c))
    assert left == right == _hist(edges, [3, 5, 3])
    assert obs.merge_histograms(a, b) == obs.merge_histograms(b, a)
    with pytest.raises(ValueError):
        obs.merge_histograms(a, _hist([0.0, 2.0, float("inf")], [0, 0, 0]))
    with pytest.raises(ValueError):
        obs.merge_histograms()


def _make_snapshot(counter_v, gauge_v, hist_sample):
    r = MetricsRegistry()
    r.counter("req_total", "r", ("k",)).inc(counter_v, k="a")
    r.gauge("ver", "v").set(gauge_v)
    r.histogram("lat_s", "l", (), edges=(0.0, 1.0, float("inf"))) \
        .observe(hist_sample)
    return r.collect()


def test_merge_snapshots_semantics_and_associativity():
    a = _make_snapshot(1.0, 10.0, 0.5)
    b = _make_snapshot(2.0, 20.0, 2.0)
    c = _make_snapshot(4.0, 30.0, 0.25)
    left = obs.merge_snapshots(obs.merge_snapshots(a, b), c)
    right = obs.merge_snapshots(a, obs.merge_snapshots(b, c))
    assert left == right
    s = left["req_total"]["series"][0]
    assert s["value"] == 7.0                       # counters ADD
    assert left["ver"]["series"][0]["value"] == 30.0   # gauges last-win
    hv = left["lat_s"]["series"][0]["value"]
    assert hv["counts"] == [0, 2, 1] and hv["count"] == 3
    assert hv["sum"] == 2.75
    # inputs are never mutated (CI left-folds the same dict repeatedly)
    assert a["req_total"]["series"][0]["value"] == 1.0


def test_merge_snapshots_schema_conflicts_raise():
    a = _make_snapshot(1.0, 10.0, 0.5)
    r = MetricsRegistry()
    r.gauge("req_total", "now a gauge", ("k",)).set(1.0, k="a")
    with pytest.raises(ValueError):
        obs.merge_snapshots(a, r.collect())
    r2 = MetricsRegistry()
    r2.histogram("lat_s", "l", (), edges=(0.0, 9.0, float("inf"))) \
        .observe(0.5)
    with pytest.raises(ValueError):
        obs.merge_snapshots(a, r2.collect())
    # disjoint metric sets union cleanly
    r3 = MetricsRegistry()
    r3.counter("other_total").inc()
    merged = obs.merge_snapshots(a, r3.collect())
    assert set(merged) == {"req_total", "ver", "lat_s", "other_total"}


# ---------------------------------------------------------------------------
# exposition: prometheus text + JSON round trip
# ---------------------------------------------------------------------------


def test_prometheus_text_cumulative_buckets(reg):
    h = reg.histogram("lat_s", "latency", ("tier",),
                      edges=(0.0, 1.0, float("inf")))
    h.observe(0.5, tier="full")
    h.observe(0.5, tier="full")
    h.observe(2.0, tier="full")
    reg.counter("req_total", "requests", ("tier",)).inc(3, tier="full")
    text = obs.to_prometheus_text(reg.collect())
    assert "# TYPE lat_s histogram" in text
    assert "# HELP req_total requests" in text
    # buckets are CUMULATIVE and the ladder ends at +Inf == _count
    assert 'lat_s_bucket{tier="full",le="0"} 0' in text
    assert 'lat_s_bucket{tier="full",le="1"} 2' in text
    assert 'lat_s_bucket{tier="full",le="+Inf"} 3' in text
    assert 'lat_s_sum{tier="full"} 3' in text
    assert 'lat_s_count{tier="full"} 3' in text
    assert 'req_total{tier="full"} 3' in text


def test_json_roundtrip_preserves_inf_edges(reg):
    reg.histogram("h_s").observe(0.01)
    loaded = json.loads(obs.to_json(reg.collect()))
    edges = loaded["h_s"]["series"][0]["value"]["edges"]
    assert math.isinf(edges[-1])
    # a JSON-reloaded snapshot is still mergeable (the CI path: fold
    # the metrics.json from disk into the live collect())
    merged = obs.merge_snapshots(loaded, reg.collect())
    assert merged["h_s"]["series"][0]["value"]["count"] == 2


# ---------------------------------------------------------------------------
# concurrency: collect() snapshots never tear
# ---------------------------------------------------------------------------


def test_collect_is_snapshot_consistent_under_load(reg):
    h = reg.histogram("h_s", edges=(0.0, 1.0, float("inf")))
    c = reg.counter("c_total")
    bh, bc = h.labels(), c.labels()
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            bc.inc()
            bh.observe(0.5)              # sum stays exactly 0.5 * count
            bh.observe_seq([0.5, 0.5])

    threads = [threading.Thread(target=hammer, daemon=True)
               for _ in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(200):
            snap = reg.collect()
            if "h_s" not in snap or not snap["h_s"]["series"]:
                continue
            v = snap["h_s"]["series"][0]["value"]
            # a torn histogram shows count != sum(bucket counts) or a
            # sum that drifted off the exact 0.5-per-sample line
            assert sum(v["counts"]) == v["count"]
            assert v["sum"] == 0.5 * v["count"]
    finally:
        stop.set()
        for t in threads:
            t.join(5.0)
    assert not any(t.is_alive() for t in threads)


# ---------------------------------------------------------------------------
# tracer: explicit endpoints, bounded ring, filters, exports
# ---------------------------------------------------------------------------


def test_tracer_records_explicit_endpoints_verbatim():
    tr = Tracer(clock=FakeClock(step=1.0))
    tr.add_span("a", 2.0, 5.0, cat="x", trace_id=7, args={"k": 1})
    tr.add_span("b", 5.0, 6.0, cat="y", trace_id=8)
    (a,) = tr.spans(name="a")
    assert a["ts"] == 2.0 and a["dur"] == 3.0 and a["ph"] == "X"
    assert a["cat"] == "x" and a["trace_id"] == 7 and a["args"] == {"k": 1}
    assert [s["name"] for s in tr.spans(cat="y")] == ["b"]
    assert [s["name"] for s in tr.spans(trace_id=7)] == ["a"]
    assert len(tr) == 2


def test_tracer_add_spans_matches_sequential_add_span():
    one, bulk = Tracer(), Tracer()
    specs = [("q", 0.0, 1.0, "serve", 1, None, None),
             ("x", 1.0, 3.0, "serve", 1, 42, {"n": 2})]
    for name, t0, t1, cat, tid_, tid, args in specs:
        one.add_span(name, t0, t1, cat=cat, trace_id=tid_, tid=tid,
                     args=args)
    bulk.add_spans(specs)
    a, b = one.spans(), bulk.spans()
    # tid defaults to the recording thread in both paths
    assert [{k: v for k, v in s.items() if k != "tid"} for s in a] \
        == [{k: v for k, v in s.items() if k != "tid"} for s in b]
    assert a[1]["tid"] == b[1]["tid"] == 42


def test_tracer_ring_bound_and_disabled_skip():
    tr = Tracer(capacity=3)
    for i in range(5):
        tr.add_span(f"s{i}", float(i), float(i) + 1.0)
    assert [s["name"] for s in tr.spans()] == ["s2", "s3", "s4"]
    tr.enabled = False
    tr.add_span("dropped", 0.0, 1.0)
    tr.event("dropped")
    tr.add_spans([("dropped", 0.0, 1.0, "", None, None, None)])
    with tr.span("dropped"):
        pass
    assert len(tr) == 3
    tr.clear()
    assert len(tr) == 0
    with pytest.raises(ValueError):
        Tracer(capacity=0)


def test_tracer_span_contextmanager_and_event_use_own_clock():
    clock = FakeClock(step=1.0)
    tr = Tracer(clock=clock)
    with tr.span("work", cat="c", trace_id=3):
        pass                             # t0=0, end=1
    tr.event("tick", cat="c", args={"x": 1})
    (w,) = tr.spans(name="work")
    assert w["ts"] == 0.0 and w["dur"] == 1.0
    (e,) = tr.spans(name="tick")
    assert e["ph"] == "i" and e["ts"] == 2.0 and e["dur"] == 0.0


class FakeAnnotation:
    """An injected profiler-annotation factory that logs what the
    tracer asks of it: (enter, name, args) and (exit, name, exc type)."""

    def __init__(self):
        self.log = []

    def __call__(self, name, **args):
        log = self.log

        class Mirror:
            def __enter__(self):
                log.append(("enter", name, args))

            def __exit__(self, kind, value, tb):
                log.append(("exit", name, kind))
                return False

        return Mirror()


def test_tracer_span_mirrors_into_injected_annotation():
    fake = FakeAnnotation()
    tr = Tracer(clock=FakeClock(step=1.0), annotation=fake)
    with tr.span("outer", cat="serve", args={"w": 8, "tier": "full"}):
        with tr.span("inner"):
            pass
    assert fake.log == [("enter", "outer", {"w": 8, "tier": "full"}),
                        ("enter", "inner", {}),
                        ("exit", "inner", None),
                        ("exit", "outer", None)]
    # an exception closes both the mirror and the ring record
    with pytest.raises(KeyError):
        with tr.span("boom"):
            raise KeyError("x")
    assert fake.log[-2:] == [("enter", "boom", {}),
                             ("exit", "boom", KeyError)]
    assert [s["name"] for s in tr.spans()] == ["inner", "outer", "boom"]
    (outer,) = tr.spans(name="outer")
    assert outer["args"] == {"w": 8, "tier": "full"}
    assert outer["cat"] == "serve"


def test_tracer_span_disabled_or_without_factory_mirrors_nothing():
    reads = []

    def clock():
        reads.append(1)
        return 0.0

    fake = FakeAnnotation()
    off = Tracer(clock=clock, annotation=fake, enabled=False)
    with off.span("x", args={"k": 1}) as got:
        assert got is off
    assert fake.log == [] and reads == [] and len(off) == 0
    plain = Tracer(clock=FakeClock(step=1.0))
    assert plain.annotation is None
    with plain.span("y") as got:
        assert got is plain
    assert [s["name"] for s in plain.spans()] == ["y"]
    # retrospective spans never reach the mirror
    on = Tracer(annotation=fake)
    on.add_span("r", 0.0, 1.0)
    on.add_spans([("r2", 1.0, 2.0, "", None, None, None)])
    assert fake.log == [] and len(on) == 2


def test_obs_imports_no_jax():
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    code = ("import sys, repro.obs; "
            "print(sorted(m for m in sys.modules if m == 'jax' "
            "or m.startswith(('jax.', 'repro.')) and "
            "not m.startswith('repro.obs')))")
    out = subprocess.run([sys.executable, "-c", code],
                         env={**os.environ, "PYTHONPATH": src},
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"


def test_trace_exports_round_trip(tmp_path):
    tr = Tracer(clock=FakeClock(step=1.0))
    tr.add_span("req", 1.0, 3.5, cat="serve", trace_id=9,
                args={"tier": "full"})
    tr.event("mark", cat="maintain")
    chrome = json.loads(tr.export_chrome_trace(
        tmp_path / "t.json").read_text())
    by_name = {e["name"]: e for e in chrome["traceEvents"]}
    req = by_name["req"]
    assert req["ph"] == "X" and req["ts"] == 1.0e6 and req["dur"] == 2.5e6
    assert req["args"] == {"tier": "full", "trace_id": 9}
    assert by_name["mark"]["ph"] == "i" and "dur" not in by_name["mark"]
    lines = (tr.export_jsonl(tmp_path / "t.jsonl")
             .read_text().strip().splitlines())
    assert [json.loads(ln)["name"] for ln in lines] == ["req", "mark"]
    assert json.loads(lines[0])["dur"] == 2.5


def test_new_trace_ids_are_unique_and_monotone():
    ids = [obs.new_trace_id() for _ in range(100)]
    assert ids == sorted(ids) and len(set(ids)) == 100


def test_format_snapshot_mentions_every_metric(reg):
    reg.counter("req_total", "requests", ("k",)).inc(k="a")
    reg.histogram("lat_s", "latency").observe(0.5)
    text = obs.format_snapshot(reg.collect())
    assert "req_total" in text and "lat_s" in text


# ---------------------------------------------------------------------------
# integration: the instrumented layers record what fig15 gates
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def sym_engine(sym_batch48):
    from repro.launch.serve import FGFTServeEngine
    mats, basis = sym_batch48
    return FGFTServeEngine(mats, basis=basis, tiers={"full": 1.0})


def test_service_spans_telescope_exactly(sym_engine):
    from repro.launch.service import AsyncFGFTService
    tracer = obs.default_tracer()
    svc = AsyncFGFTService(sym_engine, clock=FakeClock(step=1.0),
                           auto_start=False, max_batch=2,
                           name="obs-exact")
    rng = np.random.default_rng(0)
    futs = [svc.submit(i % 3, rng.standard_normal((2, 16)).astype(
        np.float32)) for i in range(4)]
    while svc.drain_once():
        pass
    results = [f.result(timeout=0) for f in futs]
    svc.close()
    assert len({r.trace_id for r in results}) == len(results)
    for res in results:
        sp = {s["name"]: s for s in tracer.spans(trace_id=res.trace_id)}
        q, bt, ex, tot = (sp["request/queue"], sp["request/batch"],
                          sp["request/execute"], sp["request"])
        # the fig15 EXACTNESS gate: shared integer endpoints, == not
        # approx — sub-spans telescope to the parent, and the parent
        # matches the ServeResult's own latency decomposition
        assert q["dur"] + bt["dur"] + ex["dur"] == tot["dur"]
        assert q["ts"] == tot["ts"]
        assert tot["dur"] == res.total_s
        assert q["dur"] + bt["dur"] == res.queue_s
        assert ex["dur"] == res.service_s
        # only the parent carries args; sub-spans link by trace_id
        assert tot["args"]["graph"] == res.graph_id
        assert tot["args"]["tier"] == res.tier == "full"
        assert tot["args"]["batch_size"] == res.batch_size
        assert q["args"] == bt["args"] == ex["args"] == {}


SERVE_STAGES = ["serve.build", "serve.put", "serve.launch",
                "serve.device", "serve.pull", "serve.reply"]


def test_service_dispatch_emits_stage_spans_in_order(sym_engine):
    from repro.launch.service import AsyncFGFTService
    tracer = obs.default_tracer()
    fake = FakeAnnotation()
    saved = tracer.annotation
    tracer.annotation = fake
    try:
        svc = AsyncFGFTService(sym_engine, clock=FakeClock(step=1.0),
                               auto_start=False, max_batch=4,
                               name="obs-stages")
        rng = np.random.default_rng(3)
        futs = [svc.submit(g, rng.standard_normal((r, 16)).astype(
            np.float32)) for g, r in ((0, 2), (2, 3), (0, 1))]
        tracer.clear()
        assert svc.drain_once() == 3
        for f in futs:
            f.result(timeout=0)
        svc.close()
    finally:
        tracer.annotation = saved
    serve = [s for s in tracer.spans() if s["name"].startswith("serve.")]
    (disp,) = [s for s in serve if s["name"] == "serve.dispatch"]
    kids = sorted((s for s in serve if s["name"] != "serve.dispatch"),
                  key=lambda s: s["ts"])
    assert [s["name"] for s in kids] == SERVE_STAGES
    assert all(s["cat"] == "serve" for s in serve)
    # the children tile the dispatch in order, inside it
    for a, b in zip(kids, kids[1:]):
        assert a["ts"] + a["dur"] <= b["ts"]
    assert disp["ts"] <= kids[0]["ts"]
    assert kids[-1]["ts"] + kids[-1]["dur"] <= disp["ts"] + disp["dur"]
    # rows 2 + 1 stack on graph 0, 3 land on graph 2: r_pad = 8; the
    # whole bucket's three blocks walk both legs of the full tier
    assert disp["args"] == {
        "tier": "full", "w": 16, "b": 3, "r_pad": 8, "requests": 3,
        "rows": 6, "signal_elements": 6 * 16,
        "block_elements": 3 * 8 * 16,
        "walk_stages": 3 * 2 * sym_engine.tiers["full"]["num_stages"],
        "lowering": "walk"}
    # the profiler mirror saw the same nesting, one span per stage
    names = [(op, name) for op, name, _ in fake.log]
    assert names == ([("enter", "serve.dispatch")]
                     + [(op, n) for n in SERVE_STAGES
                        for op in ("enter", "exit")]
                     + [("exit", "serve.dispatch")])
    assert fake.log[0][2] == disp["args"]


def test_service_stats_embed_obs_snapshot(sym_engine):
    from repro.launch.service import AsyncFGFTService
    svc = AsyncFGFTService(sym_engine, clock=FakeClock(),
                           auto_start=False, name="obs-stats")
    fut = svc.submit(0, np.zeros((1, 16), np.float32))
    svc.drain_once()
    fut.result(timeout=0)
    snap = svc.stats()["obs"]
    svc.close()
    sub = snap["service_requests_total"]["series"]
    mine = [s for s in sub if s["labels"]["service"] == "obs-stats"]
    assert mine and mine[0]["value"] >= 1.0
    stages = snap["service_stage_seconds"]["series"]
    assert any(s["labels"]["service"] == "obs-stats"
               and s["labels"]["stage"] == "execute" for s in stages)


def test_compile_spans_equal_plan_cache_misses(sym_batch48):
    from repro.kernels.plan import clear_plan_cache, plan_cache_stats
    from repro.launch.serve import FGFTServeEngine
    tracer = obs.default_tracer()
    # compiled programs live in the plan cache and are captured at
    # version build, so the engine must be built AFTER the clear for
    # its compiles to register as misses
    clear_plan_cache()
    tracer.clear()
    mats, basis = sym_batch48
    engine = FGFTServeEngine(mats, basis=basis, tiers={"full": 1.0})
    import jax.numpy as jnp
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (3, 2, 16)).astype(np.float32))
    np.asarray(engine.step(x))
    stats = plan_cache_stats()
    events = tracer.spans(cat="compile")
    # the fig15 COMPLETENESS gate: the span and the miss counter are
    # emitted INSIDE the lru-cached builder, so equality holds by
    # construction — and is non-vacuous from a cleared cache
    assert stats["misses"] > 0
    assert len(events) == stats["misses"]
    assert all(e["name"] == "plan_compile" for e in events)
    # an identical second engine finds every plan already compiled:
    # all hits, no new compile spans
    FGFTServeEngine(mats, basis=basis, tiers={"full": 1.0})
    after = plan_cache_stats()
    assert len(tracer.spans(cat="compile")) == after["misses"] \
        == stats["misses"]
    assert after["hits"] > stats["hits"]


def test_refit_decisions_land_in_timeline_and_trace():
    from repro.dynamic.refit import Action, RefitController
    tracer = obs.default_tracer()
    before = len(tracer.spans(name="refit_decision"))
    ctl = RefitController()
    ctl.record(Action.REFRESH, post_drift=0.01, drift=0.5)
    ctl.record(Action.REUSE, post_drift=0.0)
    assert [e["action"] for e in ctl.timeline] == ["refresh", "reuse"]
    events = tracer.spans(name="refit_decision")[before:]
    assert [e["args"]["action"] for e in events] == ["refresh", "reuse"]
    assert events[0]["cat"] == "maintain"
    assert events[0]["args"]["drift"] == 0.5


def test_checkpoint_save_restore_emit_spans(tmp_path):
    from repro.checkpoint.store import restore_checkpoint, save_checkpoint
    tracer = obs.default_tracer()
    saves = len(tracer.spans(name="checkpoint_save"))
    state = {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}
    save_checkpoint(tmp_path, 3, state)
    restored, step, _ = restore_checkpoint(tmp_path, state)
    assert step == 3 and np.array_equal(restored["w"], state["w"])
    (save,) = tracer.spans(name="checkpoint_save")[saves:]
    assert save["cat"] == "checkpoint" and save["args"]["step"] == 3
    assert save["args"]["leaves"] == 1
    (restore,) = tracer.spans(name="checkpoint_restore")[-1:]
    assert restore["cat"] == "checkpoint" and restore["args"]["step"] == 3


def test_export_metrics_accumulates_across_merges(tmp_path, reg):
    # the CI artifact path: export, record more, export again — the
    # on-disk metrics.json folds (counters add), metrics.prom tracks
    obs.counter("obs_test_export_total").inc()
    out = obs.export_metrics(tmp_path)
    first = json.loads(out["json"].read_text())
    v0 = first["obs_test_export_total"]["series"][0]["value"]
    obs.counter("obs_test_export_total").inc(2.0)
    obs.export_metrics(tmp_path)
    second = json.loads((tmp_path / "metrics.json").read_text())
    # merge semantics: old file + new cumulative snapshot
    assert second["obs_test_export_total"]["series"][0]["value"] \
        == v0 + (v0 + 2.0)
    assert "obs_test_export_total" in (tmp_path / "metrics.prom") \
        .read_text()
