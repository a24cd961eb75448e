"""The readers of the dispatcher's spans (``dispatch_spans.py``,
``metrics/dispatch_fill_pct.py``, ``metrics/dispatch_host_ms.py``): on
hand-made span rings, on a tiny fleet served through the front door on
the CPU, and in the merged checkout the other tests run."""
import numpy as np
import pytest

import dispatch_spans
import harness
from repro.obs.trace import Tracer


def _reader(name):
    return harness.import_path(harness.HERE / "metrics" / f"{name}.py",
                               name)


def _dispatch(tr, t0, tid, signal, block, stages):
    """One dispatch span starting at ``t0`` with back-to-back children
    of the given durations, recorded as the program records them."""
    t = t0
    for name, dur in stages:
        tr.add_span(f"serve.{name}", t, t + dur, cat="serve", tid=tid)
        t += dur
    tr.add_span("serve.dispatch", t0, t, cat="serve", tid=tid,
                args={"signal_elements": signal, "block_elements": block})


@pytest.fixture
def ring():
    tr = Tracer()
    tr.add_span("request", 0.0, 1.0)            # spans of other kinds
    _dispatch(tr, 10.0, 1, 30, 100, [("build", 1.0), ("device", 3.0),
                                      ("pull", 2.0)])
    _dispatch(tr, 16.0, 1, 60, 100, [("device", 1.0), ("reply", 1.0)])
    _dispatch(tr, 11.0, 2, 10, 50, [("device", 2.0)])   # another thread
    _dispatch(tr, 40.0, 1, 99, 100, [("device", 1.0)])  # after the window
    return tr


def test_dispatches_in_a_window_with_their_stages(ring):
    got = dispatch_spans.dispatches(ring, 9.0, 20.0)
    assert [(d["ts"], d["dur"]) for d in got] == [(10.0, 6.0), (11.0, 2.0),
                                                 (16.0, 2.0)]
    assert got[0]["stages"] == {"serve.build": 1.0, "serve.device": 3.0,
                                "serve.pull": 2.0}
    assert got[1]["stages"] == {"serve.device": 2.0}
    assert got[2]["stages"] == {"serve.device": 1.0, "serve.reply": 1.0}
    assert got[0]["args"] == {"signal_elements": 30, "block_elements": 100}
    assert dispatch_spans.dispatches(Tracer(), 0.0, 1.0) == []


def test_a_ring_that_lost_the_window_start_raises(monkeypatch):
    tr = Tracer(capacity=4)
    for k in range(3):
        _dispatch(tr, 10.0 + 5 * k, 1, 1, 2, [("device", 1.0)])
    assert len(tr) == 4
    with pytest.raises(dispatch_spans.RingOverrun):
        dispatch_spans.dispatches(tr, 9.0, 30.0)
    assert len(dispatch_spans.dispatches(tr, 16.0, 30.0)) == 1
    monkeypatch.setattr(dispatch_spans, "program_tracer", lambda: tr)
    obs = harness.Observations(window=(9.0, 30.0),
                               trace_window=(9.0, 30.0))
    for name in ("dispatch_fill_pct", "dispatch_host_ms"):
        with pytest.raises(dispatch_spans.RingOverrun):
            _reader(name).read(obs)


def test_readers_on_a_hand_made_ring(ring, monkeypatch):
    monkeypatch.setattr(dispatch_spans, "program_tracer", lambda: ring)
    obs = harness.Observations(window=(9.0, 20.0),
                               trace_window=(15.0, 20.0))
    fill = _reader("dispatch_fill_pct").read
    host = _reader("dispatch_host_ms").read
    assert fill(obs) == pytest.approx(100.0 * (30 + 60 + 10) / 250)
    # the slice holds the dispatch at 16 s alone: 2 s less 1 s on device
    assert host(obs) == pytest.approx(1e3 * 1.0)
    obs.trace_window = (9.0, 20.0)
    assert host(obs) == pytest.approx(1e3 * (3.0 + 0.0 + 1.0) / 3)
    # an untraced run, or a program that records no dispatch spans
    assert host(harness.Observations(window=(9.0, 20.0))) is None
    monkeypatch.setattr(dispatch_spans, "program_tracer", Tracer)
    assert fill(obs) is None and host(obs) is None


def test_fill_from_spans_equals_the_service_counters():
    from repro import obs as program_obs
    from repro.launch.serve import RaggedFGFTServeEngine
    from repro.launch.service import AsyncFGFTService

    def sym(n, seed):
        x = np.random.default_rng(seed).standard_normal((n, n))
        return (x + x.T).astype(np.float32)

    engine = RaggedFGFTServeEngine([sym(5, 0), sym(6, 1), sym(7, 2),
                                    sym(12, 3)], 12, n_iter=1,
                                   tiers={"full": 1.0})
    tracer = program_obs.default_tracer()
    svc = AsyncFGFTService(engine, auto_start=False, max_batch=4,
                           name="chipbench-fill")
    rng = np.random.default_rng(0)
    t0 = harness.now()
    futs = [svc.submit(g, rng.standard_normal(
        (r, engine.sizes[g])).astype(np.float32))
        for g, r in ((0, 3), (2, 5), (1, 9), (3, 2), (0, 4), (0, 1))]
    while svc.drain_once():
        pass
    for f in futs:
        f.result(timeout=0)
    st = svc.stats()
    svc.close()
    obs = harness.Observations(window=(t0, harness.now()))
    spans = dispatch_spans.dispatches(tracer, *obs.window)
    assert len(spans) == st["dispatches"] == 3
    assert _reader("dispatch_fill_pct").read(obs) == pytest.approx(
        100.0 * st["signal_elements"] / st["block_elements"])


@pytest.mark.parametrize("cell", ["sym-bulk", "dir-bulk"])
def test_merged_checkout_lists_each_metric_once(bench_root, cell):
    """sym-bulk reports the dispatch metrics; dir-bulk, still pending,
    keeps the metrics its pending entries list."""
    spec = harness.load_json(bench_root / "BENCHMARK.json")
    harness.cell_files(bench_root, spec, cell)
    traced = [m["name"] for m in harness.cell_metrics(spec, cell, True)]
    assert len(traced) == len(set(traced))
    assert "device_idle_pct.serve" in traced
    assert ({"dispatch_fill_pct", "dispatch_host_ms"} <= set(traced)) \
        == (cell == "sym-bulk")
    plain = [m["name"] for m in harness.cell_metrics(spec, cell, False)]
    assert sorted(plain) == ["p95_ms", "setup_s", "signals_per_s"]
