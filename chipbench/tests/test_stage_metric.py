"""``metrics/device_us_per_stage.py``: the device time of the traced slice
over the walk stages its dispatches recorded, on a hand-made span ring
and reduction, from a program that records no ``walk_stages`` (none),
and on a tiny directed fleet served through the front door on the CPU."""
import numpy as np
import pytest

import dispatch_spans
import harness
import reduce as red
from repro.obs.trace import Tracer


def _reader():
    return harness.import_path(
        harness.HERE / "metrics" / "device_us_per_stage.py",
        "device_us_per_stage")


def _reduction(busy_s, window_s=2.0):
    return red.Reduction(busy_s=busy_s, window_s=window_s, modules={},
                         top_ops=[], idle_gaps=[], devices=1)


def _dispatch(tr, t0, dur, args):
    tr.add_span("serve.device", t0, t0 + dur, cat="serve", tid=1)
    tr.add_span("serve.dispatch", t0, t0 + dur, cat="serve", tid=1,
                args=args)


@pytest.fixture
def ring():
    tr = Tracer()
    tr.add_span("request", 0.0, 1.0)
    _dispatch(tr, 10.0, 1.0, {"b": 2, "walk_stages": 1000})
    _dispatch(tr, 12.0, 1.0, {"b": 1, "walk_stages": 600})
    _dispatch(tr, 15.0, 1.0, {"b": 1, "walk_stages": 400})
    _dispatch(tr, 40.0, 1.0, {"b": 3, "walk_stages": 9999})  # outside
    return tr


def test_busy_time_over_the_slice_stages(ring, monkeypatch):
    monkeypatch.setattr(dispatch_spans, "program_tracer", lambda: ring)
    obs = harness.Observations(trace_window=(9.0, 20.0),
                               reduction=_reduction(0.02))
    assert _reader().read(obs) == pytest.approx(1e6 * 0.02 / 2000)
    # the slice holds the middles of the last two dispatches alone
    obs.trace_window = (12.0, 20.0)
    assert _reader().read(obs) == pytest.approx(1e6 * 0.02 / 1000)


@pytest.mark.parametrize("case", ["untraced", "no-device", "idle",
                                  "no-arg", "no-dispatch"])
def test_none_where_there_is_nothing_to_read(ring, monkeypatch, case):
    tracer = ring
    obs = harness.Observations(trace_window=(9.0, 20.0),
                               reduction=_reduction(0.02))
    if case == "untraced":
        obs = harness.Observations()
    elif case == "no-device":
        obs.reduction = None
    elif case == "idle":
        obs.reduction = _reduction(0.0)
    elif case == "no-arg":
        # a program that records the dispatches without the arg
        tracer = Tracer()
        _dispatch(tracer, 10.0, 1.0, {"b": 2, "block_elements": 64})
    else:
        tracer = Tracer()
    monkeypatch.setattr(dispatch_spans, "program_tracer", lambda: tracer)
    assert _reader().read(obs) is None


def test_a_ring_that_lost_the_slice_start_raises(monkeypatch):
    tr = Tracer(capacity=4)
    for k in range(3):
        _dispatch(tr, 10.0 + 5 * k, 1.0, {"walk_stages": 10})
    monkeypatch.setattr(dispatch_spans, "program_tracer", lambda: tr)
    obs = harness.Observations(trace_window=(9.0, 30.0),
                               reduction=_reduction(0.01))
    with pytest.raises(dispatch_spans.RingOverrun):
        _reader().read(obs)


def test_stages_from_a_served_directed_fleet():
    """The reader divides by the stages the program's engines state:
    every dispatch's ``walk_stages`` is its blocks times both legs of
    its tier's (or the bank's) tables."""
    from repro.launch.serve import RaggedFGFTServeEngine
    from repro.launch.service import AsyncFGFTService

    def directed(n, seed):
        a = (np.random.default_rng(seed).uniform(size=(n, n)) < 0.3)
        a = np.triu(a, 1).astype(np.float32)
        return np.diag(a.sum(axis=1)) - a

    engine = RaggedFGFTServeEngine([directed(5, 0), directed(6, 1),
                                    directed(12, 2)], 12, n_iter=1,
                                   kind="general",
                                   tiers={"full": 1.0, "half": 0.5},
                                   filters="heat,lowpass")
    svc = AsyncFGFTService(engine, auto_start=False, max_batch=4,
                           name="chipbench-stages")
    rng = np.random.default_rng(0)
    t0 = harness.now()
    asks = [(0, "half", False), (1, "half", False), (2, None, True),
            (0, "full", False)]
    futs = [svc.submit(g, rng.standard_normal(
        (3, engine.sizes[g])).astype(np.float32), tier=tier, bank=bank)
        for g, tier, bank in asks]
    while svc.drain_once():
        pass
    for f in futs:
        f.result(timeout=0)
    svc.close()
    window = (t0, harness.now())
    spans = dispatch_spans.dispatches(dispatch_spans.program_tracer(),
                                      *window)
    # (w 8, half) walks graphs 0 and 1, (w 16, bank) graph 2 and (w 8,
    # full) the width-8 bucket whole
    assert [(d["args"]["w"], d["args"]["tier"], d["args"]["b"])
            for d in spans] == [(8, "half", 2), (16, "bank", 1),
                                (8, "full", 2)]
    want = 0
    for d in spans:
        eng = engine.engines[d["args"]["w"]]
        tier = d["args"]["tier"]
        stages = (eng.basis.fwd.num_stages if tier == "bank"
                  else eng.tiers[tier]["num_stages"])
        assert d["args"]["walk_stages"] == d["args"]["b"] * 2 * stages
        want += d["args"]["walk_stages"]
    obs = harness.Observations(trace_window=window,
                               reduction=_reduction(0.003))
    assert _reader().read(obs) == pytest.approx(1e6 * 0.003 / want)
