"""The trace reduction and the peaks table, against a small trace
recorded on a TPU v5e (``data/serve_v5e.xplane.pb``: a tiny fleet served
through the front door, 0.4 s traced inside the benchmark's window
span)."""
import pathlib

import pytest

import harness
import reduce as red

TRACE = pathlib.Path(__file__).parent / "data" / "serve_v5e.xplane.pb"


@pytest.fixture(scope="module")
def reduction():
    return red.reduce_profile(str(TRACE))


def _device_ops():
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(TRACE))
    host = [e for p in pd.planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events
            if e.name == red.WINDOW_SPAN]
    lo = host[0].start_ns
    hi = lo + host[0].duration_ns
    ops = [(max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi))
           for p in pd.planes if p.name.startswith("/device:TPU")
           for ln in p.lines if ln.name == red.OPS_LINE for e in ln.events]
    return lo, hi, [(a, b) for a, b in ops if b > a]


def test_busy_is_the_union_of_device_ops(reduction):
    lo, hi, ops = _device_ops()
    assert ops, "the recorded trace holds device operations"
    # an independent union: sweep the sorted endpoints
    events = sorted([(a, 1) for a, _ in ops] + [(b, -1) for _, b in ops])
    depth, busy, last = 0, 0.0, None
    for t, d in events:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    assert reduction.busy_s == pytest.approx(busy * 1e-9, rel=1e-9)
    assert reduction.window_s == pytest.approx((hi - lo) * 1e-9)
    assert 0 < reduction.busy_s < reduction.window_s
    assert reduction.devices == 1


def test_top_ops_and_gaps(reduction):
    secs = [s for _, s in reduction.top_ops]
    assert secs == sorted(secs, reverse=True) and len(secs) <= 10
    gaps = [s for _, s in reduction.idle_gaps]
    assert gaps == sorted(gaps, reverse=True) and len(gaps) <= 10
    assert sum(gaps) <= reduction.window_s - reduction.busy_s + 1e-9
    assert all(isinstance(label, str) and label for label, _ in
               reduction.idle_gaps)
    assert sum(reduction.modules.values()) > 0


def test_peaks_table():
    peaks = harness.load_peaks("TPU v5 lite", rehearse=False)
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in peaks["source"]
    with pytest.raises(KeyError):
        harness.load_peaks("TPU v9 imaginary", rehearse=False)
