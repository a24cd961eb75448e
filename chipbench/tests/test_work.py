"""Work counts come from the algorithm's sizes, never from the staged
tables: two packings of one fitted basis give identical counts."""
import dataclasses

import numpy as np

import graphs
import reference as ref
import work


def _fit():
    from repro.core import ApproxEigenbasis
    cfg = {"graph_seed": 0, "graphs": ["email", "human_protein"],
           "rehearse_sizes": [20, 28], "family": "sym"}
    laps = [graphs.laplacian(a) for a in
            graphs.config_graphs(cfg, rehearse=True)]
    return ApproxEigenbasis.fit(laps, 160, n_iter=1, kind="sym"), laps


def _counts(basis, laps):
    g = basis.num_transforms
    reqs = []
    for gid, lap in enumerate(laps):
        for frac, filters in ((0.25, 0), (0.5, 0), (1.0, 5)):
            k = ref.tier_components(frac, g)
            assert basis.select_tier(fraction=frac)[1] == k
            reqs.append(work.Request(graph=gid, n=lap.shape[0], k=k,
                                     rows=37, filters=filters))
    return work.dispatch_work("sym", reqs)


def test_two_stage_pads_give_identical_counts():
    from repro.core.staging import pack_g_batch_pair
    basis, laps = _fit()
    repacked = []
    for pad in ((4, 8), (16, 64)):
        fwd, bwd = pack_g_batch_pair(basis.factors, basis.n, pad=pad)
        repacked.append(dataclasses.replace(basis, fwd=fwd, bwd=bwd))
    shapes = {b.fwd.idx_i.shape for b in repacked}
    assert len(shapes) == 2, "the two quanta must pack different tables"
    counts = {_counts(b, laps) for b in repacked}
    assert counts == {_counts(basis, laps)}


def test_counts_by_hand():
    # one tier request: 2 legs of 6 flops a factor and a scale of n per
    # row; one bank request of F filters: 1 + F walks and F scales
    n, k, rows, f = 100, 50, 3, 5
    flops, bytes_ = work.dispatch_work(
        "sym", [work.Request(0, n, k, rows, 0)])
    assert flops == rows * (2 * 6 * k + n)
    assert bytes_ == 4 * rows * n * 2 + 2 * 20 * k + 4 * n
    flops, bytes_ = work.dispatch_work(
        "general", [work.Request(0, n, k, rows, f)])
    assert flops == rows * (2 * k + f * (n + 2 * k))
    assert bytes_ == 4 * rows * n * (1 + f) + 2 * 16 * k + 4 * n * f


def test_least_seconds_names_its_bound():
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    assert work.least_seconds(1e12, 1e6, peaks) == (1.0, "flops")
    assert work.least_seconds(1e6, 2e9, peaks) == (2.0, "bytes")


def test_tier_components_match_the_quarters_ladder():
    from repro.core.staging import default_cut_ladder
    for g in (7, 160, 45056, 98304):
        ladder = [int(k) for k in default_cut_ladder(g) if k]
        for frac in (0.25, 0.5, 1.0, 0.3, 0.9):
            want = min(ladder, key=lambda k: (abs(k - frac * g), -k))
            assert ref.tier_components(frac, g) == want
    assert np.isclose(ref.tier_components(0.25, 98304), 24576)
