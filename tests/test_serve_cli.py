"""Serve CLI smoke matrix: ``serve.main(argv)`` end-to-end over the flag
combinations users actually type (several were previously exercised only
by benchmarks).  Every cell uses the same tiny fleet so jitted programs
are shared across cells where shapes allow; the heavy combinations carry
the ``slow`` marker to keep the fast CI tier inside its budget."""
import numpy as np
import pytest

from repro.launch import serve

# one tiny fleet shared by every uniform cell: 2 graphs, n=12, g=24
BASE = ["--graphs", "2", "--graph-n", "12", "--transforms",
        "24", "--filter-steps", "2", "--signals", "3", "--seed", "0"]
RAGGED = ["--ragged", "--graphs", "3", "--graph-sizes", "6,12"]
ASYNC = ["--serve-async", "--load-requests", "12", "--load-workers", "2",
         "--max-batch", "4"]
slow = pytest.mark.slow


@pytest.mark.parametrize("extra", [
    pytest.param([], id="base"),
    pytest.param(["--tiers", "full:1.0,draft:0.5"], id="tiers"),
    pytest.param(["--backend", "pallas"], id="pallas",
                 marks=slow),
    pytest.param(["--directed"], id="directed", marks=slow),
])
def test_cli_tiered_serving(extra):
    out = serve.main(BASE + extra)
    assert np.all(np.isfinite(out["rel_error"]))
    assert out["transforms_per_s"] > 0
    tiers = out["tiers"]
    assert set(tiers) == ({"full", "draft"} if "--tiers" in extra
                          else {"full", "balanced", "draft"})
    for ts in tiers.values():
        assert ts["num_transforms"] >= 1
    assert out["kind"] == ("general" if "--directed" in extra else "sym")


@pytest.mark.parametrize("extra", [
    pytest.param([], id="bank"),
    pytest.param(RAGGED, id="ragged-bank", marks=slow),
])
def test_cli_filter_bank(extra):
    out = serve.main(BASE + ["--filter", "heat,lowpass"] + extra)
    assert out["responses_per_s"] > 0
    if "--ragged" in extra:
        assert out["buckets"] == [8, 16]
    else:
        assert list(out["filters"]) == ["heat", "lowpass"]


def test_cli_ragged():
    out = serve.main(BASE + RAGGED)
    assert out["sizes"] == [6, 12, 6]
    assert out["buckets"] == [8, 16]
    assert np.all(np.isfinite(out["rel_error"]))


@pytest.mark.parametrize("extra", [
    pytest.param([], id="uniform", marks=slow),
    pytest.param(RAGGED, id="ragged", marks=slow),
])
def test_cli_dynamic(extra):
    out = serve.main(BASE + ["--dynamic", "--update-rounds", "2",
                             "--churn", "0.05"] + extra)
    assert len(out["actions"]) == 2
    assert all(np.asarray(out["versions"]) >= 0)


@pytest.mark.parametrize("extra", [
    pytest.param([], id="closed-loop"),
    pytest.param(["--qps", "300"], id="open-loop", marks=slow),
    pytest.param(["--filter", "heat,lowpass"], id="bank", marks=slow),
    pytest.param(["--tiers", "full:1.0,draft:0.5"], id="tiers",
                 marks=slow),
    pytest.param(["--dynamic", "--update-rounds", "2",
                  "--maintain-interval", "0.02", "--churn", "0.05"],
                 id="dynamic", marks=slow),
    pytest.param(RAGGED, id="ragged", marks=slow),
])
def test_cli_serve_async(extra):
    out = serve.main(BASE + ASYNC + extra)
    assert out["results"] == 12
    assert out["qps"] > 0
    stats = out["stats"]
    assert stats["served"] == 12 and stats["errors"] == 0
    assert stats["dispatches"] >= 1
    label = "bank" if "--filter" in extra else None
    keys = stats["latency"].keys()
    if label:
        assert f"{label}/total" in keys
    else:
        assert any(k.endswith("/total") for k in keys)
    assert out["versions"] and all(v >= 0 for v in out["versions"])
    assert stats["maintain"]["enabled"] == ("--dynamic" in extra)


def test_cli_serve_async_tight_queue_warmup():
    """Regression: the pre-load warmup burst (graphs x tiers requests) is
    bigger than a tight --max-queue; it must drain and resubmit on shed
    instead of crashing before the timed load starts."""
    out = serve.main(BASE + ASYNC + ["--max-queue", "2"])
    assert out["results"] == 12
    assert out["stats"]["errors"] == 0


def test_cli_serve_async_implies_fgft():
    """The CLI serves graphs and nothing else: no flag picks the mode
    between an LM and the FGFT service, bare arguments give the graph
    defaults, and the LM's flags are rejected."""
    args = serve.parse_args([])
    assert (args.graphs, args.graph_n, args.transforms) == (8, 64, 0)
    assert args.tier_map == serve.DEFAULT_TIERS
    assert not (args.ragged or args.dynamic or args.serve_async
                or args.directed or args.filter)
    assert serve.parse_args(["--serve-async"]).serve_async
    assert serve.parse_args(["--dynamic"]).dynamic
    for gone in (["--arch", "qwen2-1.5b"], ["--fgft"], ["--smoke"],
                 ["--requests", "4"], ["--batch-slots", "2"],
                 ["--prompt-len", "8"], ["--gen-len", "4"],
                 ["--max-len", "32"]):
        with pytest.raises(SystemExit):
            serve.parse_args(gone)


def test_cli_rejects_bad_tier_spec():
    with pytest.raises(SystemExit):
        serve.parse_args(["--graph-sizes", "6,oops"])
    with pytest.raises(ValueError):
        serve.parse_tiers("full")            # missing fraction
    with pytest.raises(ValueError):
        serve.parse_tiers("full:1.0,full:0.5")
