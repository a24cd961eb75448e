"""The fleet cache key: a changed program source misses, an unchanged
one hits, and a cached fleet is what the next run loads."""
import pathlib
import shutil

import fleet


def _tree(tmp: pathlib.Path) -> pathlib.Path:
    src = tmp / "src"
    (src / "repro" / "core").mkdir(parents=True)
    (src / "repro" / "__init__.py").write_text("")
    (src / "repro" / "core" / "fit.py").write_text("G = 1\n")
    return src


def _key(src, config=b'{"graphs": []}', kind="TPU v5 lite"):
    return fleet.cache_key(config, src, kind, "0.9.0/0.9.0", False)


def test_unchanged_sources_hit(tmp_path):
    src = _tree(tmp_path)
    assert _key(src) == _key(src)
    copy = tmp_path / "copy"
    shutil.copytree(src, copy / "src")
    assert _key(copy / "src") == _key(src)


def test_changed_source_misses(tmp_path):
    src = _tree(tmp_path)
    before = _key(src)
    (src / "repro" / "core" / "fit.py").write_text("G = 2\n")
    assert _key(src) != before


def test_added_source_config_and_device_miss(tmp_path):
    src = _tree(tmp_path)
    before = _key(src)
    assert _key(src, config=b'{"graphs": [1]}') != before
    assert _key(src, kind="TPU v6 lite") != before
    (src / "repro" / "core" / "new.py").write_text("")
    assert _key(src) != before


def test_non_python_files_do_not_count(tmp_path):
    src = _tree(tmp_path)
    before = _key(src)
    (src / "repro" / "notes.txt").write_text("x")
    assert _key(src) == before


def test_serving_router_fits_once_then_loads(tmp_path, monkeypatch):
    import types

    import graphs
    from repro.launch.serve import RaggedFGFTServeEngine
    root = tmp_path / "checkout"
    shutil.copytree(fleet.CACHE.parents[1] / "src" / "repro",
                    root / "src" / "repro",
                    ignore=shutil.ignore_patterns("__pycache__"))
    monkeypatch.setattr(fleet, "CACHE", tmp_path / "cache")
    saves = []
    real_save = RaggedFGFTServeEngine.save
    monkeypatch.setattr(RaggedFGFTServeEngine, "save",
                        lambda self, d, step=0: saves.append(d)
                        or real_save(self, d, step))
    config = {"graph_seed": 0, "graphs": ["email", "facebook"],
              "rehearse_sizes": [12, 20], "family": "sym",
              "num_transforms": 40, "n_iter": 1,
              "tiers": {"half": 0.5, "full": 1.0}, "bank": "heat",
              "backend": "xla", "precision": "f32", "fused": True}
    laps = [graphs.laplacian(a)
            for a in graphs.config_graphs(config, rehearse=True)]
    ctx = types.SimpleNamespace(root=root, config=config,
                                config_name="tiny", config_bytes=b"tiny",
                                rehearse=True, overrides={})
    first = fleet.serving_router(ctx, laps, "cpu")
    second = fleet.serving_router(ctx, laps, "cpu")
    assert len(saves) == 1
    for w, eng in first.engines.items():
        a = [leaf for leaf in eng.basis.factors]
        b = [leaf for leaf in second.engines[w].basis.factors]
        assert all((x == y).all() for x, y in zip(a, b))
    (root / "src" / "repro" / "__init__.py").write_text("# changed\n")
    fleet.serving_router(ctx, laps, "cpu")
    assert len(saves) == 2
