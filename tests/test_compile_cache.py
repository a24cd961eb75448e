"""The persistent compilation cache is configured in one helper."""
import jax

from repro.launch import compile_cache as cc


def _restore(previous):
    jax.config.update("jax_compilation_cache_dir", previous)


def test_environment_setting_is_used_as_is(tmp_path, monkeypatch):
    monkeypatch.setenv(cc.CACHE_ENV, str(tmp_path / "from_env"))
    before = jax.config.jax_compilation_cache_dir
    assert cc.configure_compile_cache(tmp_path / "unused") == str(
        tmp_path / "from_env")
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "unused").exists()


def test_default_is_a_fixed_path_in_the_checkout(tmp_path, monkeypatch):
    monkeypatch.delenv(cc.CACHE_ENV, raising=False)
    assert cc.DEFAULT_CACHE_DIR == cc.REPO_ROOT / ".jax_cache"
    assert (cc.REPO_ROOT / "src" / "repro").is_dir()
    before = jax.config.jax_compilation_cache_dir
    try:
        path = cc.configure_compile_cache(tmp_path / "cache")
        assert path == str(tmp_path / "cache")
        assert (tmp_path / "cache").is_dir()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        _restore(before)
