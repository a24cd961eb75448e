"""JAX's persistent compilation cache, configured in ONE place.

Entry points that compile (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/run.py``) call ``configure_compile_cache()`` before their
first compile, so a second run of the same programs reads them back
instead of compiling the fit, tier and bank programs again.

``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting: it is
used as it is and nothing else is set here.  Otherwise the cache lives at
a fixed path inside the checkout (``<repo>/.jax_cache``, git-ignored).
The path is part of what makes an entry found again, so it is never
built from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def configure_compile_cache(default_dir=DEFAULT_CACHE_DIR) -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    path = pathlib.Path(default_dir)
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)
