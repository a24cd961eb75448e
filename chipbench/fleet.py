"""A configuration's fitted fleet, cached inside the checkout.

The first serving run of a configuration in a checkout fits the fleet
through ``RaggedFGFTServeEngine`` and writes it with ``save`` to
``chipbench/.cache/fleets/<config>-<key>``; every run, that one
included, serves a router that ``RaggedFGFTServeEngine.load`` restored
from there: the path a restarted server takes.  The key covers the
configuration file's bytes, every ``src/repro/**/*.py``, the jax and
jaxlib versions, the device kind and the rehearsal flag, so a fit made
by other code, on another device or at other sizes is never read.
"""
from __future__ import annotations

import hashlib
import pathlib
import shutil

from harness import CACHE, log, now

SOURCES = "repro/**/*.py"


def cache_key(config_bytes: bytes, src_root: pathlib.Path,
              device_kind: str, versions: str, rehearse: bool) -> str:
    h = hashlib.sha256()
    h.update(config_bytes)
    for path in sorted(pathlib.Path(src_root).glob(SOURCES)):
        h.update(str(path.relative_to(src_root)).encode())
        h.update(path.read_bytes())
    h.update(f"{versions}|{device_kind}|{int(rehearse)}".encode())
    return h.hexdigest()[:20]


def fleet_dir(ctx, device_kind: str) -> pathlib.Path:
    import jax
    import jaxlib
    key = cache_key(ctx.config_bytes, ctx.root / "src", device_kind,
                    f"{jax.__version__}/{jaxlib.__version__}",
                    ctx.rehearse)
    return CACHE / "fleets" / f"{ctx.config_name}-{key}"


def fit_kwargs(config: dict) -> dict:
    """RaggedFGFTServeEngine arguments a configuration states."""
    return dict(num_transforms=int(config["num_transforms"]),
                n_iter=int(config["n_iter"]), kind=config["family"],
                filters=config["bank"], tiers=dict(config["tiers"]),
                backend=config["backend"], precision=config["precision"],
                fused=bool(config["fused"]))


def serving_router(ctx, laps, device_kind: str):
    """The configuration's router, restored from the fleet cache (fitted
    and saved first when the cache has no entry)."""
    from repro.launch.serve import RaggedFGFTServeEngine
    path = fleet_dir(ctx, device_kind)
    if not (path / "router.json").is_file():
        t0 = now()
        partial = path.with_name(path.name + ".partial")
        shutil.rmtree(partial, ignore_errors=True)
        router = RaggedFGFTServeEngine(laps, **fit_kwargs(ctx.config))
        log(f"fitted the fleet in {now() - t0:.2f}s "
            f"(per bucket: {router.onboard_seconds})")
        router.save(partial)
        del router
        partial.rename(path)
    t0 = now()
    router = RaggedFGFTServeEngine.load(
        path, backend=ctx.config["backend"],
        precision=ctx.overrides.get("precision", ctx.config["precision"]))
    log(f"loaded the fleet from {path.name} in {now() - t0:.2f}s")
    return router
