"""pjit step builders: train_step / prefill_step / decode_step with full
sharding annotations, remat-scan layers, donation, and the optional
butterfly gradient compression with error feedback.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import transformer as tfm
from repro.models.common import ModelConfig, set_batch_axes
from repro.optim import adamw, compress
from . import lm_sharding as shd
from .sharding import dp_axes


class TrainState(NamedTuple):
    params: Any
    opt: adamw.AdamWState
    ef_err: Optional[Any] = None  # error-feedback buffers (compression on)


class StepBundle(NamedTuple):
    """Everything the launcher / dry-run needs for one jitted step."""
    fn: Any                 # the jitted function
    state_shardings: Any
    batch_shardings: Any
    abstract_state: Any
    abstract_batch: Any


def _state_shardings(cfg: ModelConfig, mesh: Mesh, rules,
                     use_compression: bool):
    axes_params, _ = tfm.init_params(cfg, mode="axes")
    p_sh = shd.sharding_tree(axes_params, mesh, rules)
    opt_sh = adamw.AdamWState(
        step=NamedSharding(mesh, P()),
        mu=jax.tree.map(lambda s: s, p_sh),
        nu=jax.tree.map(lambda s: s, p_sh))
    ef_sh = jax.tree.map(lambda s: s, p_sh) if use_compression else None
    return TrainState(p_sh, opt_sh, ef_sh)


def abstract_train_state(cfg: ModelConfig, use_compression: bool = False,
                         moment_dtype=jnp.float32):
    params, _ = tfm.init_params(cfg, abstract=True)
    opt = adamw.init_abstract(params, moment_dtype)
    ef = compress.init_error_abstract(params) if use_compression else None
    return TrainState(params, opt, ef)


def concrete_train_state(cfg: ModelConfig, key, mesh=None, shardings=None,
                         use_compression: bool = False,
                         moment_dtype=jnp.float32):
    params, _ = tfm.init_params(cfg, key)
    opt = adamw.init(params, moment_dtype)
    ef = compress.init_error(params) if use_compression else None
    state = TrainState(params, opt, ef)
    if shardings is not None:
        state = jax.device_put(state, shardings)
    return state


def _train_batch_abstract(cfg: ModelConfig, seq_len: int, global_batch: int):
    batch = {"tokens": jax.ShapeDtypeStruct((global_batch, seq_len),
                                            jnp.int32)}
    if cfg.family == "vlm":
        batch["memory"] = jax.ShapeDtypeStruct(
            (global_batch, cfg.num_patches, cfg.d_model), jnp.bfloat16)
    elif cfg.family == "audio":
        batch["memory"] = jax.ShapeDtypeStruct(
            (global_batch, max(seq_len // cfg.enc_ratio, 1), cfg.d_model),
            jnp.bfloat16)
    return batch


def input_specs(cfg: ModelConfig, seq_len: int, global_batch: int,
                mode: str = "train"):
    """ShapeDtypeStruct stand-ins for every model input of a cell."""
    if mode in ("train", "prefill"):
        return _train_batch_abstract(cfg, seq_len, global_batch)
    batch = {"token": jax.ShapeDtypeStruct((global_batch, 1), jnp.int32),
             "pos": jax.ShapeDtypeStruct((global_batch,), jnp.int32)}
    if cfg.family == "vlm":
        batch["memory"] = jax.ShapeDtypeStruct(
            (global_batch, cfg.num_patches, cfg.d_model), jnp.bfloat16)
    elif cfg.family == "audio":
        batch["memory"] = jax.ShapeDtypeStruct(
            (global_batch, max(seq_len // cfg.enc_ratio, 1), cfg.d_model),
            jnp.bfloat16)
    return batch




def _batch_axes_of(rules, mesh):
    """(axes, total) for set_batch_axes from the batch rule."""
    r = rules.get("batch")
    if not r:
        return None, 1
    axes = r if isinstance(r, tuple) else (r,)
    total = int(np.prod([mesh.shape[a] for a in axes]))
    return axes, total


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, mesh: Mesh, *, seq_len: int,
                    global_batch: int, fsdp: bool = False,
                    peak_lr: float = 3e-4, warmup: int = 100,
                    total_steps: int = 10_000, weight_decay: float = 0.1,
                    grad_compress_ratio: float = 0.0,
                    moment_dtype=jnp.float32,
                    donate: bool = True) -> StepBundle:
    rules = shd.make_rules(mesh, cfg, fsdp=fsdp, global_batch=global_batch)
    use_comp = grad_compress_ratio > 0
    state_sh = _state_shardings(cfg, mesh, rules, use_comp)
    batch_sh = shd.batch_sharding(
        mesh, rules, with_memory=cfg.family in ("vlm", "audio"),
        mode="train")
    spec = (compress.make_spec(ratio=grad_compress_ratio)
            if use_comp else None)

    bx_axes, bx_total = _batch_axes_of(rules, mesh)
    model_n = mesh.shape.get("model", 1)

    def step(state: TrainState, batch):
        set_batch_axes(bx_axes, bx_total, model_n)  # trace-time

        def loss_of(p):
            return tfm.loss_fn(p, cfg, batch)

        (loss, metrics), grads = jax.value_and_grad(
            loss_of, has_aux=True)(state.params)
        ef_err = state.ef_err
        if use_comp:
            # EF butterfly compression; on a multi-pod mesh the compact
            # coefficients are what conceptually crosses pods (DESIGN.md §3)
            grads, ef_err = compress.tree_ef_compress(
                spec, grads, ef_err, step=state.opt.step)
        lr = adamw.warmup_cosine(state.opt.step, peak_lr=peak_lr,
                                 warmup=warmup, total=total_steps)
        new_params, new_opt, om = adamw.update(
            grads, state.opt, state.params, lr=lr,
            weight_decay=weight_decay)
        metrics = dict(metrics)
        metrics.update(om)
        metrics["lr"] = lr
        return TrainState(new_params, new_opt, ef_err), metrics

    jitted = jax.jit(
        step,
        in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, None),
        donate_argnums=(0,) if donate else (),
    )
    return StepBundle(jitted, state_sh, batch_sh,
                      abstract_train_state(cfg, use_comp, moment_dtype),
                      _train_batch_abstract(cfg, seq_len, global_batch))


# ---------------------------------------------------------------------------
# Serve steps (prefill + decode)
# ---------------------------------------------------------------------------

def make_prefill_step(cfg: ModelConfig, mesh: Mesh, *, seq_len: int,
                      global_batch: int, fsdp: bool = False) -> StepBundle:
    seq_shard = global_batch < int(np.prod(
        [mesh.shape[a] for a in dp_axes(mesh)]))
    rules = shd.make_rules(mesh, cfg, fsdp=fsdp, seq_shard=seq_shard,
                           global_batch=global_batch)
    axes_params, _ = tfm.init_params(cfg, mode="axes")
    p_sh = shd.sharding_tree(axes_params, mesh, rules)
    batch_sh = shd.batch_sharding(
        mesh, rules, with_memory=cfg.family in ("vlm", "audio"),
        mode="prefill")
    cache_ax, _ = tfm.init_cache(cfg, global_batch, seq_len, mode="axes")
    cache_sh = shd.sharding_tree(cache_ax, mesh, rules)

    bx_axes, bx_total = _batch_axes_of(rules, mesh)
    model_n = mesh.shape.get("model", 1)

    def fn(params, cache, batch):
        set_batch_axes(bx_axes, bx_total, model_n)
        logits, new_cache, memory = tfm.prefill(params, cfg, cache, batch)
        return logits, new_cache

    jitted = jax.jit(fn, in_shardings=(p_sh, cache_sh, batch_sh),
                     out_shardings=None, donate_argnums=(1,))
    abstract_cache, _ = tfm.init_cache(cfg, global_batch, seq_len,
                                       abstract=True)
    abstract_params, _ = tfm.init_params(cfg, abstract=True)
    return StepBundle(jitted, (p_sh, cache_sh), batch_sh,
                      (abstract_params, abstract_cache),
                      input_specs(cfg, seq_len, global_batch, "prefill"))


def make_decode_step(cfg: ModelConfig, mesh: Mesh, *, seq_len: int,
                     global_batch: int, fsdp: bool = False) -> StepBundle:
    """serve_step: one new token against a KV cache of length seq_len."""
    seq_shard = global_batch < int(np.prod(
        [mesh.shape[a] for a in dp_axes(mesh)]))
    rules = shd.make_rules(mesh, cfg, fsdp=fsdp, seq_shard=seq_shard,
                           global_batch=global_batch)
    axes_params, _ = tfm.init_params(cfg, mode="axes")
    p_sh = shd.sharding_tree(axes_params, mesh, rules)
    cache_ax, _ = tfm.init_cache(cfg, global_batch, seq_len, mode="axes")
    cache_sh = shd.sharding_tree(cache_ax, mesh, rules)
    batch_sh = shd.batch_sharding(
        mesh, rules, with_memory=cfg.family in ("vlm", "audio"),
        mode="decode")

    bx_axes, bx_total = _batch_axes_of(rules, mesh)
    model_n = mesh.shape.get("model", 1)

    def fn(params, cache, batch):
        set_batch_axes(bx_axes, bx_total, model_n)
        return tfm.decode_step(params, cfg, cache, batch)

    jitted = jax.jit(fn, in_shardings=(p_sh, cache_sh, batch_sh),
                     out_shardings=None, donate_argnums=(1,))
    abstract_params, _ = tfm.init_params(cfg, abstract=True)
    abstract_cache, _ = tfm.init_cache(cfg, global_batch, seq_len,
                                       abstract=True)
    return StepBundle(jitted, (p_sh, cache_sh), batch_sh,
                      (abstract_params, abstract_cache),
                      input_specs(cfg, seq_len, global_batch, "decode"))
