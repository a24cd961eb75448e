"""pjit step builders: train_step / prefill_step / decode_step with full
sharding annotations, remat-scan layers, donation, and the optional
butterfly gradient-compression path (cross-pod, shard_map psum).
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import transformer as tfm
from repro.models.common import Axes, ModelConfig, set_batch_axes
from repro.optim import adamw, compress
from . import sharding as shd


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
# Compressed cross-pod training step (the paper's operator as a
# distributed-optimization feature, DESIGN.md §3)
# ---------------------------------------------------------------------------

def make_pod_compressed_train_step(
        cfg: ModelConfig, mesh: Mesh, *, seq_len: int, global_batch: int,
        fsdp: bool = False, compress_ratio: float = 0.125,
        moment_dtype=jnp.float32, peak_lr: float = 3e-4, warmup: int = 100,
        total_steps: int = 10_000, weight_decay: float = 0.1) -> StepBundle:
    """Train step whose CROSS-POD gradient reduction runs in the compressed
    butterfly basis with error feedback.

    The pod axis is made manual with a partial shard_map: each pod computes
    gradients of its own half of the global batch (data/model axes stay
    GSPMD-automatic), then only the compact coefficient blocks are
    psum'ed across pods — cross-pod all-reduce bytes drop by ~1/ratio.
    Error-feedback buffers are per-pod (leading (npod,) dim).
    """
    assert "pod" in mesh.axis_names, "multi-pod mesh required"
    npod = mesh.shape["pod"]
    rules = shd.make_rules(mesh, cfg, fsdp=fsdp, global_batch=global_batch)
    state_sh = _state_shardings(cfg, mesh, rules, use_compression=False)
    axes_params, _ = tfm.init_params(cfg, mode="axes")
    # per-leaf data/model specs (grads share the params' shardings)
    leaf_specs = jax.tree.map(
        lambda a: shd.spec_for(a.axes, rules), axes_params,
        is_leaf=lambda x: isinstance(x, Axes))
    ef_sh = jax.tree.map(
        lambda a: NamedSharding(mesh, P("pod", *shd.spec_for(a.axes,
                                                             rules))),
        axes_params, is_leaf=lambda x: isinstance(x, Axes))
    state_sh = TrainState(state_sh.params, state_sh.opt, ef_sh)
    batch_sh = shd.batch_sharding(
        mesh, rules, with_memory=cfg.family in ("vlm", "audio"),
        mode="train")
    spec = compress.make_spec(ratio=compress_ratio)

    abstract_params, _ = tfm.init_params(cfg, abstract=True)
    p_specs = jax.tree.map(lambda _: P(), abstract_params)
    ef_pod_specs = jax.tree.map(lambda _: P("pod"), abstract_params)
    b_specs = {"tokens": P("pod")}
    if cfg.family in ("vlm", "audio"):
        b_specs["memory"] = P("pod")

    def compress_reduce(grads, ef, step):
        """Per-CHIP shard-local EF compression; only compact coefficient
        blocks cross pods (nested fully-manual shard_map: data/model
        become manual here so each chip compresses its own shard)."""
        def local(g, e, s):
            return compress.tree_ef_compress(
                spec, g, e,
                reduce_fn=lambda c: lax.psum(c, "pod") / npod, step=s)

        # mesh omitted: inherits the context mesh, whose pod axis is
        # already Manual from the enclosing shard_map
        return jax.shard_map(
            local, axis_names={"data", "model"},
            in_specs=(leaf_specs, leaf_specs, P()),
            out_specs=(leaf_specs, leaf_specs),
            check_vma=False)(grads, ef, step)

    def inner(params, batch, ef, step):
        inner_axes = tuple(a for a in ("data",) if a in mesh.axis_names)
        set_batch_axes(inner_axes, mesh.shape.get("data", 1),
                       mesh.shape.get("model", 1))
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: tfm.loss_fn(p, cfg, batch), has_aux=True)(params)
        ef = jax.tree.map(lambda e: e[0], ef)          # drop pod dim
        grads, ef = compress_reduce(grads, ef, step)
        loss = lax.pmean(loss, "pod")
        metrics = jax.tree.map(lambda m: lax.pmean(m, "pod"), metrics)
        ef = jax.tree.map(lambda e: e[None], ef)
        return loss, metrics, grads, ef

    smap = jax.shard_map(
        inner, mesh=mesh, axis_names={"pod"},
        in_specs=(p_specs, b_specs, ef_pod_specs, P()),
        out_specs=(P(), {"loss": P(), "ppl_proxy": P()},
                   jax.tree.map(lambda _: P(), abstract_params),
                   ef_pod_specs),
        check_vma=False)

    def step(state: TrainState, batch):
        loss, metrics, grads, new_ef = smap(
            state.params, batch, state.ef_err, state.opt.step)
        lr = adamw.warmup_cosine(state.opt.step, peak_lr=peak_lr,
                                 warmup=warmup, total=total_steps)
        new_params, new_opt, om = adamw.update(
            grads, state.opt, state.params, lr=lr,
            weight_decay=weight_decay)
        metrics = dict(metrics)
        metrics.update(om)
        metrics["lr"] = lr
        return TrainState(new_params, new_opt, new_ef), metrics

    jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                     out_shardings=(state_sh, None), donate_argnums=(0,))
    opt = adamw.init_abstract(abstract_params, moment_dtype)
    ef_abs = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct((npod,) + p.shape, jnp.bfloat16),
        abstract_params)
    abstract_state = TrainState(abstract_params, opt, ef_abs)
    return StepBundle(jitted, state_sh, batch_sh, abstract_state,
                      _train_batch_abstract(cfg, seq_len, global_batch))


# ---------------------------------------------------------------------------
# Serve steps (prefill + decode)
# ---------------------------------------------------------------------------

def make_prefill_step(cfg: ModelConfig, mesh: Mesh, *, seq_len: int,
                      global_batch: int, fsdp: bool = False) -> StepBundle:
    seq_shard = global_batch < int(np.prod(
        [mesh.shape[a] for a in shd.dp_axes(mesh)]))
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
        [mesh.shape[a] for a in shd.dp_axes(mesh)]))
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
