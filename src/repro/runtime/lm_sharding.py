"""Logical-axis -> mesh-axis sharding rules of the LM stack (DP / TP / EP
/ SP / FSDP); runtime/steps.py is their caller.

Logical axes used by the model zoo:
  batch     -> data parallel axes ("pod","data") / ("data",)
  vocab, heads, ff, expert, inner -> tensor/expert parallel axis ("model")
  kv_heads  -> "model" when divisible, else replicated (GQA with few KV heads)
  embed     -> "data" when FSDP is on (fully-sharded params: required for
               kimi-k2-1t); else replicated across data
  kv_seq    -> decode-time sequence parallelism for underfilled batches
               (long_500k: batch=1 shards the KV cache over "data")
  layers    -> never sharded (scan axis)
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.common import Axes, ModelConfig
from repro.runtime.sharding import dp_axes


def make_rules(mesh: Mesh, cfg: ModelConfig, *, fsdp: bool = False,
               seq_shard: bool = False,
               global_batch: Optional[int] = None) -> Dict[str, Any]:
    tp = mesh.shape.get("model", 1)
    dp = dp_axes(mesh)
    fsdp_n = mesh.shape.get("data", 1)

    def fits(dim: int) -> bool:
        return dim > 0 and dim % tp == 0

    # batch: drop data-parallel axes until the global batch divides (decode
    # at batch=1 falls back to a replicated batch + KV-seq sharding)
    batch_rule: Any = dp
    if global_batch is not None:
        while batch_rule and global_batch % int(
                np.prod([mesh.shape[a] for a in batch_rule])) != 0:
            batch_rule = batch_rule[:-1]
        batch_rule = batch_rule or None

    return {
        "batch": batch_rule,
        "vocab": "model" if fits(cfg.vocab) else None,
        "heads": "model" if fits(cfg.n_heads) else None,
        "kv_heads": "model" if fits(cfg.n_kv_heads) else None,
        "ff": "model" if fits(cfg.d_ff) else None,
        "expert": "model" if fits(cfg.n_experts) else None,
        "inner": "model",
        "embed": ("data" if fsdp and cfg.d_model % fsdp_n == 0 else None),
        "kv_seq": "data" if seq_shard else None,
        "layers": None,
        None: None,
    }


def spec_for(axes, rules) -> P:
    """Map logical axes to a PartitionSpec, deduplicating mesh axes.

    A mesh axis may appear at most once in a spec; the first logical axis
    (left-to-right) claims it (e.g. MoE expert weights ("expert", "embed",
    "ff") -> P("model", ..., None): "expert" wins the "model" axis and the
    per-expert ff dim stays unsharded)."""
    used = set()
    out = []
    for a in axes:
        r = rules.get(a)
        items = r if isinstance(r, tuple) else (r,) if r else ()
        if any(m in used for m in items):
            out.append(None)
        else:
            used.update(items)
            out.append(r)
    return P(*out)


def sharding_tree(axes_tree, mesh: Mesh, rules) -> Any:
    """Map an Axes-leaf tree to a NamedSharding tree (same structure)."""
    return jax.tree.map(
        lambda leaf: NamedSharding(mesh, spec_for(leaf.axes, rules)),
        axes_tree, is_leaf=lambda x: isinstance(x, Axes))


def batch_sharding(mesh: Mesh, rules, *, with_memory=False,
                   mode: str = "train"):
    """Shardings for input batches."""
    bsp = rules["batch"]
    tok = NamedSharding(mesh, P(bsp, None))
    if mode in ("train", "prefill"):
        out = {"tokens": tok}
        if with_memory:
            out["memory"] = NamedSharding(mesh, P(bsp, None, None))
        return out
    out = {"token": tok, "pos": NamedSharding(mesh, P(bsp))}
    if with_memory:
        out["memory"] = NamedSharding(mesh, P(bsp, None, None))
    return out
