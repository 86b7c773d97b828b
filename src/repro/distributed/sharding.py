"""Logical-axis sharding rules for the production mesh.

Axes (weights + activations):
  batch      -> (pod, data)   data parallelism (pods are outer DP)
  vocab      -> model         embedding / LM-head vocab sharding
  heads      -> model         attention Q heads (tensor parallelism)
  kv_heads   -> model         KV heads (falls back to replicated for MQA)
  mlp        -> model         FFN hidden
  expert     -> model         expert parallelism (MoE)
  embed      -> data          FSDP: weights' d_model dim sharded over data
  seq        -> (off)         sequence parallelism knob ("model" when on)
  embed_act  -> (none)        norm scales etc., replicated
  layers     -> (none)        stacked-layer leading dim

Variants are the §Perf hillclimb levers: ``sp`` turns on sequence sharding
of the residual stream; ``no_fsdp`` replicates weights over data (baseline
ablation); ``fsdp_pod`` extends FSDP across pods (DCN all-gathers).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import params as P_
from .ctx import logical_pspec


def default_rules(variant: str = "base") -> Dict[str, Any]:
    rules = {
        "batch": ("pod", "data"),
        "vocab": "model",
        "heads": "model",
        "kv_heads": "model",
        "mlp": "model",
        "expert": "model",
        "embed": "data",
        "embed_act": None,
        "layers": None,
        "seq": None,
    }
    if variant == "base":
        return rules
    if variant == "sp":                 # sequence parallelism on residual
        rules["seq"] = "model"
        return rules
    if variant == "no_fsdp":
        rules["embed"] = None
        return rules
    if variant == "fsdp_pod":
        rules["embed"] = ("pod", "data")
        return rules
    raise ValueError(f"unknown sharding variant {variant!r}")


def param_shardings(specs, mesh: Mesh, rules: Dict[str, Any]):
    return P_.shardings_for(specs, mesh, rules)


def _ns(mesh: Mesh, rules, axes, shape=None) -> NamedSharding:
    """Shape/mesh-aware NamedSharding (missing axes and non-divisible dims
    fall back to replication — e.g. the pod axis on a single-pod mesh, or a
    global batch of 1 on the data axis)."""
    return NamedSharding(mesh, logical_pspec(rules, axes, shape=shape,
                                             mesh=mesh))


_BATCH_AXES = {
    "tokens": ("batch", None),
    "labels": ("batch", None),
    "dec_tokens": ("batch", None),
    "dec_labels": ("batch", None),
    "frames": ("batch", None, None),
}


def batch_shardings(cfg, mesh: Mesh, rules: Dict[str, Any], abs_inputs):
    """NamedSharding tree matching an abstract input dict."""
    return {
        k: _ns(mesh, rules, _BATCH_AXES[k], shape=v.shape)
        for k, v in abs_inputs.items()
    }


def cache_axes(cfg):
    """Logical axes tree matching the family's cache structure."""
    kv5 = (None, "batch", None, "kv_heads", None)     # [L, B, S, KV, dh]
    if cfg.family == "hybrid":
        from ..models.hybrid import HybridCaches
        from ..models.ssm import SsmCache
        return HybridCaches(
            ssm=SsmCache(
                conv_x=(None, "batch", None, "mlp"),
                conv_bc=(None, "batch", None, None),
                state=(None, "batch", "heads", None, None),
            ),
            shared_k=kv5, shared_v=kv5, window_pos=(),
        )
    if cfg.is_encdec:
        from ..models.encdec import EncDecCaches
        return EncDecCaches(self_k=kv5, self_v=kv5, cross_k=kv5, cross_v=kv5)
    if cfg.family == "ssm":
        from ..models.ssm import SsmCache
        return SsmCache(
            conv_x=(None, "batch", None, "mlp"),
            conv_bc=(None, "batch", None, None),
            state=(None, "batch", "heads", None, None),
        )
    from ..models.transformer import KvCaches, LatentCaches
    if cfg.mla:
        lat = (None, "batch", None, None)
        return LatentCaches(prefix=lat, blocks=lat)
    kv = (None, "batch", "kv_heads", None, None)      # [L, B, KV, S, dh]
    return KvCaches(k=kv, v=kv)


def cache_shardings(cfg, mesh: Mesh, rules: Dict[str, Any], abs_caches):
    """Sharding tree for decode caches, shape-aware via the abstract tree."""
    axes_tree = cache_axes(cfg)
    flat_abs, treedef = jax.tree_util.tree_flatten(abs_caches)
    flat_axes = treedef.flatten_up_to(axes_tree)
    return jax.tree_util.tree_unflatten(treedef, [
        _ns(mesh, rules, axes, shape=ab.shape)
        for ab, axes in zip(flat_abs, flat_axes)
    ])


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())
