"""Mixture-of-Experts block with capacity buffers and SPRING occupancy taps.

Routing is top-k with a fixed per-expert capacity buffer — the direct
datacenter analogue of the paper's FIFO: tokens *queue* into each expert's
buffer; tokens beyond capacity overflow (drop).  The in-band profile reports
per-expert fullness and overflow (``repro.core.metrics.expert_fullness``),
giving operators exactly the signal the paper extracts from its FPGA FIFOs —
how full the queues run, and where they overflow — without any out-of-band
instrumentation.

Dispatch is sort-based and *per batch row*, so under data parallelism the
routing never crosses shards: argsort the (S·k) expert assignments of each
row, rank entries within their expert run, keep ranks below capacity, and
gather/scatter through an [E, C] buffer.  Experts shard over the ``expert``
logical axis (EP on the mesh's model axis).

``expert_share_apply`` is the DeepSeek-V3 layer (sigmoid scores, a
selection-only correction bias, dropless) holding one share of the experts,
as one chip of an expert-parallel deployment does.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .common import ACTIVATIONS
from .params import ParamSpec
from ..distributed.ctx import shard_act


# std of the seeded correction bias: small against the spread of sigmoid
# scores, so it moves some selections and never the weights
ROUTER_BIAS_STD = 0.05


def moe_specs(d_model: int, d_ff: int, n_experts: int, dtype,
              stacked: int = 0, n_shared: int = 0, n_held: int = 0,
              router_bias: bool = False) -> Dict[str, ParamSpec]:
    """Router over all ``n_experts``; expert weights for the ``n_held`` held
    here (all by default); a float32 correction bias for sigmoid routing."""
    def spec(shape, axes, dt=dtype, **kw):
        if stacked:
            return ParamSpec((stacked,) + shape, dt, ("layers",) + axes, **kw)
        return ParamSpec(shape, dt, axes, **kw)

    n_held = n_held or n_experts
    specs = {
        "router": spec((d_model, n_experts), ("embed", None)),
        "w1": spec((n_held, d_model, d_ff), ("expert", "embed", None)),
        "wg": spec((n_held, d_model, d_ff), ("expert", "embed", None)),
        "w2": spec((n_held, d_ff, d_model), ("expert", None, "embed")),
    }
    if router_bias:
        specs["router_bias"] = spec((n_experts,), (None,), jnp.float32,
                                    scale=ROUTER_BIAS_STD)
    if n_shared:
        specs.update({
            "shared_wi": spec((d_model, n_shared * d_ff), ("embed", "mlp")),
            "shared_wg": spec((d_model, n_shared * d_ff), ("embed", "mlp")),
            "shared_wo": spec((n_shared * d_ff, d_model), ("mlp", "embed")),
        })
    return specs


def capacity_for(seq_len: int, top_k: int, n_experts: int, factor: float) -> int:
    return max(1, math.ceil(seq_len * top_k / n_experts * factor))


def _rank_within_expert(sorted_e: jnp.ndarray) -> jnp.ndarray:
    """Per-row rank of each sorted entry inside its expert run.

    sorted_e: [B, M] ascending expert ids.  rank[i] = i - first index of
    run(sorted_e[i]) — computed with a vmapped searchsorted.
    """
    def per_row(row):
        first = jnp.searchsorted(row, row, side="left")
        return jnp.arange(row.shape[0]) - first
    return jax.vmap(per_row)(sorted_e)


def moe_apply(
    p: Dict[str, jnp.ndarray],
    x: jnp.ndarray,                  # [B, S, d]
    *,
    top_k: int,
    capacity_factor: float,
    activation: str,
) -> Tuple[jnp.ndarray, jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Returns (y, aux_loss, profile) with profile = expert fullness/overflow."""
    act = ACTIVATIONS[activation]
    B, S, d = x.shape
    E = p["router"].shape[-1]
    C = capacity_for(S, top_k, E, capacity_factor)
    M = S * top_k

    # ---- routing ----
    logits = (x @ p["router"]).astype(jnp.float32)            # [B, S, E]
    probs = jax.nn.softmax(logits, axis=-1)
    topk_w, topk_e = jax.lax.top_k(probs, top_k)              # [B, S, k]
    topk_w = topk_w / jnp.sum(topk_w, axis=-1, keepdims=True)  # renormalize

    e_ids = topk_e.reshape(B, M)
    w_flat = topk_w.reshape(B, M)
    order = jnp.argsort(e_ids, axis=-1, stable=True)
    sorted_e = jnp.take_along_axis(e_ids, order, axis=-1)
    rank = _rank_within_expert(sorted_e)
    keep = rank < C
    dest_slot = jnp.where(keep, rank, C)                      # C = trash slot
    tok = order // top_k                                      # token of entry
    w_sorted = jnp.take_along_axis(w_flat, order, axis=-1)

    bidx = jnp.arange(B)[:, None]
    # ---- dispatch buffer [B, E, C] of token indices (S = zero-pad row) ----
    # All gathers/scatters below are vmapped over the batch row so they
    # lower with an explicit scatter/gather BATCHING dim — GSPMD then keeps
    # them batch-parallel instead of all-gathering rows across the data
    # axis (§Perf H3).
    disp = jax.vmap(
        lambda e_, s_, t_: jnp.full((E, C + 1), S, jnp.int32)
        .at[e_, s_].set(t_))(sorted_e, dest_slot, tok.astype(jnp.int32))
    disp = disp[:, :, :C]
    x_pad = jnp.concatenate([x, jnp.zeros((B, 1, d), x.dtype)], axis=1)
    xe = jax.vmap(lambda xp, d_: xp[d_])(x_pad, disp)         # [B, E, C, d]
    xe = shard_act(xe, "batch", "expert", None, None)

    # ---- expert FFN (E sharded over the expert/model axis) ----
    h = act(jnp.einsum("becd,edf->becf", xe, p["wg"])) * jnp.einsum(
        "becd,edf->becf", xe, p["w1"])
    ye = jnp.einsum("becf,efd->becd", h, p["w2"])             # [B, E, C, d]
    ye = shard_act(ye, "batch", "expert", None, None)

    # ---- combine (scatter in dispatch layout) ----
    # Scatter-add expert outputs back to tokens FROM the [B, E, C] buffer
    # layout, weighting each slot by its routing weight.  Because the updates
    # stay sharded on the expert axis, SPMD lowers this to local partial
    # sums + ONE [B, S, d] all-reduce — versus the gather-based combine,
    # which all-reduces the f32 [B, S·k, d] gathered tensor (top_k· and
    # fp32-fold larger).
    wbuf = jax.vmap(
        lambda e_, s_, w_: jnp.zeros((E, C + 1), topk_w.dtype)
        .at[e_, s_].set(w_))(sorted_e, dest_slot, w_sorted)
    wbuf = wbuf[:, :, :C]                                     # [B, E, C]
    contrib = ye * wbuf[..., None].astype(ye.dtype)
    y = jax.vmap(
        lambda c_, d_: jnp.zeros((S + 1, d), x.dtype)
        .at[d_].add(c_))(contrib, disp)[:, :S, :]
    y = shard_act(y, "batch", "seq", None)

    # ---- load-balancing aux (Switch-style) ----
    counts = jnp.zeros((B, E), jnp.float32).at[bidx, e_ids].add(1.0)
    frac_tokens = counts / M
    mean_probs = jnp.mean(probs, axis=1)                      # [B, E]
    aux = E * jnp.mean(jnp.sum(frac_tokens * mean_probs, axis=-1))

    # ---- SPRING tap: expert buffer fullness / overflow (FIFO metric) ----
    worst = jnp.max(counts, axis=0)                           # [E] worst row
    fullness = jnp.minimum(worst, float(C))
    overflow = jnp.maximum(worst - float(C), 0.0)
    profile = {"expert_fullness": fullness, "expert_overflow": overflow,
               "capacity": jnp.full((1,), float(C))}

    # ---- shared experts (dense path, always-on) ----
    if "shared_wi" in p:
        hs = act(x @ p["shared_wg"]) * (x @ p["shared_wi"])
        y = y + hs @ p["shared_wo"]

    return y, aux, profile


def sigmoid_route(p, x, top_k: int, scaling: float):
    """DeepSeek-V3 routing (noaux_tc, one group) in float32.

    Returns (scores [.., E], selected experts [.., k], their weights [.., k]):
    the top-k of the sigmoid scores plus the correction bias are selected;
    the weights are the selected scores without the bias, renormalised and
    times ``scaling``.
    """
    logits = jnp.matmul(x.astype(jnp.float32), p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + p["router_bias"], top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    return scores, idx, w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scaling


def expert_share_apply(
    p: Dict[str, jnp.ndarray],
    x: jnp.ndarray,                  # [B, S, d]
    *,
    top_k: int,
    scaling: float,
    held: Tuple[int, int],
    activation: str,
) -> Tuple[jnp.ndarray, jnp.ndarray, Dict[str, jnp.ndarray]]:
    """DeepSeek-V3 MoE layer holding experts ``[lo, hi)`` of the router's E.

    Routes over all E (``sigmoid_route``) and returns its held experts'
    part of the routed sum plus the shared experts, whole: what one chip of
    an expert-parallel deployment computes before the exchange.
    No token is dropped: every held expert runs on every token and weights
    it by its routing weight, zero where it was not selected.  At decode
    batches this reads each held expert's weights once, as a grouped
    product would.

    Returns (y, aux, profile): the sequence-wise balance loss over all E
    (DeepSeek-V3), and per held expert the tokens routed to it plus the
    share of assignments routed to experts not held.
    """
    act = ACTIVATIONS[activation]
    B, S, d = x.shape
    E = p["router"].shape[-1]
    lo, hi = held
    with jax.named_scope("mlp"):
        with jax.named_scope("router"):
            scores, idx, w = sigmoid_route(p, x, top_k, scaling)
            picked = idx[..., None] == jnp.arange(lo, hi)     # [B, S, k, Eh]
            gate = jnp.sum(jnp.where(picked, w[..., None], 0.0), axis=-2)
        with jax.named_scope("experts"):
            xt = x.reshape(B * S, d)
            h = act(jnp.einsum("td,edf->etf", xt, p["wg"])) * jnp.einsum(
                "td,edf->etf", xt, p["w1"])
            ye = jnp.einsum("etf,efd->etd", h, p["w2"])       # [Eh, T, d]
            y = jnp.einsum("etd,te->td", ye, gate.reshape(B * S, hi - lo),
                           preferred_element_type=jnp.float32)
            y = y.astype(x.dtype).reshape(B, S, d)
        with jax.named_scope("shared_experts"):
            hs = act(x @ p["shared_wg"]) * (x @ p["shared_wi"])
            y = y + hs @ p["shared_wo"]

    counts = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=(1, 2))
    frac = counts * E / (top_k * S)                           # [B, E]
    probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
    aux = jnp.mean(jnp.sum(frac * jnp.mean(probs, axis=1), axis=-1))

    tokens = jnp.sum(picked, axis=(0, 1, 2)).astype(jnp.float32)
    unheld = 1.0 - jnp.sum(tokens) / (B * S * top_k)
    profile = {"expert_tokens": tokens, "unheld_share": unheld[None]}
    return y, aux, profile
