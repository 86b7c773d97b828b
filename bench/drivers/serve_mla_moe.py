"""Serve driver for a latent-attention MoE model holding one expert share.

The serve driver's calls, window, timings and profile-record check
(``bench/drivers/serve.py``), with two differences:

* set-up builds the program's configuration from the configuration file:
  the registry's model, checked key by key against the file's published
  values, holding the share of the routed experts that
  ``expert_parallel`` names; ``run_serve`` serves that configuration;
* ``correct`` compares the served tokens with the float32 reference of
  this architecture holding the same share (``bench/reference/mla_moe.py``)
  by their mean logit gap over the sampled rows' generated positions, not
  the largest: bfloat16 flips about a tenth of the expert selections near
  the top-6 boundary, and a flipped selection moves that position's logits
  about as far as float8 does, so the largest gap does not separate the
  two precisions and the mean does (``PERF.md``, section 4).
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from bench.drivers import serve


def program_config(config: dict):
    """The program's configuration for the file's model and expert share;
    raises where the program's model is not the file's."""
    from repro.configs import get_config

    prog = config["program"]
    cfg = get_config(prog["arch"])
    if prog.get("reduced"):
        cfg = cfg.reduced()
    cfg = cfg.with_expert_share(prog["expert_shard"], prog["expert_shards"])
    lo, hi = cfg.held_experts
    ep = config["expert_parallel"]
    want = {
        "num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.n_heads, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_dim,
        "qk_rope_head_dim": cfg.qk_rope_dim, "v_head_dim": cfg.v_head_dim,
        "first_k_dense_replace": cfg.first_k_dense,
        "intermediate_size": cfg.dense_d_ff,
        "moe_intermediate_size": cfg.d_ff, "n_routed_experts": hi - lo,
        "n_shared_experts": cfg.n_shared_experts,
        "num_experts_per_tok": cfg.top_k, "vocab_size": cfg.vocab_size,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
        "routed_scaling_factor": cfg.routed_scaling,
        "tie_word_embeddings": cfg.tie_embeddings,
        "torch_dtype": cfg.param_dtype,
    }
    wrong = {k: (config[k], v) for k, v in want.items() if config[k] != v}
    if (wrong or ep["router_width"] != cfg.n_experts
            or list(ep["held_experts"]) != [lo, hi]
            or config["scoring_func"] != cfg.router
            or config["q_lora_rank"] is not None
            or config["topk_method"] != "noaux_tc" or config["n_group"] != 1
            or not config["norm_topk_prob"]
            or cfg.activation_dtype != cfg.param_dtype
            or cfg.vocab_size != cfg.padded_vocab
            or cfg.activation != config["hidden_act"]):
        raise RuntimeError(f"the program's {prog['arch']} is not the "
                           f"configuration file's model: {wrong}")
    return cfg


class Driver(serve.Driver):
    def setup(self) -> None:
        from repro.launch.serve import run_serve

        self.arch, self.reduced = program_config(self.config), False
        if self.policy != "off":
            # compile the host profiling path on a tiny model, not in the
            # window
            run_serve(self.arch, reduced=True, batch=1, prompt_len=2, gen=3,
                      seed=0, profile_policy=self.policy)

    def check(self) -> Dict[str, Tuple[float, float]]:
        import jax
        import jax.numpy as jnp

        from bench.reference import mla_moe

        limits = self.ctx.workload["limits"]
        vocab = self.config["vocab_size"]
        want = self.expected_signals()
        prompt_bad = profile_bad = 0
        for c in self.calls:
            prompts = np.asarray(jax.random.randint(
                jax.random.PRNGKey(c["seed"] + 1),
                (self.batch, self.prompt_len), 0, vocab, jnp.int32))
            toks = c["tokens"]
            prompt_bad += int((toks[:, :self.prompt_len] != prompts).any(1)
                              .sum())
            prompt_bad += int(((toks < 0) | (toks >= vocab)).any(1).sum())
            got = c["signals"]
            profile_bad += sum(1 for k in set(want) | set(got)
                               if not serve._close(got.get(k), want.get(k)))
            profile_bad += int(c["profile_steps"] != (self.gen if want else 0))
        call, rows = self.sample()
        weights = mla_moe.make_weights(self.config, call["seed"])
        toks = call["tokens"][rows]
        ref = np.asarray(mla_moe.logits(self.config, weights, toks))
        del weights
        P = self.prompt_len
        gap = float(mla_moe.position_gaps(ref, toks[:, P:], P).mean())
        return {"logit_gap_mean": (gap, limits["logit_gap_mean"]),
                "prompt_rows_wrong": (prompt_bad, 0),
                "profile_records_wrong": (profile_bad, 0)}
