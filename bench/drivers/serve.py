"""Serve driver: ``repro.launch.serve.run_serve`` calls of the cell's shape.

Each call draws its weights and prompts from its own seed, prefills by
streaming the prompt through the decode step, and greedy-decodes ``gen``
tokens for every sequence of the batch under the traffic's profile policy.
The window is the decode phase of calls made back to back until their
decode windows sum to ``--seconds`` (at least one call):

* a call's decode window is ``(gen - 1) * step_s`` (the program times its
  generated steps from the second one, to ``block_until_ready``);
* its time to first token is its prefill-plus-decode time,
  ``batch * (max_len - 1) / toks_per_s``, less that window;
* ``setup_s`` runs from the start of the process to the start of the first
  call's prefill: imports, the warm-up, the weights and the step's compile.

Every call is also timed from outside; a call whose own timings do not fit
inside that time fails the run.  A call whose profiling degraded or lost a
stream counts all its requests as failed.

``correct`` compares what the timed calls produced with the float32
reference: the prompts, the profile records the collector decoded, and the
served tokens' logit gap on rows drawn from the seed.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import numpy as np

# run_serve uses seed for the weights and seed + 1 for the prompts
SEED_MOD = 2**31 - 1024


def _program_config(config: dict):
    from repro.configs import get_config

    prog = config["program"]
    cfg = get_config(prog["arch"])
    if prog.get("reduced"):
        cfg = cfg.reduced()
    want = {
        "num_layers": cfg.n_layers, "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.n_heads,
        "multi_query_group_num": cfg.n_kv_heads, "kv_channels": cfg.head_dim,
        "ffn_hidden_size": cfg.d_ff, "padded_vocab_size": cfg.padded_vocab,
        "add_qkv_bias": cfg.qkv_bias, "layernorm_epsilon": cfg.norm_eps,
        "torch_dtype": cfg.param_dtype,
        "tie_word_embeddings": cfg.tie_embeddings,
    }
    wrong = {k: (config[k], v) for k, v in want.items() if config[k] != v}
    if (wrong or cfg.activation_dtype != cfg.param_dtype
            or cfg.rotary_fraction != 0.5 or cfg.vocab_size != cfg.padded_vocab
            or not cfg.mlp_gated or cfg.activation != "silu"):
        raise RuntimeError(f"the program's {prog['arch']} is not the "
                           f"configuration file's model: {wrong}")
    return cfg


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.config, self.traffic = ctx.config, ctx.traffic
        t = self.traffic
        self.batch, self.prompt_len, self.gen = (t["batch"], t["prompt_len"],
                                                 t["gen"])
        self.max_len = self.prompt_len + self.gen
        self.policy = t["profile_policy"]
        self.calls: List[dict] = []
        self.setup_s: Optional[float] = None
        self.records = {"program": "serve_step", "calls": self.calls,
                        "batch": self.batch, "prompt_len": self.prompt_len,
                        "gen": self.gen, "config": self.config}

    # ----------------------------------------------------------------- #
    def call_seed(self, i: int) -> int:
        return (self.ctx.seed + 2 * i) % SEED_MOD

    def setup(self) -> None:
        from repro.launch.serve import run_serve

        _program_config(self.config)
        self.arch = self.config["program"]["arch"]
        self.reduced = bool(self.config["program"].get("reduced"))
        if self.policy != "off":
            # the host profiling path's eager ops do not depend on the
            # model's shapes: compile them on a tiny model, not in the window
            run_serve(self.arch, reduced=True, batch=1, prompt_len=2, gen=3,
                      seed=0, profile_policy=self.policy)

    def _call(self, i: int) -> dict:
        from repro.launch.serve import run_serve

        seed = self.call_seed(i)
        with self.ctx.span("bench.serve.call"):
            t_a = time.perf_counter()
            res = run_serve(self.arch, reduced=self.reduced, batch=self.batch,
                            prompt_len=self.prompt_len, gen=self.gen,
                            seed=seed, profile_policy=self.policy)
            tokens = np.asarray(res.tokens)
            t_b = time.perf_counter()
        outer = t_b - t_a
        busy = self.batch * (self.max_len - 1) / res.toks_per_s
        decode = (self.gen - 1) * res.step_s
        if not (0 < decode <= busy <= outer):
            raise RuntimeError(
                f"call {i}: decode window {decode:.4f} s and prefill+decode "
                f"{busy:.4f} s do not fit inside the call's {outer:.4f} s")
        sig = {k: (v.max.tolist(), v.min.tolist(), v.mean.tolist(), v.count)
               for k, v in res.collector.signals.items()}
        return {"seed": seed, "t_start": t_a, "outer_s": outer,
                "busy_s": busy, "decode_s": decode,
                "ttft_s": busy - decode, "tokens": tokens,
                "degraded": len(res.supervisor.events),
                "integrity_failures": res.collector.integrity_failures,
                "profile_steps": res.collector.steps, "signals": sig}

    def window(self, seconds: float) -> None:
        total = 0.0
        while not self.calls or total < seconds:
            rec = self._call(len(self.calls))
            if self.setup_s is None:
                self.setup_s = (rec["t_start"] - self.ctx.t0
                                + rec["outer_s"] - rec["busy_s"])
            self.calls.append(rec)
            total += rec["decode_s"]

    def end_to_end(self) -> Dict[str, float]:
        steps = len(self.calls) * (self.gen - 1)
        return {
            "decode_step_ms": 1e3 * sum(c["decode_s"] for c in self.calls)
            / steps,
            "ttft_ms": 1e3 * float(np.mean([c["ttft_s"] for c in self.calls])),
            "setup_s": self.setup_s,
        }

    def attempted_failed(self) -> Tuple[int, int]:
        bad = sum(1 for c in self.calls
                  if c["degraded"] or c["integrity_failures"])
        return self.batch * len(self.calls), self.batch * bad

    def trace_window(self, trace):
        """The decode window of the traced call: from the start of the
        second generated step's program to the end of the last step's."""
        steps = trace.modules(self.records["program"])
        n = self.prompt_len - 1 + self.gen
        if len(steps) < n:
            return None
        steps = steps[-n:]
        return steps[self.prompt_len].start, steps[-1].end

    def release(self) -> None:
        import gc

        gc.collect()

    # ----------------------------------------------------------------- #
    def expected_signals(self) -> Dict[str, tuple]:
        """What the collector should hold after one call: one record set per
        generated step at cache positions prompt_len .. max_len - 1."""
        if self.policy == "off":
            return {}
        lo, hi = float(self.prompt_len), float(self.max_len - 1)
        mean, ml = (lo + hi) / 2, float(self.max_len)
        if self.policy == "inline":
            return {"kv/occupancy": ([hi, ml], [lo, ml], [mean, ml], self.gen),
                    "kv/position": ([hi], [lo], [mean], self.gen)}
        return {"kv/record": ([hi, ml, hi], [lo, ml, lo], [mean, ml, mean],
                              self.gen)}

    def sample(self):
        """The call and the rows of it that the logit check reads, drawn
        from the seed."""
        rng = np.random.default_rng(self.ctx.seed)
        call = self.calls[int(rng.integers(len(self.calls)))]
        rows = np.sort(rng.choice(self.batch, self.ctx.workload["limits"]
                                  ["sample_rows"], replace=False))
        return call, rows

    def check(self) -> Dict[str, Tuple[float, float]]:
        import jax
        import jax.numpy as jnp

        from bench.reference import chatglm

        limits = self.ctx.workload["limits"]
        vocab = self.config["padded_vocab_size"]
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
                               if not _close(got.get(k), want.get(k)))
            steps = self.gen if want else 0
            profile_bad += int(c["profile_steps"] != steps)
        call, rows = self.sample()
        weights = chatglm.make_weights(self.config, call["seed"])
        toks = call["tokens"][rows]
        ref = np.asarray(chatglm.logits(self.config, weights, toks))
        del weights
        gap = chatglm.served_gap(ref, toks, self.prompt_len)
        return {"logit_gap": (gap, limits["logit_gap"]),
                "prompt_rows_wrong": (prompt_bad, 0),
                "profile_records_wrong": (profile_bad, 0)}


def _close(got, want) -> bool:
    if got is None or want is None:
        return got is None and want is None
    (gmax, gmin, gmean, gn), (wmax, wmin, wmean, wn) = got, want
    return (gn == wn and gmax == wmax and gmin == wmin
            and np.allclose(gmean, wmean, rtol=1e-9, atol=1e-9))
