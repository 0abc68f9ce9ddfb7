"""Serving cells of a latent-attention MoE model (DeepSeek-V2): the
``serve`` kind's open-loop window, end-to-end metrics and layer context,
with this configuration's set-up, sizes and check.

Set-up builds the program's ``ModelConfig`` from the configuration file
(every width, the router's experts and the share this chip holds, the
YaRN scaling), draws the weights from the seed on the device
(``reference/mla_ref.make_params``) and warms every prompt length of the
run on every slot.  The window is ``kinds/serve.py``'s; each engine step
also records what the held experts computed in it (the engine's MoE
counters), for the expert metrics.  The check runs ``mla_ref``: the
longest finished request and those served beside it through the float32
reference over prompt plus served tokens.
"""
from __future__ import annotations

import dataclasses
import pathlib

import numpy as np

import generate
from harness import BenchError, load_module, sub_seed
from reference import mla_ref

serve = load_module(pathlib.Path(__file__).with_name("serve.py"),
                    "bench_kind_serve")

#: the engine's MoE counters that each step's record takes the change of
MOE_COUNTERS = ("moe_rows_total", "moe_expert_steps", "moe_experts_touched",
                "moe_dropped_rows")


def model_config(cfg: dict):
    """The program's ModelConfig for what the file states."""
    from repro import configs
    from repro.configs.base import MLAConfig, YarnConfig
    base = configs.get(cfg["arch"])
    y, dep = cfg["rope_scaling"], cfg["deployment"]
    return dataclasses.replace(
        base, n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_head=cfg["v_head_dim"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        first_dense=cfg["first_k_dense_replace"],
        mla=MLAConfig(kv_rank=cfg["kv_lora_rank"],
                      nope_dim=cfg["qk_nope_head_dim"],
                      rope_dim=cfg["qk_rope_head_dim"],
                      v_dim=cfg["v_head_dim"]),
        rope_scaling=YarnConfig(
            factor=float(y["factor"]),
            original_max_position=int(y["original_max_position_embeddings"]),
            beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
            mscale=float(y["mscale"]),
            mscale_all_dim=float(y["mscale_all_dim"])),
        moe=dataclasses.replace(
            base.moe, n_experts=dep["router_experts"],
            top_k=cfg["num_experts_per_tok"],
            d_ff=cfg["moe_intermediate_size"],
            n_shared=cfg["n_shared_experts"],
            norm_topk=cfg["norm_topk_prob"], first_held=dep["first_held"],
            n_held=cfg["n_routed_experts"]),
        param_dtype=cfg["torch_dtype"], dtype=cfg["activation_dtype"],
        kv_cache_dtype=cfg["kv_cache_dtype"])


class Driver(serve.Driver):
    def __init__(self, cell, seed: int, seconds: float, tracing, control: bool):
        super().__init__(cell, seed, seconds, tracing, control)
        self.sizes = mla_ref.sizes(self.cfg)

    def setup(self):
        import jax
        from repro.models import build
        from repro.serve.engine import ServingEngine
        c = self.cfg
        if c.get("routed_scaling_factor", 1) != 1:
            raise BenchError("the program has no routed scaling factor")
        model = build(model_config(c))
        want = jax.tree.map(lambda a: (a.shape, str(a.dtype)),
                            model.abstract_params())
        self.weights_seed = sub_seed(self.seed, "weights")
        params = mla_ref.make_params(self.weights_seed, self.sizes)
        got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
        if got != want:
            raise BenchError("the program's parameter tree differs from the "
                             "one the benchmark draws")
        eng = ServingEngine(model, params, max_slots=c["engine"]["max_slots"],
                            capacity=c["engine"]["capacity"])
        self.engine = eng
        self.reqs = [serve.ReqRecord(r["due"], r["prompt"], r["max_new"])
                     for r in generate.serve_requests(
                         self.mix, self.seed, self.seconds,
                         vocab=c["vocab_size"], capacity=eng.capacity)]
        # warm-up: every prompt length of this run, on every slot
        rng = np.random.default_rng(sub_seed(self.seed, "warm"))
        lengths = sorted({len(r.prompt) for r in self.reqs})
        while len(lengths) < eng.max_slots:
            lengths = lengths + lengths
        for n in lengths:
            eng.submit(rng.integers(0, c["vocab_size"], size=n,
                                    dtype=np.int32), max_new=2)
        eng.run_until_drained()
        eng.completed.clear()

    def window(self):
        """``serve``'s window; each engine step also records the change of
        the engine's MoE counters, in the order of the window's steps."""
        eng = self.engine
        step = eng.step
        self.moe_steps = []

        def counted():
            c = eng.counters
            before = [getattr(c, k, 0) for k in MOE_COUNTERS]
            active = step()
            self.moe_steps.append(dict(zip(MOE_COUNTERS, (
                getattr(c, k, 0) - b for k, b in zip(MOE_COUNTERS, before)))))
            return active

        eng.step = counted
        try:
            super().window()
        finally:
            del eng.step

    def checks(self) -> dict:
        done = [r for r in self.reqs if r.req is not None and r.req.done]
        if not done:
            raise BenchError("no request finished")
        rng = np.random.default_rng(sub_seed(self.seed, "check"))
        longest = max(done, key=lambda r: len(r.req.tokens))
        lo, hi = longest.times[0], longest.times[-1]
        others = [r for r in done if r is not longest]
        beside = [r.times[0] <= hi and r.times[-1] >= lo for r in others]
        order = sorted(rng.permutation(len(others)), key=lambda i: not beside[i])
        sample = [longest] + [others[i] for i in
                              order[:self.mix["check_requests"] - 1]]
        seqs = [(r.prompt, np.asarray(r.req.tokens, np.int32)) for r in sample]
        gaps = mla_ref.served_gaps(self.weights_seed, self.sizes, seqs,
                                   quant="int8", control=self.control)
        widest = float(max(g.max() for g in gaps))
        return {"served_logit_gap": {
            "value": widest, "limit": self.cfg["limits"]["served_logit_gap"]}}

    def layer_context(self) -> dict:
        ctx = super().layer_context()
        ctx["moe_steps"] = self.moe_steps
        ctx["max_slots"] = self.cfg["engine"]["max_slots"]
        return ctx
