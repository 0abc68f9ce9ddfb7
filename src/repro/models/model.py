"""Model facade: init, the jit-able forward, prefill and decode step, and
the decode cache."""
from __future__ import annotations

import functools
from typing import Mapping

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from . import kvcache, transformer


class Model:
    def __init__(self, cfg: ModelConfig, rules: Mapping[str, object] | None
                 = None, backend: str = "auto"):
        self.cfg = cfg
        self.rules = dict(rules if rules is not None else cfg.rules)
        self.backend = backend

    # ------------------------------------------------------------------
    def init(self, key):
        params, _ = transformer.init_stack(self.cfg, key)
        return params

    def abstract_params(self):
        """``init``'s tree as ShapeDtypeStructs, with no allocation."""
        return jax.eval_shape(self.init, jax.random.PRNGKey(0))

    # ------------------------------------------------------------------
    def forward(self, params, batch):
        """Returns (logits, stats) over the whole sequence."""
        logits, _, stats = transformer.forward(
            self.cfg, params, batch, self.rules, backend=self.backend)
        return logits, stats

    def prefill(self, params, batch, capacity: int | None = None):
        """Returns (last-token logits, decode caches, stats).

        ``capacity``: cache slots to allocate (default prompt length + 64
        so a generation loop can append without reallocation).  ``stats``
        holds the MoE layers' ``moe_rows`` (layers, held experts) and
        ``moe_dropped``; it is empty for a model without MoE."""
        seq = (batch["embeds"] if self.cfg.embeds_only
               else batch["token_ids"]).shape[1]
        return transformer.forward(
            self.cfg, params, batch, self.rules, backend=self.backend,
            capacity=capacity or seq + 64, last_only=True)

    def decode_step(self, params, caches, batch):
        """Returns (logits, new caches, stats), ``stats`` as ``prefill``'s."""
        return transformer.decode_step(self.cfg, params, caches, batch,
                                       self.rules, backend=self.backend)

    def init_cache(self, batch: int, capacity: int):
        cfg = self.cfg
        H = cfg.n_heads if cfg.n_heads else cfg.d_model // 64
        dh_rwkv = cfg.d_model // H

        def one(kind):
            if kind == "mla":
                return kvcache.init_layer(batch, capacity, 1,
                                          cfg.mla.cache_width,
                                          cfg.kv_cache_dtype)
            if kind in ("attn", "local"):
                cap = (min(cfg.local_window, capacity) if kind == "local"
                       else capacity)
                return {
                    "k": kvcache.init_layer(batch, cap, cfg.n_kv_heads,
                                            cfg.d_head, cfg.kv_cache_dtype),
                    "v": kvcache.init_layer(batch, cap, cfg.n_kv_heads,
                                            cfg.d_head, cfg.kv_cache_dtype),
                }
            if kind == "rglru":
                return {"h": jnp.zeros((batch, cfg.d_rnn), jnp.float32),
                        "conv": jnp.zeros((batch, 3, cfg.d_rnn),
                                          jnp.dtype(cfg.dtype))}
            if kind == "rwkv":
                return {"S": jnp.zeros((batch, H, dh_rwkv, dh_rwkv),
                                       jnp.float32),
                        "x_t": jnp.zeros((batch, cfg.d_model),
                                         jnp.dtype(cfg.dtype)),
                        "x_c": jnp.zeros((batch, cfg.d_model),
                                         jnp.dtype(cfg.dtype))}
            raise ValueError(kind)

        kinds = cfg.layer_kinds
        n_lead = cfg.first_dense
        P = len(cfg.block_pattern)
        n_groups = ((len(kinds) - n_lead) // P) if cfg.scan_layers else 0
        n_scanned = n_groups * P
        groups = None
        if n_groups:
            # zeros of the stacked shape at once: stacking per-layer arrays
            # holds them and their copies on the device beside the stack
            groups = tuple(
                jax.tree.map(lambda a: jnp.zeros((n_groups, *a.shape),
                                                 a.dtype),
                             jax.eval_shape(functools.partial(
                                 one, cfg.block_pattern[pos])))
                for pos in range(P))
        tail = tuple(one(kinds[i])
                     for i in range(n_lead + n_scanned, len(kinds)))
        caches = {"groups": groups, "tail": tail}
        if n_lead:
            caches["lead"] = tuple(one(kinds[i]) for i in range(n_lead))
        return caches


def build(cfg: ModelConfig, **kw) -> Model:
    return Model(cfg, **kw)
