"""Model facade: init/specs, jit-able step functions, dry-run input specs."""
from __future__ import annotations

import functools
from typing import Mapping

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES, ModelConfig, ShapeCell
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

    def _abstract_init(self):
        box = {}

        def f(k):
            p, s = transformer.init_stack(self.cfg, k)
            box["specs"] = s          # static logical tuples, not jax types
            return p

        params = jax.eval_shape(f, jax.random.PRNGKey(0))
        return params, box["specs"]

    def specs(self):
        """Pytree of logical-axis tuples mirroring init()'s params."""
        return self._abstract_init()[1]

    def abstract_params(self):
        return self._abstract_init()[0]

    # ------------------------------------------------------------------
    def loss_fn(self, params, batch):
        return transformer.loss_fn(self.cfg, params, batch, self.rules,
                                   backend=self.backend)

    def forward(self, params, batch, last_only=False):
        logits, _, aux = transformer.forward(
            self.cfg, params, batch, self.rules, backend=self.backend,
            last_only=last_only)
        return logits, aux

    def prefill(self, params, batch, capacity: int | None = None):
        """Returns (last-token logits, decode caches, stats).

        ``capacity``: cache slots to allocate (default prompt length + 64
        so a generation loop can append without reallocation).  ``stats``
        holds the served MoE layers' ``moe_rows`` (layers, held experts)
        and ``moe_dropped``; it is empty for a model without MoE."""
        seq = (batch["embeds"] if self.cfg.embeds_only
               else batch["token_ids"]).shape[1]
        logits, caches, aux = transformer.forward(
            self.cfg, params, batch, self.rules, backend=self.backend,
            collect_kv=True, last_only=True,
            cache_capacity=capacity or seq + 64)
        return logits, caches, {k: aux[k] for k in transformer.STATS
                                if k in aux}

    def decode_step(self, params, caches, batch):
        """Returns (logits, new caches, stats), ``stats`` as ``prefill``'s."""
        return transformer.decode_step(self.cfg, params, caches, batch,
                                       self.rules, backend=self.backend)

    # ------------------------------------------------------------------
    # dry-run stand-ins: ShapeDtypeStructs, no allocation
    # ------------------------------------------------------------------
    def input_specs(self, shape: str | ShapeCell):
        cell = SHAPES[shape] if isinstance(shape, str) else shape
        cfg = self.cfg
        B, S = cell.global_batch, cell.seq_len
        i32 = jnp.int32
        act = jnp.dtype(cfg.dtype)
        sds = jax.ShapeDtypeStruct

        def token_inputs(seq):
            if cfg.embeds_only:     # [audio]/encoder stub: frame embeddings
                return {"embeds": sds((B, seq, cfg.d_model), act)}
            d = {"token_ids": sds((B, seq), i32)}
            if cfg.mm_prefix:       # [vlm] stub: precomputed patch embeds
                d["mm_embeds"] = sds((B, cfg.mm_prefix, cfg.mm_embed_dim),
                                     act)
            return d

        if cell.kind == "train":
            batch = token_inputs(S)
            batch["labels"] = sds((B, S), i32)
            return batch
        if cell.kind == "prefill":
            return token_inputs(S)
        # decode: one new token + caches holding `seq_len` history
        batch = {"lengths": sds((B,), i32)}
        if cfg.embeds_only:
            batch["embeds"] = sds((B, 1, cfg.d_model), act)
        else:
            # decode is always past the multimodal prefix: token ids only
            batch["token_ids"] = sds((B, 1), i32)
        return batch

    def cache_specs(self, shape: str | ShapeCell):
        """Abstract decode caches with capacity = cell.seq_len."""
        cell = SHAPES[shape] if isinstance(shape, str) else shape
        caches = jax.eval_shape(
            lambda: self.init_cache(cell.global_batch, cell.seq_len))
        return caches

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
