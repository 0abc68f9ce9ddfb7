"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1).

Per layer, with ``h = RMSNorm(x)``:

* ``q = h W_q`` per head, ``q_dim = nope_dim + rope_dim`` wide: ``q_n``
  then ``q_r``;
* ``[c, k_r] = h W_kva``; ``c = RMSNorm_kv(c)`` (``kv_rank`` wide) and
  ``k_r`` (``rope_dim`` wide) is one rotary key shared by every head;
* per head ``k_n = c W_uk`` and ``v = c W_uv``;
* ``score = (q_n . k_n + q_r . k_r) * q_dim^-1/2 * m^2``, with YaRN's
  attention factor ``m`` when the rotary embedding is scaled; causal.

Prefill expands keys and values per head and runs the attention kernel
(``ops.attention``, whose scale is the query width's ``d^-1/2``: the
YaRN factor is folded into ``q``).  Decode runs in absorbed form over the
latent cache, one row ``[c | k_r | 0]`` per position, zero-padded to
whole lane tiles (``MLAConfig.cache_width``; ``models/kvcache.py``):
``q~ = q_n W_uk^T``, ``score = ([q~ | q_r | 0] . [c | k_r | 0]) * scale``,
the weighted sum of ``c`` per head (``ops.mla_decode``), then ``W_uv``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from . import kvcache, sharding
from .layers import dense_init, rmsnorm, rope


def init_mla(cfg: ModelConfig, key):
    d, H, m = cfg.d_model, cfg.n_heads, cfg.mla
    pdt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 5)
    p, s = {}, {}
    p["ln"], s["ln"] = jnp.zeros((d,), pdt), ("embed",)
    p["wq"], s["wq"] = dense_init(ks[0], (d, H, m.q_dim),
                                  ("embed", "heads", "head_dim"), pdt)
    p["wkv_a"], s["wkv_a"] = dense_init(ks[1], (d, m.latent_width),
                                        ("embed", None), pdt)
    p["kv_ln"], s["kv_ln"] = jnp.zeros((m.kv_rank,), pdt), (None,)
    p["wuk"], s["wuk"] = dense_init(ks[2], (m.kv_rank, H, m.nope_dim),
                                    (None, "heads", "head_dim"), pdt)
    p["wuv"], s["wuv"] = dense_init(ks[3], (m.kv_rank, H, m.v_dim),
                                    (None, "heads", "head_dim"), pdt)
    p["wo"], s["wo"] = dense_init(ks[4], (H, m.v_dim, d),
                                  ("heads", "head_dim", "embed"), pdt,
                                  fan_in_axes=(0, 1))
    return p, s


def score_scale(cfg: ModelConfig) -> float:
    """Softmax's scale: ``q_dim^-1/2``, times YaRN's ``m^2``."""
    m2 = cfg.rope_scaling.attn_scale if cfg.rope_scaling is not None else 1.0
    return cfg.mla.q_dim ** -0.5 * m2


def mla_block(cfg: ModelConfig, p, rules, x, positions, *, cache=None,
              lengths=None, layer=None, backend="auto"):
    """Pre-norm latent-attention residual block.

    Prefill (``cache is None``): returns ``(y, rows)``, the ``(B, S, W)``
    latent rows a cache is built from.  Decode: ``cache`` is the layer's
    latent cache view (with ``layer``, the decode scan's stacked views
    and this layer's index in them), ``lengths`` (B,) the positions
    already cached; the new row goes in at ``lengths`` and ``(y, new
    cache)`` comes back.
    """
    m = cfg.mla
    dt = jnp.dtype(cfg.dtype)
    h = rmsnorm(x, p["ln"]).astype(dt)

    def W(name, logical):
        return sharding.weight_use(p[name].astype(dt), rules, logical)

    q = jnp.einsum("bsd,dhk->bshk", h, W("wq", ("embed", "heads",
                                                "head_dim")))
    kva = jnp.einsum("bsd,dc->bsc", h, W("wkv_a", ("embed", None)))
    c = rmsnorm(kva[..., :m.kv_rank], p["kv_ln"])
    q_n = q[..., :m.nope_dim]
    q_r = rope(q[..., m.nope_dim:], positions, cfg.rope_theta,
               cfg.rope_scaling)
    k_r = rope(kva[..., None, m.kv_rank:], positions, cfg.rope_theta,
               cfg.rope_scaling)[:, :, 0]
    pad = jnp.zeros((*c.shape[:-1], m.cache_width - m.latent_width), dt)
    rows = jnp.concatenate([c, k_r, pad], axis=-1)          # (B, S, W)
    wuk = W("wuk", (None, "heads", "head_dim"))
    wuv = W("wuv", (None, "heads", "head_dim"))
    scale = score_scale(cfg)
    with jax.named_scope("mla"):
        if cache is None:
            B, S, H = q.shape[:3]
            k_n = jnp.einsum("bsc,chk->bshk", c, wuk)
            v = jnp.einsum("bsc,chk->bshk", c, wuv)
            k = jnp.concatenate(
                [k_n, jnp.broadcast_to(k_r[:, :, None], (B, S, H, m.rope_dim))],
                axis=-1)
            # the kernel scales by q_dim^-1/2; the rest of the scale rides on q
            qf = (jnp.concatenate([q_n, q_r], axis=-1)
                  * (scale * m.q_dim ** 0.5)).astype(dt)
            out = ops.attention(qf, k, v, causal=True,
                                block_q=cfg.attn_block_q,
                                block_kv=cfg.attn_block_kv, backend=backend)
            new = rows
        else:
            new = kvcache.insert(cache, rows[:, 0, None], lengths,
                                 index=layer)
            q_lat = jnp.einsum("bhk,chk->bhc", q_n[:, 0], wuk)
            q_pad = jnp.zeros((*q_lat.shape[:-1], pad.shape[-1]), dt)
            qa = (jnp.concatenate([q_lat, q_r[:, 0], q_pad], axis=-1)
                  * scale).astype(dt)
            rows_all, at = kvcache.kernel_view(new, layer)
            o_lat = ops.mla_decode(qa, rows_all, lengths + 1, layer=at,
                                   value_width=m.kv_rank, backend=backend)
            out = jnp.einsum("bhc,chv->bhv", o_lat.astype(dt), wuv)[:, None]
    out = sharding.constrain(out, rules, ("batch", "seq", "heads",
                                          "head_dim"))
    y = jnp.einsum("bshv,hvd->bsd", out,
                   W("wo", ("heads", "head_dim", "embed")))
    y = sharding.constrain(y, rules, ("batch", "seq", "embed"))
    return x + y, new
