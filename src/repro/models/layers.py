"""Primitive layers: norms, rotary embeddings, attention blocks, MLPs.

Functional style: ``init_*`` builds ``(params, specs)`` where ``specs``
mirrors the param tree with tuples of *logical* axis names, the names
:mod:`repro.models.sharding` resolves against a mesh.  Forward functions
are pure.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from . import sharding

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def dense_init(key, shape, logical, dtype, fan_in_axes=(0,)):
    fan_in = 1
    for a in fan_in_axes:
        fan_in *= shape[a]
    return _normal(key, shape, fan_in ** -0.5, dtype), tuple(logical)


# ---------------------------------------------------------------------------
# norms / rotary
# ---------------------------------------------------------------------------


def rmsnorm(x, scale, eps: float = 1e-6):
    """RMSNorm with f32 math inside, in x's dtype outside."""
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * inv * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def yarn_freq(d: int, theta: float, yarn):
    """YaRN's rotary frequencies for a ``d``-wide rotary vector: pairs
    below the ramp keep ``theta^(-2i/d)``, pairs above it are divided by
    ``yarn.factor``, linearly in between."""
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    low, high = yarn.ramp_bounds(d, theta)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return freq / yarn.factor * ramp + freq * (1.0 - ramp)


def rope(x, positions, theta: float, yarn=None):
    """x: (B, S, H, D); positions: (B, S) or (S,).  ``yarn``: a
    :class:`~repro.configs.base.YarnConfig` scaling the frequencies."""
    d = x.shape[-1]
    half = d // 2
    if yarn is None:
        freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freq = yarn_freq(d, theta, yarn)
    ang = positions.astype(jnp.float32)[..., None] * freq      # (B,S,half)
    cos = jnp.cos(ang)[..., None, :]                           # (B,S,1,half)
    sin = jnp.sin(ang)[..., None, :]
    if yarn is not None and yarn.cos_sin_scale != 1.0:
        cos, sin = cos * yarn.cos_sin_scale, sin * yarn.cos_sin_scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention block (full / local / bidirectional; GQA; qkv bias)
# ---------------------------------------------------------------------------


def init_attention(cfg: ModelConfig, key):
    d, hq, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = jax.random.split(key, 5)
    pdt = jnp.dtype(cfg.param_dtype)
    p, s = {}, {}
    p["ln"], s["ln"] = jnp.zeros((d,), pdt), ("embed",)
    p["wq"], s["wq"] = dense_init(ks[0], (d, hq, dh),
                                  ("embed", "heads", "head_dim"), pdt)
    p["wk"], s["wk"] = dense_init(ks[1], (d, hkv, dh),
                                  ("embed", "kv_heads", "head_dim"), pdt)
    p["wv"], s["wv"] = dense_init(ks[2], (d, hkv, dh),
                                  ("embed", "kv_heads", "head_dim"), pdt)
    p["wo"], s["wo"] = dense_init(ks[3], (hq, dh, d),
                                  ("heads", "head_dim", "embed"), pdt,
                                  fan_in_axes=(0, 1))
    if cfg.qkv_bias:
        p["bq"], s["bq"] = jnp.zeros((hq, dh), pdt), ("heads", "head_dim")
        p["bk"], s["bk"] = jnp.zeros((hkv, dh), pdt), ("kv_heads", "head_dim")
        p["bv"], s["bv"] = jnp.zeros((hkv, dh), pdt), ("kv_heads", "head_dim")
    return p, s


def attention_block(cfg: ModelConfig, p, rules, x, positions, *,
                    kind: str, cache=None, lengths=None, layer=None,
                    backend="auto"):
    """Pre-norm attention residual block.

    Prefill: ``cache is None`` — self-attention over x; returns
    (y, (k, v)) so prefill can build the cache.
    Decode: ``cache = (k_cache, v_cache)``, :mod:`~repro.models.kvcache`
    layer views stored lane-dense as ``(B, S, Hkv·D)`` — or, with
    ``layer``, the stacked views ``(L, B, S, Hkv·D)`` of the decode scan
    and this layer's index in them — and ``lengths`` (B,) = tokens
    already cached; the new token's k/v are inserted at ``lengths`` and
    attention runs over ``lengths + 1`` on the layers as stored, with no
    re-layout and no slice of the layer.
    """
    dt = jnp.dtype(cfg.dtype)
    h = rmsnorm(x, p["ln"]).astype(dt)

    def W(name, logical):
        return sharding.weight_use(p[name].astype(dt), rules, logical)

    q = jnp.einsum("bsd,dhk->bshk", h, W("wq", ("embed", "heads",
                                                "head_dim")))
    k = jnp.einsum("bsd,dhk->bshk", h, W("wk", ("embed", "kv_heads",
                                                "head_dim")))
    v = jnp.einsum("bsd,dhk->bshk", h, W("wv", ("embed", "kv_heads",
                                                "head_dim")))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    q = sharding.constrain(q, rules, ("batch", "seq", "heads", "head_dim"))
    k = sharding.constrain(k, rules, ("batch", "seq", "kv_heads", "head_dim"))

    causal = not cfg.bidirectional
    window = cfg.local_window if kind == "local" else None
    if cache is None:
        out = ops.attention(q, k, v, causal=causal, window=window,
                            block_q=cfg.attn_block_q,
                            block_kv=cfg.attn_block_kv, backend=backend)
        new_kv = (k, v)
    else:
        from . import kvcache
        kc, vc = cache
        kc = kvcache.insert(kc, k[:, 0], lengths, window, index=layer)
        vc = kvcache.insert(vc, v[:, 0], lengths, window, index=layer)
        if kind == "local":
            eff_len = jnp.minimum(lengths + 1, kvcache.size(kc))
        else:
            eff_len = lengths + 1
        k_all, at = kvcache.kernel_view(kc, layer)
        v_all, _ = kvcache.kernel_view(vc, layer)
        out = ops.decode_attention(q, k_all, v_all, eff_len, layer=at,
                                   backend=backend)
        new_kv = (kc, vc)
    out = sharding.constrain(out, rules, ("batch", "seq", "heads",
                                          "head_dim"))
    y = jnp.einsum("bshk,hkd->bsd", out,
                   sharding.weight_use(p["wo"].astype(dt), rules,
                                       ("heads", "head_dim", "embed")))
    y = sharding.constrain(y, rules, ("batch", "seq", "embed"))
    return x + y, new_kv


# ---------------------------------------------------------------------------
# MLP block (swiglu / squared_relu / gelu)
# ---------------------------------------------------------------------------


def init_mlp(cfg: ModelConfig, key):
    d, ff = cfg.d_model, cfg.d_ff
    pdt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 3)
    p, s = {}, {}
    p["ln"], s["ln"] = jnp.zeros((d,), pdt), ("embed",)
    if cfg.act == "swiglu":
        p["wi_gate"], s["wi_gate"] = dense_init(ks[0], (d, ff),
                                                ("embed", "mlp"), pdt)
    p["wi"], s["wi"] = dense_init(ks[1], (d, ff), ("embed", "mlp"), pdt)
    p["wo"], s["wo"] = dense_init(ks[2], (ff, d), ("mlp", "embed"), pdt)
    return p, s


def _act(cfg, gate, up):
    if cfg.act == "swiglu":
        return jax.nn.silu(gate) * up
    if cfg.act == "squared_relu":
        r = jax.nn.relu(up)
        return r * r
    if cfg.act == "gelu":
        return jax.nn.gelu(up)
    raise ValueError(cfg.act)


def mlp_block(cfg: ModelConfig, p, rules, x):
    dt = jnp.dtype(cfg.dtype)
    h = rmsnorm(x, p["ln"]).astype(dt)
    return x + mlp(cfg, p, rules, h)


def mlp(cfg: ModelConfig, p, rules, h):
    """The MLP of ``p`` on normed activations ``h``, without residual."""
    dt = h.dtype
    up = h @ sharding.weight_use(p["wi"].astype(dt), rules,
                                 ("embed", "mlp"))
    gate = (h @ sharding.weight_use(p["wi_gate"].astype(dt), rules,
                                    ("embed", "mlp"))
            if cfg.act == "swiglu" else None)
    a = _act(cfg, gate, up)
    a = sharding.constrain(a, rules, ("batch", "seq", "mlp"))
    y = a @ sharding.weight_use(p["wo"].astype(dt), rules,
                                ("mlp", "embed"))
    return sharding.constrain(y, rules, ("batch", "seq", "embed"))


# ---------------------------------------------------------------------------
# embeddings / head
# ---------------------------------------------------------------------------


def init_embeddings(cfg: ModelConfig, key):
    pdt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    p, s = {}, {}
    if not cfg.embeds_only:
        p["tok"], s["tok"] = (_normal(ks[0], (cfg.vocab, cfg.d_model), 0.02,
                                      pdt), ("vocab", "embed"))
    p["final_ln"], s["final_ln"] = jnp.zeros((cfg.d_model,), pdt), ("embed",)
    if not cfg.tie_embeddings:
        p["head"], s["head"] = dense_init(ks[1], (cfg.d_model, cfg.vocab),
                                          ("embed", "vocab"), pdt)
    if cfg.mm_prefix:
        p["mm_proj"], s["mm_proj"] = dense_init(
            ks[2], (cfg.mm_embed_dim, cfg.d_model), ("embed", None), pdt)
    return p, s


def embed_tokens(cfg: ModelConfig, p, rules, batch):
    dt = jnp.dtype(cfg.dtype)
    if cfg.embeds_only:
        x = batch["embeds"].astype(dt)
    else:
        tok = sharding.weight_use(p["tok"].astype(dt), rules,
                                  ("vocab", "embed"))
        x = tok[batch["token_ids"]]
        if cfg.mm_prefix and "mm_embeds" in batch:
            proj = batch["mm_embeds"].astype(dt) @ p["mm_proj"].astype(dt)
            prefix = min(cfg.mm_prefix, x.shape[1])
            x = x.at[:, :prefix].set(proj[:, :prefix])
    return sharding.constrain(x, rules, ("batch", "seq", "embed"))


def logits_head(cfg: ModelConfig, p, rules, x):
    h = rmsnorm(x, p["final_ln"])
    if cfg.tie_embeddings:
        w = sharding.weight_use(p["tok"], rules, ("vocab", "embed")).T
    else:
        w = sharding.weight_use(p["head"], rules, ("embed", "vocab"))
    logits = jnp.einsum("bsd,dv->bsv", h.astype(jnp.float32),
                        w.astype(jnp.float32))
    return sharding.constrain(logits, rules, ("batch", "seq", "vocab"))

