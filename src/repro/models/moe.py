"""Mixture-of-Experts channel mixing: top-k routing over every expert,
computed by the experts this chip holds, plus optional shared experts.

One dispatch, :func:`moe_block`, which **drops nothing**: every
assignment to a held expert is computed by a grouped matmul over the
rows sorted by expert (``ops.moe_gmm``), so a token's output never
depends on which tokens share its batch.  It reports the rows each held
expert computed.  Experts are SwiGLU (``ModelConfig`` holds MoE models to
it).

The router always scores all ``n_experts``; a chip holding the share
``[first_held, first_held + n_held)`` computes those experts' part of the
result and leaves out what the absent ones would add (expert parallelism
without its exchange).  Gates are the softmax mass of the top k,
renormalised unless ``norm_topk`` is off.  Shared experts run on every
token as one SwiGLU of ``n_shared`` experts' width.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from . import sharding
from .layers import dense_init, mlp, rmsnorm


def init_moe(cfg: ModelConfig, key):
    mo = cfg.moe
    d, ff, E, En = cfg.d_model, cfg.expert_ff, mo.n_experts, mo.n_held
    pdt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 5)
    p, s = {}, {}
    p["ln"], s["ln"] = jnp.zeros((d,), pdt), ("embed",)
    p["router"], s["router"] = dense_init(ks[0], (d, E),
                                          ("embed", "experts"), pdt)
    p["wi_gate"], s["wi_gate"] = dense_init(
        ks[1], (En, d, ff), ("experts", "embed", "expert_mlp"), pdt,
        fan_in_axes=(1,))
    p["wi"], s["wi"] = dense_init(ks[2], (En, d, ff),
                                  ("experts", "embed", "expert_mlp"), pdt,
                                  fan_in_axes=(1,))
    p["wo"], s["wo"] = dense_init(ks[3], (En, ff, d),
                                  ("experts", "expert_mlp", "embed"), pdt,
                                  fan_in_axes=(1,))
    if mo.n_shared:
        sff = mo.n_shared * ff
        kg, ki, ko = jax.random.split(ks[4], 3)
        sh, ss = {}, {}
        sh["wi_gate"], ss["wi_gate"] = dense_init(kg, (d, sff),
                                                  ("embed", "mlp"), pdt)
        sh["wi"], ss["wi"] = dense_init(ki, (d, sff), ("embed", "mlp"), pdt)
        sh["wo"], ss["wo"] = dense_init(ko, (sff, d), ("mlp", "embed"), pdt)
        p["shared"], s["shared"] = sh, ss
    return p, s


def _route(cfg: ModelConfig, p, h):
    """The top-k gates and expert ids of each row of ``h`` (T, d), from
    the softmax over all experts."""
    mo = cfg.moe
    logits = jnp.dot(h.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)                      # (T, E)
    gate_vals, expert_idx = jax.lax.top_k(probs, mo.top_k)       # (T, k)
    if mo.norm_topk:
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
    return gate_vals, expert_idx


def _shared(cfg: ModelConfig, p, rules, h, shape):
    """The shared experts' output for normed rows ``h``, as ``shape``."""
    if "shared" not in p:
        return 0.0
    return mlp(cfg, p["shared"], rules, h.reshape(shape))


def moe_block(cfg: ModelConfig, p, rules, x, *, backend="auto"):
    """Drop-free MoE. x: (B, S, d) -> (x + y, stats) with
    ``stats["moe_rows"]`` (n_held,) int32, the rows each held expert
    computed, and ``stats["moe_dropped"]``, the held assignments left
    uncomputed (0: every one is)."""
    mo = cfg.moe
    dt = jnp.dtype(cfg.dtype)
    B, S, d = x.shape
    T, k, En = B * S, mo.top_k, mo.n_held
    h = rmsnorm(x, p["ln"]).astype(dt)
    with jax.named_scope("moe"):
        gate_vals, expert_idx = _route(cfg, p, h.reshape(T, d))
        local = expert_idx.reshape(T * k) - mo.first_held
        held = (local >= 0) & (local < En)
        group = jnp.where(held, local, En)          # rows held elsewhere last
        order = jnp.argsort(group, stable=True)
        sizes = (group[:, None] == jnp.arange(En)[None]).sum(
            0, dtype=jnp.int32)
        tok = order // k
        rows = h.reshape(T, d)[tok]
        W = [sharding.weight_use(p[n].astype(dt), rules, lg) for n, lg in (
            ("wi_gate", ("experts", "embed", "expert_mlp")),
            ("wi", ("experts", "embed", "expert_mlp")),
            ("wo", ("experts", "expert_mlp", "embed")))]
        out = ops.moe_gmm(rows, *W, sizes, backend=backend)      # (T·k, d)
        # back to (token, choice) order: a gather, where a scatter-add
        # would serialise on the TPU; rows held elsewhere are zeros
        out = out[jnp.argsort(order)].reshape(T, k, d)
        gate = jnp.where(held.reshape(T, k), gate_vals, 0.0)
        y = (out * gate[..., None]).sum(1)        # float32, not an MXU pass
        y = y.reshape(B, S, d).astype(dt) + _shared(cfg, p, rules, h,
                                                    (B, S, d))
    y = sharding.constrain(y, rules, ("batch", "seq", "embed"))
    stats = {"moe_rows": sizes,
             "moe_dropped": jnp.sum(held, dtype=jnp.int32) - jnp.sum(sizes)}
    return x + y, stats

