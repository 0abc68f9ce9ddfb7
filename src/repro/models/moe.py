"""Mixture-of-Experts channel mixing: top-k routing over every expert,
computed by the experts this chip holds, plus optional shared experts.

Two dispatches, chosen by entry point:

* Training and full-sequence evaluation (:func:`moe_block`, reached from
  ``transformer.forward`` without a cache) use sort-based capacity
  dispatch: token·expert assignments are sorted by expert id, positions
  within each expert group come from a cumsum over bincounts, tokens
  beyond the per-expert capacity ``round(T·k·capacity_factor/E)`` are
  **dropped** (their combine weight is zero; the residual path carries
  them), and expert FFNs run as one batched einsum over the (experts,
  capacity, d) buffer.  Experts shard over the "experts" logical axis
  (EP over the model mesh axis); the inner FFN dim can additionally
  shard over "expert_mlp" (2-D sharding for huge serving models).
* Serving (:func:`moe_block_served`, reached from ``Model.prefill`` and
  ``Model.decode_step``) **drops nothing**: every assignment to a held
  expert is computed by a grouped matmul over the rows sorted by expert
  (``ops.moe_gmm``), so a served token never depends on which requests
  share its batch.  It reports the rows each held expert computed.

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
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels import ops
from . import sharding
from .layers import dense_init, mlp, rmsnorm


def _ffn(cfg, x_in, wi, wg, wo):
    """Expert FFN over (E_loc, C, d) inputs with (E_loc, d, ff) weights."""
    up = jnp.einsum("ecd,edf->ecf", x_in, wi)
    if cfg.act == "swiglu":
        act = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x_in, wg)) * up
    elif cfg.act == "squared_relu":
        r = jax.nn.relu(up)
        act = r * r
    else:
        act = jax.nn.gelu(up)
    return jnp.einsum("ecf,efd->ecd", act, wo)


def _moe_ep_shardmap(cfg, h, gate_vals, expert_idx, wi, wg, wo, rules, mesh):
    """Expert-parallel dispatch with explicit all_to_all (shard_map).

    Per device: local tokens are sorted by expert, packed into a
    (tp, E_loc, C, d) send buffer grouped by owner rank, exchanged with
    ``all_to_all`` over the expert axis, run through the local experts, and
    returned by the inverse all_to_all — the canonical EP schedule.  GSPMD
    left to its own devices on the HLO scatter materializes the same
    exchange as (T*k, d) all-reduces over the model axis (v1 baseline:
    ~70x the structural-floor bytes).
    """
    mo = cfg.moe
    dt = h.dtype
    T, d = h.shape
    E, k = mo.n_experts, mo.top_k
    mesh_axes = tuple(mesh.axis_names)
    tp_axis = rules.get("experts")
    batch_axes = tuple(a for a in (rules.get("batch") or ())
                       if a in mesh_axes)
    tp = mesh.shape[tp_axis]
    E_loc = E // tp
    dp = 1
    for a in batch_axes:
        dp *= mesh.shape[a]
    T_loc = T // dp
    # tokens are replicated over the expert axis: each tp rank dispatches
    # its own 1/tp chunk (otherwise every owner receives tp identical
    # copies and expert compute inflates tp-fold).
    chunk = T_loc // tp
    cap = int(max(1, round(chunk * k * mo.capacity_factor / E)))

    def body(h_l, gates_l, idx_l, wi_l, wg_l, wo_l):
        r = jax.lax.axis_index(tp_axis)
        h_c = jax.lax.dynamic_slice_in_dim(h_l, r * chunk, chunk, 0)
        gates_c = jax.lax.dynamic_slice_in_dim(gates_l, r * chunk, chunk, 0)
        idx_c = jax.lax.dynamic_slice_in_dim(idx_l, r * chunk, chunk, 0)
        flat_e = idx_c.reshape(chunk * k)
        order = jnp.argsort(flat_e)
        sorted_e = flat_e[order]
        counts = (sorted_e[:, None] == jnp.arange(E)[None]).sum(0)
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(chunk * k) - starts[sorted_e]
        keep = pos < cap
        tok = order // k
        owner = sorted_e // E_loc
        e_loc = sorted_e % E_loc
        we = jnp.where(keep, owner, 0)
        wl = jnp.where(keep, e_loc, 0)
        wc = jnp.where(keep, pos, 0)
        src = jnp.where(keep[:, None], h_c[tok], 0)
        send = jnp.zeros((tp, E_loc, cap, d), dt).at[we, wl, wc].add(src)
        recv = jax.lax.all_to_all(send, tp_axis, 0, 0, tiled=False)             if tp > 1 else send
        x_in = recv.transpose(1, 0, 2, 3).reshape(E_loc, tp * cap, d)
        out = _ffn(cfg, x_in, wi_l, wg_l, wo_l)
        out_send = out.reshape(E_loc, tp, cap, d).transpose(1, 0, 2, 3)
        back = jax.lax.all_to_all(out_send, tp_axis, 0, 0, tiled=False)             if tp > 1 else out_send
        gathered = back[we, wl, wc]
        gathered = jnp.where(keep[:, None], gathered, 0)
        gates_sorted = gates_c.reshape(chunk * k)[order]
        y_c = jnp.zeros((chunk, d), dt).at[tok].add(
            gathered * gates_sorted[:, None].astype(dt))
        if tp > 1:   # reassemble the T_loc tokens from the tp chunks
            return jax.lax.all_gather(y_c, tp_axis, axis=0, tiled=True)
        return y_c

    bspec = P(batch_axes if len(batch_axes) > 1 else
              (batch_axes[0] if batch_axes else None))
    wspec = P(tp_axis)
    y = jax.shard_map(
        body, mesh=mesh,
        in_specs=(bspec, bspec, bspec, wspec, wspec, wspec),
        out_specs=bspec, check_vma=False,
    )(h, gate_vals.astype(dt), expert_idx, wi,
      wg if wg is not None else wi, wo)
    return y


def init_moe(cfg: ModelConfig, key):
    mo = cfg.moe
    d, ff, E, En = cfg.d_model, cfg.expert_ff, mo.n_experts, mo.n_held
    pdt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 5)
    p, s = {}, {}
    p["ln"], s["ln"] = jnp.zeros((d,), pdt), ("embed",)
    p["router"], s["router"] = dense_init(ks[0], (d, E),
                                          ("embed", "experts"), pdt)
    if cfg.act == "swiglu":
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
    """Router logits and softmax over all experts, and the top-k gates
    and expert ids of each row of ``h`` (T, d)."""
    mo = cfg.moe
    logits = jnp.dot(h.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)                      # (T, E)
    gate_vals, expert_idx = jax.lax.top_k(probs, mo.top_k)       # (T, k)
    if mo.norm_topk:
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
    return logits, probs, gate_vals, expert_idx


def _shared(cfg: ModelConfig, p, rules, h, shape):
    """The shared experts' output for normed rows ``h``, as ``shape``."""
    if "shared" not in p:
        return 0.0
    return mlp(cfg, p["shared"], rules, h.reshape(shape))


def moe_block_served(cfg: ModelConfig, p, rules, x, *, backend="auto"):
    """Drop-free MoE for serving. x: (B, S, d) -> (x + y, stats) with
    ``stats["moe_rows"]`` (n_held,) int32, the rows each held expert
    computed, and ``stats["moe_dropped"]``, the held assignments left
    uncomputed (0: every one is)."""
    mo = cfg.moe
    if cfg.act != "swiglu":
        raise ValueError("served MoE experts are SwiGLU")
    dt = jnp.dtype(cfg.dtype)
    B, S, d = x.shape
    T, k, En = B * S, mo.top_k, mo.n_held
    h = rmsnorm(x, p["ln"]).astype(dt)
    with jax.named_scope("moe"):
        _, _, gate_vals, expert_idx = _route(cfg, p, h.reshape(T, d))
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


def moe_block(cfg: ModelConfig, p, rules, x):
    """x: (B, S, d) -> (B, S, d) residual-added; returns (y, aux_losses).

    Dispatch is *block-local*: tokens are reshaped to (DP, T_loc, d) where
    DP is the resolved size of the "batch" sharding axes, and sorting /
    position assignment / scatter / combine all happen within a block.
    Every dispatch index then lives on the data shard that owns the block,
    so GSPMD keeps the scatter/gather local instead of materializing
    cross-shard scatter-adds as (T*k, d) all-reduces (the dominant
    collective of the v1 baseline: 12.9 GB/op on dbrx).  Capacity is
    enforced per block (standard local-capacity semantics); DP=1 (tests,
    single host) reduces to the global formulation exactly.
    """
    mo = cfg.moe
    dt = jnp.dtype(cfg.dtype)
    B, S, d = x.shape
    T = B * S
    E, k, En = mo.n_experts, mo.top_k, mo.n_held
    mesh = sharding._current_mesh()
    tp = sharding.resolved_size(rules, "experts")
    dp = sharding.resolved_size(rules, "batch")
    if T % dp:
        dp = 1
    T_loc = T // dp

    h = rmsnorm(x, p["ln"]).astype(dt).reshape(T, d)
    logits, probs, gate_vals, expert_idx = _route(cfg, p, h)
    shared = _shared(cfg, p, rules, h, (B, S, d))

    # ---- aux losses (load balance + router z) ----
    me = probs.mean(axis=0)                                      # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[expert_idx.reshape(-1)].add(
        1.0 / (T * k))
    aux = mo.aux_loss_weight * E * jnp.sum(me * ce)
    zloss = mo.router_z_weight * jnp.mean(
        jax.nn.logsumexp(logits, axis=-1) ** 2)

    # shard_map EP pays off at train/prefill token counts; at decode scale
    # (T ~ batch) the jnp path's scatters are a few MB and the 2-D expert
    # weight sharding (expert_mlp -> data) must stay resident.
    if (mesh is not None and tp > 1 and E % tp == 0 and T % dp == 0
            and (T // dp) % tp == 0 and T // dp >= 2048 and En == E):
        wi = sharding.weight_use(p["wi"].astype(dt), rules,
                                 ("experts", "embed", "expert_mlp"))
        wg = (sharding.weight_use(p["wi_gate"].astype(dt), rules,
                                  ("experts", "embed", "expert_mlp"))
              if cfg.act == "swiglu" else None)
        wo = sharding.weight_use(p["wo"].astype(dt), rules,
                                 ("experts", "expert_mlp", "embed"))
        y = _moe_ep_shardmap(cfg, h, gate_vals, expert_idx, wi, wg, wo,
                             rules, mesh)
        y = y.reshape(B, S, d) + shared
        y = sharding.constrain(y, rules, ("batch", "seq", "embed"))
        return x + y, {"moe_aux": aux, "moe_z": zloss}

    # ---- block-local sort-based dispatch with per-block capacity ----
    cap = int(max(1, round(T_loc * k * mo.capacity_factor / E)))
    h_blk = h.reshape(dp, T_loc, d)
    flat_e = expert_idx.reshape(dp, T_loc * k) - mo.first_held
    # assignments to experts held elsewhere sort last and are left out
    flat_e = jnp.where((flat_e >= 0) & (flat_e < En), flat_e, En)
    order = jnp.argsort(flat_e, axis=1)                          # per block
    sorted_e = jnp.take_along_axis(flat_e, order, axis=1)
    counts = (sorted_e[:, :, None] == jnp.arange(En)[None, None]).sum(1)
    starts = jnp.cumsum(counts, axis=1) - counts                 # (dp, En)
    pos = (jnp.arange(T_loc * k)[None]
           - jnp.take_along_axis(starts, jnp.minimum(sorted_e, En - 1),
                                 axis=1))
    keep = (pos < cap) & (sorted_e < En)
    tok_loc = order // k                                         # (dp, Tk)
    blk = jnp.broadcast_to(jnp.arange(dp)[:, None], tok_loc.shape)

    write_e = jnp.where(keep, sorted_e, 0)
    write_c = jnp.where(keep, pos, 0)
    src = jnp.take_along_axis(h_blk, tok_loc[..., None], axis=1)
    src = jnp.where(keep[..., None], src, 0)
    buf = jnp.zeros((En, dp, cap, d), dt)
    buf = buf.at[write_e, blk, write_c].add(src.astype(dt))
    buf = sharding.constrain(buf, rules, ("experts", "batch", None, "embed"))

    # ---- expert FFNs (weights gathered from fsdp storage) ----
    wi = sharding.weight_use(p["wi"].astype(dt), rules,
                             ("experts", "embed", "expert_mlp"))
    up = jnp.einsum("ebcd,edf->ebcf", buf, wi)
    if cfg.act == "swiglu":
        wg = sharding.weight_use(p["wi_gate"].astype(dt), rules,
                                 ("experts", "embed", "expert_mlp"))
        act = jax.nn.silu(jnp.einsum("ebcd,edf->ebcf", buf, wg)) * up
    elif cfg.act == "squared_relu":
        r = jax.nn.relu(up)
        act = r * r
    else:
        act = jax.nn.gelu(up)
    act = sharding.constrain(act, rules,
                             ("experts", "batch", None, "expert_mlp"))
    wo = sharding.weight_use(p["wo"].astype(dt), rules,
                             ("experts", "expert_mlp", "embed"))
    out_buf = jnp.einsum("ebcf,efd->ebcd", act, wo)
    out_buf = sharding.constrain(out_buf, rules,
                                 ("experts", "batch", None, "embed"))

    # ---- block-local combine ----
    gathered = out_buf[write_e, blk, write_c]                    # (dp,Tk,d)
    gathered = jnp.where(keep[..., None], gathered, 0)
    gates_sorted = jnp.take_along_axis(
        gate_vals.reshape(dp, T_loc * k), order, axis=1)
    y = jnp.zeros((dp, T_loc, d), dt).at[blk, tok_loc].add(
        gathered * gates_sorted[..., None].astype(dt))
    y = y.reshape(B, S, d) + shared
    y = sharding.constrain(y, rules, ("batch", "seq", "embed"))
    return x + y, {"moe_aux": aux, "moe_z": zloss}
