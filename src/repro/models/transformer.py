"""Layer stack assembly: scan-over-groups, remat, heterogeneous patterns.

The repeating ``cfg.block_pattern`` (e.g. ("rglru","rglru","attn") for
recurrentgemma) defines a *supergroup*; parameters of all full supergroups
are stacked on a leading group axis and executed with ``jax.lax.scan``
(compact HLO, fast SPMD compile); pattern-remainder tail layers run
unrolled.  A model with ``cfg.first_dense`` leading dense-MLP layers (an
MoE model whose first layers have no experts) runs those unrolled before
the scan, under the params and cache key ``"lead"``, which only such a
model has.  Every layer kind exposes the same interface:

    apply_layer(cfg, kind, params, rules, x, positions,
                cache=None, lengths=None, backend) -> (x, new_cache, aux)

with cache pytrees per kind (attention: kv cache views; mla: one latent
cache view; rglru: h + conv state; rwkv: matrix state + token-shift
carries).  The serving entry points (prefill, decode) run MoE layers
drop-free and return what each held expert computed; training and
full-sequence evaluation run the capacity dispatch
(:mod:`repro.models.moe`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from . import kvcache, layers, mla, moe, recurrent

#: per-layer statistics of the served MoE, collected layer by layer (the
#: other aux entries are losses, summed over layers)
STATS = ("moe_rows", "moe_dropped")


# ---------------------------------------------------------------------------
# per-layer init / apply
# ---------------------------------------------------------------------------

def init_layer(cfg: ModelConfig, kind: str, key, dense: bool = False):
    """One layer's (params, specs); ``dense`` gives an MoE model's layer a
    dense ``d_ff`` MLP (its leading layers)."""
    kt, kc = jax.random.split(key)
    p, s = {}, {}
    if kind in ("attn", "local"):
        p["t"], s["t"] = layers.init_attention(cfg, kt)
    elif kind == "mla":
        p["t"], s["t"] = mla.init_mla(cfg, kt)
    elif kind == "rglru":
        p["t"], s["t"] = recurrent.init_rglru(cfg, kt)
    elif kind == "rwkv":
        p["t"], s["t"] = recurrent.init_rwkv(cfg, kt)
    else:
        raise ValueError(kind)
    if kind != "rwkv":                     # rwkv carries its own channel mix
        if cfg.moe is not None and not dense:
            p["c"], s["c"] = moe.init_moe(cfg, kc)
        else:
            p["c"], s["c"] = layers.init_mlp(cfg, kc)
    return p, s


def apply_layer(cfg: ModelConfig, kind: str, p, rules, x, positions, *,
                cache=None, lengths=None, layer=None, collect_kv=False,
                backend="auto", cache_capacity=None):
    """One layer.  With ``layer`` the cache leaves are the decode scan's
    stacks ``(L, ...)`` and this layer is ``layer`` of them: attention
    kinds write their new row into the stack and read it in place; the
    recurrent kinds, whose state is small, take their layer out and put
    it back."""
    aux = {}
    new_cache = None
    served = cache is not None or collect_kv
    if layer is not None and kind in ("rglru", "rwkv"):
        stack = cache
        cache = jax.tree.map(lambda a: a[layer], stack)
    if kind in ("attn", "local"):
        att_cache = None if cache is None else (cache["k"], cache["v"])
        x, kv = layers.attention_block(
            cfg, p["t"], rules, x, positions, kind=kind, cache=att_cache,
            lengths=lengths, layer=layer, backend=backend)
        if cache is not None:
            new_cache = {"k": kv[0], "v": kv[1]}
        elif collect_kv:
            cap = cache_capacity or x.shape[1]
            kc, vc = kvcache.from_prefill(
                kv[0], kv[1], cap, cfg.kv_cache_dtype,
                cfg.local_window if kind == "local" else None)
            new_cache = {"k": kc, "v": vc}
    elif kind == "mla":
        x, rows = mla.mla_block(cfg, p["t"], rules, x, positions,
                                cache=cache, lengths=lengths, layer=layer,
                                backend=backend)
        if cache is not None:
            new_cache = rows
        elif collect_kv:
            new_cache = kvcache.latent_from_prefill(
                rows, cache_capacity or x.shape[1], cfg.kv_cache_dtype)
    elif kind == "rglru":
        x, st = recurrent.rglru_block(cfg, p["t"], rules, x,
                                      state=cache, backend=backend)
        new_cache = st if (cache is not None or collect_kv) else None
    elif kind == "rwkv":
        x, st = recurrent.rwkv_block(cfg, p["t"], rules, x,
                                     state=cache, backend=backend)
        new_cache = st if (cache is not None or collect_kv) else None
    if layer is not None and kind in ("rglru", "rwkv"):
        new_cache = jax.tree.map(lambda a, n: a.at[layer].set(n), stack,
                                 new_cache)
    if "c" in p:
        if "router" not in p["c"]:
            x = layers.mlp_block(cfg, p["c"], rules, x)
        elif served:
            x, aux = moe.moe_block_served(cfg, p["c"], rules, x,
                                          backend=backend)
        else:
            x, aux = moe.moe_block(cfg, p["c"], rules, x)
    return x, new_cache, aux


def _split_aux(aux):
    """(losses, per-layer statistics) of one layer's aux."""
    return ({k: v for k, v in aux.items() if k not in STATS},
            {k: v for k, v in aux.items() if k in STATS})


def _gather_stats(per_layer):
    """One dict of the served MoE statistics over the layers: each a list
    of per-layer values or of scan-stacked ones."""
    if not per_layer:
        return {}
    rows = jnp.concatenate([s["moe_rows"].reshape(-1, s["moe_rows"].shape[-1])
                            for s in per_layer], axis=0)
    dropped = sum(jnp.sum(s["moe_dropped"]) for s in per_layer)
    return {"moe_rows": rows, "moe_dropped": dropped}


# ---------------------------------------------------------------------------
# stack init
# ---------------------------------------------------------------------------

def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def init_stack(cfg: ModelConfig, key):
    """Returns (params, specs).  params = {"emb", "groups", "tail"}, and
    "lead" (the leading dense layers) where ``cfg.first_dense``."""
    kinds = cfg.layer_kinds
    n_lead = cfg.first_dense
    body = kinds[n_lead:]
    P = len(cfg.block_pattern) if cfg.scan_layers else 1
    n_groups = len(body) // P if cfg.scan_layers else 0
    n_scanned = n_groups * P

    keys = jax.random.split(key, len(kinds) + 1)
    p_emb, s_emb = layers.init_embeddings(cfg, keys[-1])
    params = {"emb": p_emb}
    specs = {"emb": s_emb}

    if n_lead:
        lead = [init_layer(cfg, kinds[li], keys[li], dense=True)
                for li in range(n_lead)]
        params["lead"] = tuple(lp for lp, _ in lead)
        specs["lead"] = tuple(ls for _, ls in lead)

    if cfg.scan_layers and n_groups > 0:
        groups, gspecs = [], []
        for pos in range(P):
            per_pos = []
            for g in range(n_groups):
                li = n_lead + g * P + pos
                lp, ls = init_layer(cfg, cfg.block_pattern[pos], keys[li])
                per_pos.append(lp)
                gspecs_pos = ls
            groups.append(_stack(per_pos))
            # prepend the scan ("layers") axis to every logical tuple
            gspecs.append(jax.tree.map(
                lambda lg: ("layers",) + lg, gspecs_pos,
                is_leaf=lambda x: isinstance(x, tuple) and len(x) > 0
                and all(isinstance(e, (str, type(None))) for e in x)))
        params["groups"] = tuple(groups)
        specs["groups"] = tuple(gspecs)
    else:
        n_scanned = 0
        params["groups"] = ()
        specs["groups"] = ()

    tail_p, tail_s = [], []
    for li in range(n_lead + n_scanned, len(kinds)):
        lp, ls = init_layer(cfg, kinds[li], keys[li])
        tail_p.append(lp)
        tail_s.append(ls)
    params["tail"] = tuple(tail_p)
    specs["tail"] = tuple(tail_s)
    return params, specs


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------

def _is_spec_leaf(x):
    return isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)


def forward(cfg: ModelConfig, params, batch, rules, *, backend="auto",
            collect_kv=False, last_only=False, cache_capacity=None):
    """Full-sequence forward (train / prefill).

    Returns (logits, caches, aux) — caches is None unless collect_kv, the
    prefill entry point, which also serves MoE layers drop-free and puts
    their per-layer statistics (``STATS``) in aux.
    """
    x = layers.embed_tokens(cfg, params["emb"], rules, batch)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    kinds = cfg.layer_kinds
    P = len(cfg.block_pattern)
    aux_total = {"moe_aux": 0.0, "moe_z": 0.0}
    stats = []

    def add(aux):
        losses, st = _split_aux(aux)
        for k in losses:
            aux_total[k] = aux_total.get(k, 0.0) + losses[k]
        if st:
            stats.append(st)

    def unrolled(x, lp, kind):
        def fn(x_, lp_):
            return apply_layer(cfg, kind, lp_, rules, x_, positions,
                               collect_kv=collect_kv, backend=backend,
                               cache_capacity=cache_capacity)

        if cfg.remat:   # cost-parity with the checkpointed scan groups
            fn = jax.checkpoint(fn)
        return fn(x, lp)

    lead_caches = []
    for i, lp in enumerate(params.get("lead", ())):
        x, cache, aux = unrolled(x, lp, kinds[i])
        lead_caches.append(cache)
        add(aux)

    group_caches = None
    if params["groups"]:
        def group_body(carry, group_params):
            x, aux_in = carry
            new_caches, new_stats = [], []
            for pos in range(P):
                kind = cfg.block_pattern[pos]
                x, cache, aux = apply_layer(
                    cfg, kind, group_params[pos], rules, x, positions,
                    collect_kv=collect_kv, backend=backend,
                    cache_capacity=cache_capacity)
                new_caches.append(cache)
                losses, st = _split_aux(aux)
                for k in losses:
                    aux_in = dict(aux_in, **{k: aux_in.get(k, 0.0)
                                             + losses[k]})
                if st:
                    new_stats.append(st)
            return (x, aux_in), (tuple(new_caches) if collect_kv else None,
                                 tuple(new_stats))

        body = jax.checkpoint(group_body) if cfg.remat else group_body
        (x, aux_total), (group_caches, group_stats) = jax.lax.scan(
            body, (x, aux_total), params["groups"])
        stats.extend(group_stats)

    tail_caches = []
    n_scanned = len(kinds) - len(params["tail"])
    for i, lp in enumerate(params["tail"]):
        x, cache, aux = unrolled(x, lp, kinds[n_scanned + i])
        tail_caches.append(cache)
        add(aux)

    if last_only:
        x = x[:, -1:]
    logits = layers.logits_head(cfg, params["emb"], rules, x)
    caches = None
    if collect_kv:
        caches = {"groups": group_caches, "tail": tuple(tail_caches)}
        if "lead" in params:
            caches["lead"] = tuple(lead_caches)
    return logits, caches, dict(aux_total, **_gather_stats(stats))


def decode_step(cfg: ModelConfig, params, caches, batch, rules, *,
                backend="auto"):
    """One-token decode. batch: {"token_ids": (B,1) or "embeds",
    "lengths": (B,)}.  Returns (logits (B,1,V), new caches, stats): the
    served MoE layers' statistics (``STATS``), empty without MoE."""
    lengths = batch["lengths"]
    x = layers.embed_tokens(cfg, params["emb"], rules, batch)
    positions = lengths[:, None]                      # (B,1) absolute pos
    kinds = cfg.layer_kinds
    P = len(cfg.block_pattern)
    stats = []

    def one(x, kind, lp, cache):
        x, cache, aux = apply_layer(cfg, kind, lp, rules, x, positions,
                                    cache=cache, lengths=lengths,
                                    backend=backend)
        st = _split_aux(aux)[1]
        if st:
            stats.append(st)
        return x, cache

    new_lead = []
    for i, lp in enumerate(params.get("lead", ())):
        x, cache = one(x, kinds[i], lp, caches["lead"][i])
        new_lead.append(cache)

    new_group_caches = None
    if params["groups"]:
        # the stacked caches ride in the carry and each layer writes its
        # one new row into them: scanning them as inputs and outputs would
        # slice every layer out whole and write it back whole, every step
        def group_body(carry, scanned):
            x, group_cache = carry
            group_params, g = scanned
            new_caches, new_stats = [], []
            for pos in range(P):
                kind = cfg.block_pattern[pos]
                x, cache, aux = apply_layer(
                    cfg, kind, group_params[pos], rules, x, positions,
                    cache=group_cache[pos], lengths=lengths, layer=g,
                    backend=backend)
                new_caches.append(cache)
                st = _split_aux(aux)[1]
                if st:
                    new_stats.append(st)
            return (x, tuple(new_caches)), tuple(new_stats)

        n_groups = jax.tree.leaves(params["groups"])[0].shape[0]
        (x, new_group_caches), group_stats = jax.lax.scan(
            group_body, (x, caches["groups"]),
            (params["groups"], jnp.arange(n_groups)))
        stats.extend(group_stats)

    new_tail = []
    n_scanned = len(kinds) - len(params["tail"])
    for i, lp in enumerate(params["tail"]):
        x, cache = one(x, kinds[n_scanned + i], lp, caches["tail"][i])
        new_tail.append(cache)

    logits = layers.logits_head(cfg, params["emb"], rules, x)
    new = {"groups": new_group_caches, "tail": tuple(new_tail)}
    if "lead" in params:
        new["lead"] = tuple(new_lead)
    return logits, new, _gather_stats(stats)


def loss_fn(cfg: ModelConfig, params, batch, rules, *, backend="auto"):
    logits, _, aux = forward(cfg, params, batch, rules, backend=backend)
    labels = batch["labels"]
    mask = batch.get("mask")
    loss, metrics = layers.cross_entropy(cfg, logits, labels, mask)
    for k, v in aux.items():
        loss = loss + v
        metrics[k] = v
    metrics["loss"] = loss
    return loss, metrics
