"""Layer stack assembly: scan-over-groups, heterogeneous patterns, and
the one walker that both serving entry points run.

The repeating ``cfg.block_pattern`` (e.g. ("rglru","rglru","attn") for
recurrentgemma) defines a *supergroup*; parameters of all full supergroups
are stacked on a leading group axis and executed with ``jax.lax.scan``
(compact HLO, fast compile); pattern-remainder tail layers run unrolled.
A model with ``cfg.first_dense`` leading dense-MLP layers (an MoE model
whose first layers have no experts) runs those unrolled before the scan,
under the params and cache key ``"lead"``, which only such a model has.

:func:`_walk` runs lead → scanned groups → tail and builds the
``{"lead", "groups", "tail"}`` cache tree once.  What a layer does with
its cache is the only thing the three modes change:

* no cache (:func:`forward`): each layer over the whole sequence;
* emit a cache (:func:`forward` with ``capacity``, the prefill): each
  layer also returns its cache at that capacity, and the scan emits the
  groups' caches as its outputs, stacked;
* carry the cache (:func:`decode_step`): the stacked group caches ride
  in the scan's carry, and layer ``g`` writes its one new row into them
  in place.

Every layer kind exposes the same interface (:func:`apply_layer`), with
cache pytrees per kind (attention: kv cache views; mla: one latent cache
view; rglru: h + conv state; rwkv: matrix state + token-shift carries).
MoE layers run the drop-free dispatch (:mod:`repro.models.moe`) and
return their per-layer statistics, ``moe_rows`` and ``moe_dropped``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from . import kvcache, layers, mla, moe, recurrent


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
                cache=None, lengths=None, layer=None, capacity=None,
                backend="auto"):
    """One layer: (x, new cache, stats).  Without ``cache``, over the
    whole sequence, returning this layer's cache at ``capacity`` slots
    (None without ``capacity``).  With ``cache``, one decode token at
    ``lengths``; with ``layer`` the cache leaves are the decode scan's
    stacks ``(L, ...)`` and this layer is ``layer`` of them: attention
    kinds write their new row into the stack and read it in place; the
    recurrent kinds, whose state is small, take their layer out and put
    it back.  ``stats`` holds an MoE layer's ``moe_rows`` and
    ``moe_dropped``, else is empty."""
    stats = {}
    new_cache = None
    emit = capacity is not None
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
        elif emit:
            kc, vc = kvcache.from_prefill(
                kv[0], kv[1], capacity, cfg.kv_cache_dtype,
                cfg.local_window if kind == "local" else None)
            new_cache = {"k": kc, "v": vc}
    elif kind == "mla":
        x, rows = mla.mla_block(cfg, p["t"], rules, x, positions,
                                cache=cache, lengths=lengths, layer=layer,
                                backend=backend)
        if cache is not None:
            new_cache = rows
        elif emit:
            new_cache = kvcache.latent_from_prefill(rows, capacity,
                                                    cfg.kv_cache_dtype)
    elif kind == "rglru":
        x, st = recurrent.rglru_block(cfg, p["t"], rules, x,
                                      state=cache, backend=backend)
        new_cache = st if (cache is not None or emit) else None
    elif kind == "rwkv":
        x, st = recurrent.rwkv_block(cfg, p["t"], rules, x,
                                     state=cache, backend=backend)
        new_cache = st if (cache is not None or emit) else None
    if layer is not None and kind in ("rglru", "rwkv"):
        new_cache = jax.tree.map(lambda a, n: a.at[layer].set(n), stack,
                                 new_cache)
    if "c" in p:
        if "router" not in p["c"]:
            x = layers.mlp_block(cfg, p["c"], rules, x)
        else:
            x, stats = moe.moe_block(cfg, p["c"], rules, x, backend=backend)
    return x, new_cache, stats


def _gather_stats(per_layer):
    """One dict of the MoE statistics over the layers: each a list of
    per-layer values or of scan-stacked ones."""
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
# the stack walker and its two entry points
# ---------------------------------------------------------------------------

def _walk(cfg: ModelConfig, params, x, rules, positions, caches=None,
          **layer_kw):
    """Run ``x`` through lead → scanned groups → tail, each layer as
    ``apply_layer(..., **layer_kw)``.

    Without ``caches`` each layer gets ``cache=None`` and the scan emits
    the groups' new caches as its outputs; with ``caches`` the stacked
    group caches ride in the scan's carry and each scanned layer gets the
    whole stack and its group index ``layer=g``.  Returns (x, the caches
    tree, the MoE layers' statistics, layer by layer or scan-stacked)."""
    kinds = cfg.layer_kinds
    stats = []

    def layer(x, kind, lp, cache, g, st_out):
        x, c, st = apply_layer(cfg, kind, lp, rules, x, positions,
                               cache=cache, layer=g, **layer_kw)
        if st:
            st_out.append(st)
        return x, c

    def unrolled(x, part, first):
        out = []
        for i, lp in enumerate(params.get(part, ())):
            x, c = layer(x, kinds[first + i], lp,
                         None if caches is None else caches[part][i], None,
                         stats)
            out.append(c)
        return x, tuple(out)

    def group(x, group_params, group_cache, g):
        new, st = [], []
        for pos, kind in enumerate(cfg.block_pattern):
            x, c = layer(x, kind, group_params[pos],
                         None if group_cache is None else group_cache[pos],
                         g, st)
            new.append(c)
        return x, tuple(new), tuple(st)

    new = {}
    if "lead" in params:
        x, new["lead"] = unrolled(x, "lead", 0)

    new["groups"] = None
    if params["groups"]:
        if caches is None:
            def body(x, group_params):
                x, c, st = group(x, group_params, None, None)
                return x, (c, st)

            x, (new["groups"], group_stats) = jax.lax.scan(
                body, x, params["groups"])
        else:
            # the stacked caches ride in the carry and each layer writes
            # its one new row into them: scanning them as inputs and
            # outputs would slice every layer out whole and write it back
            # whole, every step
            def body(carry, scanned):
                x, group_cache = carry
                group_params, g = scanned
                x, c, st = group(x, group_params, group_cache, g)
                return (x, c), st

            n_groups = jax.tree.leaves(params["groups"])[0].shape[0]
            (x, new["groups"]), group_stats = jax.lax.scan(
                body, (x, caches["groups"]),
                (params["groups"], jnp.arange(n_groups)))
        stats.extend(group_stats)

    x, new["tail"] = unrolled(x, "tail", len(kinds) - len(params["tail"]))
    return x, new, stats


def forward(cfg: ModelConfig, params, batch, rules, *, backend="auto",
            capacity=None, last_only=False):
    """Full-sequence forward.  Returns (logits, caches, stats): caches is
    None unless ``capacity`` (the prefill), then each layer's decode cache
    with ``capacity`` slots; stats are ``_gather_stats``' over the MoE
    layers, empty without MoE."""
    x = layers.embed_tokens(cfg, params["emb"], rules, batch)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    x, caches, stats = _walk(cfg, params, x, rules, positions,
                             capacity=capacity, backend=backend)
    if last_only:
        x = x[:, -1:]
    logits = layers.logits_head(cfg, params["emb"], rules, x)
    return (logits, caches if capacity is not None else None,
            _gather_stats(stats))


def decode_step(cfg: ModelConfig, params, caches, batch, rules, *,
                backend="auto"):
    """One-token decode. batch: {"token_ids": (B,1) or "embeds",
    "lengths": (B,)}.  Returns (logits (B,1,V), new caches, stats): the
    MoE layers' statistics, as ``forward``'s."""
    lengths = batch["lengths"]
    x = layers.embed_tokens(cfg, params["emb"], rules, batch)
    positions = lengths[:, None]                      # (B,1) absolute pos
    x, caches, stats = _walk(cfg, params, x, rules, positions, caches,
                             lengths=lengths, backend=backend)
    logits = layers.logits_head(cfg, params["emb"], rules, x)
    return logits, caches, _gather_stats(stats)
