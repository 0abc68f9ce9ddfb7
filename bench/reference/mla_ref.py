"""Plain float32 reference for the served latent-attention MoE decoder
(DeepSeek-V2), and the seeded weights that both it and the program under
test are given.

It imports nothing of the program.  Per layer, over positions 0..T-1:

* attention: ``h = RMSNorm(x)``; ``q = h W_q`` per head, split into
  ``q_n`` and the rotary ``q_r``; ``[c, k_r] = h W_kva`` with
  ``c = RMSNorm_kv(c)``; YaRN rotary on ``q_r`` and on ``k_r``, one key
  shared by every head; ``k_n = c W_uk``, ``v = c W_uv`` per head;
  ``score = (q_n . k_n + q_r . k_r) * q_dim^-1/2 * m^2``, causal softmax,
  ``x += concat_h(p v) W_o``;
* channel mix: the first ``first_k_dense_replace`` layers a dense SwiGLU;
  the others ``h2 = RMSNorm(x)``, ``p = softmax(h2 W_r)`` over every
  expert the router scores, the top k by ``p`` with gates ``p`` (not
  renormalised unless ``norm_topk_prob``), and
  ``x += sum over top-k experts held here of p_e SwiGLU_e(h2) + SwiGLU_shared(h2)``;
* a final RMSNorm and the untied head.

RMSNorm is ``x / rms(x) * (1 + g)`` (eps from the file), rotary pairs are
the halves of each rotary vector: both as the configuration's ``notes``
state.  A chip holding the experts ``[first, first + n)`` of each layer
computes those experts' part; the reference is given the same share
(``held``), so what the absent experts would add is left out of both.

Every matrix product runs in float32 at ``Precision.HIGHEST`` over the
weights as the program holds them (drawn in float32, rounded to the
configuration's parameter dtype).  The control (``quant="int8"``) rounds
both operands of every product to int8 with one absmax scale per tensor
first, as ``lm_ref`` does.  Weights are drawn layer by layer, each routed
expert from a key of its own, so the reference can rebuild any layer, and
any share of its experts, alone; ``make_params`` stacks the same draws
into the program's parameter tree in one jitted call.
"""
from __future__ import annotations

import functools
import importlib.util
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np


def _lm_ref():
    """``lm_ref`` beside this file: its seed key, int8 products, norm,
    output rows and embedding draws are this reference's too."""
    name = "bench_reference_lm_ref"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, pathlib.Path(__file__).with_name("lm_ref.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


lm = _lm_ref()
ROWS = lm.ROWS
base_key = lm.base_key
_mm, _norm = lm._mm, lm._norm


def sizes(cfg: dict) -> dict:
    """The configuration file's sizes under short names (hashable values,
    so a jitted function can take them as static)."""
    y = cfg["rope_scaling"]
    dep = cfg["deployment"]
    return dict(
        L=cfg["num_hidden_layers"], d=cfg["hidden_size"],
        H=cfg["num_attention_heads"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], v=cfg["v_head_dim"],
        r=cfg["kv_lora_rank"], ff=cfg["intermediate_size"],
        eff=cfg["moe_intermediate_size"], k=cfg["num_experts_per_tok"],
        E=dep["router_experts"], first=dep["first_held"],
        held=cfg["n_routed_experts"], shared=cfg["n_shared_experts"],
        dense=cfg["first_k_dense_replace"], V=cfg["vocab_size"],
        theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
        norm_topk=bool(cfg["norm_topk_prob"]), dtype=cfg["torch_dtype"],
        y_factor=float(y["factor"]),
        y_orig=int(y["original_max_position_embeddings"]),
        y_fast=float(y["beta_fast"]), y_slow=float(y["beta_slow"]),
        y_mscale=float(y["mscale"]), y_mscale_all=float(y["mscale_all_dim"]))


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------

def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_bounds(s: dict) -> tuple[int, int]:
    """The ramp's (low, high) rotary pairs, from YaRN's correction range."""
    dim, theta = s["rope"], s["theta"]

    def corr(rot):
        return (dim * math.log(s["y_orig"] / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    return (max(math.floor(corr(s["y_fast"])), 0),
            min(math.ceil(corr(s["y_slow"])), dim - 1))


def yarn_inv_freq(s: dict) -> np.ndarray:
    half = s["rope"] // 2
    f = s["theta"] ** (-2.0 * np.arange(half) / s["rope"])
    low, high = yarn_bounds(s)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    return (f / s["y_factor"] * ramp + f * (1 - ramp)).astype(np.float32)


def score_scale(s: dict) -> float:
    m = _yarn_mscale(s["y_factor"], s["y_mscale_all"])
    return (s["nope"] + s["rope"]) ** -0.5 * m * m


def _rope(x, s):
    """x: (T, H, D) rotary columns at positions 0..T-1."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * yarn_inv_freq(s)
    scale = (_yarn_mscale(s["y_factor"], s["y_mscale"])
             / _yarn_mscale(s["y_factor"], s["y_mscale_all"]))
    cos = jnp.cos(ang)[:, None, :] * scale
    sin = jnp.sin(ang)[:, None, :] * scale
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _n(k, shape, scale):
    return jax.random.normal(k, shape, jnp.float32) * scale


def expert_weights(key, layer: int, e: int, s: dict) -> dict:
    """Routed expert ``e`` of ``layer``: a key of its own, so any share of
    the experts draws the same weights."""
    d, f = s["d"], s["eff"]
    ks = jax.random.split(jax.random.fold_in(
        jax.random.fold_in(jax.random.fold_in(key, layer), 1 << 20), e), 3)
    return {"wi_gate": _n(ks[0], (d, f), d ** -0.5),
            "wi": _n(ks[1], (d, f), d ** -0.5),
            "wo": _n(ks[2], (f, d), f ** -0.5)}


def layer_weights(key, layer, s: dict, dense: bool, held=None) -> dict:
    """One layer in float32; ``held`` = (first, n) experts, by default the
    configuration's share."""
    d, H, r, q_dim = s["d"], s["H"], s["r"], s["nope"] + s["rope"]
    ks = jax.random.split(jax.random.fold_in(key, layer), 14)
    t = {"ln": _n(ks[0], (d,), 0.1),
         "wq": _n(ks[1], (d, H, q_dim), d ** -0.5),
         "wkv_a": _n(ks[2], (d, r + s["rope"]), d ** -0.5),
         "kv_ln": _n(ks[3], (r,), 0.1),
         "wuk": _n(ks[4], (r, H, s["nope"]), r ** -0.5),
         "wuv": _n(ks[5], (r, H, s["v"]), r ** -0.5),
         "wo": _n(ks[6], (H, s["v"], d), (H * s["v"]) ** -0.5)}
    if dense:
        ff = s["ff"]
        c = {"ln": _n(ks[7], (d,), 0.1),
             "wi_gate": _n(ks[8], (d, ff), d ** -0.5),
             "wi": _n(ks[9], (d, ff), d ** -0.5),
             "wo": _n(ks[10], (ff, d), ff ** -0.5)}
        return {"t": t, "c": c}
    first, n = held if held is not None else (s["first"], s["held"])
    sff = s["shared"] * s["eff"]
    ex = [expert_weights(key, layer, e, s) for e in range(first, first + n)]
    c = {"ln": _n(ks[7], (d,), 0.1),
         "router": _n(ks[11], (d, s["E"]), d ** -0.5),
         **jax.tree.map(lambda *xs: jnp.stack(xs), *ex),
         "shared": {"wi_gate": _n(ks[8], (d, sff), d ** -0.5),
                    "wi": _n(ks[9], (d, sff), d ** -0.5),
                    "wo": _n(ks[10], (sff, d), sff ** -0.5)}}
    return {"t": t, "c": c}


def _as_held(tree, s: dict):
    """The weights as the program holds them, in float32."""
    dt = jnp.dtype(s["dtype"])
    return jax.tree.map(lambda a: a.astype(dt).astype(jnp.float32), tree)


def make_params(seed: int, s: dict):
    """The program's parameter tree in the configuration's dtype, on the
    device, in one jitted call: ``{"emb", "lead": (dense layers,),
    "groups": (MoE layers stacked,), "tail": ()}``."""
    key = base_key(seed)
    dt = jnp.dtype(s["dtype"])

    def cast(tree):       # per layer, so no float32 copy of the whole model
        return jax.tree.map(lambda a: a.astype(dt), tree)

    @jax.jit
    def build(key):
        lead = tuple(cast(layer_weights(key, l, s, True))
                     for l in range(s["dense"]))
        moe = jax.lax.map(lambda l: cast(layer_weights(key, l, s, False)),
                          jnp.arange(s["dense"], s["L"]))
        return {"emb": cast(lm.emb_weights(key, s)), "lead": lead,
                "groups": (moe,), "tail": ()}

    return build(key)


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

def _swiglu(h, w, quant):
    up = _mm("td,df->tf", h, w["wi"], quant)
    gate = _mm("td,df->tf", h, w["wi_gate"], quant)
    return _mm("tf,fd->td", jax.nn.silu(gate) * up, w["wo"], quant)


def attention(x, a, s: dict, quant=None):
    """The attention residual's output (without ``x``), in blocks of
    ``ROWS`` queries over every key."""
    r, nope = s["r"], s["nope"]
    h = _norm(x, a["ln"], s["eps"])
    q = _mm("td,dhk->thk", h, a["wq"], quant)
    kva = _mm("td,dc->tc", h, a["wkv_a"], quant)
    c = _norm(kva[:, :r], a["kv_ln"], s["eps"])
    k_r = _rope(kva[:, None, r:], s)[:, 0]
    q_n, q_r = q[..., :nope], _rope(q[..., nope:], s)
    k_n = _mm("tc,chk->thk", c, a["wuk"], quant)
    v = _mm("tc,chk->thk", c, a["wuv"], quant)
    T = x.shape[0]
    rows = min(ROWS, T)
    sigma = score_scale(s)

    def block(i):
        qn = jax.lax.dynamic_slice_in_dim(q_n, i * rows, rows)
        qr = jax.lax.dynamic_slice_in_dim(q_r, i * rows, rows)
        sc = (_mm("qhk,shk->hqs", qn, k_n, quant)
              + _mm("qhk,sk->hqs", qr, k_r, quant)) * sigma
        causal = (i * rows + jnp.arange(rows))[:, None] >= jnp.arange(T)[None]
        p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        return _mm("hqs,shv->qhv", p, v, quant)

    o = jax.lax.map(block, jnp.arange(T // rows)).reshape(T, s["H"], s["v"])
    return _mm("thv,hvd->td", o, a["wo"], quant)


def routed(h, c, s: dict, held, quant=None):
    """What the routed experts ``held`` = (first, n), whose weights ``c``
    holds, add for normed rows ``h``: the top-k gates over every expert
    the router scores, then each held expert's SwiGLU."""
    probs = jax.nn.softmax(_mm("td,de->te", h, c["router"], quant), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, s["k"])
    if s["norm_topk"]:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], top_i].set(top_p)
    first, n = held
    y = jnp.zeros_like(h)
    for j in range(n):
        w = {name: c[name][j] for name in ("wi_gate", "wi", "wo")}
        y = y + gates[:, first + j, None] * _swiglu(h, w, quant)
    return y


@functools.partial(jax.jit, static_argnames=("s", "quant", "held"))
def layer(x, w, s, quant=None, held=None):
    """One whole layer; ``s`` as sorted items (hashable)."""
    s = dict(s)
    held = held or (s["first"], s["held"])
    x = x + attention(x, w["t"], s, quant)
    c = w["c"]
    h = _norm(x, c["ln"], s["eps"])
    if "router" not in c:
        return x + _swiglu(h, c, quant)
    return x + routed(h, c, s, held, quant) + _swiglu(h, c["shared"], quant)


_layer_w = jax.jit(
    lambda key, l, s, dense: _as_held(layer_weights(key, l, dict(s), dense),
                                      dict(s)),
    static_argnames=("s", "dense"))
_emb_w = jax.jit(lambda key, s: _as_held(lm.emb_weights(key, dict(s)), dict(s)),
                 static_argnames=("s",))


def served_gaps(seed: int, s: dict, seqs, quant=None, control=False):
    """For each (prompt, served) pair, the gap at every served position by
    which the chosen token's reference logit lies below the reference's
    best; ``control=True`` chooses, in place of the served token, the one
    the reference computed with ``quant`` puts first.  As
    ``lm_ref.served_gaps``: sequences padded to a multiple of ``ROWS`` and
    run layer by layer, one layer's weights on the device at a time."""
    sf = tuple(sorted(s.items()))
    key = base_key(seed)
    toks, spans = [], []
    for prompt, served in seqs:
        ids = np.concatenate([np.asarray(prompt),
                              np.asarray(served[:-1])]).astype(np.int32)
        pad = np.zeros(lm._bucket(len(ids)), np.int32)
        pad[:len(ids)] = ids
        toks.append(pad)
        spans.append((len(prompt) - 1, len(prompt) - 1 + len(served)))
    modes = [None] + ([quant] if control else [])
    emb = _emb_w(key, s=sf)
    xs = {m: [emb["tok"][jnp.asarray(t)] for t in toks] for m in modes}
    for l in range(s["L"]):
        w = _layer_w(key, l, s=sf, dense=l < s["dense"])
        for m in modes:
            xs[m] = [layer(x, w, sf, m) for x in xs[m]]
        del w
    gaps = []
    for i, (prompt, served) in enumerate(seqs):
        lo, hi = spans[i]
        chosen = np.zeros(len(toks[i]), np.int32)
        chosen[lo:hi] = served
        best = np.zeros(len(toks[i]), np.float32)
        got = np.zeros(len(toks[i]), np.float32)
        for r in range(lo - lo % ROWS, hi, ROWS):
            rows = slice(r, r + ROWS)
            if control:
                chosen[rows] = np.asarray(lm._rows(
                    xs[quant][i], r, emb, jnp.asarray(chosen[rows]), sf,
                    quant)[2])
            b, g, _ = lm._rows(xs[None][i], r, emb, jnp.asarray(chosen[rows]),
                               sf, None)
            best[rows], got[rows] = np.asarray(b), np.asarray(g)
        gaps.append(best[lo:hi] - got[lo:hi])
    return gaps


def forward_logits(seed: int, s: dict, ids, quant=None):
    """Logits at every position of ``ids`` (T,), T a multiple of ``ROWS``
    or below it: the full forward the tests compare the served path with."""
    sf = tuple(sorted(s.items()))
    key = base_key(seed)
    emb = _emb_w(key, s=sf)
    x = emb["tok"][jnp.asarray(ids)]
    for l in range(s["L"]):
        x = layer(x, _layer_w(key, l, s=sf, dense=l < s["dense"]), sf, quant)
    return _mm("td,dv->tv", _norm(x, emb["final_ln"], s["eps"]), emb["head"],
               quant)
