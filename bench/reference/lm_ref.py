"""Plain float32 reference for the served dense decoder, and the seeded
weights that both it and the program under test are given.

It imports nothing of the program.  The block it computes is the one
the configuration file states (and its ``notes`` list where that departs
from the published model): pre-norm RMSNorm (``x / rms(x) * (1 + g)``,
eps 1e-6), multi-head attention with rotary embedding over the whole
head (halves rotated, base ``rope_theta``), causal softmax, a SwiGLU MLP,
a final RMSNorm and an untied output head, with no biases.

Every matrix product runs in float32 at ``Precision.HIGHEST``.  The
control (``quant="int8"``) rounds both operands of every product to
int8 with one absmax scale per tensor first, the step below the
bfloat16 that the configuration serves in.

Weights are drawn layer by layer from the seed, so the reference can
rebuild any one layer alone after the program's own copy is freed:
``make_params`` stacks the same draws (to float32 rounding: XLA may fuse
the normal transform differently in the two programs) into the program's
parameter tree in one jitted call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def sizes(cfg: dict) -> dict:
    """The configuration file's sizes under short names."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(L=cfg["num_hidden_layers"], d=d, H=h,
                Hkv=cfg["num_key_value_heads"], dh=d // h,
                ff=cfg["intermediate_size"], V=cfg["vocab_size"],
                theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]))


def base_key(seed: int):
    """A threefry key from a seed of any size."""
    words = np.random.SeedSequence([seed & 0xFFFFFFFF, seed >> 32]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def layer_weights(key, layer, s: dict) -> dict:
    d, H, Hkv, dh, ff = s["d"], s["H"], s["Hkv"], s["dh"], s["ff"]
    ks = jax.random.split(jax.random.fold_in(key, layer), 9)

    def n(k, shape, scale):
        return jax.random.normal(k, shape, jnp.float32) * scale

    return {
        "t": {"ln": n(ks[0], (d,), 0.1),
              "wq": n(ks[1], (d, H, dh), d ** -0.5),
              "wk": n(ks[2], (d, Hkv, dh), d ** -0.5),
              "wv": n(ks[3], (d, Hkv, dh), d ** -0.5),
              "wo": n(ks[4], (H, dh, d), (H * dh) ** -0.5)},
        "c": {"ln": n(ks[5], (d,), 0.1),
              "wi_gate": n(ks[6], (d, ff), d ** -0.5),
              "wi": n(ks[7], (d, ff), d ** -0.5),
              "wo": n(ks[8], (ff, d), ff ** -0.5)},
    }


def emb_weights(key, s: dict) -> dict:
    ks = jax.random.split(jax.random.fold_in(key, s["L"]), 3)
    return {"tok": jax.random.normal(ks[0], (s["V"], s["d"]), jnp.float32) * 0.02,
            "final_ln": jax.random.normal(ks[1], (s["d"],), jnp.float32) * 0.1,
            "head": jax.random.normal(ks[2], (s["d"], s["V"]), jnp.float32)
            * s["d"] ** -0.5}


def make_params(seed: int, s: dict):
    """The program's parameter tree, on the device, in one jitted call:
    ``{"emb": ..., "groups": (layers stacked on a leading axis,), "tail": ()}``."""
    key = base_key(seed)

    @jax.jit
    def build(key):
        layers = jax.lax.map(lambda l: layer_weights(key, l, s),
                             jnp.arange(s["L"]))
        return {"emb": emb_weights(key, s), "groups": (layers,), "tail": ()}

    return build(key)


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

def _q(x, quant):
    if quant is None:
        return x
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _mm(eq, a, b, quant):
    return jnp.einsum(eq, _q(a, quant), _q(b, quant), precision=HI)


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + g)


def _rope(x, theta):
    """x: (T, H, D), positions 0..T-1."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("s", "quant"))
def _layer(x, w, s, quant):
    s = dict(s)
    a, c = w["t"], w["c"]
    h = _norm(x, a["ln"], s["eps"])
    q = _rope(_mm("td,dhk->thk", h, a["wq"], quant), s["theta"])
    k = _rope(_mm("td,dhk->thk", h, a["wk"], quant), s["theta"])
    v = _mm("td,dhk->thk", h, a["wv"], quant)
    g = s["H"] // s["Hkv"]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    sc = _mm("thk,shk->hts", q, k, quant) / np.sqrt(s["dh"])
    T = x.shape[0]
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    o = _mm("hts,shk->thk", p, v, quant)
    x = x + _mm("thk,hkd->td", o, a["wo"], quant)
    h = _norm(x, c["ln"], s["eps"])
    up = _mm("td,df->tf", h, c["wi"], quant)
    gate = _mm("td,df->tf", h, c["wi_gate"], quant)
    return x + _mm("tf,fd->td", jax.nn.silu(gate) * up, c["wo"], quant)


@functools.partial(jax.jit, static_argnames=("s", "quant"))
def _rows(x, r, e, chosen, s, quant):
    """For ``ROWS`` rows of ``x`` from row ``r``: the best logit, the logit
    of ``chosen``, and the token that comes first."""
    s = dict(s)
    x = jax.lax.dynamic_slice_in_dim(x, r, ROWS)
    lg = _mm("td,dv->tv", _norm(x, e["final_ln"], s["eps"]), e["head"], quant)
    return (lg.max(-1), jnp.take_along_axis(lg, chosen[:, None], -1)[:, 0],
            lg.argmax(-1))


_layer_w = jax.jit(lambda key, layer, s: layer_weights(key, layer, dict(s)),
                   static_argnames=("s",))
_emb_w = jax.jit(lambda key, s: emb_weights(key, dict(s)), static_argnames=("s",))


#: rows of logits computed at once; sequences pad to a multiple of it
ROWS = 512


def _bucket(n: int, step: int = ROWS) -> int:
    return -(-n // step) * step


def served_gaps(seed: int, s: dict, seqs, quant=None, control=False):
    """For each (prompt, served) pair, the gap at every served position by
    which the chosen token's reference logit lies below the reference's
    best.  ``control=True`` chooses, in place of the served token, the one
    that the reference computed with ``quant`` puts first.

    Sequences are padded at the end to a multiple of 512 tokens (causal
    attention leaves the earlier positions as they were) and run layer by
    layer, so one layer's weights are on the device at a time."""
    sf = tuple(sorted(s.items()))
    key = base_key(seed)
    toks, spans = [], []
    for prompt, served in seqs:
        ids = np.concatenate([np.asarray(prompt), np.asarray(served[:-1])]).astype(np.int32)
        pad = np.zeros(_bucket(len(ids)), np.int32)
        pad[:len(ids)] = ids
        toks.append(pad)
        spans.append((len(prompt) - 1, len(prompt) - 1 + len(served)))
    modes = [None] + ([quant] if control else [])
    emb = _emb_w(key, s=sf)
    xs = {m: [emb["tok"][jnp.asarray(t)] for t in toks] for m in modes}
    for layer in range(s["L"]):
        w = _layer_w(key, layer, s=sf)
        for m in modes:
            xs[m] = [_layer(x, w, sf, m) for x in xs[m]]
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
                chosen[rows] = np.asarray(_rows(
                    xs[quant][i], r, emb, jnp.asarray(chosen[rows]), sf, quant)[2])
            b, g, _ = _rows(xs[None][i], r, emb, jnp.asarray(chosen[rows]), sf, None)
            best[rows], got[rows] = np.asarray(b), np.asarray(g)
        gaps.append(best[lo:hi] - got[lo:hi])
    return gaps
