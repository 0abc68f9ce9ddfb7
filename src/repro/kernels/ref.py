"""Pure-jnp reference oracles for every kernel.

These are the semantic ground truth: naive, O(S^2)-memory where applicable,
no blocking, no numerics tricks beyond float32 softmax.  Kernel tests sweep
shapes/dtypes and assert allclose against these.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def piecewise_slowdown(own, ext, own_knots, ext_knots, table):
    """Reference batched piecewise-linear PCCS slowdown surface.

    Bilinear interpolation of ``table`` over the (own, ext) grid with
    clamped extension outside, expressed gather-free as a tensor product of
    1-D hat bases: ``s = Σ_i Σ_j hat_i(own) hat_j(ext) table[i, j]`` — the
    same contraction the Pallas kernel in :mod:`repro.kernels.slowdown`
    runs blocked on the MXU.  Zero own/external demand is the identity
    (slowdown 1), mirroring ``repro.core.contention.PiecewiseModel``.
    """
    own = jnp.asarray(own)
    ext = jnp.asarray(ext)
    shape = own.shape
    ho = _hat_weights(jnp.asarray(own_knots, own.dtype), own.reshape(-1))
    he = _hat_weights(jnp.asarray(ext_knots, ext.dtype), ext.reshape(-1))
    tab = jnp.asarray(table, own.dtype)
    s = jnp.einsum("bk,km,bm->b", ho, tab, he).reshape(shape)
    return jnp.where((own <= 0.0) | (ext <= 0.0), jnp.ones((), own.dtype), s)


def _hat_weights(knots, x):
    """(B, K) linear-interpolation hat weights of x against sorted knots.

    Row b holds the barycentric weights of ``x[b]``: for x inside
    ``[knots[i], knots[i+1]]`` exactly hats i and i+1 are non-zero and sum
    to 1; outside the grid the nearest end knot gets weight 1 (clamping).
    """
    k = knots[None, :]
    kprev = jnp.concatenate([knots[:1], knots[:-1]])[None, :]
    knext = jnp.concatenate([knots[1:], knots[-1:]])[None, :]
    xb = x[:, None]
    tiny = jnp.asarray(1e-30, x.dtype)
    up = (xb - kprev) / jnp.maximum(k - kprev, tiny)     # rising edge
    dn = (knext - xb) / jnp.maximum(knext - k, tiny)     # falling edge
    h = jnp.clip(jnp.minimum(up, dn), 0.0, 1.0)
    n = knots.shape[0]
    col = jnp.arange(n)[None, :]
    h = jnp.where((col == 0) & (xb <= knots[0]), 1.0, h)
    h = jnp.where((col == n - 1) & (xb >= knots[-1]), 1.0, h)
    return h


def anneal_select(cur, prop, best, cur_obj, prop_obj, best_obj, u, temp):
    """Reference Metropolis accept + incumbent select over a population.

    ``cur``/``prop``/``best`` are (P, L) assignment rows; ``cur_obj``/
    ``prop_obj``/``best_obj``/``u`` are (P,); ``temp`` is a scalar
    temperature.  A proposal is accepted when it does not regress, or with
    the Metropolis probability ``exp(-delta/temp)`` against the uniform
    draw ``u``; the per-chain incumbent takes every strict improvement
    (first-found wins on ties).  Chains whose proposal scored non-finite
    (error-poisoned lanes) always reject.  Returns
    ``(new_cur, new_cur_obj, new_best, new_best_obj)``.
    """
    cur_obj = jnp.asarray(cur_obj)
    dt = cur_obj.dtype
    prop_obj = jnp.asarray(prop_obj, dt)
    best_obj = jnp.asarray(best_obj, dt)
    u = jnp.asarray(u, dt)
    temp = jnp.maximum(jnp.asarray(temp, dt), jnp.asarray(1e-30, dt))
    delta = prop_obj - cur_obj
    accept = (delta <= 0) | (u < jnp.exp(-delta / temp))
    accept &= jnp.isfinite(prop_obj)
    improved = prop_obj < best_obj
    new_cur = jnp.where(accept[:, None], prop, cur)
    new_cur_obj = jnp.where(accept, prop_obj, cur_obj)
    new_best = jnp.where(improved[:, None], prop, best)
    new_best_obj = jnp.where(improved, prop_obj, best_obj)
    return new_cur, new_cur_obj, new_best, new_best_obj


def _gqa_expand(k, n_heads):
    """(B,S,Hkv,D) -> (B,S,Hq,D) by repeating kv heads."""
    b, s, hkv, d = k.shape
    rep = n_heads // hkv
    return jnp.repeat(k, rep, axis=2)


def attention(q, k, v, *, causal: bool = True, window: int | None = None,
              lengths=None):
    """Reference attention.

    q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, Dk/Dv).  GQA via head repetition.
    ``window``: local attention — position i attends to [i-window+1, i]
    (combined with causal).  ``lengths``: (B,) valid kv lengths (decode).
    For Sq < Skv the queries are the *last* Sq positions (decode offset).
    """
    b, sq, hq, d = q.shape
    skv = k.shape[1]
    k = _gqa_expand(k, hq)
    v = _gqa_expand(v, hq)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / np.sqrt(d)
    q_pos = jnp.arange(sq) + (skv - sq)         # absolute query positions
    k_pos = jnp.arange(skv)
    mask = jnp.ones((sq, skv), dtype=bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    mask = jnp.broadcast_to(mask[None, None], logits.shape)
    if lengths is not None:
        valid = k_pos[None, :] < lengths[:, None]          # (B, Skv)
        mask &= valid[:, None, None, :]
    logits = jnp.where(mask, logits, -jnp.inf)
    w = jnp.nan_to_num(jnp.exp(logits - logits.max(-1, keepdims=True)))
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-30)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32))
    return out.astype(q.dtype)


def linear_scan(a, b, h0=None):
    """Reference gated linear recurrence: h_t = a_t * h_{t-1} + b_t.

    a, b: (B, S, D); h0: (B, D) or None (zeros).  Returns (h_all, h_last).
    Sequential python loop over S — the oracle for rglru.
    """
    B, S, D = a.shape
    h = jnp.zeros((B, D), jnp.float32) if h0 is None else h0.astype(jnp.float32)
    hs = []
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    for t in range(S):
        h = af[:, t] * h + bf[:, t]
        hs.append(h)
    h_all = jnp.stack(hs, axis=1).astype(a.dtype)
    return h_all, h


def rwkv6(r, k, v, w, u, state0=None):
    """Reference RWKV-6 (Finch) recurrence.

    Per head with state S in R^{D x Dv}:
        y_t = (S_{t-1} + (u ⊙ k_t) v_t^T)^T r_t
        S_t = diag(w_t) S_{t-1} + k_t v_t^T
    r, k, w: (B, T, H, D); v: (B, T, H, Dv); u: (H, D);
    state0: (B, H, D, Dv).  Returns (y (B,T,H,Dv), state (B,H,D,Dv)).
    ``w`` is the per-step decay in (0, 1) (already exp(-exp(...))-activated).
    """
    B, T, H, D = r.shape
    Dv = v.shape[-1]
    f32 = jnp.float32
    S = (jnp.zeros((B, H, D, Dv), f32) if state0 is None
         else state0.astype(f32))
    ys = []
    rf, kf, vf, wf = (x.astype(f32) for x in (r, k, v, w))
    uf = u.astype(f32)
    for t in range(T):
        kt = kf[:, t]                     # (B,H,D)
        vt = vf[:, t]                     # (B,H,Dv)
        rt = rf[:, t]
        wt = wf[:, t]
        kv = jnp.einsum("bhd,bhe->bhde", kt, vt)
        y = jnp.einsum("bhd,bhde->bhe", rt, S + uf[None] [..., None] * kv)
        S = wt[..., None] * S + kv
        ys.append(y)
    y_all = jnp.stack(ys, axis=1).astype(v.dtype)
    return y_all, S


def mla_decode(q, cache, lengths, value_width):
    """Reference absorbed latent attention: per head, softmax over the
    first ``lengths`` cached rows of ``q . row``, then the weighted sum of
    the rows' first ``value_width`` lanes.  q: (B, H, W); cache (B, S, W)."""
    qf = q.astype(jnp.float32)
    cf = cache.astype(jnp.float32)
    s = jnp.einsum("bhw,bsw->bhs", qf, cf)
    valid = jnp.arange(cache.shape[1])[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None, :], s, -1e30)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return jnp.einsum("bhs,bsv->bhv", p, cf[..., :value_width])


def moe_gmm(x, wi_gate, wi, wo, group_sizes):
    """Reference grouped SwiGLU: row r in group e gets expert e's SwiGLU;
    rows past the groups are zero."""
    xf = x.astype(jnp.float32)
    ends = jnp.cumsum(group_sizes)
    row = jnp.arange(x.shape[0])
    grp = jnp.searchsorted(ends, row, side="right")          # (M,)
    live = grp < wi.shape[0]
    g = jnp.minimum(grp, wi.shape[0] - 1)
    up = jnp.einsum("md,mdf->mf", xf, wi[g].astype(jnp.float32))
    gate = jnp.einsum("md,mdf->mf", xf, wi_gate[g].astype(jnp.float32))
    act = (gate / (1 + jnp.exp(-gate))) * up
    out = jnp.einsum("mf,mfd->md", act, wo[g].astype(jnp.float32))
    return jnp.where(live[:, None], out, 0.0)
