"""Dispatching wrappers: one call site per op, three interchangeable backends.

  * ``pallas``           — the TPU kernels (Mosaic lowering on TPU).
  * ``pallas_interpret`` — same kernel bodies, interpreted on CPU (tests).
  * ``xla``              — blocked pure-JAX implementations with the same
                           memory behaviour (O(tile) attention, scan-carried
                           recurrences).  Used on CPU and for the dry-run so
                           the lowered HLO is backend-portable.

``backend="auto"`` picks pallas on TPU, xla elsewhere (:func:`resolve`,
which also counts every choice in the ``kernel_dispatch`` metric so a run
can print which path each kernel took).  All backends are bit-compatible
up to float tolerance with :mod:`repro.kernels.ref`.
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import get_registry

from . import decode_attention as _dec
from . import flash_attention as _fa
from . import mla_decode as _mla
from . import moe_gmm as _moe
from . import ref as _ref
from . import rglru as _rglru
from . import rwkv6 as _rwkv6

Backend = Literal["auto", "xla", "pallas", "pallas_interpret", "ref", "stub"]
# "stub": HBM-traffic stand-in for dry-run cost probes — reads every input
# once and writes the true output shape, with negligible flops, matching
# the Pallas kernel's memory behaviour (tiles never spill score tensors to
# HBM).  The dry-run adds the kernels' flops analytically.


def resolve(kernel: str, backend: str, *, pallas_ok: bool = True) -> str:
    """The backend one call of ``kernel`` takes, counted in the
    ``kernel_dispatch`` metric under ``kernel``/``backend`` labels.

    ``auto`` is pallas on TPU when the call site allows it (``pallas_ok``:
    big enough to pay for a launch, a dtype Mosaic supports), xla
    otherwise; explicit backends pass through.  Dispatch happens while
    tracing, so under ``jax.jit`` the count is per trace, not per run.
    """
    b = backend
    if b == "auto":
        b = "pallas" if (jax.default_backend() == "tpu" and pallas_ok) \
            else "xla"
    get_registry().counter(
        "kernel_dispatch", "kernel calls dispatched (traced) per backend"
    ).labels(kernel=kernel, backend=b).inc()
    return b


LANES = 128


def lane_tiling(n: int, block: int) -> tuple[int, int]:
    """Row blocking of a flat length-``n`` array laid out as lane-dense
    ``(rows, LANES)`` tiles: returns ``(row_block, n_blocks)`` with the
    row block a multiple of 8 near ``block`` elements, or all the rows
    when they fit in one block — the two shapes Mosaic accepts.  Callers
    pad the array to ``row_block * n_blocks * LANES`` elements."""
    rows = -(-n // LANES)
    rb = max(8, -(-(block // LANES) // 8) * 8)
    if rows <= rb:
        return rows, 1
    return rb, -(-rows // rb)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def attention(q, k, v, *, causal: bool = True, window: int | None = None,
              block_q: int = 1024, block_kv: int = 1024,
              backend: Backend = "auto"):
    """Multi-head GQA attention. q: (B,Sq,Hq,D); k,v: (B,Skv,Hkv,D)."""
    b = resolve("attention", backend)
    if b == "stub":
        hq, hkv = q.shape[2], k.shape[2]
        kv = (k.sum(1) + v.sum(1))[:, None]            # reads k, v fully
        return (q * jnp.repeat(kv, hq // hkv, 2)).astype(q.dtype)
    if b == "ref":
        return _ref.attention(q, k, v, causal=causal, window=window)
    if b in ("pallas", "pallas_interpret"):
        return _fa.flash_attention(
            q, k, v, causal=causal, window=window,
            block_q=min(block_q, 512), block_kv=min(block_kv, 512),
            interpret=(b == "pallas_interpret"))
    return _attention_xla(q, k, v, causal=causal, window=window,
                          block_kv=block_kv)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_kv"))
def _attention_xla(q, k, v, *, causal, window, block_kv):
    """Blocked online-softmax attention in pure JAX (scan over kv tiles)."""
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, Dv = v.shape
    group = Hq // Hkv
    block_kv = int(min(block_kv, Skv))
    n_tiles = (Skv + block_kv - 1) // block_kv
    pad = n_tiles * block_kv - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qf = q.astype(jnp.float32) / np.sqrt(D)
    qg = qf.reshape(B, Sq, Hkv, group, D)
    kt = k.reshape(B, n_tiles, block_kv, Hkv, D).transpose(1, 0, 2, 3, 4)
    vt = v.reshape(B, n_tiles, block_kv, Hkv, Dv).transpose(1, 0, 2, 3, 4)
    q_pos = jnp.arange(Sq) + (Skv - Sq)

    def step(carry, tile):
        m, l, acc = carry
        kb, vb, it = tile
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qg, kb.astype(jnp.float32))
        k_pos = it * block_kv + jnp.arange(block_kv)
        mask = k_pos[None, :] < Skv
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(mask[None, :, None, None, :], s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bqhgk,bkhe->bqhge", p, vb.astype(jnp.float32))
        return (m_new, l, acc), None

    m0 = jnp.full((B, Sq, Hkv, group), -1e30, jnp.float32)
    l0 = jnp.zeros((B, Sq, Hkv, group), jnp.float32)
    a0 = jnp.zeros((B, Sq, Hkv, group, Dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), (kt, vt, jnp.arange(n_tiles)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, Sq, Hq, Dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# decode attention (one token, KV cache, per-sequence lengths)
# ---------------------------------------------------------------------------
def decode_attention(q, k_cache, v_cache, lengths, *, layer=None,
                     backend: Backend = "auto"):
    """One query token over lane-dense caches. q: (B, 1, Hq, D);
    k_cache, v_cache: (B, S, Hkv·D), heads major in the last dim, as
    ``models/kvcache.py`` stores them, or with ``layer`` the stacked
    layers (L, B, S, Hkv·D) and the index of the one to read; lengths:
    (B,) valid positions."""
    if layer is None:
        k_cache, v_cache, layer = k_cache[None], v_cache[None], 0
    b = resolve("decode_attention", backend)
    if b in ("pallas", "pallas_interpret"):
        return _dec.decode_attention(q, k_cache, v_cache, lengths, layer,
                                     interpret=(b == "pallas_interpret"))
    k_cache, v_cache = k_cache[layer], v_cache[layer]
    d = q.shape[-1]
    k_cache = k_cache.reshape(*k_cache.shape[:2], -1, d)
    v_cache = v_cache.reshape(*v_cache.shape[:2], -1, d)
    if b == "stub":
        hq, hkv = q.shape[2], k_cache.shape[2]
        kv = (k_cache.sum(1) + v_cache.sum(1))[:, None]
        scale = (1 + lengths.astype(q.dtype) * 0)[:, None, None, None]
        return (q * jnp.repeat(kv, hq // hkv, 2) * scale).astype(q.dtype)
    if b == "ref":
        return _ref.attention(q, k_cache, v_cache, causal=True,
                              lengths=lengths)
    return _decode_xla(q, k_cache, v_cache, lengths)


@jax.jit
def _decode_xla(q, k_cache, v_cache, lengths):
    """k_cache, v_cache viewed as (B, S, Hkv, D)."""
    B, _, Hq, D = q.shape
    _, S, Hkv, Dv = v_cache.shape
    group = Hq // Hkv
    qg = q.astype(jnp.float32).reshape(B, Hkv, group, D) / np.sqrt(D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache.astype(jnp.float32))
    mask = jnp.arange(S)[None, :] < lengths[:, None]
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhe->bhge", p, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, Hq, Dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# latent-attention decode (one token, latent cache, per-sequence lengths)
# ---------------------------------------------------------------------------
def mla_decode(q, cache, lengths, *, value_width: int, layer=None,
               backend: Backend = "auto"):
    """Absorbed latent attention for one query token. q: (B, H, W) scaled
    queries ``[q_n W_uk^T | q_r]``; cache: (B, S, W) rows ``[c | k_r]`` as
    ``models/kvcache.py`` stores them, or with ``layer`` the stacked
    layers (L, B, S, W) and the index of the one to read; lengths: (B,)
    valid positions.  Returns (B, H, value_width), each head's weighted
    sum of latents."""
    if layer is None:
        cache, layer = cache[None], 0
    b = resolve("mla_decode", backend)
    if b in ("pallas", "pallas_interpret"):
        return _mla.mla_decode(q, cache, lengths, layer,
                               value_width=value_width,
                               interpret=(b == "pallas_interpret"))
    cache = cache[layer]
    if b == "stub":
        kv = cache.sum(1)[:, None, :value_width]             # reads the cache
        scale = (1 + lengths.astype(q.dtype) * 0)[:, None, None]
        return (q[..., :value_width] * kv * scale).astype(cache.dtype)
    if b == "ref":
        return _ref.mla_decode(q, cache, lengths, value_width)
    return _mla_decode_xla(q, cache, lengths, value_width)


@functools.partial(jax.jit, static_argnums=(3,))
def _mla_decode_xla(q, cache, lengths, value_width):
    return _ref.mla_decode(q, cache, lengths, value_width).astype(cache.dtype)


# ---------------------------------------------------------------------------
# grouped expert SwiGLU (drop-free MoE dispatch)
# ---------------------------------------------------------------------------
def moe_gmm(x, wi_gate, wi, wo, group_sizes, *, backend: Backend = "auto"):
    """SwiGLU of each held expert over its rows. x: (M, d) rows sorted by
    expert, ``group_sizes[e]`` of them for expert e; weights (E, d, ff) and
    (E, ff, d).  Rows past the groups come back as zeros.  (M, d) float32."""
    b = resolve("moe_gmm", backend)
    if b in ("pallas", "pallas_interpret"):
        return _moe.moe_gmm(x, wi_gate, wi, wo, group_sizes,
                            interpret=(b == "pallas_interpret"))
    if b == "stub":
        w = wi.sum(0) + wi_gate.sum(0)                       # reads weights
        return (x @ w @ wo.sum(0)).astype(jnp.float32)
    if b == "ref":
        return _ref.moe_gmm(x, wi_gate, wi, wo, group_sizes)
    return _moe_gmm_xla(x, wi_gate, wi, wo, group_sizes)


@jax.jit
def _moe_gmm_xla(x, wi_gate, wi, wo, group_sizes):
    """``jax.lax.ragged_dot`` products: rows past the groups are zeros."""
    gs = group_sizes.astype(jnp.int32)
    f32 = jnp.float32
    up = jax.lax.ragged_dot(x, wi, gs, preferred_element_type=f32)
    gate = jax.lax.ragged_dot(x, wi_gate, gs, preferred_element_type=f32)
    act = (jax.nn.silu(gate) * up).astype(x.dtype)
    return jax.lax.ragged_dot(act, wo, gs, preferred_element_type=f32)


# ---------------------------------------------------------------------------
# gated linear recurrence (RG-LRU core)
# ---------------------------------------------------------------------------
def linear_scan(a, b, h0=None, *, backend: Backend = "auto"):
    """h_t = a_t h_{t-1} + b_t over axis 1.  a, b: (B, S, D)."""
    be = resolve("linear_scan", backend)
    if be == "stub":
        h = (a * b).astype(a.dtype)                    # reads a, b; writes h
        last = h[:, -1].astype(jnp.float32) + (
            0.0 if h0 is None else h0.astype(jnp.float32))
        return h, last
    if be == "ref":
        return _ref.linear_scan(a, b, h0)
    if be in ("pallas", "pallas_interpret"):
        return _rglru.rglru_scan(a, b, h0,
                                 interpret=(be == "pallas_interpret"))
    return _linear_scan_xla(a, b, h0)


@jax.jit
def _linear_scan_xla(a, b, h0=None):
    """Log-depth associative scan (Blelloch) — XLA-friendly."""
    B, S, D = a.shape
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    if h0 is not None:
        # fold h0 into the first step: h_1 = a_1 (h0) + b_1
        bf = bf.at[:, 0].add(af[:, 0] * h0.astype(jnp.float32))

    def combine(x, y):
        ax, bx = x
        ay, by = y
        return ax * ay, ay * bx + by

    aa, bb = jax.lax.associative_scan(combine, (af, bf), axis=1)
    h_all = bb.astype(a.dtype)
    return h_all, bb[:, -1]


# ---------------------------------------------------------------------------
# RWKV-6 recurrence
# ---------------------------------------------------------------------------
def rwkv6(r, k, v, w, u, state0=None, *, backend: Backend = "auto"):
    be = resolve("rwkv6", backend)
    if be == "stub":
        g = (r + k + w).sum(-1, keepdims=True)         # reads r, k, w
        y = (v * g).astype(v.dtype)                    # reads v, writes y
        B, T, H, D = r.shape
        Dv = v.shape[-1]
        s0 = (jnp.zeros((B, H, D, Dv), jnp.float32) if state0 is None
              else state0.astype(jnp.float32))
        sT = s0 + (k.astype(jnp.float32).mean(1)[..., None]
                   * v.astype(jnp.float32).mean(1)[..., None, :])
        return y, sT
    if be == "ref":
        return _ref.rwkv6(r, k, v, w, u, state0)
    if be in ("pallas", "pallas_interpret"):
        return _rwkv6.rwkv6_scan(r, k, v, w, u, state0,
                                 interpret=(be == "pallas_interpret"))
    return _rwkv6_xla(r, k, v, w, u, state0)


@jax.jit
def _rwkv6_xla(r, k, v, w, u, state0=None):
    B, T, H, D = r.shape
    Dv = v.shape[-1]
    S0 = (jnp.zeros((B, H, D, Dv), jnp.float32) if state0 is None
          else state0.astype(jnp.float32))
    uf = u.astype(jnp.float32)

    def step(S, xs):
        rt, kt, vt, wt = xs
        kv = kt[..., None] * vt[..., None, :]               # (B,H,D,Dv)
        y = ((S + uf[None, :, :, None] * kv)
             * rt[..., None]).sum(axis=2)                   # (B,H,Dv)
        S = wt[..., None] * S + kv
        return S, y

    xs = tuple(x.astype(jnp.float32).transpose(1, 0, 2, 3)
               for x in (r, k, v, w))
    S, ys = jax.lax.scan(step, S0, xs)
    y = ys.transpose(1, 0, 2, 3).astype(v.dtype)            # (B,T,H,Dv)
    return y, S
