"""Single-token GQA decode attention over a KV cache (Pallas TPU kernel).

One query token per sequence attends over a long cache with per-sequence
valid lengths.  Grid = (batch, kv_tiles); the kv tile axis is
innermost/sequential with the online-softmax state in VMEM scratch, so HBM
traffic is exactly one read of the live cache region — the memory roofline
for decode.  Tiles past a sequence's length are neither read nor computed:
the lengths arrive by scalar prefetch, and the kv index map clamps to the
last live tile, so a skipped step re-uses the block already in VMEM.

The cache arrives as it is stored (``models/kvcache.py``), lane-dense
``(B, S, Hkv·D)`` with heads major in the last dim, inside the stack of
layers ``(L, B, S, Hkv·D)`` the decode scan carries: the layer index is
a second scalar-prefetch operand, so the kernel reads its layer's tiles
where they lie and no layer is sliced out of the stack first.  A block
is ``(1, kv_tile, Hkv·D)`` (the layer dim squeezed), every head of a
tile of positions, with lane-dense last dims as Mosaic requires.  A
``(B, S, Hkv, D)`` cache would need a reshape here, and on a TPU that is
no free view: with D = 64 XLA stores such a cache sequence-minor and
re-lays it out whole.  Per-head dot products become one segmented lane
reduction — ``(k ⊙ q) @ E`` with the 0/1 head-indicator matrix ``E``
(Hkv·D, Hkv) on the MXU — and the softmax weights are broadcast back onto
their head's lanes by ``p @ Eᵀ``.  For GQA the query heads are regrouped
to ``(group, Hkv·D)`` rows, one per member of each kv head's query group.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
#: bytes of one float32 kv tile the body works on; sizes the kv tile so a
#: step's float32 temporaries stay well inside the default scoped VMEM.
_TILE_BYTES = 1 << 20


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _kernel(len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
            acc_scr, *, scale: float, block_kv: int, head_dim: int,
            group: int):
    b = pl.program_id(0)
    ikv = pl.program_id(1)
    n_kv = pl.num_programs(1)
    hd = k_ref.shape[-1]
    seg = (jax.lax.broadcasted_iota(jnp.int32, (hd, hd // head_dim), 0)
           // head_dim == jax.lax.broadcasted_iota(
               jnp.int32, (hd, hd // head_dim), 1)).astype(jnp.float32)

    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]

    @pl.when(ikv * block_kv < length)
    def _tile():
        valid = (ikv * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_kv, 1), 0)) < length
        k = jnp.where(valid, k_ref[0].astype(jnp.float32), 0.0)  # (bkv, hd)
        v = jnp.where(valid, v_ref[0].astype(jnp.float32), 0.0)
        for g in range(group):                    # static: query group
            q = q_ref[0, g:g + 1, :].astype(jnp.float32) * scale  # (1, hd)
            s = _dot(k * q, seg, ((1,), (0,)))                  # (bkv, Hkv)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_scr[g:g + 1, :]                          # (1, Hkv)
            m_new = jnp.maximum(m_prev, s.max(axis=0, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[g:g + 1, :] = (l_scr[g:g + 1, :] * alpha
                                 + p.sum(axis=0, keepdims=True))
            acc_scr[g:g + 1, :] = (
                acc_scr[g:g + 1, :] * _dot(alpha, seg, ((1,), (1,)))
                + (_dot(p, seg, ((1,), (1,))) * v).sum(axis=0, keepdims=True))
            m_scr[g:g + 1, :] = m_new

    @pl.when(ikv == n_kv - 1)
    def _finalize():
        denom = _dot(jnp.maximum(l_scr[...], 1e-30), seg, ((1,), (1,)))
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_kv", "interpret"))
def decode_attention(q, k_cache, v_cache, lengths, layer, *,
                     block_kv: int = 512, interpret: bool = False):
    """q: (B, 1, Hq, D); caches: stacked layers (L, B, S, Hkv·D), of which
    layer ``layer`` (an int32 scalar) is read; lengths: (B,) int32."""
    b, sq, hq, d = q.shape
    assert sq == 1, "decode kernel: one query token"
    _, _, skv, hd = v_cache.shape
    assert hd % d == 0, "decode kernel: one head dim for q, k and v"
    hkv = hd // d
    group = hq // hkv
    fit = max(8, _TILE_BYTES // (4 * hd) // 8 * 8)
    block_kv = min(block_kv, fit, skv)
    if block_kv < skv:
        block_kv = max(8, block_kv // 8 * 8)
    scale = 1.0 / (d ** 0.5)
    # query head h belongs to kv head h // group: rows g hold, for every kv
    # head, its g-th query head — the same (Hkv·D) lane layout as the cache.
    qg = q.reshape(b, hkv, group, d).transpose(0, 2, 1, 3).reshape(
        b, group, hd)

    def kv_map(b_, ikv, lens, lay):
        last = jnp.maximum(lens[b_] - 1, 0) // block_kv
        return lay[0], b_, jnp.minimum(ikv, last), 0

    kernel = functools.partial(_kernel, scale=scale, block_kv=block_kv,
                               head_dim=d, group=group)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, pl.cdiv(skv, block_kv)),
            in_specs=[
                pl.BlockSpec((1, group, hd),
                             lambda b_, ikv, lens, lay: (b_, 0, 0)),
                pl.BlockSpec((pl.squeezed, 1, block_kv, hd), kv_map),
                pl.BlockSpec((pl.squeezed, 1, block_kv, hd), kv_map),
            ],
            out_specs=pl.BlockSpec((1, group, hd),
                                   lambda b_, ikv, lens, lay: (b_, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((group, hkv), jnp.float32),    # running max
                pltpu.VMEM((group, hkv), jnp.float32),    # denominator
                pltpu.VMEM((group, hd), jnp.float32),     # output accum
            ]),
        out_shape=jax.ShapeDtypeStruct((b, group, hd), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      qg, k_cache, v_cache)
    return out.reshape(b, group, hkv, d).transpose(0, 2, 1, 3).reshape(
        b, 1, hq, d)
