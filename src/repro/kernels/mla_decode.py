"""Single-token latent-attention decode over the latent cache (Pallas TPU
kernel).

Multi-head latent attention (DeepSeek-V2) caches one row per position:
the normed latent ``c`` (``kv_rank`` wide) and the rotary key ``k_r``
shared by every head.  Decode runs in absorbed form: each head's query
arrives as ``[q_n W_uk^T | q_r]`` (scaled), so its score against a
position is one dot product with the cached row, and its output, before
``W_uv``, is the softmax-weighted sum of the rows' latents.  Every head
reads the same rows: one ``(heads, W) x (W, kv_tile)`` product gives all
scores of a tile and one ``(heads, kv_tile) x (kv_tile, kv_rank)``
product all outputs, so each cached row is read from HBM once per step
and serves both.

Grid = (batch, kv_tiles), the kv tile axis innermost and sequential with
the online-softmax state in VMEM scratch.  Tiles past a sequence's length
are neither read nor computed: the lengths arrive by scalar prefetch, and
the kv index map clamps to the last live tile.  The cache arrives as
stored (``models/kvcache.py``), ``(B, S, W)`` with ``W`` the row's
``kv_rank + rope_dim`` lanes zero-padded to whole 128-lane tiles, inside
the stack of layers ``(L, B, S, W)`` the decode scan carries; the layer
index is a second scalar-prefetch operand, and each block holds whole
rows of that layer (the layer dim squeezed).  The query is padded alike.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, layer_ref, q_ref, c_ref, o_ref, m_scr, l_scr, acc_scr,
            *, block_kv: int, value_width: int):
    b = pl.program_id(0)
    ikv = pl.program_id(1)
    n_kv = pl.num_programs(1)

    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]

    @pl.when(ikv * block_kv < length)
    def _tile():
        start = ikv * block_kv
        row_ok = (start + jax.lax.broadcasted_iota(
            jnp.int32, (block_kv, 1), 0)) < length
        col_ok = (start + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_kv), 1)) < length
        rows = jnp.where(row_ok, c_ref[0], 0)                   # (bkv, W)
        s = jax.lax.dot_general(q_ref[0], rows, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(col_ok, s, NEG_INF)                        # (H, bkv)
        m_prev = m_scr[...]                                      # (H, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :value_width],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ikv == n_kv - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(
            o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("value_width", "block_kv", "interpret"))
def mla_decode(q, cache, lengths, layer, *, value_width: int,
               block_kv: int = 512, interpret: bool = False):
    """q: (B, H, W) absorbed, scaled queries; cache: stacked layers of
    latent rows (L, B, S, W), of which layer ``layer`` (an int32 scalar)
    is read; lengths: (B,) int32 valid positions.  Returns (B, H,
    value_width): each head's softmax-weighted sum of the rows' first
    ``value_width`` lanes."""
    b, h, w = q.shape
    _, _, skv, wc = cache.shape
    assert wc == w, "mla decode: the query is as wide as a cached row"
    block_kv = min(block_kv, skv)
    if block_kv < skv:
        block_kv = max(8, block_kv // 8 * 8)

    def kv_map(b_, ikv, lens, lay):
        last = jnp.maximum(lens[b_] - 1, 0) // block_kv
        return lay[0], b_, jnp.minimum(ikv, last), 0

    kernel = functools.partial(_kernel, block_kv=block_kv,
                               value_width=value_width)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, pl.cdiv(skv, block_kv)),
            in_specs=[
                pl.BlockSpec((1, h, w),
                             lambda b_, ikv, lens, lay: (b_, 0, 0)),
                pl.BlockSpec((pl.squeezed, 1, block_kv, w), kv_map),
            ],
            out_specs=pl.BlockSpec((1, h, value_width),
                                   lambda b_, ikv, lens, lay: (b_, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),              # running max
                pltpu.VMEM((h, 1), jnp.float32),              # denominator
                pltpu.VMEM((h, value_width), jnp.float32),    # output accum
            ]),
        out_shape=jax.ShapeDtypeStruct((b, h, value_width), cache.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      q.astype(cache.dtype), cache)
