"""Blocked flash attention as a Pallas TPU kernel.

Online-softmax attention with explicit BlockSpec VMEM tiling, MXU-aligned
(128-multiple) q/kv tiles, GQA via index-mapped kv head selection, and
causal / local-window / bidirectional masking with fully-masked-tile
skipping.  Grid = (batch, q_heads, q_tiles, kv_tiles); the kv dimension is
innermost (sequential on TPU), with the running max / denominator / output
accumulator carried in VMEM scratch across kv tiles.  The kernel sees
head-major ``(B, H, S, D)`` operands, so each block is ``(1, 1, tile, D)``:
the last two block dims are a sequence tile (a multiple of 8, or the whole
sequence) and the full head dim, as Mosaic requires.

Validated against :mod:`repro.kernels.ref` in interpret mode on CPU; on a
real TPU backend the same ``pl.pallas_call`` lowers to Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int | None,
            block_q: int, block_kv: int, seq_q: int, seq_kv: int):
    iq = pl.program_id(2)
    ikv = pl.program_id(3)
    n_kv = pl.num_programs(3)

    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # absolute positions of this tile's queries/keys (queries are the last
    # seq_q positions of the kv timeline — decode-style offset).
    q_pos = (iq * block_q + (seq_kv - seq_q)
             + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0))
    k_pos = ikv * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_kv), 1)

    def _tile():
        q = q_ref[0, 0].astype(jnp.float32) * scale           # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)                   # (bkv, d)
        v = v_ref[0, 0].astype(jnp.float32)                   # (bkv, dv)
        # zero the padded kv tail: p is 0 there but 0*NaN would poison acc
        kv_valid = (ikv * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_kv, 1), 0)) < seq_kv
        k = jnp.where(kv_valid, k, 0.0)
        v = jnp.where(kv_valid, v, 0.0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = (k_pos < seq_kv) & (q_pos < seq_kv)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                                   # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    if causal or window is not None:
        # skip tiles that are entirely masked out
        first_q = iq * block_q + (seq_kv - seq_q)
        last_q = first_q + block_q - 1
        first_k = ikv * block_kv
        last_k = first_k + block_kv - 1
        live = jnp.bool_(True)
        if causal:
            live &= first_k <= last_q
        if window is not None:
            live &= last_k > first_q - window
        pl.when(live)(_tile)
    else:
        _tile()

    @pl.when(ikv == n_kv - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_kv", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    block_q: int = 512, block_kv: int = 512,
                    interpret: bool = False):
    """q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D) -> (B, Sq, Hq, D)."""
    b, sq, hq, d = q.shape
    _, skv, hkv, dv = v.shape
    assert hq % hkv == 0, "GQA requires n_heads % n_kv_heads == 0"
    group = hq // hkv
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    nq = pl.cdiv(sq, block_q)
    nkv = pl.cdiv(skv, block_kv)
    scale = 1.0 / (d ** 0.5)

    grid = (b, hq, nq, nkv)
    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_kv=block_kv, seq_q=sq, seq_kv=skv)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h, iq, ikv: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b_, h, iq, ikv, g=group: (b_, h // g, ikv, 0)),
            pl.BlockSpec((1, 1, block_kv, dv),
                         lambda b_, h, iq, ikv, g=group: (b_, h // g, ikv, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dv),
                               lambda b_, h, iq, ikv: (b_, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),        # running max
            pltpu.VMEM((block_q, 1), jnp.float32),        # denominator
            pltpu.VMEM((block_q, dv), jnp.float32),       # output accum
        ],
        interpret=interpret,
    )(*(x.transpose(0, 2, 1, 3) for x in (q, k, v)))
    return out.transpose(0, 2, 1, 3)
