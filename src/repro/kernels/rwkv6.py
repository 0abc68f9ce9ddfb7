"""RWKV-6 (Finch) time-mix recurrence as a Pallas TPU kernel.

Per head h with matrix state S in R^{DxDv}:

    y_t = r_t^T (S_{t-1} + (u ⊙ k_t) v_t^T)
    S_t = diag(w_t) S_{t-1} + k_t v_t^T          (w_t: data-dependent decay)

Grid = (batch, heads, time_tiles); the (D, Dv) state lives in VMEM scratch
across the sequential time-tile axis, and each tile walks its steps in
aligned 16-step chunks (the sequence is padded to whole chunks) of rank-1
updates (outer products on the VPU — D=64 keeps the state at 16 KiB, far
under VMEM).  This is the TPU-native adaptation of the
CUDA wkv kernels: channels-per-head map to lanes, the head axis to the grid.

The kernel sees head-major ``(B, H, T, D)`` operands, so a block is
``(1, 1, time_tile, D)`` with the full channel dim last, as Mosaic
requires.  A step's rank-1 update needs k, w and r as columns against the
(D, Dv) state; each row is turned into a column by masking it onto the
diagonal and reducing across lanes (:func:`_column`) — exact, and built
only from broadcasts and reductions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .rglru import _CHUNK


def _column(row, eye):
    """(1, D) row -> (D, 1) column: the row masked onto the diagonal."""
    return jnp.where(eye, row, 0.0).sum(axis=1, keepdims=True)


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sT_ref,
            state, *, block_t: int, seq_len: int, chunk: int):
    it = pl.program_id(2)
    n_t = pl.num_programs(2)
    d = r_ref.shape[-1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (d, d), 1))

    @pl.when(it == 0)
    def _init():
        state[...] = s0_ref[0, 0].astype(jnp.float32)

    u = _column(u_ref[0].astype(jnp.float32), eye)          # (D, 1)

    def body(c, S):
        # a single-chunk tile indexes statically (a dynamic start must be
        # provably aligned to the packed tile, which one row is not)
        start = 0 if block_t == chunk else pl.multiple_of(c * chunk, chunk)
        rows = pl.ds(start, chunk)
        r, k, v, w = (ref[0, 0, rows, :].astype(jnp.float32)
                      for ref in (r_ref, k_ref, v_ref, w_ref))
        ys = []
        for j in range(chunk):                   # static: rows of the chunk
            kv = _column(k[j:j + 1], eye) * v[j:j + 1]       # (D, Dv)
            ys.append(((S + u * kv) * _column(r[j:j + 1], eye)
                       ).sum(axis=0, keepdims=True))         # (1, Dv)
            # steps past seq_len are padding: keep state unchanged
            valid = it * block_t + c * chunk + j < seq_len
            S = jnp.where(valid, _column(w[j:j + 1], eye) * S + kv, S)
        y_ref[0, 0, rows, :] = jnp.concatenate(ys, axis=0).astype(y_ref.dtype)
        return S

    state[...] = jax.lax.fori_loop(0, block_t // chunk, body, state[...])

    @pl.when(it == n_t - 1)
    def _finalize():
        sT_ref[0, 0] = state[...].astype(sT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def rwkv6_scan(r, k, v, w, u, state0=None, *, block_t: int = 128,
               interpret: bool = False):
    """r,k,w: (B,T,H,D); v: (B,T,H,Dv); u: (H,D); state0: (B,H,D,Dv).

    Returns (y (B,T,H,Dv), state (B,H,D,Dv)).
    """
    B, T, H, D = r.shape
    Dv = v.shape[-1]
    if state0 is None:
        state0 = jnp.zeros((B, H, D, Dv), jnp.float32)
    # a one-step decode is one row; longer sequences pad to whole chunks
    chunk = 1 if T == 1 else _CHUNK
    T_pad = pl.cdiv(T, chunk) * chunk
    block_t = min(pl.cdiv(block_t, chunk) * chunk, T_pad)
    grid = (B, H, pl.cdiv(T_pad, block_t))
    kernel = functools.partial(_kernel, block_t=block_t, seq_len=T,
                               chunk=chunk)

    def seq(width):
        return pl.BlockSpec((1, 1, block_t, width),
                            lambda b, h, it: (b, h, it, 0))

    def head_major(x):
        return jnp.pad(x, ((0, 0), (0, T_pad - T), (0, 0), (0, 0))
                       ).transpose(0, 2, 1, 3)

    state = pl.BlockSpec((1, 1, D, Dv), lambda b, h, it: (b, h, 0, 0))
    y, sT = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[seq(D), seq(D), seq(Dv), seq(D),
                  pl.BlockSpec((1, 1, D), lambda b, h, it: (h, 0, 0)),
                  state],
        out_specs=[seq(Dv), state],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T_pad, Dv), v.dtype),
            jax.ShapeDtypeStruct((B, H, D, Dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((D, Dv), jnp.float32)],
        interpret=interpret,
    )(*(head_major(x) for x in (r, k, v, w)), u.reshape(H, 1, D), state0)
    return y[:, :, :T].transpose(0, 2, 1, 3), sT
