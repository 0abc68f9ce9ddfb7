"""Gated linear recurrence (RG-LRU core) as a Pallas TPU kernel.

Computes ``h_t = a_t * h_{t-1} + b_t`` over the time axis with the carry in
VMEM scratch.  Grid = (batch, channel_tiles, time_tiles); time is innermost
(sequential), channels are vectorized across the VPU lanes (tile = 128·k
channels), and each time tile is walked with an in-kernel fori_loop over
aligned 16-step chunks (the sequence is padded to whole chunks).  This is
the TPU-native shape of the RG-LRU: the recurrence is memory-bound and
element-wise, so lane-parallel channels + sequential time maximize VPU
utilization without any MXU involvement.

The carry and the initial/final states are ``(1, channels)`` rows (states
are passed as ``(B, 1, D)``), so every block's last two dims are a time
tile (a multiple of 16, or the whole sequence) or 1, and a channel tile —
the shapes Mosaic accepts.

The same primitive serves recurrentgemma's RG-LRU (a, b precomputed from the
recurrence/input gates) and any diagonal SSM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


#: time steps loaded and stored per aligned access: a 16-row slice is whole
#: (8, 128) float32 tiles and (16, 128) bfloat16 tiles.
_CHUNK = 16


def _kernel(a_ref, b_ref, h0_ref, h_ref, hlast_ref, carry, *,
            block_t: int, seq_len: int, chunk: int):
    it = pl.program_id(2)
    n_t = pl.num_programs(2)

    @pl.when(it == 0)
    def _init():
        carry[...] = h0_ref[0].astype(jnp.float32)

    def body(c, h):
        # a single-chunk tile indexes statically (a dynamic start must be
        # provably aligned to the packed tile, which one row is not)
        start = 0 if block_t == chunk else pl.multiple_of(c * chunk, chunk)
        rows = pl.ds(start, chunk)
        a = a_ref[0, rows, :].astype(jnp.float32)            # (chunk, bd)
        b = b_ref[0, rows, :].astype(jnp.float32)
        hs = []
        for j in range(chunk):                   # static: rows of the chunk
            # steps past seq_len are padding: keep h (NaN-poison guard)
            valid = it * block_t + c * chunk + j < seq_len
            h = jnp.where(valid, a[j:j + 1] * h + b[j:j + 1], h)
            hs.append(h)
        h_ref[0, rows, :] = jnp.concatenate(hs, axis=0).astype(h_ref.dtype)
        return h

    carry[...] = jax.lax.fori_loop(0, block_t // chunk, body, carry[...])

    @pl.when(it == n_t - 1)
    def _finalize():
        hlast_ref[0] = carry[...].astype(hlast_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_t", "block_d", "interpret"))
def rglru_scan(a, b, h0=None, *, block_t: int = 256, block_d: int = 256,
               interpret: bool = False):
    """a, b: (B, S, D); h0: (B, D) -> (h_all (B,S,D), h_last (B,D))."""
    B, S, D = a.shape
    if h0 is None:
        h0 = jnp.zeros((B, D), a.dtype)
    # a one-step decode is one row; longer sequences pad to whole chunks
    chunk = 1 if S == 1 else _CHUNK
    S_pad = pl.cdiv(S, chunk) * chunk
    block_t = min(pl.cdiv(block_t, chunk) * chunk, S_pad)
    block_d = min(block_d, D)
    if S_pad > S:
        a, b = (jnp.pad(x, ((0, 0), (0, S_pad - S), (0, 0))) for x in (a, b))
    grid = (B, pl.cdiv(D, block_d), pl.cdiv(S_pad, block_t))
    kernel = functools.partial(_kernel, block_t=block_t, seq_len=S,
                               chunk=chunk)
    seq = pl.BlockSpec((1, block_t, block_d), lambda b_, id_, it: (b_, it, id_))
    state = pl.BlockSpec((1, 1, block_d), lambda b_, id_, it: (b_, 0, id_))
    h_all, h_last = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[seq, seq, state],
        out_specs=[seq, state],
        out_shape=[
            jax.ShapeDtypeStruct((B, S_pad, D), a.dtype),
            jax.ShapeDtypeStruct((B, 1, D), a.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_d), jnp.float32)],
        interpret=interpret,
    )(a, b, h0.reshape(B, 1, D))
    return h_all[:, :S], h_last.reshape(B, D)
