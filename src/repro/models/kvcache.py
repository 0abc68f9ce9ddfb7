"""KV-cache storage: full or ring-buffer (local attention), bf16 or int8.

A cache *layer view* is a dict ``{"data": (B, S, Hkv·D)}`` plus, when
quantized, ``{"scale": (B, S, Hkv) float32}``.  The layout is the one the
decode kernel reads: every head of a position in one lane-dense row, heads
major, so the kernel takes the layer as stored.  (A ``(B, S, Hkv, D)``
layout costs whole-layer re-layout copies on a TPU every decode step.)
int8 quantization is per (position, head) absmax, one scale broadcast over
its head's D lanes — a beyond-paper memory optimization that keeps the
40-kv-head qwen1.5-32b decode_32k cell inside 16 GB/chip (recorded in
EXPERIMENTS.md §Perf).  Ring buffers exploit softmax permutation-invariance:
slots are overwritten modulo the window and masking is by valid count only.

A latent-attention (``mla``) layer keeps one such view with a single
"head" as wide as its row: per position the normed latent ``c_kv`` and
the shared rotary key, zero-padded to whole lane tiles
(``MLAConfig.cache_width``), from which every head's keys and values are
rebuilt (:func:`latent_from_prefill`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def init_layer(batch: int, seq: int, n_kv: int, d: int, dtype: str):
    if dtype == "int8":
        return {"data": jnp.zeros((batch, seq, n_kv * d), jnp.int8),
                "scale": jnp.zeros((batch, seq, n_kv), jnp.float32)}
    return {"data": jnp.zeros((batch, seq, n_kv * d), jnp.dtype(dtype))}


def size(layer) -> int:
    """Positions a layer holds; a stacked leaf ``(L, B, S, ·)`` too."""
    return layer["data"].shape[-2]


def _quant(x):
    """x: (..., Hkv, D) -> (int8 data (..., Hkv·D), f32 scale (..., Hkv))."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(scale, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8).reshape(*x.shape[:-2], -1), scale[..., 0]


def _rows(x, dtype):
    """x: (..., Hkv, D) -> the stored rows (..., Hkv·D) in ``dtype``."""
    return x.reshape(*x.shape[:-2], -1).astype(dtype)


def dequant(layer):
    """The layer's values, (B, S, Hkv·D): int8 data times its scale."""
    if "scale" in layer:
        data, scale = layer["data"], layer["scale"]
        heads = data.reshape(*scale.shape, -1).astype(jnp.float32)
        return (heads * scale[..., None]).reshape(data.shape).astype(
            jnp.bfloat16)
    return layer["data"]


def insert(layer, new, lengths, window: int | None = None, index=None):
    """Insert one token's kv. new: (B, Hkv, D); lengths: (B,) tokens cached.

    With ``index`` the view is a stacked leaf ``(L, B, S, ·)`` and the row
    goes into its layer ``index``: one row per sequence is written, the
    rest of the stack is left as it is (in place where the caller donates
    or carries it)."""
    b = new.shape[0]
    slot = lengths % size(layer) if window is not None else lengths
    at = (jnp.arange(b), slot)
    if index is not None:
        at = (index,) + at
    if "scale" in layer:
        q, s = _quant(new)
        return {"data": layer["data"].at[at].set(q),
                "scale": layer["scale"].at[at].set(s)}
    return {"data": layer["data"].at[at].set(
        _rows(new, layer["data"].dtype))}


def kernel_view(layer, index=None):
    """What a decode kernel reads: (values, the index of the layer in
    them, or ``None`` for an unstacked layer).

    A stored leaf goes as it is, so the kernel reads a stacked layer
    where it lies.  An int8 layer is dequantised, which materialises the
    one layer (taken from its stack first)."""
    if "scale" not in layer:
        return layer["data"], index
    if index is not None:
        layer = jax.tree.map(lambda a: a[index], layer)
    return dequant(layer), None


def _build(x, capacity: int, dtype: str, window: int | None):
    """One cache layer holding ``x``: (B, S, H, D) from position 0."""
    B, S, H, D = x.shape
    if window is not None:
        cap = min(window, capacity)
        take = min(S, cap)
        x = x[:, S - take:]                                 # last positions
        at = (slice(None), jnp.arange(S - take, S) % cap)
    else:
        cap = capacity
        at = (slice(None), slice(0, S))
    layer = init_layer(B, cap, H, D, dtype)
    if "scale" in layer:
        q, s = _quant(x)
        return {"data": layer["data"].at[at].set(q),
                "scale": layer["scale"].at[at].set(s)}
    return {"data": layer["data"].at[at].set(_rows(x, layer["data"].dtype))}


def from_prefill(k, v, capacity: int, dtype: str, window: int | None = None):
    """Build cache layers from prefill-computed k, v: (B, S, Hkv, D).

    For local attention only the last ``window`` positions are kept (ring
    layout with slot = pos % window so subsequent inserts line up).
    """
    return (_build(k, capacity, dtype, window),
            _build(v, capacity, dtype, window))


def latent_from_prefill(rows, capacity: int, dtype: str):
    """A latent cache layer from prefill-computed rows: (B, S, W)."""
    return _build(rows[:, :, None], capacity, dtype, None)
