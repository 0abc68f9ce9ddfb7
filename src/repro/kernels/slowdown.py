"""Batched piecewise-linear PCCS slowdown surface as a Pallas kernel.

The innermost op of the XLA schedule evaluator
(:mod:`repro.core.simulate_jax`) is the contention model: one slowdown
lookup per candidate × workload × contention interval.  For PCCS proper
(:class:`~repro.core.contention.PiecewiseModel`) that lookup is bilinear
interpolation of a calibration table over (own, external) demand — a
gather, which TPUs hate.  This kernel reformulates it gather-free as a
tensor-product of 1-D *hat* bases:

    s(own, ext) = Σ_i Σ_j hat_i(own) · hat_j(ext) · table[i, j]
                = hatO @ table @ hatE^T        (row-wise)

so each block of demands becomes a static unroll of K·M multiply-adds on
the VPU — no dynamic indexing, no scatter.  The demands are laid out as
lane-dense ``(rows, 128)`` tiles (row blocks a multiple of 8, as Mosaic
requires); the knots and table ride along whole in SMEM (they are a
handful of floats, read as scalars).

Backends follow the repo-wide dispatch idiom (:mod:`repro.kernels.ops`):

  * ``pallas``           — Mosaic lowering on TPU;
  * ``pallas_interpret`` — same kernel body, interpreted (tests on CPU);
  * ``xla``              — the identical contraction in pure jnp
                           (:func:`repro.kernels.ref.piecewise_slowdown`),
                           used on CPU and inside vmapped/tiny call sites
                           where a kernel launch cannot pay for itself;
  * ``auto``             — pallas on TPU for big flat float32 batches, xla
                           otherwise.

The NumPy evaluator stack never reaches this module: its fallback is
``repro.core.lowering.slowdown_array`` (surface dispatch + elementwise
last resort), which the differential suite pins to the scalar models.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ops import LANES, lane_tiling, resolve
from .ref import piecewise_slowdown as _ref_piecewise

#: below this many demand points a pallas launch cannot pay for itself —
#: ``backend="auto"`` stays on the fused-XLA contraction instead.
_MIN_PALLAS_ELEMS = 4096


def _knot_rows(knots):
    """(5, K) scalar table per knot: value, left and right neighbours, and
    the two hat-edge widths — the per-knot constants of
    :func:`repro.kernels.ref._hat_weights`, computed once outside the
    kernel so the body only reads SMEM scalars."""
    prev = jnp.concatenate([knots[:1], knots[:-1]])
    nxt = jnp.concatenate([knots[1:], knots[-1:]])
    tiny = jnp.asarray(1e-30, knots.dtype)
    return jnp.stack([knots, prev, nxt, jnp.maximum(knots - prev, tiny),
                      jnp.maximum(nxt - knots, tiny)])


def _hats(kr, x):
    """Hat weights of tile ``x`` against every knot of ``kr`` (a (5, K)
    SMEM ref from :func:`_knot_rows`): a list of K tiles shaped like x."""
    n = kr.shape[1]
    out = []
    for i in range(n):
        up = (x - kr[1, i]) / kr[3, i]            # rising edge
        dn = (kr[2, i] - x) / kr[4, i]            # falling edge
        h = jnp.clip(jnp.minimum(up, dn), 0.0, 1.0)
        if i == 0:
            h = jnp.where(x <= kr[0, 0], 1.0, h)
        if i == n - 1:
            h = jnp.where(x >= kr[0, n - 1], 1.0, h)
        out.append(h)
    return out


def _kernel(ok_ref, ek_ref, tab_ref, own_ref, ext_ref, out_ref):
    own = own_ref[...]                      # (rows, 128)
    ext = ext_ref[...]
    m = ek_ref.shape[1]
    ho = _hats(ok_ref, own)
    he = _hats(ek_ref, ext)
    s = jnp.zeros_like(own)
    for i, hi in enumerate(ho):
        row = he[0] * tab_ref[i * m]
        for j in range(1, m):
            row = row + he[j] * tab_ref[i * m + j]
        s = s + hi * row
    out_ref[...] = jnp.where((own <= 0.0) | (ext <= 0.0), 1.0, s)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _pallas_piecewise(own, ext, own_knots, ext_knots, table, *,
                      block: int, interpret: bool):
    n = own.shape[0]
    rb, nb = lane_tiling(n, block)
    pad = nb * rb * LANES - n
    own2 = jnp.pad(own, (0, pad)).reshape(-1, LANES)
    ext2 = jnp.pad(ext, (0, pad)).reshape(-1, LANES)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    tile = pl.BlockSpec((rb, LANES), lambda i: (i, 0))
    flat = pl.pallas_call(
        _kernel,
        grid=(nb,),
        in_specs=[smem, smem, smem, tile, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((nb * rb, LANES), own.dtype),
        interpret=interpret,
    )(_knot_rows(own_knots), _knot_rows(ext_knots), table.reshape(-1),
      own2, ext2)
    return flat.reshape(-1)[:n]


def piecewise_slowdown(own, ext, own_knots, ext_knots, table, *,
                       backend: str = "auto", block: int = 8192):
    """Batched PCCS slowdown over equal-shaped demand arrays.

    ``own``/``ext`` are demand fractions of any shape; ``own_knots`` (K,),
    ``ext_knots`` (M,) and ``table`` (K, M) are the calibration surface.
    Returns the elementwise slowdown (1.0 wherever either demand is zero),
    matching ``PiecewiseModel.slowdown`` within float tolerance.
    """
    own = jnp.asarray(own)
    ext = jnp.asarray(ext)
    ok = jnp.asarray(own_knots, own.dtype)
    ek = jnp.asarray(ext_knots, own.dtype)
    tab = jnp.asarray(table, own.dtype)
    # Mosaic has no float64: the x64 evaluator's surfaces stay on XLA.
    b = resolve("piecewise_slowdown", backend, pallas_ok=(
        own.size >= _MIN_PALLAS_ELEMS and own.dtype == jnp.float32))
    if b in ("xla", "ref"):
        return _ref_piecewise(own, ext, ok, ek, tab)
    if b in ("pallas", "pallas_interpret"):
        out = _pallas_piecewise(own.reshape(-1), ext.reshape(-1), ok, ek,
                                tab, block=block,
                                interpret=(b == "pallas_interpret"))
        return out.reshape(own.shape)
    raise ValueError(f"unknown backend {b!r}")
