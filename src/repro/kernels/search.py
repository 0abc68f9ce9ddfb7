"""Annealing select step (Metropolis accept + incumbent update) as a kernel.

The inner step of the device-resident schedule search
(:mod:`repro.core.search_jax`) is, per temperature step: every chain's
mutated assignment row has been scored by the event machine, and the
population must be *selected* — Metropolis-accept each proposal against the
chain's current state and fold strict improvements into the per-chain
incumbent.  That step is one elementwise decision broadcast across a
(P, L) block of assignment rows: a natural Pallas kernel, blocked over the
chain axis with the row length riding whole and each chain's scalars as a
``(P, 1)`` column blocked alongside its row.

Backends follow the repo-wide dispatch idiom (:mod:`repro.kernels.slowdown`):

  * ``pallas``           — Mosaic lowering on TPU;
  * ``pallas_interpret`` — same kernel body, interpreted (tests on CPU);
  * ``xla``              — the identical decision in pure jnp
                           (:func:`repro.kernels.ref.anneal_select`), used
                           on CPU where a kernel launch cannot pay for
                           itself;
  * ``auto``             — pallas on TPU for big float32 populations, xla
                           otherwise.

All backends compute the same accept predicate from the same uniform draws,
so the search incumbent is bit-identical across them — pinned by
``tests/test_search.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ops import resolve
from .ref import anneal_select as _ref_select

#: below this many chains a pallas launch cannot pay for itself —
#: ``backend="auto"`` stays on the fused-XLA decision instead.
_MIN_PALLAS_CHAINS = 1024


def _kernel(temp_ref, cur_ref, prop_ref, best_ref, curo_ref, propo_ref,
            besto_ref, u_ref, out_cur_ref, out_curo_ref, out_best_ref,
            out_besto_ref):
    curo = curo_ref[...]                     # (B, 1): one chain per row
    propo = propo_ref[...]
    besto = besto_ref[...]
    temp = jnp.maximum(temp_ref[...], 1e-30)         # (1, 1)
    delta = propo - curo
    accept = (delta <= 0) | (u_ref[...] < jnp.exp(-delta / temp))
    accept &= jnp.isfinite(propo)
    improved = propo < besto
    out_cur_ref[...] = jnp.where(accept, prop_ref[...], cur_ref[...])
    out_curo_ref[...] = jnp.where(accept, propo, curo)
    out_best_ref[...] = jnp.where(improved, prop_ref[...], best_ref[...])
    out_besto_ref[...] = jnp.where(improved, propo, besto)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _pallas_select(cur, prop, best, cur_obj, prop_obj, best_obj, u, temp, *,
                   block: int, interpret: bool):
    p, l = cur.shape
    if p <= block:
        block = p                       # one block: the full array
    nb = pl.cdiv(p, block)
    pad = nb * block - p
    # chain-major layout: the (P, L) rows and the (P, 1) per-chain scalars
    # share the row blocking, so every block's last dim is the full array
    # dim and its row count a multiple of 8 (or the whole population).
    rows = [jnp.pad(a, ((0, pad), (0, 0))) for a in (cur, prop, best)]
    cols = [jnp.pad(a, (0, pad)).reshape(-1, 1)
            for a in (cur_obj, prop_obj, best_obj, u)]
    row = pl.BlockSpec((block, l), lambda i: (i, 0))
    col = pl.BlockSpec((block, 1), lambda i: (i, 0))
    dt = cur_obj.dtype
    out = pl.pallas_call(
        _kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)),
                  row, row, row, col, col, col, col],
        out_specs=[row, col, row, col],
        out_shape=[
            jax.ShapeDtypeStruct((nb * block, l), cur.dtype),
            jax.ShapeDtypeStruct((nb * block, 1), dt),
            jax.ShapeDtypeStruct((nb * block, l), cur.dtype),
            jax.ShapeDtypeStruct((nb * block, 1), dt),
        ],
        interpret=interpret,
    )(temp.reshape(1, 1).astype(dt), *rows, *cols)
    return (out[0][:p], out[1][:p, 0], out[2][:p], out[3][:p, 0])


def anneal_select(cur, prop, best, cur_obj, prop_obj, best_obj, u, temp, *,
                  backend: str = "auto", block: int = 256,
                  global_lanes: int | None = None):
    """Metropolis accept + per-chain incumbent update over (P, L) rows.

    Semantics (and the reference oracle) live in
    :func:`repro.kernels.ref.anneal_select`; this wrapper dispatches the
    same decision to a blocked Pallas kernel or the fused XLA form.
    ``global_lanes`` is the population across *all* mesh shards — under
    ``shard_map`` each device sees only its slice of the chain axis, and
    the ``auto`` big-population threshold must be judged on the global
    lane count so backend choice (hence bit-identity) does not change
    with device count.  Returns ``(new_cur, new_cur_obj, new_best,
    new_best_obj)``.
    """
    cur = jnp.asarray(cur)
    cur_obj = jnp.asarray(cur_obj)
    dt = cur_obj.dtype
    prop_obj = jnp.asarray(prop_obj, dt)
    best_obj = jnp.asarray(best_obj, dt)
    u = jnp.asarray(u, dt)
    temp = jnp.asarray(temp, dt)
    # Mosaic has no float64: x64-precision searches select on XLA.
    b = resolve("anneal_select", backend, pallas_ok=(
        (global_lanes or cur.shape[0]) >= _MIN_PALLAS_CHAINS
        and dt == jnp.float32))
    if b in ("xla", "ref"):
        return _ref_select(cur, jnp.asarray(prop), jnp.asarray(best),
                           cur_obj, prop_obj, best_obj, u, temp)
    if b in ("pallas", "pallas_interpret"):
        return _pallas_select(
            cur, jnp.asarray(prop), jnp.asarray(best), cur_obj, prop_obj,
            best_obj, u, temp, block=block, interpret=(b == "pallas_interpret"))
    raise ValueError(f"unknown backend {b!r}")
