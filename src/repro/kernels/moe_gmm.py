"""Drop-free grouped SwiGLU over the experts a chip holds (Pallas TPU).

Rows arrive sorted by expert: the first ``group_sizes[0]`` rows go to
held expert 0, the next ``group_sizes[1]`` to expert 1, and so on; rows
past the groups' sum (assignments to experts held elsewhere) are not
computed and come back as zeros.  Each of the three products is megablox's
grouped matmul (``jax.experimental.pallas.ops.tpu.megablox``): its grid
visits only the row tiles some group owns, and an expert with no rows is
never read.  The kernels run under this module's jit, so the device trace
names them ``moe_gmm``.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp

#: megablox's kernel without its jit, so the ops take this module's name
_gmm = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm").gmm.__wrapped__


def _tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    """Row tile 16 for decode-sized batches, 128 past 1,024 rows; the
    contraction whole up to 2,048 and 512 output lanes, so a step's
    weight tile stays near 2 MB of VMEM."""
    tm = 128 if m >= 1024 else 16
    return tm, min(k, 2048), min(n, 512)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_gmm(x, wi_gate, wi, wo, group_sizes, *, interpret: bool = False):
    """x: (M, d) rows sorted by held expert; wi_gate, wi: (E, d, ff);
    wo: (E, ff, d); group_sizes: (E,) int32.  Returns (M, d) float32:
    ``SwiGLU_e(x_r)`` for each row r of group e, zeros past the groups."""
    m, d = x.shape
    ff = wi.shape[-1]
    tm = _tiling(m, d, ff)[0]
    mp = -(-m // tm) * tm
    xp = jnp.pad(x, ((0, mp - m), (0, 0)))
    gs = group_sizes.astype(jnp.int32)
    up = _gmm(xp, wi, gs, jnp.float32, _tiling(mp, d, ff),
              interpret=interpret)
    gate = _gmm(xp, wi_gate, gs, jnp.float32, _tiling(mp, d, ff),
                interpret=interpret)
    act = (jax.nn.silu(gate) * up).astype(x.dtype)
    out = _gmm(act, wo, gs, jnp.float32, _tiling(mp, ff, d),
               interpret=interpret)
    # megablox leaves rows past the groups unwritten
    live = jnp.arange(mp) < jnp.sum(gs)
    return jnp.where(live[:, None], out, 0.0)[:m]
