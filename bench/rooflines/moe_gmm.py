"""The drop-free expert kernel (``kernels/moe_gmm.py``): three grouped
products per MoE layer over the rows routed to the experts this chip
holds, SwiGLU of width ``eff``.  The algorithm needs 2 * 3 * d * eff
operations per routed row, and reads each held expert's weights that got
at least one row (3 * d * eff bfloat16 elements) once, the rows in
(bfloat16) and writes them out (float32).  The engine's counters give,
per engine step, the rows and the experts touched over every MoE layer
and every model call in it.

The kernel's work includes bringing its weights in.  Inside the scanned
stack XLA slices each layer's held-expert stack out of the stacked
parameters before the kernel runs, and on a v5e it places that slice in
the core's own memory, so the kernel reads its weights from there: the
slice's time is part of the kernel's.  ``staging`` finds those slices:
ops whose output is a whole held-expert stack, ``(n_held, d, eff)`` or
``(n_held, eff, d)``."""
import re

from rooflines.common import out_shape

NAME = re.compile(r"^moe_gmm(\.\d+)?$")


def match(name: str, stats: dict) -> bool:
    return bool(NAME.match(name))


def staging(s: dict):
    """A predicate over (name, stats): an op that writes a held-expert
    weight stack."""
    shapes = {(s["held"], s["d"], s["eff"]), (s["held"], s["eff"], s["d"])}

    def pred(name: str, stats: dict) -> bool:
        shape = out_shape(stats)
        return (not match(name, stats) and shape is not None
                and tuple(shape[1]) in shapes)

    return pred


def cost(rows: int, touched: int, s: dict):
    """(flops, bytes) of ``rows`` routed rows over ``touched`` experts."""
    d, f = s["d"], s["eff"]
    return 2 * 3 * d * f * rows, 3 * d * f * 2 * touched + rows * d * (2 + 4)
