"""The Metropolis-plus-incumbent select kernel (``kernels/search.py``):
per call, P chains with W = w * gmax assignment lanes.  It reads the
current, proposed and best assignments (int32) and four float32 columns
(current, proposed and best objectives, the uniform draw), and writes the
new current and best assignments and objectives.  Its operations (the
acceptance test and the selects) are a few per chain and lane; the bytes
bound it."""
import re

from rooflines.common import out_shape

NAME = re.compile(r"^_pallas_select(\.\d+)?$")


def match(name: str, stats: dict) -> bool:
    return bool(NAME.match(name))


def cost(stats: dict):
    """(flops, bytes) of one call, from its output shape s32[P, W]."""
    shape = out_shape(stats)
    if shape is None or len(shape[1]) != 2:
        return None
    P, W = shape[1]
    reads = 3 * P * W * 4 + 4 * P * 4 + 4
    writes = 2 * P * W * 4 + 2 * P * 4
    return 2 * P * W + 8 * P, reads + writes
