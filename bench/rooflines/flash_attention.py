"""The causal flash-attention prefill kernel (``kernels/flash_attention.py``):
per call, head-major q, k, v of (B, H, S, D) in bfloat16 and an output
of the same shape.  The algorithm needs QK and PV over the causal
triangle, 2 * 2 * B * H * D * S (S + 1) / 2 operations, and one read of
q, k, v and one write of the output."""
import re

from rooflines.common import BYTES, out_shape

NAME = re.compile(r"^flash_attention(\.\d+)?$")


def match(name: str, stats: dict) -> bool:
    return bool(NAME.match(name))


def cost(stats: dict):
    shape = out_shape(stats)
    if shape is None or len(shape[1]) != 4:
        return None
    dtype, (B, H, S, D) = shape
    flops = 2 * 2 * B * H * D * S * (S + 1) // 2
    return flops, 4 * B * H * S * D * BYTES[dtype]
