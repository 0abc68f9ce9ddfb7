"""The single-token decode-attention kernel (``kernels/decode_attention.py``):
per call (one layer), each live sequence's query attends over its
``context`` cached positions.  The algorithm needs 2 * 2 * H * D *
context operations and one read of the live keys and values, 2 * context
* Hkv * D elements, plus q and the output; slots the engine holds empty
need nothing."""
import re

NAME = re.compile(r"^decode_attention(\.\d+)?$")


def match(name: str, stats: dict) -> bool:
    return bool(NAME.match(name))


def cost(contexts, s: dict, elem_bytes: int = 2):
    """(flops, bytes) of one layer's call over live ``contexts``."""
    H, Hkv, D = s["H"], s["Hkv"], s["dh"]
    flops = sum(2 * 2 * H * D * c for c in contexts)
    kv = sum(2 * c * Hkv * D for c in contexts)
    qo = 2 * len(contexts) * H * D
    return flops, (kv + qo) * elem_bytes
