"""The latent-attention decode kernel (``kernels/mla_decode.py``): per
call (one layer), each live sequence's H absorbed queries, ``W = kv_rank
+ rope_dim`` wide, attend over its ``context`` cached rows.  The
algorithm needs, per position and head, a W-wide score product and a
``kv_rank``-wide output product, 2 * H * (W + kv_rank) * context
operations, and one read of the live rows, context * W elements, plus
the queries and the outputs; slots the engine holds empty need nothing."""
import re

NAME = re.compile(r"^mla_decode(\.\d+)?$")


def match(name: str, stats: dict) -> bool:
    return bool(NAME.match(name))


def cost(contexts, s: dict, elem_bytes: int = 2):
    """(flops, bytes) of one layer's call over live ``contexts``."""
    H, r = s["H"], s["r"]
    w = r + s["rope"]
    flops = sum(2 * H * (w + r) * c for c in contexts)
    rows = sum(c * w for c in contexts)
    qo = len(contexts) * H * (w + r)
    return flops, (rows + qo) * elem_bytes
