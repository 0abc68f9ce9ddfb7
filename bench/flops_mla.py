"""Model FLOPs of the latent-attention MoE decoder on one chip's share,
from the configuration's sizes (``reference.mla_ref.sizes``): 2 per
multiply-add of every weight matrix a token passes through, of the routed
experts' rows actually computed, and of absorbed attention over the
cached context."""


def dense_per_token(s: dict) -> int:
    """Every layer's attention projections (absorbed form: ``W_uk`` on
    the query, ``W_uv`` on the output), the leading dense MLPs, the
    routers and shared experts, and the output head."""
    d, H, r = s["d"], s["H"], s["r"]
    q_dim = s["nope"] + s["rope"]
    attn = d * H * q_dim + d * (r + s["rope"]) + H * s["nope"] * r \
        + H * r * s["v"] + H * s["v"] * d
    moe_layers = s["L"] - s["dense"]
    mlp = s["dense"] * 3 * d * s["ff"] + moe_layers * (
        d * s["E"] + 3 * d * s["shared"] * s["eff"])
    return 2 * (s["L"] * attn + mlp + d * s["V"])


def routed_row(s: dict) -> int:
    """One row through one routed expert's SwiGLU."""
    return 2 * 3 * s["d"] * s["eff"]


def attention(s: dict, context: int) -> int:
    """Absorbed attention of one token over ``context`` rows, all layers."""
    r = s["r"]
    return 2 * s["L"] * s["H"] * (r + s["rope"] + r) * context


def decode_token(s: dict, context: int, routed_rows: float) -> float:
    """One generated token over ``context`` cached rows, with
    ``routed_rows`` expert rows (over every MoE layer) computed for it."""
    return dense_per_token(s) + routed_rows * routed_row(s) + attention(s, context)
