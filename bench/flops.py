"""Model FLOPs of the dense decoder, from the configuration's sizes
(``reference.lm_ref.sizes``): 2 per multiply-add of every weight matrix,
and of attention's QK and PV products over the causal context."""


def _matmul_per_token(s: dict) -> int:
    d, ff = s["d"], s["ff"]
    qkvo = d * s["dh"] * (2 * s["H"] + 2 * s["Hkv"])
    return 2 * s["L"] * (qkvo + 3 * d * ff)


def prefill(s: dict, prompt: int) -> int:
    """A prompt of ``prompt`` tokens, with the output head for its last
    token only (what the first generated token needs)."""
    attn = 2 * 2 * s["L"] * s["H"] * s["dh"] * prompt * (prompt + 1) // 2
    return _matmul_per_token(s) * prompt + attn + 2 * s["d"] * s["V"]


def decode_token(s: dict, context: int) -> int:
    """One generated token attending over ``context`` cached positions."""
    attn = 2 * 2 * s["L"] * s["H"] * s["dh"] * context
    return _matmul_per_token(s) + attn + 2 * s["d"] * s["V"]
