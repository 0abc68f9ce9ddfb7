"""Model FLOPs of the tokens decoded in the window over the engine's
decode time, as a percent of the chip's bf16 peak, for the chip's share
of the latent-attention MoE decoder (``flops_mla.py``).  The routed rows
a token costs are the window's: rows the held experts computed per slot
in the engine steps that admitted nothing, from the program's MoE
counters.  Programs without them report nothing."""
import flops_mla


def read(ctx):
    moe = ctx.get("moe_steps")
    if not moe:
        return None
    recs = [m for s, m in zip(ctx["steps"], moe) if not s.admitted]
    slot_steps = len(recs) * ctx["max_slots"]
    if not slot_steps:
        return None
    per_token = sum(m["moe_rows_total"] for m in recs) / slot_steps
    work = sum(flops_mla.decode_token(ctx["sizes"], c, per_token)
               for s in ctx["steps"] for c in s.decoded)
    secs = ctx["decode_ms"] / 1e3
    if secs <= 0 or not work:
        return None
    return 100.0 * work / secs / ctx["peak"]["bf16_flops_per_s"]
