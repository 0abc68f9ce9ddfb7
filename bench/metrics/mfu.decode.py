"""Model FLOPs of the tokens decoded in the window over the engine's
decode time, as a percent of the chip's bf16 peak."""
import flops


def read(ctx):
    work = sum(flops.decode_token(ctx["sizes"], c)
               for s in ctx["steps"] for c in s.decoded)
    secs = ctx["decode_ms"] / 1e3
    if secs <= 0 or not work:
        return None
    return 100.0 * work / secs / ctx["peak"]["bf16_flops_per_s"]
