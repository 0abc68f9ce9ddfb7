"""Model FLOPs of the window's prefills over the admission time that
carried them, as a percent of the chip's bf16 peak."""
import flops


def read(ctx):
    steps = [s for s in ctx["steps"] if s.admitted]
    secs = sum((s.t1 - s.t0) - s.decode_ms / 1e3 for s in steps)
    work = sum(flops.prefill(ctx["sizes"], p) for s in steps for p in s.prompts)
    if secs <= 0 or not work:
        return None
    return 100.0 * work / secs / ctx["peak"]["bf16_flops_per_s"]
