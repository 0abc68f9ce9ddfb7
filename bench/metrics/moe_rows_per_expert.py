"""Rows each held expert computed per MoE layer per decode step, over
the window's engine steps that admitted nothing (so decode alone), from
the program's MoE counters.  Programs without them report nothing."""


def read(ctx):
    moe = ctx.get("moe_steps")
    if not moe:
        return None
    recs = [m for s, m in zip(ctx["steps"], moe) if not s.admitted]
    slots = sum(m["moe_expert_steps"] for m in recs)
    return sum(m["moe_rows_total"] for m in recs) / slots if slots else None
