"""Share of its roofline the drop-free expert kernel reached over the
traced window.  The kernel's calls in each engine step (the benchmark's
``engine_step`` annotation), with the slices of the held experts'
weights that feed them (``rooflines/moe_gmm.py``), are costed together
from the rows and the experts touched that the program's counters give
for the step; a step's least time is the larger of its summed operations
and bytes over the peaks, which never exceeds the sum over its calls.
Programs without the counters report nothing."""
import tracefile
from rooflines import moe_gmm
from rooflines.common import share


def read(ctx):
    tr, moe = ctx["trace"], ctx.get("moe_steps")
    if tr is None or not moe:
        return None
    kernel = tracefile.ops_matching(tr, moe_gmm.match)
    if not kernel:
        return None
    ops = kernel + tracefile.ops_matching(tr, moe_gmm.staging(ctx["sizes"]))
    calls = []
    for name, t0, t1, args in tracefile.annotations(tr, "engine_step"):
        rec = moe[int(args["n"])]
        secs = sum((o[3] - o[2]) * 1e-9 for o in tracefile.within(ops, t0, t1))
        if rec["moe_rows_total"] and secs > 0:
            f, b = moe_gmm.cost(rec["moe_rows_total"],
                                rec["moe_experts_touched"], ctx["sizes"])
            calls.append((f, b, secs))
    return share(calls, ctx["peak"]) if calls else None
