"""Share of its roofline the latent-attention decode kernel reached over
the traced window.  Each call is matched to the engine step it ran in
(the benchmark's ``engine_step`` annotation), whose live context lengths
give its operations and bytes (``rooflines/mla_decode.py``).  A program
without the kernel reports nothing."""
import tracefile
from rooflines import mla_decode
from rooflines.common import share


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    ops = tracefile.ops_matching(tr, mla_decode.match)
    if not ops:
        return None
    calls = []
    for name, t0, t1, args in tracefile.annotations(tr, "engine_step"):
        step = ctx["steps"][int(args["n"])]
        if not step.decoded:
            continue
        f, b = mla_decode.cost(step.decoded, ctx["sizes"])
        calls += [(f, b, (o[3] - o[2]) * 1e-9)
                  for o in tracefile.within(ops, t0, t1)]
    return share(calls, ctx["peak"]) if calls else None
