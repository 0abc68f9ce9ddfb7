"""Share of its roofline the decode attention kernel reached over the
traced window.  Each call is matched to the engine step it ran in (the
benchmark's ``engine_step`` annotation), whose live context lengths give
its operations and bytes (``rooflines/decode_attention.py``)."""
import tracefile
from rooflines import decode_attention
from rooflines.common import share


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    ops = tracefile.ops_matching(tr, decode_attention.match)
    calls = []
    for name, t0, t1, args in tracefile.annotations(tr, "engine_step"):
        step = ctx["steps"][int(args["n"])]
        if not step.decoded:
            continue
        f, b = decode_attention.cost(step.decoded, ctx["sizes"])
        calls += [(f, b, (o[3] - o[2]) * 1e-9) for o in tracefile.within(ops, t0, t1)]
    return share(calls, ctx["peak"]) if calls else None
