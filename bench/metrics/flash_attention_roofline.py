"""Share of its roofline the prefill attention kernel reached over the
traced window (``rooflines/flash_attention.py``)."""
import tracefile
from rooflines import flash_attention
from rooflines.common import share


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    calls = []
    for o in tracefile.ops_matching(tr, flash_attention.match):
        c = flash_attention.cost(o[4])
        if c is None:
            return None
        calls.append((*c, (o[3] - o[2]) * 1e-9))
    return share(calls, ctx["peak"]) if calls else None
