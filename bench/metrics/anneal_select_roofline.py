"""Share of its roofline the select kernel reached over the traced
window: its calls' least time at the chip's peaks over their device time
(``rooflines/anneal_select.py``)."""
import tracefile
from rooflines import anneal_select
from rooflines.common import share


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    calls = []
    for o in tracefile.ops_matching(tr, anneal_select.match):
        c = anneal_select.cost(o[4])
        if c is None:
            return None
        calls.append((*c, (o[3] - o[2]) * 1e-9))
    return share(calls, ctx["peak"]) if calls else None
