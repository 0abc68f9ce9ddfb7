"""Host part of an admission: from the request leaving the queue to the
start of its first-token sync (prefill and cache splice dispatched),
mean ms over the window's admissions, from the ``AdmissionTiming`` the
program's engine keeps on each ``Request``.  Programs without it report
nothing."""
import math


def read(ctx):
    tms = [getattr(r.req, "timing", None) for r in ctx["reqs"]]
    tms = [t for t in tms if t is not None and math.isfinite(t.first_token)]
    return sum(t.dispatch_ms for t in tms) / len(tms) if tms else None
