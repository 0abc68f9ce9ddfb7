"""Device part of an admission: the time the host blocks in the
first-token sync, from the prefill and splice dispatched to the first
token on the host; mean ms over the window's admissions, from the
``AdmissionTiming`` the program's engine keeps on each ``Request``.
Programs without it report nothing."""
import math


def read(ctx):
    tms = [getattr(r.req, "timing", None) for r in ctx["reqs"]]
    tms = [t for t in tms if t is not None and math.isfinite(t.first_token)]
    return sum(t.wait_ms for t in tms) / len(tms) if tms else None
