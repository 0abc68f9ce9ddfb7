"""Bytes the cache splice of an admission wrote into the batched cache,
as the program's engine counts them from the leaves it replaces, in GB
(1e9 bytes), mean over the window's admissions.  Programs without an
``AdmissionTiming`` on their requests report nothing."""
import math


def read(ctx):
    tms = [getattr(r.req, "timing", None) for r in ctx["reqs"]]
    tms = [t for t in tms if t is not None and math.isfinite(t.first_token)]
    return sum(t.copy_bytes for t in tms) / len(tms) / 1e9 if tms else None
