"""Admission per request: for each engine ``step()`` that admitted, the
step's wall time minus the engine's own decode time (``last_step_ms``),
over the admissions in it (prefill, cache splice, first-token sync);
mean ms over the window's admissions."""


def read(ctx):
    steps = [s for s in ctx["steps"] if s.admitted]
    n = sum(s.admitted for s in steps)
    if not n:
        return None
    return sum((s.t1 - s.t0) * 1e3 - s.decode_ms for s in steps) / n
