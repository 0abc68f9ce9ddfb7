"""The engine's own decode-step time, ``EngineMetrics.decode_ms_total``
over its steps in the window, in ms."""


def read(ctx):
    n = ctx["decode_steps"]
    return ctx["decode_ms"] / n if n else None
