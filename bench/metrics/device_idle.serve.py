"""Percent of the traced window in which no operation ran on the chip."""
import tracefile


def read(ctx):
    return tracefile.idle_share(ctx["trace"]) if ctx["trace"] else None
