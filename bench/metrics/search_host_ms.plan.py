"""Host work inside a plan's device search: the ``anneal_search`` span
minus its ``anneal.wait`` children (the pulls that block on the device
loop), so uploads, dispatch and the scattered population; mean ms per
plan, from the program's spans.  Programs without ``anneal.wait`` spans
report nothing."""


def read(ctx):
    srch = [e for e in ctx["spans"] if e.get("name") == "anneal_search"]
    wait = [e for e in ctx["spans"] if e.get("name") == "anneal.wait"]
    if not srch or not wait:
        return None
    waited = sum(w["dur"] for w in wait if any(
        s["tid"] == w["tid"] and s["ts"] <= w["ts"]
        and w["ts"] + w["dur"] <= s["ts"] + s["dur"] for s in srch))
    return (sum(s["dur"] for s in srch) - waited) / len(srch) / 1e3
