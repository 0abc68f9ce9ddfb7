"""Host part of a plan: ``scheduler.resolve`` minus its ``anneal_search``
span (lowering, table build, baseline seeding, scalar re-simulation),
mean ms per plan in the window, from the program's ``repro.obs`` spans."""


def read(ctx):
    res = [e["dur"] for e in ctx["spans"] if e.get("name") == "scheduler.resolve"]
    srch = [e["dur"] for e in ctx["spans"] if e.get("name") == "anneal_search"]
    if not res or len(res) != len(srch):
        return None
    return (sum(res) - sum(srch)) / len(res) / 1e3
