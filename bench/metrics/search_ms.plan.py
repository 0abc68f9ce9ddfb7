"""Device search of a plan: the ``anneal_search`` span (mutation, the
event machine and selection in one while loop, up to the incumbents'
return to the host), mean ms per plan, from the program's spans."""


def read(ctx):
    srch = [e["dur"] for e in ctx["spans"] if e.get("name") == "anneal_search"]
    return sum(srch) / len(srch) / 1e3 if srch else None
