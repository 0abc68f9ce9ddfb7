"""Plain reference for the planning cells: an event-driven simulator of a
concurrent schedule and an exhaustive branch-and-bound over assignments.

It imports nothing of the program under test.  A problem is plain data:

    {"accs": ["GPU", "DLA"],
     "domains": {"EMC": ["GPU", "DLA"]},
     "transition_bw": bytes/s, "trans_out_ms": {acc: ms}, "trans_in_ms": {acc: ms},
     "contention": {"capacity": 1.0, "sensitivity": 3.0},
     "objective": "latency" | "throughput" | "sum_inverse",
     "max_transitions": k,
     "workloads": [{"iterations": n, "depends_on": None | index, "arrival_ms": 0.0,
                    "groups": [{"times": {acc: ms}, "demand": {acc: fraction},
                                "out_bytes": bytes, "legal_after": bool}, ...]}]}

Semantics (HaX-CoNN, arXiv:2308.05869, Eqs. 2-8): each accelerator runs at
most one layer group at a time, FIFO by ready time then workload index; a
transition after a group delays the workload by the fixed out/in costs plus
bytes over the shared path; between events every running group progresses
at 1/slowdown, where the slowdown of a group demanding ``own`` while groups
on other accelerators of its contention domain demand ``external`` is
proportional sharing: 1 + sensitivity * min(1, own/capacity) *
((own + external)/capacity - 1) once the domain is over capacity.
"""
from __future__ import annotations

import itertools
import math

import numpy as np

_TOL = 1e-9


def transition_ms(prob: dict, out_bytes: float, src: str, dst: str) -> float:
    if src == dst:
        return 0.0
    bw = prob["transition_bw"]
    move = out_bytes / bw * 1e3 if bw else 0.0
    return move + prob["trans_out_ms"][src] + prob["trans_in_ms"][dst]


def slowdown(prob: dict, own: float, external: float) -> float:
    cap = prob["contention"]["capacity"]
    sens = prob["contention"]["sensitivity"]
    own, external = max(0.0, own), max(0.0, external)
    if own == 0.0 or own + external <= cap:
        return 1.0
    return 1.0 + sens * min(1.0, own / cap) * ((own + external) / cap - 1.0)


def simulate(prob: dict, assignment) -> dict:
    """Makespan and per-workload finish times of one joint assignment
    (a sequence, per workload, of accelerator names per group)."""
    wls = prob["workloads"]
    n = len(wls)
    acc_domains = {a: [d for d, m in prob["domains"].items() if a in m]
                   for a in prob["accs"]}
    grp = [0] * n
    it = [0] * n
    remaining = [wls[i]["groups"][0]["times"][assignment[i][0]] for i in range(n)]
    ready = [float(w.get("arrival_ms", 0.0)) for w in wls]
    done = [False] * n
    finish = [0.0] * n
    running: dict[str, int] = {}
    t = 0.0

    def acc(i):
        return assignment[i][grp[i]]

    def demand(i):
        return wls[i]["groups"][grp[i]]["demand"].get(acc(i), 0.0)

    def dep_ready(i):
        d = wls[i].get("depends_on")
        return d is None or done[d] or it[d] > it[i]

    def rate(i):
        a = acc(i)
        ext = 0.0
        for dom in acc_domains[a]:
            for j in running.values():
                if j != i and acc(j) != a and acc(j) in prob["domains"][dom]:
                    ext += demand(j)
        own = demand(i)
        if ext <= 0.0 or own <= 0.0:
            return 1.0
        return max(1.0, slowdown(prob, own, ext))

    guard = 200000 + 200 * sum(len(w["groups"]) * w["iterations"] for w in wls)
    while not all(done):
        guard -= 1
        if guard < 0:
            raise RuntimeError("reference simulation did not converge")
        busy = set(running.values())
        waiting = sorted((i for i in range(n) if not done[i] and i not in busy
                          and ready[i] <= t + _TOL and dep_ready(i)),
                         key=lambda i: (ready[i], i))
        for i in waiting:
            if acc(i) not in running:
                running[acc(i)] = i
        if not running:
            t = min(ready[i] for i in range(n) if not done[i] and ready[i] > t + _TOL)
            continue
        rates = {i: rate(i) for i in running.values()}
        horizon = t + min(remaining[i] * rates[i] for i in running.values())
        busy = set(running.values())
        for i in range(n):
            if not done[i] and i not in busy and t + _TOL < ready[i] < horizon - _TOL:
                horizon = ready[i]
        span = horizon - t
        for i in running.values():
            remaining[i] -= span / rates[i]
        t = horizon
        for a, i in list(running.items()):
            if remaining[i] > _TOL:
                continue
            del running[a]
            groups = wls[i]["groups"]
            if grp[i] + 1 < len(groups):
                g = grp[i]
                grp[i] = g + 1
                remaining[i] = groups[g + 1]["times"][assignment[i][g + 1]]
                ready[i] = t + transition_ms(prob, groups[g]["out_bytes"],
                                             assignment[i][g], assignment[i][g + 1])
            else:
                it[i] += 1
                if it[i] >= wls[i]["iterations"]:
                    done[i] = True
                    finish[i] = t
                else:
                    grp[i] = 0
                    remaining[i] = groups[0]["times"][assignment[i][0]]
                    ready[i] = t
    return {"makespan": t, "finish": finish}


def objective(prob: dict, sim: dict) -> float:
    """Lower is better for every kind, as the paper's solver states it."""
    kind = prob["objective"]
    if kind == "latency":
        return sim["makespan"]
    if kind == "throughput":
        n = sum(w["iterations"] for w in prob["workloads"])
        return -1e3 * n / sim["makespan"]
    if kind == "sum_inverse":
        return -sum(1.0 / f for f in sim["finish"] if f > 0)
    raise ValueError(kind)


def evaluate(prob: dict, assignment) -> float:
    return objective(prob, simulate(prob, assignment))


def assignments(prob: dict, w: dict) -> list[tuple[str, ...]]:
    """Every legal assignment of one workload with at most
    ``max_transitions`` accelerator changes, at legal boundaries only."""
    groups = w["groups"]
    accs = [a for a in prob["accs"] if all(a in g["times"] for g in groups)]
    out = []

    def rec(i, cur, used):
        if i == len(groups):
            out.append(tuple(cur))
            return
        for a in accs:
            if i and a != cur[-1]:
                if used >= prob["max_transitions"] or not groups[i - 1]["legal_after"]:
                    continue
                rec(i + 1, cur + [a], used + 1)
            else:
                rec(i + 1, cur + [a], used)

    rec(0, [], 0)
    return out


def legal(prob: dict, joint) -> bool:
    """Every group on an accelerator it can run on, at most
    ``max_transitions`` changes per workload, each at a legal boundary."""
    for w, asg in zip(prob["workloads"], joint):
        g = w["groups"]
        if len(asg) != len(g) or any(a not in g[i]["times"] for i, a in enumerate(asg)):
            return False
        moves = [i for i in range(len(asg) - 1) if asg[i] != asg[i + 1]]
        if len(moves) > prob["max_transitions"] or any(
                not g[i]["legal_after"] for i in moves):
            return False
    return True


def lower_bound(prob: dict, joint) -> float:
    """Contention- and queueing-free makespan bound: the longest workload
    path and the busiest accelerator."""
    load = {a: 0.0 for a in prob["accs"]}
    path = 0.0
    for w, asg in zip(prob["workloads"], joint):
        g = w["groups"]
        t = sum(g[i]["times"][a] for i, a in enumerate(asg))
        t += sum(transition_ms(prob, g[i]["out_bytes"], asg[i], asg[i + 1])
                 for i in range(len(asg) - 1))
        path = max(path, t * w["iterations"])
        for i, a in enumerate(asg):
            load[a] += g[i]["times"][a] * w["iterations"]
    return max(path, max(load.values()))


def optimum(prob: dict) -> tuple[float, tuple]:
    """The exact best objective over the whole legal joint space, and one
    assignment that reaches it.  Candidates are visited by ascending lower
    bound; for latency and throughput (both monotone in the makespan) the
    visit stops once the bound reaches the incumbent's makespan."""
    cands = [assignments(prob, w) for w in prob["workloads"]]
    joint = sorted(itertools.product(*cands), key=lambda j: lower_bound(prob, j))
    prune = prob["objective"] in ("latency", "throughput")
    best, best_asg, best_mk = math.inf, None, math.inf
    for j in joint:
        if prune and best_asg is not None and lower_bound(prob, j) >= best_mk - 1e-12:
            break
        sim = simulate(prob, j)
        obj = objective(prob, sim)
        if obj < best:
            best, best_asg, best_mk = obj, j, sim["makespan"]
    return best, best_asg
