"""Planning cells: one client sends the next ``ScheduleRequest`` when the
last ``Plan`` returns (closed loop), through ``Scheduler.resolve`` with
the device annealing solver.

The configuration file names the platform, the contention model, the
solver's knobs and the matrix-product precision JAX runs them at; the
traffic mix lists the problems.  After the window, every plan is held to
the plain reference in ``reference/plan_ref.py``:

* ``plan_gap``: the plan's objective over the exact optimum, as a share
  of |optimum| (both by the reference), against the quality limit the
  configuration states;
* ``plan_obj_err``: the objective the plan states (the program's scalar
  re-simulation) against the reference's for the plan's schedule;
* ``device_obj_err``: the objective the device search reported for its
  own incumbent against the reference's objective of that incumbent, as
  a share of it.  The incumbent is taken from the search, not from the
  plan, which may be the solver's seed baseline instead.

The control lowers the device search alone: its tables (durations,
demands, transition costs) are stored in bfloat16 before the search
sees them, while the plan keeps its float64 re-simulation.
"""
from __future__ import annotations

import dataclasses
import math
import sys
import time

import numpy as np

import generate
from harness import BenchError, sub_seed
from reference import plan_ref

#: the search tables' float entries that the control stores in bfloat16
LOWERED = ("dur_t", "dem_t", "move_ms", "tau_pair")


def _next_pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def bf16_tables(tables):
    """``tables`` with the entries the device search reads from rounded
    to bfloat16 (and stored back in their own dtype)."""
    import ml_dtypes
    return dataclasses.replace(tables, **{
        f: np.asarray(getattr(tables, f)).astype(ml_dtypes.bfloat16)
        .astype(np.asarray(getattr(tables, f)).dtype) for f in LOWERED})


@dataclasses.dataclass
class PlanRecord:
    index: int            # problem index in the mix
    request: object
    t0: float
    t1: float = 0.0
    plan: object = None
    outcome: object = None   # the device search's SearchOutcome


class Driver:
    def __init__(self, cell, seed: int, seconds: float, tracing, control: bool):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.tracing, self.control = tracing, control
        self.cfg, self.mix = cell.config, cell.traffic
        self.records: list[PlanRecord] = []
        self.spans = None

    # ------------------------------------------------------------------
    def _graphs(self, sched, dnns):
        from repro.core.profiles import chain
        return [chain(*sched.graphs(d)) if isinstance(d, list)
                else sched.graphs([d])[0] for d in dnns]

    @staticmethod
    def _balanced(graphs):
        """Table-8 iteration balancing (§5.4): the faster DNN runs
        proportionally more iterations."""
        t = [min(g.standalone_time(a) for a in g.accelerators) for g in graphs]
        return [max(1, round(max(t) / x)) for x in t]

    def _problem(self, sched, spec):
        graphs = self._graphs(sched, spec["dnns"])
        its = spec.get("iterations", [1] * len(graphs))
        if its == "balanced":
            its = self._balanced(graphs)
        deps = spec.get("depends_on", [None] * len(graphs))
        return graphs, list(its), list(deps)

    def _request(self, k: int):
        """The k-th request of the run: the problem the order names, with
        the search seed of its round, so no two requests are alike."""
        idx, rnd = self.order[k]
        spec = self.mix["problems"][idx]
        graphs, its, deps = self.problems[idx]
        return idx, self.sched.request(
            graphs, spec["objective"], solver=self.cfg["solver"],
            max_transitions=spec["max_transitions"], iterations=its,
            depends_on=deps, seed=(self.base_seed + rnd) % 2**31, **self.knobs)

    def setup(self):
        import jax
        from repro.core import Scheduler, search_jax
        from repro.core.contention import ProportionalShareModel
        c = self.cfg
        # the precision the configuration states, for every product the
        # search's float32 code leaves at JAX's default
        jax.config.update("jax_default_matmul_precision", c["matmul_precision"])
        model = ProportionalShareModel(capacity=c["contention"]["capacity"],
                                       sensitivity=c["contention"]["sensitivity"])
        self.sched = Scheduler(c["platform"], model=model)
        knobs = dict(c["solver_knobs"])
        if self.cell.chips > 1:
            knobs["devices"] = self.cell.chips
        problems = [self._problem(self.sched, p) for p in self.mix["problems"]]
        # cost proxy: the compiled search's padded depth, then the events
        costs = [(_next_pow2(max(len(g) for g in gr)),
                  sum(len(g) * it for g, it in zip(gr, its)))
                 for gr, its, _ in problems]
        self.order = generate.plan_order(costs, self.mix["strata"], self.seed,
                                         self.mix.get("max_requests", 10000))
        self.base_seed = sub_seed(self.seed, "search")
        self.knobs = knobs
        self.problems = problems
        # the client prepares its first requests before the window
        self.queue = [self._request(k) for k in range(min(64, len(self.order)))]

        # every compiled search program the window uses, warmed on a
        # scheduler of its own so the window's plan cache stays cold
        warm = Scheduler(c["platform"], model=model)
        seen = set()
        for idx, (graphs, its, deps) in enumerate(problems):
            spec = self.mix["problems"][idx]
            key = (len(graphs), _next_pow2(max(len(g) for g in graphs)),
                   spec["objective"])
            if key not in seen:
                seen.add(key)
                warm.solve(graphs, spec["objective"], solver=c["solver"],
                           max_transitions=spec["max_transitions"],
                           iterations=its, depends_on=deps,
                           seed=(self.base_seed - 1) % 2**31, **knobs)

        # the search's own incumbent, as the solver receives it
        orig = self.search = search_jax.anneal_search

        def recorded(tables, *a, **kw):
            if self.control:
                tables = bf16_tables(tables)
            out = self.search(tables, *a, **kw)
            if self.records:
                self.records[-1].outcome = out
            return out

        self._unpatch = lambda: setattr(search_jax, "anneal_search", orig)
        search_jax.anneal_search = recorded

    # ------------------------------------------------------------------
    def window(self):
        from repro.obs import Tracer, set_tracer
        obs = Tracer() if self.tracing.on else None
        prev = set_tracer(obs) if obs else None
        tr = self.tracing
        t0 = time.perf_counter()
        tr.start()
        try:
            while time.perf_counter() - t0 < self.seconds:
                k = len(self.records)
                if k >= len(self.order):
                    raise BenchError("the window outran the mix's max_requests")
                idx, req = self.queue[k] if k < len(self.queue) else self._request(k)
                rec = PlanRecord(idx, req, time.perf_counter() - t0)
                self.records.append(rec)
                with tr.ann("solve", n=len(self.records) - 1):
                    rec.plan = self.sched.resolve(req)
                rec.t1 = time.perf_counter() - t0
                tr.poll()
        finally:
            tr.stop()
            if obs:
                set_tracer(prev)
                self.spans = obs.events()
        self.span_s = self.records[-1].t1 if self.records else 0.0

    def free(self):
        self._unpatch()

    # ------------------------------------------------------------------
    def end_to_end(self) -> dict:
        return {"plan_ms": 1e3 * self.span_s / len(self.records)}

    def attempted_failed(self):
        return len(self.records), sum(r.plan is None for r in self.records)

    def plain(self, rec: PlanRecord) -> dict:
        """The request as plain data for the reference."""
        req = rec.request
        p = req.platform
        return {
            "accs": list(p.names),
            "domains": {k: list(v) for k, v in p.domains.items()},
            "transition_bw": p.transition_bw,
            "trans_out_ms": {a.name: a.transition_out_ms for a in p.accelerators},
            "trans_in_ms": {a.name: a.transition_in_ms for a in p.accelerators},
            "contention": dict(self.cfg["contention"]),
            "objective": req.objective,
            "max_transitions": req.max_transitions,
            "workloads": [
                {"iterations": int(it), "depends_on": dep, "arrival_ms": 0.0,
                 "groups": [{"times": dict(g.times), "demand": dict(g.mem_demand),
                             "out_bytes": float(g.out_bytes),
                             "legal_after": bool(g.can_transition_after)}
                            for g in graph]}
                for graph, it, dep in zip(req.graphs,
                                          req.iterations or [1] * len(req.graphs),
                                          req.depends_on or [None] * len(req.graphs))],
        }

    def checks(self) -> dict:
        """The compared numbers, worst over the checked plans; those whose
        limit the configuration gives are held to it."""
        recs = [r for r in self.records if r.plan is not None]
        if not recs:
            raise BenchError("no plan completed in the window")
        worst = {"plan_gap": 0.0, "plan_obj_err": 0.0, "device_obj_err": 0.0}
        optima = {}
        for r in recs:
            prob = self.plain(r)
            asg = r.plan.solution.assignments
            if not plan_ref.legal(prob, asg):
                worst["plan_obj_err"] = math.inf
                continue
            got = plan_ref.evaluate(prob, asg)
            said = r.plan.objective
            if r.index not in optima:
                optima[r.index] = plan_ref.optimum(prob)[0]
            best = optima[r.index]
            inc = r.outcome.assignment if r.outcome is not None else None
            ref = plan_ref.evaluate(prob, inc) if inc is not None else math.nan
            dev = r.outcome.objective if inc is not None else math.inf
            for name, v in (("plan_gap", (got - best) / abs(best)),
                            ("plan_obj_err", abs(said - got) / abs(got)),
                            ("device_obj_err", abs(dev - ref) / abs(ref))):
                worst[name] = max(worst[name], v if math.isfinite(v) else math.inf)
        lim = self.cfg["limits"]
        for name, v in worst.items():
            print(f"reading {name}: {v!r}", file=sys.stderr)
        return {n: {"value": worst[n], "limit": lim[n]} for n in worst if n in lim}

    # ------------------------------------------------------------------
    def layer_context(self) -> dict:
        return {"records": self.records, "spans": self.spans or [],
                "cfg": self.cfg}
