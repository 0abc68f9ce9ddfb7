"""The program's own timing of a plan's solve and of a request's
admission: the ``anneal.*`` and ``engine.*`` spans, and the
``AdmissionTiming`` record every served request carries.

Pins that the spans nest as documented in ``docs/observability.md``,
that a span and the record it shares boundaries with agree to the
stamp, that the splice's byte count is the bytes of the cache leaves it
replaces, and that tracing changes no served token.
"""
import importlib.util
import math
import pathlib
import types

import jax
import numpy as np
import pytest

from repro import configs
from repro.models import build
from repro.obs import Tracer, set_tracer
from repro.serve.engine import ServingEngine

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _restore_global_tracer():
    prev = set_tracer(None)
    yield
    set_tracer(prev)


def inside(child, parent, slack_us=0.01):
    """``child`` lies within ``parent`` on the same track (exported
    times are rounded to the ns, hence the slack)."""
    return (child["tid"] == parent["tid"]
            and parent["ts"] - slack_us <= child["ts"]
            and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"] + slack_us)


def parent_of(ev, events, names):
    """The innermost event named in ``names`` that encloses ``ev``."""
    around = [p for p in events
              if p is not ev and p["name"] in names and inside(ev, p)]
    assert around, f"{ev['name']} has no parent among {names}"
    return min(around, key=lambda p: p["dur"])


# ---------------------------------------------------------------------------
# serving engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_lm():
    cfg = configs.get("llama3.2-3b").reduced(n_layers=2, vocab=64)
    model = build(cfg, backend="xla")
    return cfg, model, model.init(jax.random.PRNGKey(0))


def serve(tiny_lm, tracer=None):
    """Five requests through three slots, so some wait in the queue."""
    cfg, model, params = tiny_lm
    prev = set_tracer(tracer)
    try:
        eng = ServingEngine(model, params, max_slots=3, capacity=64)
        reqs = [eng.submit((np.arange(4 + 2 * i) * (i + 1)) % cfg.vocab,
                           max_new=4) for i in range(5)]
        eng.run_until_drained()
    finally:
        set_tracer(prev)
    return eng, reqs


@pytest.fixture(scope="module")
def untraced(tiny_lm):
    return serve(tiny_lm)


@pytest.fixture(scope="module")
def traced(tiny_lm):
    tr = Tracer()
    eng, reqs = serve(tiny_lm, tr)
    return eng, reqs, tr.events()


def load_reader(name):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestAdmissionTiming:
    def test_stamps_are_ordered(self, untraced):
        _, reqs = untraced
        for r in reqs:
            t = r.timing
            stamps = [t.submitted, t.admitted, t.prefilled, t.dispatched,
                      t.first_token]
            assert all(math.isfinite(s) for s in stamps)
            assert stamps == sorted(stamps)
            assert t.queue_ms >= 0 and t.dispatch_ms >= 0 and t.wait_ms >= 0
        # three slots for five requests: the last two waited for a slot
        assert min(r.timing.queue_ms for r in reqs[3:]) > max(
            r.timing.queue_ms for r in reqs[:3])

    def test_copy_bytes_are_the_replaced_leaves(self, untraced):
        eng, reqs = untraced
        leaves = (jax.tree.leaves(eng.caches["groups"])
                  + jax.tree.leaves(eng.caches["tail"]))
        whole = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in leaves)
        assert whole > 0
        assert [r.timing.copy_bytes for r in reqs] == [whole] * len(reqs)
        ctx = {"reqs": [types.SimpleNamespace(req=r) for r in reqs]}
        assert load_reader("admit_copy_gb").read(ctx) == whole / 1e9

    def test_tracer_changes_no_token(self, untraced, traced):
        (_, off), (_, on, events) = untraced, traced
        assert events
        assert [r.tokens for r in on] == [r.tokens for r in off]


class TestEngineSpans:
    def test_admit_spans_carry_rid_and_nest_under_step(self, traced):
        eng, reqs, events = traced
        admits = [e for e in events if e["name"] == "engine.admit"]
        assert sorted(e["args"]["rid"] for e in admits) == [
            r.rid for r in reqs]
        for e in admits:
            req = reqs[e["args"]["rid"]]
            assert e["args"]["prompt_len"] == len(req.prompt)
            assert 0 <= e["args"]["slot"] < eng.max_slots
            assert parent_of(e, events, {"engine.step"})

    def test_admit_children_and_decode_nest(self, traced):
        _, _, events = traced
        for name in ("engine.prefill", "engine.splice", "engine.first_token"):
            evs = [e for e in events if e["name"] == name]
            assert len(evs) == 5
            for e in evs:
                assert parent_of(e, events, {"engine.admit", "engine.step"}
                                 )["name"] == "engine.admit"
        decodes = [e for e in events if e["name"] == "engine.decode"]
        steps = [e for e in events if e["name"] == "engine.step"]
        assert decodes and len(decodes) <= len(steps)
        assert all(parent_of(e, events, {"engine.step"}) for e in decodes)

    def test_spans_reuse_the_record_stamps(self, traced):
        _, reqs, events = traced
        for e in events:
            if e["name"] != "engine.admit":
                continue
            t = reqs[e["args"]["rid"]].timing
            assert e["ts"] == round(t.admitted * 1e3, 3)
            assert e["dur"] == round((t.first_token - t.admitted) * 1e3, 3)


# ---------------------------------------------------------------------------
# anneal solver
# ---------------------------------------------------------------------------

class TestAnnealSpans:
    def test_solve_phases_nest_under_solver_anneal(self):
        from repro.core import Scheduler
        tr = Tracer()
        set_tracer(tr)
        sched = Scheduler("xavier-agx")
        sched.resolve(sched.request(
            ["vgg19", "resnet101"], solver="anneal", max_transitions=1,
            population=64, steps=4, island=32))
        events = tr.events()

        def one(name):
            (ev,) = [e for e in events if e["name"] == name]
            return ev

        solver = one("solver.anneal")
        phases = [one(n) for n in ("anneal.tables", "anneal.seed",
                                   "anneal_search", "anneal.verify")]
        for ev in phases:
            assert parent_of(ev, events, {"solver.anneal"}) is solver
        # in order, and apart
        for a, b in zip(phases, phases[1:]):
            assert a["ts"] + a["dur"] <= b["ts"]
        search = one("anneal_search")
        assert parent_of(one("anneal.upload"), events,
                         {"anneal_search", "anneal.chunk"}) is search
        wait = one("anneal.wait")
        assert parent_of(wait, events, {"anneal_search", "anneal.chunk"}
                         ) is one("anneal.chunk")
        assert inside(wait, search)
