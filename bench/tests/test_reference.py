"""The plain references agree with an independent path where one exists:
the planning reference with the program's exact branch-and-bound, and
the weights the reference rebuilds layer by layer with the ones the
program is given."""
import numpy as np
import pytest

from reference import lm_ref, plan_ref


def _plain(sched, graphs, its, deps, objective, mt):
    p = sched.platform
    return {"accs": list(p.names), "domains": {k: list(v) for k, v in p.domains.items()},
            "transition_bw": p.transition_bw,
            "trans_out_ms": {a.name: a.transition_out_ms for a in p.accelerators},
            "trans_in_ms": {a.name: a.transition_in_ms for a in p.accelerators},
            "contention": {"capacity": 1.0, "sensitivity": 3.0},
            "objective": objective, "max_transitions": mt,
            "workloads": [{"iterations": it, "depends_on": d, "arrival_ms": 0.0,
                           "groups": [{"times": dict(g.times), "demand": dict(g.mem_demand),
                                       "out_bytes": g.out_bytes,
                                       "legal_after": g.can_transition_after}
                                      for g in gr]}
                          for gr, it, d in zip(graphs, its, deps)]}


@pytest.mark.parametrize("dnns,its,deps,objective,mt", [
    (["googlenet", "inception"], [3, 1], [None, None], "throughput", 1),
    (["vgg19", "resnet152"], [1, 1], [None, None], "latency", 2),
    (["googlenet", "resnet101"], [4, 4], [None, 0], "throughput", 2),
])
def test_plan_reference_matches_program_bb(dnns, its, deps, objective, mt):
    from repro.core import Scheduler
    from repro.core.simulate import Workload, simulate
    sched = Scheduler("agx-orin")
    graphs = sched.graphs(dnns)
    prob = _plain(sched, graphs, its, deps, objective, mt)
    best, asg = plan_ref.optimum(prob)
    bb = sched.solve(graphs, objective, solver="bb", max_transitions=mt,
                     iterations=its, depends_on=deps)
    assert best == pytest.approx(bb.objective, rel=1e-12)
    assert plan_ref.legal(prob, asg)
    wls = [Workload(g, a, iterations=i, depends_on=d)
           for g, a, i, d in zip(graphs, asg, its, deps)]
    res = simulate(sched.platform, wls, sched.model, record_timeline=False)
    assert plan_ref.evaluate(prob, asg) == pytest.approx(res.objective(objective),
                                                         rel=1e-12)


def test_control_rounds_only_the_search_tables():
    import harness
    from repro.core import Scheduler, search_jax
    plan = harness.load_module(harness.BENCH / "kinds" / "plan.py", "bench_kind_plan")
    sched = Scheduler("agx-orin")
    graphs = sched.graphs(["googlenet", "resnet101"])
    tables = search_jax.build_tables(sched.platform, graphs, sched.model,
                                     iterations=[1, 1], max_transitions=1)
    low = plan.bf16_tables(tables)
    for f in plan.LOWERED:
        a, b = getattr(tables, f), getattr(low, f)
        assert a.dtype == b.dtype
        np.testing.assert_allclose(b, a, rtol=2 ** -8)
    assert not np.array_equal(tables.dur_t, low.dur_t)
    assert np.array_equal(tables.domshare, low.domshare)


def test_illegal_assignment_refused():
    prob = {"accs": ["A", "B"], "max_transitions": 1, "workloads": [
        {"groups": [{"times": {"A": 1, "B": 1}, "legal_after": True},
                    {"times": {"A": 1, "B": 1}, "legal_after": False},
                    {"times": {"A": 1}, "legal_after": True}]}]}
    assert plan_ref.legal(prob, [("A", "B", "B")]) is False   # B not allowed
    assert plan_ref.legal(prob, [("B", "A", "A")]) is True
    assert plan_ref.legal(prob, [("B", "B", "A")]) is False   # illegal boundary


def test_layer_weights_match_the_stacked_params():
    import jax
    s = lm_ref.sizes({"num_hidden_layers": 3, "hidden_size": 64,
                      "num_attention_heads": 2, "num_key_value_heads": 2,
                      "intermediate_size": 96, "vocab_size": 50, "rope_theta": 1e4,
                      "rms_norm_eps": 1e-6})
    params = lm_ref.make_params(2**35 + 3, s)
    key = lm_ref.base_key(2**35 + 3)
    for layer in range(3):
        one = lm_ref.layer_weights(key, layer, s)
        stacked = jax.tree.map(lambda a: a[layer], params["groups"][0])
        # the same draws; XLA may fuse the normal transform differently
        # inside and outside the stacking loop, a float32 ulp apart
        for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(stacked)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)
    emb = lm_ref.emb_weights(key, s)
    np.testing.assert_allclose(np.asarray(emb["head"]),
                               np.asarray(params["emb"]["head"]), rtol=1e-6, atol=1e-7)
