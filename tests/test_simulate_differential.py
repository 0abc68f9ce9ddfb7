"""Differential property tests: batch == scalar == jax simulators.

The scalar event-driven simulator (:mod:`repro.core.simulate`) is the
authoritative evaluator of the paper's Eq. 2-8 timeline; the vectorized
batch evaluator (:mod:`repro.core.simulate_batch`) must agree with it within
1e-6 on every observable — makespan, per-workload finish times and
per-iteration latencies, the contention-interval integral (``contention_ms``
= Σ (1 - 1/s)·len) and per-accelerator busy time — across randomly generated
platforms, graphs, assignments, transition delays, ``depends_on`` pipelines,
``arrival_ms`` offsets and multi-iteration workloads.  The XLA evaluator
(:mod:`repro.core.simulate_jax`, ``evaluator="jax"``) is held to the same
observables at 1e-5 (its float64 mode is ~1e-12 from the NumPy path in
practice; the looser bound is the cross-backend contract on float32-safe
inputs), on the random corpus *and* on the three golden Table-6 plan
fixtures.

Scenarios are generated from a seeded ``random.Random`` (shared generators
in ``tests/_prop.py``) so the property is "for any seed, all backends agree
on the scenario derived from that seed": deterministic under the fallback
grid, fully explorable under hypothesis (``HYPOTHESIS_PROFILE=thorough``
raises the example count in the scheduled CI job).
"""
from __future__ import annotations

import random

import numpy as np
import pytest

from _prop import (contention_models, examples, given, problem_specs,
                   random_model, random_platform, random_scenario,
                   random_workloads, settings, st)

from repro.core.accelerators import Accelerator, Platform
from repro.core.contention import PiecewiseModel, ProportionalShareModel
from repro.core.graph import DNNGraph, LayerGroup
from repro.core.simulate import Workload, simulate
from repro.core.simulate_batch import (simulate_assignments, simulate_batch,
                                       slowdown_array)

TOL = 1e-6
#: the jax evaluator's cross-backend contract (float32-safe inputs).
JAX_TOL = 1e-5

from repro.core import simulate_jax


def assert_equivalent(ref, res, context="", tol=TOL):
    __tracebackhide__ = True
    assert res.makespan == pytest.approx(ref.makespan, abs=tol), context
    assert res.finish_times == pytest.approx(ref.finish_times, abs=tol), \
        context
    assert len(res.iteration_latencies) == len(ref.iteration_latencies)
    for a, b in zip(res.iteration_latencies, ref.iteration_latencies):
        assert a == pytest.approx(b, abs=tol), context
    assert res.contention_ms == pytest.approx(ref.contention_ms, abs=tol), \
        context
    for acc, t in ref.busy_ms.items():
        assert res.busy_ms[acc] == pytest.approx(t, abs=tol), context


# ---------------------------------------------------------------------------
# the differential property
# ---------------------------------------------------------------------------

class TestDifferential:
    @given(seed=st.integers(min_value=0, max_value=10_000_000))
    @settings(max_examples=examples(200), deadline=None)
    def test_batch_matches_scalar_on_random_scenarios(self, seed):
        platform, wls, model = random_scenario(seed)
        ref = simulate(platform, wls, model, record_timeline=False)
        res = simulate_batch(platform, [wls], model).result(0)
        assert_equivalent(ref, res, f"seed={seed}")

    @given(seed=st.integers(min_value=0, max_value=1_000_000))
    @settings(max_examples=examples(25), deadline=None)
    def test_candidates_in_one_batch_are_independent(self, seed):
        """A population must score each member exactly as it would alone."""
        rng = random.Random(seed)
        platform = random_platform(rng)
        model = random_model(rng, platform)
        batch = [random_workloads(rng, platform) for _ in range(8)]
        w = min(len(b) for b in batch)
        batch = [b[:w] for b in batch]
        bt = simulate_batch(platform, batch, model)
        for i, wls in enumerate(batch):
            ref = simulate(platform, wls, model, record_timeline=False)
            assert_equivalent(ref, bt.result(i), f"seed={seed} cand={i}")

    @given(seed=st.integers(min_value=0, max_value=1_000_000),
           model=contention_models())
    @settings(max_examples=examples(50), deadline=None)
    def test_shared_model_strategies_agree_too(self, seed, model):
        platform, wls, _ = random_scenario(seed)
        ref = simulate(platform, wls, model, record_timeline=False)
        res = simulate_batch(platform, [wls], model).result(0)
        assert_equivalent(ref, res, f"seed={seed}")


class TestVectorizedSlowdown:
    @given(model=contention_models(),
           own=st.floats(0.0, 1.5), ext=st.floats(0.0, 1.5))
    @settings(max_examples=examples(200), deadline=None)
    def test_slowdown_array_matches_scalar(self, model, own, ext):
        arr = slowdown_array(model, np.array([own]), np.array([ext]))
        assert float(arr[0]) == pytest.approx(model.slowdown(own, ext),
                                              abs=1e-12)

    def test_unregistered_model_falls_back_elementwise(self):
        class Odd:
            def slowdown(self, own, external):
                return 1.0 + 0.25 * own * external

        own = np.array([0.2, 0.8, 1.1])
        ext = np.array([0.5, 0.0, 1.2])
        got = slowdown_array(Odd(), own, ext)
        want = [Odd().slowdown(o, e) for o, e in zip(own, ext)]
        assert got == pytest.approx(want, abs=1e-12)

    def test_wrapper_model_with_base_factor_attrs_uses_its_own_semantics(self):
        """A third-party wrapper exposing .base/.factor must NOT be treated
        as a ScaledContentionModel — the elementwise fallback has to call
        *its* slowdown, not guess a formula from attribute names."""
        class PowModel:
            def __init__(self, base, factor):
                self.base = base
                self.factor = factor

            def slowdown(self, own, external):
                return self.base.slowdown(own, external) ** self.factor

        m = PowModel(ProportionalShareModel(), 2.0)
        got = float(slowdown_array(m, np.array([0.9]), np.array([0.9]))[0])
        assert got == pytest.approx(m.slowdown(0.9, 0.9), abs=1e-12)

    def test_scaled_model_vectorized_path_matches_scalar(self):
        from repro.core.dynamic import ScaledContentionModel
        m = ScaledContentionModel(ProportionalShareModel(), 2.5)
        own = np.array([0.2, 0.9, 1.2])
        ext = np.array([0.9, 0.9, 0.3])
        got = slowdown_array(m, own, ext)
        want = [m.slowdown(o, e) for o, e in zip(own, ext)]
        assert got == pytest.approx(want, abs=1e-12)


class TestTargetedDifferential:
    """Deterministic corner cases the random generator may visit rarely."""

    def setup_method(self):
        self.plat = Platform(
            name="t", accelerators=(
                Accelerator("A", 1e12, 1e11, transition_in_ms=0.01,
                            transition_out_ms=0.02),
                Accelerator("B", 1e12, 1e11, transition_in_ms=0.03,
                            transition_out_ms=0.04)),
            transition_bw=1e11,
            domains={"EMC": ("A", "B")}, domain_bw={"EMC": 1e11})
        self.model = ProportionalShareModel(capacity=1.0, sensitivity=2.0)

    def _check(self, wls):
        ref = simulate(self.plat, wls, self.model, record_timeline=False)
        res = simulate_batch(self.plat, [wls], self.model).result(0)
        assert_equivalent(ref, res)

    def test_transition_delays(self):
        g = DNNGraph("n", (
            LayerGroup("a", {"A": 1.0, "B": 2.0}, {"A": 0.9, "B": 0.9},
                       out_bytes=5e7),
            LayerGroup("b", {"A": 2.0, "B": 1.0}, {"A": 0.9, "B": 0.9},
                       out_bytes=5e7),
            LayerGroup("c", {"A": 1.0, "B": 1.5}, {"A": 0.9, "B": 0.9})))
        other = DNNGraph("m", (
            LayerGroup("x", {"A": 4.0, "B": 4.0}, {"A": 0.8, "B": 0.8}),))
        self._check([Workload(g, ("A", "B", "A")),
                     Workload(other, ("B",))])

    def test_streaming_pipeline_with_arrivals(self):
        prod = DNNGraph("prod", (
            LayerGroup("p", {"A": 1.0, "B": 1.5}, {"A": 0.7, "B": 0.7}),))
        cons = DNNGraph("cons", (
            LayerGroup("c", {"A": 1.2, "B": 0.8}, {"A": 0.9, "B": 0.9}),))
        self._check([
            Workload(prod, ("A",), iterations=4, arrival_ms=0.5),
            Workload(cons, ("B",), iterations=4, depends_on=0,
                     arrival_ms=1.25),
        ])

    def test_queueing_same_accelerator_multi_iteration(self):
        g1 = DNNGraph("g1", (
            LayerGroup("a", {"A": 2.0, "B": 3.0}, {"A": 0.9, "B": 0.9}),))
        g2 = DNNGraph("g2", (
            LayerGroup("b", {"A": 1.0, "B": 1.0}, {"A": 0.9, "B": 0.9}),))
        self._check([Workload(g1, ("A",), iterations=3),
                     Workload(g2, ("A",), iterations=5, arrival_ms=0.25)])

    def test_per_domain_model_mapping(self):
        mapping = {"EMC": PiecewiseModel(
            (0.2, 0.6, 1.0), (0.2, 0.6, 1.0),
            ((1.0, 1.1, 1.3), (1.1, 1.4, 1.7), (1.3, 1.7, 2.2)))}
        g = DNNGraph("n", (
            LayerGroup("a", {"A": 2.0, "B": 2.0}, {"A": 0.8, "B": 0.8}),))
        h = DNNGraph("m", (
            LayerGroup("b", {"A": 3.0, "B": 3.0}, {"A": 0.7, "B": 0.7}),))
        wls = [Workload(g, ("A",)), Workload(h, ("B",))]
        ref = simulate(self.plat, wls, mapping, record_timeline=False)
        res = simulate_batch(self.plat, [wls], mapping).result(0)
        assert_equivalent(ref, res)

    def test_assignment_fast_path_matches_workload_path(self):
        g1 = DNNGraph("g1", (
            LayerGroup("a", {"A": 1.0, "B": 2.0}, {"A": 0.9, "B": 0.6},
                       out_bytes=1e8),
            LayerGroup("b", {"A": 2.0, "B": 1.0}, {"A": 0.5, "B": 0.8})))
        g2 = DNNGraph("g2", (
            LayerGroup("c", {"A": 1.5, "B": 1.5}, {"A": 0.7, "B": 0.7}),))
        combos = [(("A", "A"), ("B",)), (("A", "B"), ("A",)),
                  (("B", "B"), ("B",)), (("B", "A"), ("A",))]
        bt = simulate_assignments(self.plat, [g1, g2], combos, self.model,
                                  iterations=[2, 3], depends_on=[None, 0])
        for i, (a1, a2) in enumerate(combos):
            ref = simulate(self.plat, [
                Workload(g1, a1, iterations=2),
                Workload(g2, a2, iterations=3, depends_on=0)],
                self.model, record_timeline=False)
            assert_equivalent(ref, bt.result(i), f"cand={i}")

    def test_objective_vector_matches_scalar_objectives(self):
        g = DNNGraph("n", (
            LayerGroup("a", {"A": 1.0, "B": 2.0}, {"A": 0.9, "B": 0.9}),))
        h = DNNGraph("m", (
            LayerGroup("b", {"A": 2.0, "B": 1.0}, {"A": 0.9, "B": 0.9}),))
        combos = [(("A",), ("B",)), (("B",), ("A",)), (("A",), ("A",))]
        bt = simulate_assignments(self.plat, [g, h], combos, self.model)
        for kind in ("latency", "throughput", "sum_inverse"):
            objs = bt.objective(kind)
            for i, (a1, a2) in enumerate(combos):
                ref = simulate(self.plat,
                               [Workload(g, a1), Workload(h, a2)],
                               self.model, record_timeline=False)
                assert objs[i] == pytest.approx(ref.objective(kind),
                                                rel=1e-9)

    def test_validation_matches_scalar(self):
        g = DNNGraph("n", (
            LayerGroup("a", {"A": 1.0}, can_transition_after=False),
            LayerGroup("b", {"A": 1.0, "B": 1.0})))
        with pytest.raises(ValueError, match="illegal transition"):
            simulate_assignments(self.plat, [g], [(("A", "B"),)], self.model)
        with pytest.raises(ValueError):
            simulate_assignments(self.plat, [g], [(("A", "C"),)], self.model)

    def test_empty_batch(self):
        bt = simulate_batch(self.plat, [], self.model)
        assert len(bt) == 0
        assert bt.objective("latency").shape == (0,)


class TestJaxDifferential:
    """Three-way parity: the XLA evaluator against scalar and batch.

    Covers the full random corpus (transition delays, ``depends_on``
    pipelines, ``arrival_ms`` offsets, multi-iteration workloads,
    per-domain model mappings) plus the assignment fast path and both
    precisions.
    """

    @given(seed=st.integers(min_value=0, max_value=10_000_000))
    @settings(max_examples=examples(60), deadline=None)
    def test_jax_matches_scalar_and_batch_on_random_scenarios(self, seed):
        platform, wls, model = random_scenario(seed)
        ref = simulate(platform, wls, model, record_timeline=False)
        res_b = simulate_batch(platform, [wls], model).result(0)
        res_j = simulate_jax.simulate_batch(platform, [wls], model).result(0)
        assert_equivalent(ref, res_j, f"seed={seed} jax-vs-scalar",
                          tol=JAX_TOL)
        assert_equivalent(res_b, res_j, f"seed={seed} jax-vs-batch",
                          tol=JAX_TOL)

    @given(seed=st.integers(min_value=0, max_value=1_000_000))
    @settings(max_examples=examples(15), deadline=None)
    def test_jax_population_members_are_independent(self, seed):
        rng = random.Random(seed)
        platform = random_platform(rng)
        model = random_model(rng, platform)
        batch = [random_workloads(rng, platform) for _ in range(6)]
        w = min(len(b) for b in batch)
        batch = [b[:w] for b in batch]
        bt = simulate_jax.simulate_batch(platform, batch, model)
        for i, wls in enumerate(batch):
            ref = simulate(platform, wls, model, record_timeline=False)
            assert_equivalent(ref, bt.result(i), f"seed={seed} cand={i}",
                              tol=JAX_TOL)

    def test_assignment_path_three_way(self):
        plat = Platform(
            name="t", accelerators=(
                Accelerator("A", 1e12, 1e11, transition_in_ms=0.01,
                            transition_out_ms=0.02),
                Accelerator("B", 1e12, 1e11, transition_in_ms=0.03,
                            transition_out_ms=0.04)),
            transition_bw=1e11,
            domains={"EMC": ("A", "B")}, domain_bw={"EMC": 1e11})
        model = ProportionalShareModel(capacity=1.0, sensitivity=2.0)
        g1 = DNNGraph("g1", (
            LayerGroup("a", {"A": 1.0, "B": 2.0}, {"A": 0.9, "B": 0.6},
                       out_bytes=1e8),
            LayerGroup("b", {"A": 2.0, "B": 1.0}, {"A": 0.5, "B": 0.8})))
        g2 = DNNGraph("g2", (
            LayerGroup("c", {"A": 1.5, "B": 1.5}, {"A": 0.7, "B": 0.7}),))
        combos = [(("A", "A"), ("B",)), (("A", "B"), ("A",)),
                  (("B", "B"), ("B",)), (("B", "A"), ("A",))]
        kw = dict(iterations=[2, 3], depends_on=[None, 0])
        bt_np = simulate_assignments(plat, [g1, g2], combos, model, **kw)
        bt_j = simulate_jax.simulate_assignments(plat, [g1, g2], combos,
                                                 model, **kw)
        for i, (a1, a2) in enumerate(combos):
            ref = simulate(plat, [
                Workload(g1, a1, iterations=2),
                Workload(g2, a2, iterations=3, depends_on=0)],
                model, record_timeline=False)
            assert_equivalent(ref, bt_j.result(i), f"cand={i}", tol=JAX_TOL)
            assert_equivalent(bt_np.result(i), bt_j.result(i), f"cand={i}",
                              tol=JAX_TOL)
        for kind in ("latency", "throughput", "sum_inverse"):
            assert bt_j.objective(kind) == pytest.approx(
                bt_np.objective(kind), rel=1e-6, abs=JAX_TOL)

    @given(spec=problem_specs())
    @settings(max_examples=examples(20), deadline=None)
    def test_spec_level_parity_numpy_vs_jax(self, spec):
        from repro.core.simulate_batch import simulate_spec as np_spec
        bn = np_spec(spec)
        bj = simulate_jax.simulate_spec(spec)
        assert bj.makespan == pytest.approx(bn.makespan, abs=JAX_TOL)
        assert bj.contention_ms == pytest.approx(bn.contention_ms,
                                                 abs=JAX_TOL)
        np.testing.assert_allclose(bj.finish_times, bn.finish_times,
                                   atol=JAX_TOL)

    def test_float32_precision_ranks_like_x64(self):
        """float32 is ranking-grade: makespans within ~1e-3 relative."""
        rng = random.Random(1234)
        platform = random_platform(rng)
        model = random_model(rng, platform)
        batch = [random_workloads(rng, platform) for _ in range(4)]
        w = min(len(b) for b in batch)
        batch = [b[:w] for b in batch]
        b64 = simulate_jax.simulate_batch(platform, batch, model)
        b32 = simulate_jax.simulate_batch(platform, batch, model,
                                          precision="float32")
        assert b32.makespan == pytest.approx(b64.makespan, rel=1e-3)

    def test_unlowerable_model_is_rejected_with_guidance(self):
        class Odd:
            def slowdown(self, own, external):
                return 1.0 + 0.25 * own * external

        platform, wls, _ = random_scenario(42)
        model = Odd()
        # NumPy path: works through the elementwise fallback.
        simulate_batch(platform, [wls], model)
        with pytest.raises(ValueError, match="register_surface_lowering"):
            simulate_jax.simulate_batch(platform, [wls], model)

    def test_scaled_model_three_way(self):
        from repro.core.dynamic import ScaledContentionModel
        platform, wls, base = random_scenario(77)
        if isinstance(base, dict):
            model = {k: ScaledContentionModel(v, 1.5)
                     for k, v in base.items()}
        else:
            model = ScaledContentionModel(base, 1.5)
        ref = simulate(platform, wls, model, record_timeline=False)
        assert_equivalent(ref,
                          simulate_batch(platform, [wls], model).result(0))
        assert_equivalent(
            ref, simulate_jax.simulate_batch(platform, [wls], model)
            .result(0), tol=JAX_TOL)


class TestJaxGoldenPlans:
    """The jax evaluator must reproduce the pinned Table-6 fixtures."""

    def _fixtures(self):
        import pathlib
        return sorted((pathlib.Path(__file__).parent / "fixtures" /
                       "plans").glob("*.json"))

    def test_three_way_on_golden_fixtures(self):
        from repro.core import Plan
        paths = self._fixtures()
        assert len(paths) >= 3
        for path in paths:
            plan = Plan.load(path)
            req = plan.request
            wls = plan.solution.workloads
            ref = simulate(req.platform, wls, req.model,
                           record_timeline=False)
            bt_np = simulate_batch(req.platform, [wls], req.model)
            bt_j = simulate_jax.simulate_batch(req.platform, [wls],
                                               req.model)
            assert ref.makespan == pytest.approx(plan.result.makespan,
                                                 rel=1e-9), path.stem
            assert_equivalent(ref, bt_np.result(0), path.stem)
            assert_equivalent(ref, bt_j.result(0), path.stem, tol=JAX_TOL)
            assert bt_j.objective(req.objective)[0] == pytest.approx(
                plan.objective, rel=1e-6), path.stem

    def test_jax_evaluator_reproduces_fixture_solve(self):
        """End-to-end: solving with evaluator="jax" returns the golden
        schedule (the evaluator knob steers the search, never the answer)."""
        from repro.core import Plan, Scheduler
        path = self._fixtures()[0]
        golden = Plan.load(path)
        sched = Scheduler(golden.request.platform,
                          model=golden.request.model, evaluator="jax")
        plan = sched.resolve(golden.request)
        assert plan.evaluator == "jax"
        assert plan.assignments == golden.assignments
        assert plan.objective == pytest.approx(golden.objective, rel=1e-9)


@pytest.mark.slow
class TestDifferentialSweep:
    """Wider randomized sweep — scheduled CI job territory."""

    @given(seed=st.integers(min_value=10_000_001, max_value=20_000_000))
    @settings(max_examples=examples(500), deadline=None)
    def test_batch_matches_scalar_wide(self, seed):
        platform, wls, model = random_scenario(seed)
        ref = simulate(platform, wls, model, record_timeline=False)
        res = simulate_batch(platform, [wls], model).result(0)
        assert_equivalent(ref, res, f"seed={seed}")

    @given(seed=st.integers(min_value=20_000_001, max_value=30_000_000))
    @settings(max_examples=examples(150), deadline=None)
    def test_jax_matches_scalar_wide(self, seed):
        platform, wls, model = random_scenario(seed)
        ref = simulate(platform, wls, model, record_timeline=False)
        res = simulate_jax.simulate_batch(platform, [wls], model).result(0)
        assert_equivalent(ref, res, f"seed={seed}", tol=JAX_TOL)
