"""Scheduler/Plan API: request validation, serialization round-trip,
plan-cache semantics, solver registry dispatch and solver parity."""
import json

import pytest

from repro.core import (Plan, PlanCache, Scheduler, ScheduleRequest,
                        registry, solver_bb)
from repro.core.contention import ProportionalShareModel
from repro.core.dynamic import ScaledContentionModel, reschedule_plan
from repro.core.graph import DNNGraph, LayerGroup
from repro.core.scheduler import failed
from repro.core.solver_z3 import HAVE_Z3

DNNS = ["googlenet", "resnet18"]


def small_scheduler(**kw):
    return Scheduler("xavier-agx", **kw)


def small_request(sched, **kw):
    kw.setdefault("solver", "bb")
    kw.setdefault("max_transitions", 1)
    return sched.request(DNNS, "latency", **kw)


# ---------------------------------------------------------------------------
# ScheduleRequest
# ---------------------------------------------------------------------------

class TestScheduleRequest:
    def test_normalizes_and_hashes_stably(self):
        sched = small_scheduler()
        r1 = small_request(sched)
        r2 = small_request(sched, iterations=[1, 1], depends_on=[None, None])
        assert r1.iterations == (1, 1)
        assert r1.request_hash() == r2.request_hash()

    def test_different_problem_different_hash(self):
        sched = small_scheduler()
        assert (small_request(sched).request_hash()
                != small_request(sched, iterations=[2, 1]).request_hash())
        assert (small_request(sched).request_hash()
                != small_request(sched, deadline_s=1.0).request_hash())

    def test_rejects_bad_objective(self):
        with pytest.raises(ValueError, match="objective"):
            small_scheduler().request(DNNS, "qps")

    def test_rejects_unknown_solver_with_known_names(self):
        with pytest.raises(KeyError, match="bb"):
            small_scheduler().request(DNNS, solver="simplex")

    def test_rejects_mismatched_iterations(self):
        with pytest.raises(ValueError, match="iterations"):
            small_scheduler().request(DNNS, iterations=[1, 2, 3])

    def test_rejects_bad_dependency(self):
        with pytest.raises(ValueError, match="depends_on"):
            small_scheduler().request(DNNS, depends_on=[1, 1])

    def test_rejects_dependency_cycle(self):
        with pytest.raises(ValueError, match="cycle"):
            small_scheduler().request(DNNS, depends_on=[1, 0])


# ---------------------------------------------------------------------------
# solver knobs (per-entry pass-through vocabulary)
# ---------------------------------------------------------------------------

class TestSolverKnobs:
    def test_mapping_and_kwargs_forms_normalize_identically(self):
        sched = small_scheduler()
        r1 = sched.request(DNNS, solver="anneal", max_transitions=1,
                           solver_knobs={"devices": 2, "budget_ms": 50.0})
        r2 = sched.request(DNNS, solver="anneal", max_transitions=1,
                           budget_ms=50.0, devices=2)
        assert r1.solver_knobs == (("budget_ms", 50.0), ("devices", 2))
        assert r1.request_hash() == r2.request_hash()

    def test_knobs_change_the_request_hash(self):
        sched = small_scheduler()
        bare = sched.request(DNNS, solver="anneal", max_transitions=1)
        knobbed = sched.request(DNNS, solver="anneal", max_transitions=1,
                                population=512)
        assert bare.request_hash() != knobbed.request_hash()

    def test_knob_free_serialization_is_back_compat(self):
        # pre-knob artifacts hash without a solver_knobs key; knob-free
        # requests must keep emitting (and hashing) the same document.
        sched = small_scheduler()
        bare = small_request(sched)
        assert "solver_knobs" not in bare.to_dict()
        knobbed = sched.request(DNNS, solver="anneal", max_transitions=1,
                                population=512)
        assert knobbed.to_dict()["solver_knobs"] == {"population": 512}

    def test_round_trips_through_plan_artifact(self):
        sched = small_scheduler()
        req = sched.request(DNNS, solver="anneal", max_transitions=1,
                            population=256, steps=8, island=8)
        back = ScheduleRequest.from_dict(json.loads(
            json.dumps(req.to_dict())))
        assert back.solver_knobs == req.solver_knobs
        assert back.request_hash() == req.request_hash()

    def test_unknown_knob_lists_valid_names(self):
        with pytest.raises(registry.UnknownEntryError,
                           match="population"):
            small_scheduler().request(DNNS, solver="anneal",
                                      max_transitions=1, temperature=3)

    def test_knobs_with_auto_solver_refused(self):
        with pytest.raises(registry.UnknownEntryError, match="explicit"):
            small_scheduler().request(DNNS, population=512)

    def test_non_scalar_knob_value_rejected(self):
        with pytest.raises(ValueError, match="scalar"):
            small_scheduler().request(DNNS, solver="anneal",
                                      max_transitions=1,
                                      population=[512])

    def test_knobs_reach_the_solver_and_its_provenance(self):
        sched = small_scheduler()
        plan = sched.solve(DNNS, solver="anneal", max_transitions=1,
                           population=64, steps=8, island=8,
                           evaluator="batch")
        assert plan.solver_params["population"] == 64
        assert plan.solver_params["steps"] == 8
        assert plan.solver_params["island"] == 8


# ---------------------------------------------------------------------------
# Plan serialization
# ---------------------------------------------------------------------------

class TestPlanRoundTrip:
    def test_json_round_trip_equality(self):
        sched = small_scheduler()
        plan = sched.resolve(small_request(sched))
        blob = plan.to_json()
        back = Plan.from_json(blob)
        assert back.request_hash == plan.request_hash
        assert back.request.request_hash() == plan.request_hash
        assert back.assignments == plan.assignments
        assert back.objective == pytest.approx(plan.objective, rel=1e-12)
        assert back.solver == plan.solver
        assert back.platform_fingerprint == plan.platform_fingerprint
        # serialization is a fixed point: a reloaded plan re-serializes
        # byte-identically
        assert back.to_json() == blob

    def test_save_load(self, tmp_path):
        sched = small_scheduler()
        plan = sched.resolve(small_request(sched))
        path = plan.save(tmp_path / "plans" / "p.json")
        loaded = Plan.load(path)
        assert loaded.assignments == plan.assignments

    def test_tampered_artifact_rejected(self):
        sched = small_scheduler()
        plan = sched.resolve(small_request(sched))
        doc = json.loads(plan.to_json())
        doc["request"]["max_transitions"] = 2      # silent schedule drift
        with pytest.raises(ValueError, match="hash"):
            Plan.from_json(json.dumps(doc))

    def test_custom_model_solves_and_caches_but_refuses_json(self):
        class MyModel:
            def slowdown(self, own, external):
                return 1.0 + max(0.0, own + external - 1.0)

            def __repr__(self):               # deterministic fingerprint
                return "MyModel()"

        sched = Scheduler("xavier-agx", model=MyModel())
        p1 = sched.resolve(small_request(sched))
        p2 = sched.resolve(small_request(sched))
        assert p2 is p1 and sched.solves == 1     # hash + cache still work
        with pytest.raises(TypeError, match="codec"):
            Plan.from_json(p1.to_json())          # only round-trip refuses

    def test_per_domain_model_mapping_round_trips(self):
        sched = small_scheduler()
        mapping = {"EMC": ProportionalShareModel(1.0, 2.0)}
        plan = sched.resolve(small_request(sched, model=mapping))
        back = Plan.from_json(plan.to_json())
        assert back.request.model == mapping

    def test_scaled_model_round_trips(self):
        sched = small_scheduler()
        plan = reschedule_plan(sched, sched.graphs(DNNS), 2.5,
                               objective="latency", max_transitions=1,
                               budget_s=0.2)
        back = Plan.from_json(plan.to_json())
        model = back.request.model
        assert isinstance(model, ScaledContentionModel)
        assert model.factor == 2.5
        assert isinstance(model.base, ProportionalShareModel)


# ---------------------------------------------------------------------------
# solver provenance (PR 6)
# ---------------------------------------------------------------------------

class TestSolverProvenance:
    def test_anneal_plan_records_params_and_round_trips(self):
        sched = small_scheduler()
        plan = sched.solve(DNNS, solver="anneal", max_transitions=1,
                           evaluator="batch")
        assert plan.solver == "anneal"
        for key in ("seed", "steps", "population"):
            assert key in plan.solver_params
        assert plan.solver_params["seed"] == 0
        assert "solver=anneal seed=0" in plan.summary()
        back = Plan.from_json(plan.to_json())
        assert back.solver_params == plan.solver_params
        assert back.solution.params == plan.solution.params
        assert back.to_json() == plan.to_json()

    def test_exact_solver_params_empty(self):
        sched = small_scheduler()
        plan = sched.resolve(small_request(sched))
        assert plan.solver_params == {}
        assert "seed=" not in plan.summary()

    def test_from_dict_back_compat_pre_provenance_artifacts(self):
        # PR-5-era artifacts have neither plan-level solver_params nor
        # solution-level params: they must load with empty provenance.
        sched = small_scheduler()
        plan = sched.resolve(small_request(sched))
        doc = json.loads(plan.to_json())
        del doc["solver_params"]
        del doc["solution"]["params"]
        back = Plan.from_json(json.dumps(doc))
        assert back.solver_params == {}
        assert back.solution.params == {}
        assert back.assignments == plan.assignments


# ---------------------------------------------------------------------------
# PlanCache
# ---------------------------------------------------------------------------

class TestPlanCache:
    def test_hit_and_miss_semantics(self):
        sched = small_scheduler()
        p1 = sched.resolve(small_request(sched))
        assert sched.solves == 1 and sched.cache.misses == 1
        p2 = sched.resolve(small_request(sched))
        assert p2 is p1                       # content-addressed: O(1) hit
        assert sched.solves == 1 and sched.cache.hits == 1
        sched.resolve(small_request(sched, iterations=[2, 1]))
        assert sched.solves == 2              # different problem: miss

    def test_disk_cache_cold_hit(self, tmp_path):
        s1 = small_scheduler(cache=PlanCache(tmp_path))
        p1 = s1.resolve(small_request(s1))
        # a different process with the same cache root hits cold
        s2 = small_scheduler(cache=PlanCache(tmp_path))
        p2 = s2.resolve(small_request(s2))
        assert s2.solves == 0 and s2.cache.hits == 1
        assert p2.assignments == p1.assignments

    def test_corrupt_disk_artifact_degrades_to_miss(self, tmp_path):
        s1 = small_scheduler(cache=PlanCache(tmp_path))
        s1.resolve(small_request(s1))
        cache_file = next(tmp_path.glob("plan-*.json"))
        cache_file.write_text("{not json")
        s2 = small_scheduler(cache=PlanCache(tmp_path))
        plan = s2.resolve(small_request(s2))       # re-solves, no crash
        assert s2.solves == 1 and plan.result.makespan > 0

    def test_max_entries_evicts_fifo(self):
        sched = small_scheduler(cache=PlanCache(max_entries=1))
        sched.resolve(small_request(sched))
        sched.resolve(small_request(sched, iterations=[2, 1]))
        assert len(sched.cache) == 1
        sched.resolve(small_request(sched))        # evicted: re-solved
        assert sched.solves == 3

    def test_preloaded_artifact_skips_solver(self, tmp_path):
        s1 = small_scheduler()
        path = s1.resolve(small_request(s1)).save(tmp_path / "a.json")
        s2 = small_scheduler()
        s2.cache.add(Plan.load(path))
        plan = s2.resolve(small_request(s2))
        assert s2.solves == 0 and s2.cache.hits == 1
        assert plan.solver in registry.solver_names()


# ---------------------------------------------------------------------------
# solver registry
# ---------------------------------------------------------------------------

class TestSolverRegistry:
    def test_builtins_registered_in_priority_order(self):
        names = registry.solver_names()
        assert set(("z3", "bb", "greedy")) <= set(names)
        assert names.index("z3") < names.index("bb") < names.index("greedy")

    def test_unknown_solver_lists_known_names(self):
        with pytest.raises(KeyError, match="greedy"):
            registry.get_solver("simplex")

    def test_auto_degrades_past_refusing_solver(self, monkeypatch):
        def too_large(*a, **k):
            raise ValueError("search space too large")
        entries = dict(registry._SOLVERS)
        for name in ("z3", "bb"):
            import dataclasses
            monkeypatch.setitem(registry._SOLVERS, name,
                                dataclasses.replace(entries[name],
                                                    fn=too_large))
        sched = small_scheduler()
        plan = sched.resolve(small_request(sched, solver="auto"))
        assert plan.solver == "greedy"
        assert not plan.optimal

    def test_bb_z3_parity_on_small_problem(self):
        sched = small_scheduler()
        bb_plan = sched.resolve(small_request(sched, solver="bb"))
        if not HAVE_Z3:
            pytest.skip("z3 unavailable: parity half skipped")
        z3_plan = sched.resolve(small_request(sched, solver="z3"))
        assert z3_plan.objective == pytest.approx(bb_plan.objective,
                                                  rel=1e-9)

    def test_greedy_never_worse_than_best_baseline(self):
        sched = small_scheduler()
        graphs = sched.graphs(DNNS)
        best = min(
            sched.evaluate_baseline(n, graphs)[1].objective("latency")
            for n in registry.baseline_names())
        plan = sched.resolve(small_request(sched, solver="greedy"))
        assert plan.objective <= best + 1e-9
        for wl, g in zip(plan.solution.workloads, graphs):
            assert len(wl.assignment) == len(g)
        # and the exact solver bounds greedy from below
        exact = sched.resolve(small_request(sched, solver="bb"))
        assert plan.objective >= exact.objective - 1e-9


# ---------------------------------------------------------------------------
# compare(): structured error rows (infeasible != crashed)
# ---------------------------------------------------------------------------

class TestCompareErrorRows:
    def test_infeasible_baseline_is_structured_not_none(self):
        # gpu-only + dla-only graphs: fastest_only has no common accelerator
        g1 = DNNGraph("gpu-only", (LayerGroup("a", {"GPU": 1.0},
                                              {"GPU": 0.5}),))
        g2 = DNNGraph("dla-only", (LayerGroup("b", {"DLA": 1.0},
                                              {"DLA": 0.5}),))
        sched = small_scheduler()
        rows = sched.compare([g1, g2], "latency", max_transitions=1)
        row = rows["fastest_only"]
        assert failed(row)
        assert row["error"]["type"] == "ValueError"
        assert "accelerator" in row["error"]["message"]
        assert not failed(rows["naive_concurrent"])
        assert not failed(rows["haxconn"])
        assert rows["haxconn"].solution.result.makespan > 0

    def test_deprecated_api_compare_keeps_solution_shape(self):
        from repro.core import api
        with pytest.deprecated_call():
            rows = api.compare(DNNS, platform="xavier-agx",
                               deadline_s=5.0)
        assert isinstance(rows["haxconn"], solver_bb.Solution)
        for name in registry.baseline_names():
            assert not failed(rows[name])

    def test_simulate_time_failure_is_structured_not_fatal(self):
        # a model that crashes inside the simulator must degrade to
        # per-row error dicts, not take down the whole compare() sweep
        class Exploding:
            def slowdown(self, own, external):
                raise RuntimeError("boom at simulate time")

            def __repr__(self):
                return "Exploding()"

        sched = Scheduler("xavier-agx", model=Exploding())
        rows = sched.compare(DNNS, "latency", max_transitions=1,
                             solver="greedy")
        # the sweep survives and every baseline has a row: contention-free
        # ones (fastest_only never calls slowdown) succeed, concurrent ones
        # fail as structured RuntimeError rows, not an exception
        assert set(registry.baseline_names()) <= set(rows)
        errs = [rows[n] for n in registry.baseline_names()
                if failed(rows[n])]
        assert errs, "expected at least one simulate-time failure row"
        for row in errs:
            assert row["error"]["type"] == "RuntimeError"
            assert "boom" in row["error"]["message"]

    def test_pre_evaluator_solver_signature_still_dispatches(self):
        # third-party solvers registered against the old signature (no
        # evaluator kwarg) must keep working through Scheduler.resolve
        def legacy(platform, graphs, model, *, objective, max_transitions,
                   iterations, depends_on, deadline_s):
            from repro.core import solver_greedy
            return solver_greedy.solve(
                platform, graphs, model, objective=objective,
                max_transitions=max_transitions, iterations=iterations,
                depends_on=depends_on, evaluator="scalar")

        registry.register_solver("legacy-sig", priority=99)(legacy)
        try:
            sched = small_scheduler()
            plan = sched.resolve(small_request(sched, solver="legacy-sig"))
            assert plan.solver == "legacy-sig"
            assert plan.result.makespan > 0
        finally:
            registry._SOLVERS.pop("legacy-sig")

    def test_registered_baseline_feeds_compare_and_greedy(self):
        from repro.core.baselines import fastest_only
        registry.register_baseline("everything-fastest", fastest_only)
        try:
            sched = small_scheduler()
            rows = sched.compare(DNNS, "latency", max_transitions=1)
            assert "everything-fastest" in rows
            # greedy's incumbent scan sees registry entries too
            plan = sched.resolve(small_request(sched, solver="greedy"))
            base = sched.evaluate_baseline(
                "everything-fastest", DNNS)[1].objective("latency")
            assert plan.objective <= base + 1e-9
        finally:
            registry._BASELINES.pop("everything-fastest")


# ---------------------------------------------------------------------------
# plan-cache hardening: LRU semantics, truncation tolerance, sharded store
# ---------------------------------------------------------------------------

class TestPlanCacheHardening:
    def _requests(self, sched, n):
        """n distinct problems (different iteration counts)."""
        return [small_request(sched, iterations=[i + 1, 1])
                for i in range(n)]

    def test_hit_refreshes_lru_recency(self):
        sched = small_scheduler(cache=PlanCache(max_entries=2))
        r1, r2, r3 = self._requests(sched, 3)
        sched.resolve(r1)
        sched.resolve(r2)
        sched.resolve(r1)                     # refresh: r2 is now oldest
        sched.resolve(r3)                     # evicts r2, not r1
        solves = sched.solves
        sched.resolve(r1)                     # still cached
        assert sched.solves == solves
        sched.resolve(r2)                     # evicted: re-solved
        assert sched.solves == solves + 1

    def test_truncated_disk_artifact_degrades_to_miss(self, tmp_path):
        s1 = small_scheduler(cache=PlanCache(tmp_path))
        s1.resolve(small_request(s1))
        cache_file = next(tmp_path.glob("plan-*.json"))
        blob = cache_file.read_text()
        cache_file.write_text(blob[:len(blob) // 2])   # writer died mid-save
        s2 = small_scheduler(cache=PlanCache(tmp_path))
        plan = s2.resolve(small_request(s2))           # re-solves, no crash
        assert s2.solves == 1 and plan.result.makespan > 0

    def test_wrong_hash_disk_artifact_degrades_to_miss(self, tmp_path):
        """A decodable artifact stored under the wrong name is ignored."""
        s1 = small_scheduler(cache=PlanCache(tmp_path))
        s1.resolve(small_request(s1))
        src = next(tmp_path.glob("plan-*.json"))
        other = small_request(s1, iterations=[5, 1])
        src.rename(tmp_path / f"plan-{other.request_hash()[:16]}.json")
        s2 = small_scheduler(cache=PlanCache(tmp_path))
        s2.resolve(other)
        assert s2.solves == 1                          # mismatch -> miss


class TestShardedPlanCache:
    def test_layout_and_cross_instance_cold_hit(self, tmp_path):
        from repro.core import ShardedPlanCache
        s1 = small_scheduler(cache=ShardedPlanCache(tmp_path))
        p1 = s1.resolve(small_request(s1))
        path = s1.cache.path_for(p1.request_hash)
        assert path.exists()
        assert path.parent.name == p1.request_hash[:2]   # hash-prefix shard
        # a fresh scheduler over the same root boots without solving
        s2 = small_scheduler(cache=ShardedPlanCache(tmp_path))
        p2 = s2.resolve(small_request(s2))
        assert s2.solves == 0 and s2.cache.hits == 1
        assert p2.assignments == p1.assignments

    def test_disk_eviction_bounds_every_shard(self, tmp_path):
        from repro.core import ShardedPlanCache
        cache = ShardedPlanCache(tmp_path, shard_chars=1,
                                 max_disk_entries=16)    # budget 1/shard
        sched = small_scheduler(cache=cache)
        for i in range(4):
            sched.resolve(small_request(sched, iterations=[i + 1, 1]))
        budget = 1
        for shard in tmp_path.iterdir():
            assert len(list(shard.glob("plan-*.json"))) <= budget
        assert cache.disk_entries() <= 4

    def test_corrupt_shard_entry_degrades_to_miss(self, tmp_path):
        from repro.core import ShardedPlanCache
        s1 = small_scheduler(cache=ShardedPlanCache(tmp_path))
        p1 = s1.resolve(small_request(s1))
        s1.cache.path_for(p1.request_hash).write_text("{truncated")
        s2 = small_scheduler(cache=ShardedPlanCache(tmp_path))
        s2.resolve(small_request(s2))
        assert s2.solves == 1                  # corrupt entry re-solved

    def test_rejects_bad_shard_chars(self, tmp_path):
        from repro.core import ShardedPlanCache
        with pytest.raises(ValueError, match="shard_chars"):
            ShardedPlanCache(tmp_path, shard_chars=0)
