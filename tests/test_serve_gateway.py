"""Multi-tenant gateway: planning, admission, budget, dynamic re-schedule;
the single-model engine regression after the step()/metrics refactor, and
continuous batching in the engine."""
import jax
import numpy as np
import pytest

from repro import configs
from repro.core import Plan, Scheduler
from repro.core.accelerators import tpu_pod_split
from repro.core.contention import ProportionalShareModel
from repro.core.dynamic import ScaledContentionModel, SlowdownMonitor
from repro.models import build
from repro.serve.engine import ServingEngine
from repro.serve.gateway import (GatewayConfig, MultiTenantGateway,
                                 TenantSpec, kv_bytes_per_token,
                                 plan_gateway, tenant_phase_graph)

STABLE = configs.get("stablelm-1.6b").reduced()
LLAMA = configs.get("llama3.2-3b").reduced()
PLAT = tpu_pod_split(2, 2, name="v5e-2x2-test")


def _gcfg(**kw):
    kw.setdefault("platform", PLAT)
    kw.setdefault("max_transitions", 1)
    kw.setdefault("body_groups", 1)
    return GatewayConfig(**kw)


def _specs(max_slots=2, capacity=32):
    return [TenantSpec("stable", STABLE, max_slots=max_slots,
                       capacity=capacity, prompt_len=5, max_new=4),
            TenantSpec("llama", LLAMA, max_slots=max_slots,
                       capacity=capacity, prompt_len=5, max_new=4)]


# ---------------------------------------------------------------------------
# planning
# ---------------------------------------------------------------------------

class TestPlanning:
    def test_phase_graph_structure(self):
        g = tenant_phase_graph(_specs()[0], PLAT, body_groups=1)
        names = [gr.name for gr in g.groups]
        n_pf = sum(1 for n in names if n.startswith("prefill:"))
        n_dc = sum(1 for n in names if n.startswith("decode:"))
        assert n_pf == n_dc == 3            # embed + body + head per phase
        assert names[:n_pf] == [n for n in names if n.startswith("prefill:")]

    def test_decode_macro_group_scales_with_max_new(self):
        s1 = TenantSpec("t", STABLE, prompt_len=5, max_new=1)
        s8 = TenantSpec("t", STABLE, prompt_len=5, max_new=8)
        g1 = tenant_phase_graph(s1, PLAT, body_groups=1)
        g8 = tenant_phase_graph(s8, PLAT, body_groups=1)
        acc = PLAT.names[0]
        d1 = [gr for gr in g1.groups if gr.name.startswith("decode:")]
        d8 = [gr for gr in g8.groups if gr.name.startswith("decode:")]
        for a, b in zip(d1, d8):
            assert b.time_on(acc) == pytest.approx(8 * a.time_on(acc))
            # demand is a rate: unchanged by the macro-group fusion
            assert b.demand_on(acc) == pytest.approx(a.demand_on(acc))

    def test_plan_no_worse_than_round_robin(self):
        plan = plan_gateway(_specs(), _gcfg())
        assert plan.speedup_vs_round_robin >= 1.0 - 1e-9
        assert plan.summary()

    def test_phase_assignments_cover_graph(self):
        plan = plan_gateway(_specs(), _gcfg())
        for s in plan.specs:
            ph = plan.phase_assignment(s.name)
            total = len(ph["prefill"]) + len(ph["decode"])
            assert total == len(plan.graphs[plan._idx(s.name)])
            assert plan.predicted_decode_step_ms(s.name) > 0.0

    def test_serialized_plan_boots_gateway_with_zero_solves(self, tmp_path):
        """Pre-solve offline, reload the artifact, re-plan: cache hit only."""
        s1 = Scheduler(PLAT)
        plan1 = plan_gateway(_specs(), _gcfg(), scheduler=s1)
        assert s1.solves == 1
        path = plan1.plan.save(tmp_path / "gw.json")

        s2 = Scheduler(PLAT)
        s2.cache.add(Plan.load(path))
        plan2 = plan_gateway(_specs(), _gcfg(), scheduler=s2)
        assert s2.solves == 0 and s2.cache.hits == 1
        assert plan2.solution.assignments == plan1.solution.assignments
        assert plan2.plan.request_hash == plan1.plan.request_hash

    def test_shared_scheduler_caches_across_gateways(self):
        sched = Scheduler(PLAT)
        MultiTenantGateway(_specs(), _gcfg(), scheduler=sched)
        MultiTenantGateway(_specs(), _gcfg(), scheduler=sched)
        assert sched.solves == 1 and sched.cache.hits >= 1


# ---------------------------------------------------------------------------
# runtime: multi-model admission + shared budget
# ---------------------------------------------------------------------------

class TestGatewayServing:
    def test_serves_two_models_concurrently(self):
        gw = MultiTenantGateway(_specs(), _gcfg())
        rng = np.random.default_rng(0)
        for name in gw.specs:
            for _ in range(3):
                gw.submit(name, rng.integers(0, 256, size=5))
        saw_both_active = False
        while gw.has_work and gw.total_steps < 200:
            rep = gw.step(observed_ms={"stable": 1.0, "llama": 1.0})
            if all(v > 0 for v in rep.active.values()):
                saw_both_active = True
        done = {n: e.completed for n, e in gw.engines.items()}
        assert saw_both_active, "tenants never decoded in the same step"
        for name, reqs in done.items():
            assert len(reqs) == 3
            # prefill emits the first token, decode the rest: max_new total
            assert all(len(r.tokens) == 4 for r in reqs)

    def test_memory_budget_enforced(self):
        specs = _specs()
        one_slot = max(s.kv_bytes_per_slot for s in specs)
        gw = MultiTenantGateway(specs, _gcfg(memory_budget_bytes=one_slot))
        rng = np.random.default_rng(1)
        for name in gw.specs:
            for _ in range(2):
                gw.submit(name, rng.integers(0, 256, size=5))
        while gw.has_work and gw.total_steps < 400:
            gw.step(observed_ms={"stable": 1.0, "llama": 1.0})
            assert gw.kv_bytes_in_use <= one_slot
            assert sum(e.active for e in gw.engines.values()) <= 1
        assert gw.deferred_admissions > 0
        # throttled, not starved: everything still completes
        assert all(len(e.completed) == 2 for e in gw.engines.values())

    def test_rejects_encoder_only_tenant(self):
        hubert = configs.get("hubert-xlarge").reduced()
        with pytest.raises(ValueError, match="encoder-only"):
            MultiTenantGateway([TenantSpec("enc", hubert)], _gcfg())

    def test_kv_bytes_per_token(self):
        n_attn = sum(1 for k in STABLE.layer_kinds if k in ("attn", "local"))
        assert kv_bytes_per_token(STABLE) == (
            2 * STABLE.n_kv_heads * STABLE.d_head * 4 * n_attn)  # float32

    def test_kv_bytes_per_token_counts_the_latent_cache(self):
        """An ``mla`` layer pins one latent row per token, as stored: 576
        lanes padded to 640, bfloat16, over DeepSeek-V2-Lite's 27 layers."""
        dsv2 = configs.get("deepseek-v2-lite")
        assert kv_bytes_per_token(dsv2) == 640 * 2 * 27
        small = dsv2.reduced()
        assert kv_bytes_per_token(small) == (
            small.mla.cache_width * 4 * small.n_layers)       # float32


# ---------------------------------------------------------------------------
# dynamic loop
# ---------------------------------------------------------------------------

class TestDynamicReschedule:
    def test_injected_slowdown_triggers_reschedule(self):
        gw = MultiTenantGateway(_specs(), _gcfg(patience=2, cooldown=2,
                                                warmup=1))
        rng = np.random.default_rng(2)
        for name in gw.specs:
            for _ in range(2):
                gw.submit(name, rng.integers(0, 256, size=5), max_new=12)
        fired_for = set()
        while gw.has_work and gw.total_steps < 400:
            llama_ms = 10.0 if gw.total_steps >= 4 else 1.0
            rep = gw.step(observed_ms={"stable": 1.0, "llama": llama_ms})
            fired_for.update(rep.fired)
        assert "llama" in fired_for
        assert "stable" not in fired_for
        assert gw.reschedules
        ev = gw.reschedules[0]
        assert "llama" in ev.tenants
        assert ev.observed_factor > gw.gcfg.slowdown_threshold
        # re-solve under the scaled model keeps a valid full assignment
        for wl in gw.plan.solution.workloads:
            assert len(wl.assignment) == len(wl.graph)

    def test_on_prediction_stream_never_fires(self):
        gw = MultiTenantGateway(_specs(), _gcfg(patience=2, cooldown=2))
        rng = np.random.default_rng(3)
        for name in gw.specs:
            gw.submit(name, rng.integers(0, 256, size=5))
        while gw.has_work and gw.total_steps < 200:
            rep = gw.step(observed_ms={"stable": 1.0, "llama": 1.0})
            assert not rep.fired
        assert not gw.reschedules


class TestSlowdownMonitor:
    def test_fires_after_patience_and_cools_down(self):
        m = SlowdownMonitor(threshold=1.5, patience=2, cooldown=3,
                            warmup=0, alpha=1.0)
        assert not m.observe(1.0, 1.0)
        assert not m.observe(2.0, 1.0)      # strike 1
        assert m.observe(2.0, 1.0)          # strike 2 -> fire
        assert m.fired == 1
        for _ in range(3):                  # cooldown holds
            assert not m.observe(2.0, 1.0)
        assert not m.observe(2.0, 1.0)      # strike 1 again
        assert m.observe(2.0, 1.0)          # fire again
        assert m.fired == 2

    def test_running_fast_never_fires(self):
        m = SlowdownMonitor(threshold=1.2, patience=1, warmup=0, alpha=1.0)
        for _ in range(20):
            assert not m.observe(0.5, 1.0)

    def test_warmup_absorbs_compile_spike(self):
        m = SlowdownMonitor(threshold=1.5, patience=1, cooldown=0,
                            warmup=2, alpha=1.0)
        assert not m.observe(50.0, 1.0)     # JIT compile step
        assert not m.observe(50.0, 1.0)
        assert not m.observe(1.0, 1.0)      # steady state
        assert m.observe(3.0, 1.0)          # real deviation fires

    def test_invalid_observations_ignored(self):
        m = SlowdownMonitor(warmup=0)
        assert not m.observe(1.0, 0.0)
        assert not m.observe(-1.0, 1.0)
        assert m.ratio == 1.0

    def test_scaled_model_scales_excess_only(self):
        base = ProportionalShareModel(capacity=1.0, sensitivity=1.0)
        scaled = ScaledContentionModel(base, factor=3.0)
        assert scaled.slowdown(0.2, 0.2) == 1.0          # under capacity
        excess = base.slowdown(0.8, 0.8) - 1.0
        assert scaled.slowdown(0.8, 0.8) == pytest.approx(1.0 + 3 * excess)


# ---------------------------------------------------------------------------
# regression: the refactor must not change single-model engine behavior
# ---------------------------------------------------------------------------

class TestEngineRegression:
    def test_single_model_output_unchanged_via_gateway(self):
        rng = np.random.default_rng(4)
        prompts = [rng.integers(0, 256, size=5) for _ in range(3)]

        model = build(STABLE)
        params = model.init(jax.random.PRNGKey(0))
        eng = ServingEngine(model, params, max_slots=2, capacity=32)
        for p in prompts:
            eng.submit(p, max_new=4)
        direct = sorted((r.rid, r.tokens) for r in eng.run_until_drained())

        spec = TenantSpec("solo", STABLE, max_slots=2, capacity=32,
                          prompt_len=5, max_new=4)
        gw = MultiTenantGateway([spec], _gcfg(), seed=0)
        for p in prompts:
            gw.submit("solo", p)
        via_gw = sorted((r.rid, r.tokens)
                        for r in gw.run_until_drained()["solo"])
        assert via_gw == direct

    def test_engine_metrics_and_has_work(self):
        model = build(STABLE)
        params = model.init(jax.random.PRNGKey(0))
        eng = ServingEngine(model, params, max_slots=2, capacity=32)
        assert not eng.has_work
        eng.submit(np.arange(5), max_new=3)
        assert eng.has_work
        eng.run_until_drained()
        assert not eng.has_work
        assert eng.counters.admitted == 1
        assert eng.counters.steps == eng.steps > 0
        assert eng.counters.tokens_out == 3
        assert eng.counters.last_step_ms > 0.0
        assert eng.counters.mean_step_ms > 0.0

    def test_metrics_dict_is_canonical_shape(self):
        from repro.serve.engine import METRIC_KEYS
        model = build(STABLE)
        params = model.init(jax.random.PRNGKey(0))
        eng = ServingEngine(model, params, max_slots=2, capacity=32)
        eng.submit(np.arange(5), max_new=3)
        eng.run_until_drained()
        m = eng.metrics()
        assert tuple(m) == METRIC_KEYS
        assert m["completed"] == 1 and m["deferred"] == 0

    def test_gateway_metrics_reuses_engine_shape(self):
        from repro.serve.engine import METRIC_KEYS
        spec = TenantSpec("solo", STABLE, max_slots=2, capacity=32,
                          prompt_len=5, max_new=4)
        gw = MultiTenantGateway([spec], _gcfg(), seed=0)
        gw.submit("solo", np.arange(5))
        gw.run_until_drained()
        m = gw.metrics()
        assert set(m) == {"steps", "kv_bytes_in_use", "deferred_admissions",
                          "reschedules", "tenants"}
        assert tuple(m["tenants"]["solo"]) == METRIC_KEYS

    def test_admission_gate_defers_and_preserves_fifo(self):
        model = build(STABLE)
        params = model.init(jax.random.PRNGKey(0))
        gate = {"open": False}
        eng = ServingEngine(model, params, max_slots=2, capacity=32,
                            admission_gate=lambda req: gate["open"])
        r1 = eng.submit(np.arange(5), max_new=3)
        r2 = eng.submit(np.arange(5), max_new=3)
        assert eng.step() == 0 and eng.active == 0     # everything deferred
        gate["open"] = True
        eng.step()
        assert eng.slots[0] is r1 and eng.slots[1] is r2   # FIFO kept


class TestServingEngine:
    @pytest.fixture(scope="class")
    def engine_setup(self):
        cfg = configs.get("llama3.2-3b").reduced(n_layers=2, vocab=64)
        model = build(cfg, backend="xla")
        params = model.init(jax.random.PRNGKey(0))
        return cfg, model, params

    def test_batched_requests_complete(self, engine_setup):
        cfg, model, params = engine_setup
        eng = ServingEngine(model, params, max_slots=3, capacity=64)
        reqs = [eng.submit(np.arange(4 + i) % cfg.vocab, max_new=5)
                for i in range(5)]
        done = eng.run_until_drained()
        assert len(done) == 5
        assert all(len(r.tokens) == 5 for r in reqs)

    def test_continuous_batching_matches_sequential(self, engine_setup):
        """Tokens generated under continuous batching equal those generated
        one-request-at-a-time (slot interference would corrupt caches)."""
        cfg, model, params = engine_setup
        prompts = [np.arange(5) % cfg.vocab, (np.arange(7) * 3) % cfg.vocab]
        # sequential singles
        singles = []
        for p in prompts:
            e = ServingEngine(model, params, max_slots=1, capacity=64)
            r = e.submit(p, max_new=4)
            e.run_until_drained()
            singles.append(r.tokens)
        # batched together
        e2 = ServingEngine(model, params, max_slots=2, capacity=64)
        rs = [e2.submit(p, max_new=4) for p in prompts]
        e2.run_until_drained()
        for got, want in zip([r.tokens for r in rs], singles):
            assert got == want
