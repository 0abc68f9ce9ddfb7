"""Unified observability layer: span tracer, metrics registry, timeline
export, logging hierarchy, and the canonical serving-metrics schemas.

Pins the load-bearing contracts of :mod:`repro.obs`:

* disabled tracing is a structural no-op (shared null context, no
  allocation per call);
* virtual-clock replays export **byte-identical** Perfetto JSON;
* exported traces are structurally valid Chrome trace-event documents;
* Prometheus text exposition matches a golden block exactly;
* all four serving providers (engine, multi-tenant gateway, fleet
  report/gateway, admission controller) conform to the schemas in
  :mod:`repro.obs.metrics` — key set *and* order.
"""
import io
import json
import logging
import threading

import pytest

from repro import obs
from repro.obs import (ADMISSION_SCHEMA, GATEWAY_SCHEMA, MetricsRegistry,
                       NULL_TRACER, TENANT_SCHEMA, Tracer, conform,
                       configure_logging, get_logger, get_tracer,
                       set_tracer)
from repro.obs.timeline import (ascii_gantt, plan_ascii, plan_chrome,
                                timeline_chrome, timeline_events)

from benchmarks.bench_obs import validate_chrome


@pytest.fixture(autouse=True)
def _restore_global_tracer():
    prev = set_tracer(None)
    yield
    set_tracer(prev)


def fake_clock(step_ms=1.0):
    """Deterministic monotonic clock: 0, step, 2*step, ..."""
    state = {"t": -step_ms}

    def clock():
        state["t"] += step_ms
        return state["t"]
    return clock


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

class TestSpans:
    def test_span_records_complete_event(self):
        tr = Tracer(clock=fake_clock())
        with tr.span("solve", "core", solver="bb") as sp:
            sp.set(objective=9.9)
        (ev,) = tr.events()
        assert ev["ph"] == "X" and ev["name"] == "solve"
        assert ev["cat"] == "core"
        assert ev["args"] == {"solver": "bb", "objective": 9.9}
        assert ev["ts"] == 0.0 and ev["dur"] == 1000.0  # µs, 1 ms clock

    def test_nested_spans_close_inner_first(self):
        tr = Tracer(clock=fake_clock())
        with tr.span("outer"):
            with tr.span("inner"):
                pass
        names = [e["name"] for e in tr.events()]
        assert names == ["inner", "outer"]
        inner, outer = tr.events()
        assert outer["ts"] <= inner["ts"]
        assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]

    def test_span_survives_exceptions(self):
        tr = Tracer(clock=fake_clock())
        with pytest.raises(ValueError):
            with tr.span("boom"):
                raise ValueError("x")
        assert [e["name"] for e in tr.events()] == ["boom"]

    def test_instant_with_virtual_timestamp_and_track(self):
        tr = Tracer(clock=fake_clock())
        tr.instant("fleet.reschedule", "dynamic", ts_ms=123.456,
                   track="fleet", plan="p13")
        (ev,) = tr.events()
        assert ev["ph"] == "i" and ev["s"] == "t"
        assert ev["ts"] == 123456.0
        assert ev["args"] == {"plan": "p13"}

    def test_decorator_late_binds_global_tracer(self):
        @obs.trace("decorated")
        def fn(x):
            return x + 1

        assert fn(1) == 2                      # null tracer: no events
        tr = Tracer(clock=fake_clock())
        set_tracer(tr)
        assert fn(2) == 3
        assert [e["name"] for e in tr.events()] == ["decorated"]

    def test_threads_get_own_tracks(self):
        tr = Tracer()
        n_threads, n_spans = 4, 50

        def work(i):
            for k in range(n_spans):
                with tr.span(f"t{i}.{k}"):
                    pass

        threads = [threading.Thread(target=work, args=(i,), name=f"w{i}")
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        events = tr.events()
        assert len(events) == n_threads * n_spans
        by_tid = {}
        for e in events:
            by_tid.setdefault(e["tid"], set()).add(e["name"].split(".")[0])
        # spans never leak onto another thread's track
        assert all(len(names) == 1 for names in by_tid.values())
        assert len(by_tid) == n_threads


class TestNullTracer:
    def test_default_tracer_is_null(self):
        assert get_tracer() is NULL_TRACER
        assert not get_tracer().enabled

    def test_span_returns_shared_context(self):
        # the no-op path must not allocate per call
        assert NULL_TRACER.span("a") is NULL_TRACER.span("b", "c", x=1)
        with NULL_TRACER.span("a") as sp:
            sp.set(anything="goes")        # swallowed, never raises

    def test_all_operations_are_noops(self):
        NULL_TRACER.instant("x", ts_ms=1.0, track="t")
        NULL_TRACER.complete("x", 0.0, 1.0)
        NULL_TRACER.add_events([{"ph": "X"}])
        NULL_TRACER.counter_sample("x", 0.0, {"v": 1})

    def test_decorator_returns_function_unchanged(self):
        def fn():
            return 42
        assert NULL_TRACER.trace(fn) is fn
        assert NULL_TRACER.trace("named")(fn) is fn


class TestChromeExport:
    def test_document_is_structurally_valid(self):
        tr = Tracer(clock=fake_clock())
        with tr.span("a"):
            tr.instant("evt", track="fleet")
        tr.complete("bulk", 0.0, 5.0, track="fleet/queue")
        tr.counter_sample("load", 1.0, {"q": 3})
        assert validate_chrome(tr.to_chrome()) == []

    def test_track_metadata_emitted_once_per_track(self):
        tr = Tracer(clock=fake_clock())
        tr.complete("s1", 0.0, 1.0, track="accA")
        tr.complete("s2", 1.0, 1.0, track="accA")
        doc = tr.to_chrome()
        meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        assert [m["args"]["name"] for m in meta] == ["accA"]

    def test_same_inputs_same_bytes(self):
        def build():
            tr = Tracer(clock=fake_clock())
            with tr.span("solve", solver="bb"):
                tr.instant("hit", ts_ms=3.0, track="cache")
            return tr.to_json()
        assert build() == build()

    def test_track_id_shares_tid_registry(self):
        tr = Tracer(clock=fake_clock())
        with tr.span("main-span"):
            pass
        t1 = tr.track_id("plan0")
        t2 = tr.track_id("plan0/queue")
        assert len({tr.events()[0]["tid"], t1, t2}) == 3


class TestProfilerAnnotation:
    """Wall-clock spans reach a running ``jax.profiler`` trace as
    ``repro.<name>`` host events, on the device ops' clock."""

    @staticmethod
    def profiled(tmp_path, body):
        """Run ``body()`` inside a CPU profiler session; returns the
        ``repro.*`` host events of the ``.xplane.pb`` as
        ``{name: [duration_us, ...]}``."""
        import jax
        from jax.profiler import ProfileData
        jax.profiler.start_trace(str(tmp_path))
        try:
            body()
        finally:
            jax.profiler.stop_trace()
        (path,) = tmp_path.rglob("*.xplane.pb")
        found = {}
        for plane in ProfileData.from_file(str(path)).planes:
            if not plane.name.startswith("/host"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("repro."):
                        found.setdefault(ev.name, []).append(
                            ev.duration_ns / 1e3)
        return found

    def test_wall_span_lands_in_xplane_with_its_duration(self, tmp_path):
        import time
        tr = Tracer()

        def body():
            # long enough that 1% covers the profiler clock's own
            # re-calibration step (about 0.5 ms, once a session)
            with tr.span("probe.outer", "test"):
                time.sleep(0.1)
                with tr.span("probe.inner"):
                    time.sleep(0.1)
        found = self.profiled(tmp_path, body)
        assert set(found) == {"repro.probe.outer", "repro.probe.inner"}
        for ev in tr.events():
            (got,) = found[f"repro.{ev['name']}"]
            assert abs(got - ev["dur"]) <= max(0.01 * ev["dur"], 50.0)

    def test_virtual_clock_and_null_tracers_write_nothing(self, tmp_path):
        tr = Tracer(clock=fake_clock())

        def body():
            with tr.span("probe.virtual"):
                pass
            with NULL_TRACER.span("probe.null"):
                pass
        assert self.profiled(tmp_path, body) == {}
        assert [e["name"] for e in tr.events()] == ["probe.virtual"]

    def test_caller_stamps_bound_the_span(self):
        tr = Tracer(clock=fake_clock())
        with tr.span("stamped", t0_ms=10.0, rid=3) as sp:
            sp.t1 = 12.5
        (ev,) = tr.events()
        assert (ev["ts"], ev["dur"], ev["args"]) == (1e4, 2.5e3, {"rid": 3})


class TestAnnealChunkLoop:
    def test_emits_no_incumbent_instants_or_accept_rate(self):
        from repro.core import Scheduler
        tr = Tracer()
        set_tracer(tr)
        sched = Scheduler("xavier-agx")
        sched.resolve(sched.request(
            ["vgg19", "resnet101"], solver="anneal", max_transitions=1,
            population=64, steps=4, island=32))
        events = tr.events()
        assert not [e for e in events if e["name"] == "anneal.incumbent"]
        assert not [e for e in events if e["ph"] == "i"]
        (chunk,) = [e for e in events if e["name"] == "anneal.chunk"]
        assert "accept_rate" not in chunk["args"]


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

class TestMetricsRegistry:
    def test_idempotent_getters_and_kind_conflict(self):
        reg = MetricsRegistry()
        c = reg.counter("solves", "x")
        assert reg.counter("solves") is c
        with pytest.raises(TypeError, match="already registered"):
            reg.gauge("solves")

    def test_counter_gauge_histogram_roundtrip(self):
        reg = MetricsRegistry()
        reg.counter("hits").inc()
        reg.counter("hits").inc(2)
        reg.gauge("depth").set(7)
        h = reg.histogram("lat_ms", buckets=(1.0, 10.0))
        for v in (0.5, 5.0, 50.0):
            h.observe(v)
        snap = reg.snapshot()
        assert snap["repro_hits"] == {"kind": "counter", "value": 3.0}
        assert snap["repro_depth"]["value"] == 7.0
        assert snap["repro_lat_ms"]["count"] == 3
        assert snap["repro_lat_ms"]["buckets"] == {"1": 1, "10": 2}
        assert h.quantile(0.5) == 10.0

    def test_labeled_series(self):
        reg = MetricsRegistry()
        c = reg.counter("reqs")
        c.labels(tenant="a").inc(5)
        c.labels(tenant="b").inc()
        snap = reg.snapshot()["repro_reqs"]
        assert snap["series"] == {'{tenant="a"}': 5.0, '{tenant="b"}': 1.0}

    def test_json_snapshot_is_deterministic(self):
        reg = MetricsRegistry()
        reg.gauge("b").set(2)
        reg.gauge("a").set(1)
        assert json.loads(reg.to_json()) == reg.snapshot()
        assert reg.to_json() == reg.to_json()

    def test_prometheus_exposition_golden(self):
        reg = MetricsRegistry()
        reg.counter("cache_hits", "plan cache hits").labels(
            tier="mem").inc(4)
        reg.gauge("queue_depth", "queued requests").set(2)
        h = reg.histogram("step_ms", "decode step latency",
                          buckets=(1.0, 5.0))
        h.observe(0.3)
        h.observe(0.7)
        h.observe(3.0)
        h.observe(99.5)
        assert reg.to_prometheus() == (
            "# HELP repro_cache_hits plan cache hits\n"
            "# TYPE repro_cache_hits counter\n"
            'repro_cache_hits{tier="mem"} 4\n'
            "# HELP repro_queue_depth queued requests\n"
            "# TYPE repro_queue_depth gauge\n"
            "repro_queue_depth 2\n"
            "# HELP repro_step_ms decode step latency\n"
            "# TYPE repro_step_ms histogram\n"
            'repro_step_ms_bucket{le="1"} 2\n'
            'repro_step_ms_bucket{le="5"} 3\n'
            'repro_step_ms_bucket{le="+Inf"} 4\n'
            "repro_step_ms_sum 103.5\n"
            "repro_step_ms_count 4\n"
        )


class TestConform:
    def test_preserves_schema_order(self):
        shuffled = dict(reversed(list(
            {k: i for i, k in enumerate(TENANT_SCHEMA)}.items())))
        out = conform(TENANT_SCHEMA, shuffled)
        assert list(out) == list(TENANT_SCHEMA)

    def test_missing_key_fails_at_provider(self):
        values = {k: 0 for k in GATEWAY_SCHEMA}
        del values["reschedules"]
        with pytest.raises(KeyError, match="reschedules"):
            conform(GATEWAY_SCHEMA, values)

    def test_extra_keys_append_after_canonical_block(self):
        out = conform(GATEWAY_SCHEMA, {k: 0 for k in GATEWAY_SCHEMA},
                      tenants={})
        assert list(out)[-1] == "tenants"


# ---------------------------------------------------------------------------
# logging hierarchy
# ---------------------------------------------------------------------------

class TestLogging:
    def test_get_logger_pins_repro_hierarchy(self):
        assert get_logger("repro.core.scheduler").name == \
            "repro.core.scheduler"
        assert get_logger("benchmarks.bench_obs").name == \
            "repro.benchmarks.bench_obs"
        assert get_logger("__main__").name == "repro"
        assert get_logger("repro").name == "repro"

    def test_configure_logging_is_idempotent(self):
        root = configure_logging("info", stream=io.StringIO())
        configure_logging("debug", stream=io.StringIO())
        ours = [h for h in root.handlers
                if getattr(h, "_repro_obs", False)]
        assert len(ours) == 1
        assert root.level == logging.DEBUG

    def test_json_lines_are_parseable(self):
        buf = io.StringIO()
        configure_logging("info", json=True, stream=buf)
        get_logger("repro.core.plan").warning("degraded: %s", "corrupt")
        doc = json.loads(buf.getvalue().strip())
        assert doc["level"] == "warning"
        assert doc["logger"] == "repro.core.plan"
        assert doc["msg"] == "degraded: corrupt"


# ---------------------------------------------------------------------------
# schema conformance across every serving provider
# ---------------------------------------------------------------------------

class TestProviderConformance:
    def test_metric_keys_derive_from_tenant_schema(self):
        from repro.serve.engine import METRIC_KEYS
        assert METRIC_KEYS == tuple(TENANT_SCHEMA)

    def test_admission_controller_conforms(self):
        from repro.serve.fleet import SLO, AdmissionController
        ctl = AdmissionController(default_slo=SLO(p99_ms=100.0))
        m = ctl.metrics()
        assert tuple(m) == tuple(ADMISSION_SCHEMA)

    def test_schema_kinds_are_known(self):
        for schema in (TENANT_SCHEMA, GATEWAY_SCHEMA, ADMISSION_SCHEMA):
            for key, (kind, help_text) in schema.items():
                assert kind in ("counter", "gauge", "histogram"), key
                assert help_text, key


# ---------------------------------------------------------------------------
# fleet replay: byte-identical virtual-clock traces
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fleet_pool():
    from repro import configs
    from repro.core.accelerators import tpu_pod_split
    from repro.serve.fleet import build_pool
    from repro.serve.gateway import GatewayConfig, TenantSpec
    specs = [TenantSpec("stable", configs.get("stablelm-1.6b"),
                        max_slots=2, capacity=256, prompt_len=64,
                        max_new=16),
             TenantSpec("llama", configs.get("llama3.2-3b"),
                        max_slots=2, capacity=256, prompt_len=64,
                        max_new=16)]
    gcfg = GatewayConfig(max_transitions=1, body_groups=1)
    plats = [tpu_pod_split(1, 3, name="p13"),
             tpu_pod_split(2, 2, name="p22")]
    return build_pool(specs, plats, gcfg, slots=4, deadline_s=5.0)


def _traced_replay(pool, trace):
    from repro.serve.fleet import SLO, FleetConfig, FleetGateway
    tr = Tracer(clock=lambda: 0.0)
    prev = set_tracer(tr)
    try:
        cfg = FleetConfig(policy="slo", default_slo=SLO(p99_ms=1e9))
        gw = FleetGateway(pool, n_tenants=trace.n_tenants, cfg=cfg,
                          capacity_hint=len(trace))
        rep = gw.replay(trace)
        assert not rep.reschedules     # a solve would stamp wall time
        gw.export_trace(tracer=tr)
    finally:
        set_tracer(prev)
    return tr


class TestFleetTraceDeterminism:
    def test_identical_replays_export_identical_bytes(self, fleet_pool):
        from repro.serve.fleet import bursty_trace
        trace = bursty_trace(50.0, 300.0, 400, 20, seed=3)
        a = _traced_replay(fleet_pool, trace)
        b = _traced_replay(fleet_pool, trace)
        assert a.to_json() == b.to_json()
        assert len(a.events()) > 400       # replay span + request spans

    def test_exported_trace_is_valid_chrome(self, fleet_pool):
        from repro.serve.fleet import bursty_trace
        trace = bursty_trace(50.0, 300.0, 200, 10, seed=5)
        tr = _traced_replay(fleet_pool, trace)
        doc = tr.to_chrome()
        assert validate_chrome(doc) == []
        assert doc["otherData"]["clock"] == "virtual_ms"
        cats = {e.get("cat") for e in doc["traceEvents"]
                if e["ph"] == "X"}
        assert "service" in cats and "fleet" in cats

    def test_report_trace_events_standalone(self, fleet_pool):
        from repro.serve.fleet import SLO, FleetConfig, FleetGateway, \
            bursty_trace
        trace = bursty_trace(50.0, 300.0, 150, 10, seed=8)
        cfg = FleetConfig(policy="slo", default_slo=SLO(p99_ms=1e9))
        gw = FleetGateway(fleet_pool, n_tenants=trace.n_tenants, cfg=cfg,
                          capacity_hint=len(trace))
        rep = gw.replay(trace)
        events = rep.trace_events()
        # standalone mode brings its own thread_name metadata
        assert any(e["ph"] == "M" for e in events)
        svc = [e for e in events if e.get("cat") == "service"]
        assert len(svc) == rep.completed
        assert all(e["args"]["tenant"] is not None for e in svc)

    def test_truncation_is_logged_not_silent(self, fleet_pool, caplog):
        from repro.serve.fleet import SLO, FleetConfig, FleetGateway, \
            bursty_trace
        trace = bursty_trace(50.0, 300.0, 120, 10, seed=2)
        cfg = FleetConfig(policy="slo", default_slo=SLO(p99_ms=1e9))
        gw = FleetGateway(fleet_pool, n_tenants=trace.n_tenants, cfg=cfg,
                          capacity_hint=len(trace))
        rep = gw.replay(trace)
        # configure_logging pins propagate=False on the "repro" root;
        # let records reach caplog's handler for this one assertion.
        root = logging.getLogger("repro")
        prev_propagate = root.propagate
        root.propagate = True
        try:
            with caplog.at_level(logging.INFO, logger="repro.serve.fleet"):
                events = rep.trace_events(max_requests=50)
        finally:
            root.propagate = prev_propagate
        svc = [e for e in events if e.get("cat") == "service"]
        assert len(svc) == 50
        assert any("truncat" in r.message for r in caplog.records)


# ---------------------------------------------------------------------------
# timeline gantt
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def solved_plan():
    from repro.core import Scheduler
    sched = Scheduler("xavier-agx")
    return sched.solve(sched.graphs(["vgg19", "resnet101"]), "latency",
                       solver="bb", max_transitions=2)


class TestTimeline:
    def test_plan_chrome_is_valid_and_annotated(self, solved_plan):
        doc = plan_chrome(solved_plan)
        assert validate_chrome(doc) == []
        assert doc["otherData"]["solver"] == "bb"
        assert doc["otherData"]["makespan_ms"] == pytest.approx(
            solved_plan.objective, rel=1e-6)
        cats = {e["cat"] for e in doc["traceEvents"] if e["ph"] == "X"}
        assert "compute" in cats or "contention" in cats
        tracks = {e["args"]["name"] for e in doc["traceEvents"]
                  if e["ph"] == "M"}
        assert tracks <= {"GPU", "DLA", "CPU"} and len(tracks) >= 2

    def test_interval_events_carry_slowdown(self, solved_plan):
        from repro.obs.timeline import _plan_result
        res = _plan_result(solved_plan)
        events = timeline_events(res, ["vgg19", "resnet101"])
        xs = [e for e in events if e["ph"] == "X"
              and e["cat"] in ("compute", "contention")]
        assert len(xs) == len(res.timeline)
        for e in xs:
            assert e["args"]["slowdown"] >= 1.0 or \
                e["cat"] == "compute"
        assert all(e["cat"] == "contention"
                   for e in xs if e["args"]["slowdown"] > 1.000001)

    def test_ascii_gantt_rows_cover_accelerators(self, solved_plan):
        text = plan_ascii(solved_plan, width=40)
        lines = text.splitlines()
        assert lines[0].startswith("gantt 0..")
        rows = [ln for ln in lines if "|" in ln]
        assert len(rows) >= 2                   # GPU + DLA
        assert any("#" in r or "▒" in r for r in rows)

    def test_chrome_and_ascii_agree_on_makespan(self, solved_plan):
        from repro.obs.timeline import _plan_result
        res = _plan_result(solved_plan)
        doc = timeline_chrome(res)
        last_end = max(e["ts"] + e["dur"]
                       for e in doc["traceEvents"] if e["ph"] == "X")
        assert last_end == pytest.approx(res.makespan * 1e3, rel=1e-6)
        assert f"{res.makespan:.2f}" in ascii_gantt(res).splitlines()[0]


# ---------------------------------------------------------------------------
# instrumented scheduler surfaces
# ---------------------------------------------------------------------------

class TestSchedulerInstrumentation:
    def test_resolve_spans_tag_cache_hit_and_miss(self):
        from repro.core import Scheduler
        tr = Tracer()
        set_tracer(tr)
        sched = Scheduler("xavier-agx")
        req = sched.request(["vgg19", "resnet101"], solver="bb",
                            max_transitions=1)
        sched.resolve(req)
        sched.resolve(req)
        spans = [e for e in tr.events()
                 if e["name"] == "scheduler.resolve"]
        assert [s["args"]["cache"] for s in spans] == ["miss", "hit"]
        assert spans[0]["args"]["solve_s"] > 0
        solver_spans = [e for e in tr.events()
                        if e["name"].startswith("solver.")]
        assert len(solver_spans) == 1
