"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Single-model continuous-batching service over the registered config at
its published width (``--reduced`` serves the tiny smoke-test sibling
instead, for CPU runs, tests and CI); --co-arch plans HaX-CoNN concurrent
co-serving for full configs on the production pod split; --gateway additionally *serves* both models
concurrently through the contention-aware multi-tenant gateway (phase-aware
schedule, shared KV budget, dynamic re-scheduling).

Plan artifacts (pre-solve offline, boot cold with zero solver invocations):

    # pre-solve the gateway schedule and persist it
    python -m repro.launch.serve --gateway --arch A --co-arch B \
        --save-plan artifacts/plans/gw.json --plan-only
    # later / elsewhere: boot the gateway from the cached artifact
    python -m repro.launch.serve --gateway --arch A --co-arch B \
        --plan artifacts/plans/gw.json

Fleet mode (--fleet) replays a seeded arrival trace through the
virtual-time fleet gateway: thousands of open-loop tenants multiplexed
over a pool of solved SoC plans with SLO-aware admission and routing.

    # replay a generated bursty trace at 1k requests, SLO-routed
    python -m repro.launch.serve --fleet --arch A --co-arch B \
        --trace "bursty:base=150,burst=1500,n=1000,tenants=200,seed=7" \
        --slo "p99=400" --cache-root artifacts/plancache
    # second boot from the sharded cache performs zero solver invocations
    python -m repro.launch.serve --fleet ... --cache-root artifacts/plancache \
        --expect-cached
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro import configs
from repro.models import build
from repro.serve.engine import ServingEngine


def _with_obs(args, run) -> int:
    """Run one serving mode under the requested observability outputs.

    ``--trace-out`` installs a process-wide :class:`repro.obs.Tracer`
    before the run (solver spans, cache hits, gateway/fleet instants all
    land on it) and writes the Perfetto JSON afterwards — even when the
    run exits nonzero, so a failed boot still leaves its trace behind.
    ``--metrics-out`` snapshots the metrics registry the same way.
    """
    tracer = None
    if args.trace_out:
        from repro.obs import Tracer, set_tracer
        tracer = Tracer()
        set_tracer(tracer)
    try:
        return run(args)
    finally:
        if tracer is not None:
            tracer.write(args.trace_out)
            print(f"trace: {len(tracer.events())} events -> "
                  f"{args.trace_out} (open at https://ui.perfetto.dev)")
        if args.metrics_out:
            from repro.obs import get_registry
            get_registry().write(args.metrics_out)
            print(f"metrics: registry snapshot -> {args.metrics_out}")


def _solver_knobs(args) -> tuple:
    """--devices/--search-budget-ms as GatewayConfig.solver_knobs pairs."""
    knobs = {}
    if args.devices:
        knobs["devices"] = args.devices
    if args.search_budget_ms:
        knobs["budget_ms"] = args.search_budget_ms
    return tuple(sorted(knobs.items()))


def _run_gateway(args) -> int:
    from repro.core.accelerators import tpu_pod_split
    from repro.core.plan import Plan
    from repro.core.scheduler import Scheduler
    from repro.serve.gateway import (GatewayConfig, MultiTenantGateway,
                                     TenantSpec)
    archs = [args.arch, args.co_arch]
    specs = [TenantSpec(a, _config(a, args.reduced),
                        plan_cfg=configs.get(a), max_slots=4, capacity=96,
                        max_new=args.max_new)
             for a in archs]
    budget = (args.budget_slots * max(s.kv_bytes_per_slot for s in specs)
              if args.budget_slots else None)
    platform = tpu_pod_split(4, 12, name="v5e-4x12-split")
    model = None
    if args.profile_bundle:
        from repro.profiling import ProfileBundle
        bundle = ProfileBundle.load(args.profile_bundle)
        if len(bundle.platform.names) < 2:
            print(f"ERROR: profile bundle {args.profile_bundle} measured a "
                  f"single-accelerator platform; nothing to co-schedule")
            return 1
        platform, model = bundle.platform, bundle.model
        print(f"profile bundle {bundle.bundle_hash()[:12]}: planning on "
              f"measured platform {platform.name} with calibrated "
              f"{type(model).__name__}")
    gcfg = GatewayConfig(platform=platform, model=model,
                         memory_budget_bytes=budget, solver=args.solver,
                         solver_knobs=_solver_knobs(args))
    scheduler = Scheduler(gcfg.platform, gcfg.model,
                          evaluator=args.evaluator)
    if args.plan:
        loaded = Plan.load(args.plan)
        scheduler.cache.add(loaded)
        print(f"loaded plan {loaded.request_hash[:12]} "
              f"(solver={loaded.solver}, "
              f"solved offline in {loaded.solve_time_s:.3f}s)")

    if args.plan_only:
        from repro.serve.gateway import plan_gateway
        plan = plan_gateway(specs, gcfg, scheduler=scheduler)
    else:
        gw = MultiTenantGateway(specs, gcfg, scheduler=scheduler)
        plan = gw.plan

    if args.plan:
        if scheduler.solves:
            print("ERROR: plan artifact did not cover the request — "
                  f"{scheduler.solves} fresh solver invocation(s)")
            return 1
        print(f"plan cache hit: booted from {args.plan} with zero solver "
              f"invocations")
    if args.save_plan:
        path = plan.plan.save(args.save_plan)
        print(f"plan {plan.plan.request_hash[:12]} "
              f"(solver={plan.plan.solver}) saved to {path}")
    print(plan.summary())
    if args.plan_only:
        return 0

    rng = np.random.default_rng(0)
    for name, s in gw.specs.items():
        for _ in range(args.requests):
            gw.submit(name, rng.integers(0, s.cfg.vocab, size=8))
    done = gw.run_until_drained()
    for name, reqs in done.items():
        print(f"{name}: served {len(reqs)} requests, "
              f"{sum(len(r.tokens) for r in reqs)} tokens")
    print(f"gateway steps={gw.total_steps} "
          f"deferred={gw.deferred_admissions} "
          f"reschedules={len(gw.reschedules)}")
    return 0


def _run_fleet(args) -> int:
    from repro.core.accelerators import tpu_pod_split
    from repro.core.plan import ShardedPlanCache
    from repro.serve.fleet import (FleetConfig, FleetGateway, build_pool,
                                   parse_slo, parse_trace_spec)
    from repro.serve.gateway import GatewayConfig, TenantSpec

    trace = parse_trace_spec(args.trace)
    print(f"trace: kind={trace.kind} n={len(trace)} "
          f"tenants={trace.n_tenants} rate={trace.mean_rate_rps:.1f} req/s "
          f"burstiness={trace.burstiness():.2f} hash={trace.trace_hash()[:12]}")

    bundle = model = None
    if args.profile_bundle:
        from repro.profiling import ProfileBundle
        bundle = ProfileBundle.load(args.profile_bundle)
        model = bundle.model
        print(f"profile bundle {bundle.bundle_hash()[:12]}: pool plans "
              f"priced under calibrated {type(model).__name__}")

    # full-size configs: the fleet loop bills service from the solved
    # schedule's predictions and never builds the models, so planning the
    # production shapes costs nothing extra.
    specs = [TenantSpec(a, configs.get(a), max_slots=4, capacity=256,
                        prompt_len=64, max_new=args.max_new)
             for a in (args.arch, args.co_arch)]
    cache = ShardedPlanCache(args.cache_root) if args.cache_root else None
    splits = [(4, 12), (8, 8), (12, 4)]
    plats = [tpu_pod_split(a, b, name=f"v5e-{a}x{b}-split")
             for a, b in splits]
    budget = (args.budget_slots * max(s.kv_bytes_per_slot for s in specs)
              if args.budget_slots else None)
    pool = build_pool(specs, plats,
                      GatewayConfig(solver=args.solver, model=model,
                                    solver_knobs=_solver_knobs(args)),
                      cache, slots=8)
    solves = sum(pp.scheduler.solves for pp in pool)
    print(f"pool: {len(pool)} plans, {solves} solver invocation(s)")
    if args.expect_cached and solves:
        print(f"ERROR: --expect-cached but {solves} fresh solve(s) — the "
              f"sharded cache at {args.cache_root} did not cover the pool")
        return 1

    recal = None
    if args.recalibrate:
        from repro.profiling import StreamingRecalibrator
        recal = StreamingRecalibrator(
            bundle, window=args.recalibrate_window,
            min_new=args.recalibrate_min_new)
        print(f"closed-loop recalibration on: window="
              f"{args.recalibrate_window} min_new={args.recalibrate_min_new}")
    cfg = FleetConfig(policy=args.policy, default_slo=parse_slo(args.slo),
                      memory_budget_bytes=budget, throttle=args.throttle,
                      throttle_duty=args.throttle_duty)
    gw = FleetGateway(pool, n_tenants=trace.n_tenants, cfg=cfg,
                      capacity_hint=len(trace), recalibrator=recal)
    rep = gw.replay(trace)
    print(rep.summary())
    exported = gw.export_trace()
    if exported:
        print(f"trace: {exported} per-request queue/service spans exported")
    if recal is not None:
        head = recal.bundle
        print(f"recalibration: {recal.refits} re-fit(s) published, lineage "
              f"depth {len(recal.lineage)}, head {head.bundle_hash()[:12]} "
              f"(root {recal.lineage[0].bundle_hash()[:12]})")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True, choices=configs.ARCHS)
    ap.add_argument("--co-arch", default=None, choices=configs.ARCHS,
                    help="plan concurrent serving with a second model")
    ap.add_argument("--gateway", action="store_true",
                    help="serve --arch and --co-arch concurrently through "
                         "the multi-tenant gateway (requires --co-arch)")
    ap.add_argument("--budget-slots", type=int, default=0,
                    help="shared KV budget in slot units (0 = unlimited)")
    ap.add_argument("--reduced", action="store_true",
                    help="serve each model's tiny smoke-test sibling "
                         "(ModelConfig.reduced) instead of its published "
                         "width: the size for CPU runs, tests and CI")
    ap.add_argument("--shape", default="decode_32k")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--fleet", action="store_true",
                    help="replay an arrival trace through the virtual-time "
                         "fleet gateway (requires --co-arch and --trace)")
    ap.add_argument("--trace", default=None, metavar="SPEC|PATH",
                    help="arrival trace: a saved trace JSON path or a "
                         "generator spec like "
                         "'poisson:rate=200,n=1000,tenants=100,seed=0', "
                         "'bursty:base=100,burst=1000,n=5000,tenants=200' "
                         "or 'diurnal:peak=300,n=5000,tenants=500'")
    ap.add_argument("--slo", default="p99=1000", metavar="SPEC",
                    help="default tenant SLO, e.g. 'p99=400,rps=5'")
    ap.add_argument("--policy", default="slo",
                    choices=("slo", "round_robin"),
                    help="fleet routing policy (round_robin = baseline)")
    ap.add_argument("--cache-root", default=None, metavar="DIR",
                    help="sharded disk-backed plan cache root shared by "
                         "every pool scheduler; a re-run over the same pool "
                         "boots with zero solver invocations")
    ap.add_argument("--expect-cached", action="store_true",
                    help="fail unless the pool booted entirely from "
                         "--cache-root (zero fresh solves)")
    ap.add_argument("--plan", default=None, metavar="PATH",
                    help="boot the gateway from a serialized Plan artifact "
                         "(fails if the request is not covered: zero solver "
                         "invocations are asserted)")
    ap.add_argument("--save-plan", default=None, metavar="PATH",
                    help="serialize the solved gateway Plan to PATH")
    ap.add_argument("--plan-only", action="store_true",
                    help="plan (and optionally save) without serving")
    ap.add_argument("--profile-bundle", default=None, metavar="PATH",
                    help="plan from a measured ProfileBundle "
                         "(repro.launch.profile). With --gateway the "
                         "bundle's platform and calibrated contention model "
                         "replace the built-in pod split + default model; "
                         "with --fleet the calibrated model prices every "
                         "pool plan and seeds --recalibrate")
    ap.add_argument("--recalibrate", action="store_true",
                    help="fleet mode: stream completion telemetry into a "
                         "StreamingRecalibrator seeded from "
                         "--profile-bundle; published re-fits (versioned, "
                         "lineage-hashed) are adopted by every pool plan "
                         "at reschedule time")
    ap.add_argument("--recalibrate-window", type=int, default=256,
                    metavar="N", help="telemetry window size (live "
                         "samples) for streaming re-fits")
    ap.add_argument("--recalibrate-min-new", type=int, default=128,
                    metavar="N", help="fresh samples required between "
                         "consecutive re-fits")
    ap.add_argument("--throttle", action="store_true",
                    help="fleet mode: duty-cycle tenants whose SLOs still "
                         "cannot be met after re-solving (per-tenant "
                         "hysteresis, pressure-held release)")
    ap.add_argument("--throttle-duty", type=float, default=0.5,
                    metavar="F", help="fraction of a throttled tenant's "
                         "arrivals admitted (deterministic token bucket)")
    ap.add_argument("--solver", default="auto", metavar="NAME",
                    help="registry solver entry for any fresh gateway "
                         "solve: z3 | bb | greedy | anneal (device-resident "
                         "annealing over the lowered IR; requires jax) | "
                         "auto = best available by priority. Unknown names "
                         "fail listing the registered solvers.")
    ap.add_argument("--devices", type=int, default=None, metavar="N",
                    help="fan the anneal search over N devices "
                         "(shard_map mesh with ring elite migration): the "
                         "first N accelerator devices, or on the CPU N "
                         "emulated host devices "
                         "(--xla_force_host_platform_device_count, applied "
                         "before jax initializes); requires --solver anneal")
    ap.add_argument("--search-budget-ms", type=float, default=None,
                    metavar="MS",
                    help="wall-clock budget for each fresh anneal solve: "
                         "population/steps are auto-tuned from the problem "
                         "size, --devices, and measured search throughput "
                         "instead of fixed defaults; requires --solver "
                         "anneal")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON of the run "
                         "(solver spans, plan-cache hits, fleet "
                         "queue/service spans, reschedule/throttle/"
                         "recalibration instants) to PATH; open at "
                         "https://ui.perfetto.dev")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a JSON snapshot of the metrics registry "
                         "(counters/gauges/histograms) to PATH")
    ap.add_argument("--log-level", default="info",
                    choices=("debug", "info", "warning", "error"))
    ap.add_argument("--log-json", action="store_true",
                    help="emit one JSON object per log line instead of "
                         "plain text")
    ap.add_argument("--evaluator", default="auto", metavar="NAME",
                    help="candidate-schedule evaluator for any fresh solve: "
                         "a registered evaluator name (batch = vectorized "
                         "NumPy, jax = XLA jit+vmap over the lowered IR, "
                         "scalar = the authoritative simulator looped; "
                         "auto = best available, currently batch). Unknown "
                         "names fail listing the registered evaluators.")
    args = ap.parse_args(argv)

    from repro.obs import configure_logging
    configure_logging(args.log_level, json=args.log_json)

    if (args.devices or args.search_budget_ms) and args.solver != "anneal":
        ap.error("--devices/--search-budget-ms tune the device-resident "
                 "search; they require --solver anneal")
    if args.devices:
        # before any jax device use: the emulated-device-count flag is
        # read once, at backend initialization.
        from repro.core import xla_env
        xla_env.apply(devices=args.devices)

    if args.solver != "auto":
        from repro.core import registry
        try:
            sentry = registry.get_solver(args.solver)
        except KeyError as exc:       # UnknownEntryError: lists known names
            ap.error(str(exc))
        if not sentry.available():
            avail = [e.name for e in registry.auto_order()]
            ap.error(f"solver {args.solver!r} is registered but its "
                     f"backend is not available here (available: "
                     f"{', '.join(avail) or 'none'})")

    if args.evaluator != "auto":
        from repro.core import registry
        try:
            entry = registry.get_evaluator(args.evaluator)
        except KeyError as exc:       # UnknownEntryError: lists known names
            ap.error(str(exc))
        if not entry.available():
            avail = [e for e in registry.evaluator_names()
                     if registry.get_evaluator(e).available()]
            ap.error(f"evaluator {args.evaluator!r} is registered but its "
                     f"backend is not available here (available: "
                     f"{', '.join(avail) or 'none'})")

    if args.fleet:
        if not args.co_arch:
            ap.error("--fleet requires --co-arch")
        if not args.trace:
            ap.error("--fleet requires --trace")
        if args.expect_cached and not args.cache_root:
            ap.error("--expect-cached requires --cache-root")
        if args.recalibrate and not args.profile_bundle:
            ap.error("--recalibrate requires --profile-bundle (the offline "
                     "seed of the lineage chain)")
        return _with_obs(args, _run_fleet)
    for flag in ("trace", "cache_root", "recalibrate", "throttle"):
        if getattr(args, flag):
            ap.error(f"--{flag.replace('_', '-')} requires --fleet")

    if args.plan or args.save_plan or args.plan_only:
        if not args.gateway:
            ap.error("--plan/--save-plan/--plan-only require --gateway")
    if args.profile_bundle and not args.gateway:
        ap.error("--profile-bundle requires --gateway or --fleet")
    if args.gateway:
        if not args.co_arch:
            ap.error("--gateway requires --co-arch")
        if args.co_arch == args.arch:
            ap.error("--gateway needs two distinct models")
        for a in (args.arch, args.co_arch):
            if not configs.get(a).has_decode:
                ap.error(f"{a} is encoder-only: no decode service")
        return _with_obs(args, _run_gateway)

    if args.co_arch:
        return _with_obs(args, _run_concurrent)

    return _with_obs(args, _run_single)


def _run_concurrent(args) -> int:
    from repro.serve.concurrent import plan_concurrent_serving
    plan = plan_concurrent_serving(
        [configs.get(args.arch), configs.get(args.co_arch)],
        [args.shape, args.shape], objective="latency", deadline_s=20.0)
    print(plan.summary())
    return 0


def _config(arch: str, reduced: bool):
    cfg = configs.get(arch)
    return cfg.reduced() if reduced else cfg


def build_engine(arch: str, *, reduced: bool = False, backend: str = "auto",
                 params=None) -> ServingEngine:
    """The single-model service on 4 slots of 128 tokens: ``arch`` at its
    published width (or its reduced sibling) over seeded random
    parameters, unless ``params`` are given — e.g. one set shared by
    engines on different kernel backends."""
    model = build(_config(arch, reduced), backend=backend)
    if params is None:
        params = model.init(jax.random.PRNGKey(0))
    return ServingEngine(model, params, max_slots=4, capacity=128)


def submit_requests(eng: ServingEngine, n: int, *, max_new: int):
    """Submit ``n`` seeded random prompts of 8 to 64 tokens (uniform, as
    the fleet traffic generator draws them); returns the requests."""
    rng = np.random.default_rng(0)
    return [eng.submit(rng.integers(0, eng.model.cfg.vocab,
                                    size=int(rng.integers(8, 65))),
                       max_new=max_new)
            for _ in range(n)]


def _run_single(args) -> int:
    if not configs.get(args.arch).has_decode:
        print(f"{args.arch} is encoder-only: no decode service")
        return 1
    eng = build_engine(args.arch, reduced=args.reduced)
    submit_requests(eng, args.requests, max_new=args.max_new)
    done = eng.run_until_drained()
    print(f"served {len(done)} requests, "
          f"{sum(len(r.tokens) for r in done)} tokens, "
          f"{eng.steps} decode steps")
    return 0


if __name__ == "__main__":
    from repro.core import xla_env
    xla_env.enable_compile_cache()
    raise SystemExit(main())
