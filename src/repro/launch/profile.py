"""Profiling launcher: ``python -m repro.launch.profile [...]``.

Runs the measured characterize → calibrate → bundle pipeline
(:mod:`repro.profiling`) and writes a content-hashed ``ProfileBundle``
artifact that :class:`~repro.core.scheduler.Scheduler` (and
``repro.launch.serve --profile-bundle``) can solve from directly.

Two executors:

* ``--executor virtual`` (default, CI-safe): the deterministic virtual
  SoC — ground-truth paper profiles + a generating contention model with
  seeded measurement noise.  With ``--solve`` the bundle is solved and
  compared against the plan under the generating model, closing the loop.

      PYTHONPATH=src python -m repro.launch.profile --platform xavier-agx \\
          --dnns vgg19 resnet101 --out artifacts/profiles/xavier.json --solve

* ``--executor jax``: real measurement on the local JAX backend — layer
  groups built from a registered model config run under the harness
  timing discipline, and the contention model is calibrated from genuine
  co-runs of the streaming antagonist (:mod:`repro.profiling.probes`)
  against itself at swept duty cycles.

      PYTHONPATH=src python -m repro.launch.profile --executor jax \\
          --arch stablelm-1.6b --seq 256 --batch 2 --out /tmp/cpu.json
"""
from __future__ import annotations

import argparse

from repro.core.accelerators import PLATFORMS


def _parse_levels(text: str) -> list[float]:
    try:
        levels = [float(x) for x in text.split(",") if x.strip()]
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"--ext-levels must be comma-separated floats, got {text!r}")
    if not levels or any(x <= 0 for x in levels):
        raise argparse.ArgumentTypeError("--ext-levels must be positive")
    return levels


def _virtual_bundle(args, timer):
    from repro import profiling
    from repro.core.contention import ProportionalShareModel
    from repro.core.profiles import get_graph

    platform = PLATFORMS[args.platform]()
    graphs = [get_graph(d, platform) for d in args.dnns]
    true_model = (ProportionalShareModel(capacity=1.0, sensitivity=3.0)
                  if args.true_model == "proportional"
                  else profiling.paper_like_pccs())
    vsoc = profiling.VirtualSoC(
        platform, graphs, true_model, noise=args.noise,
        outlier_rate=args.outlier_rate, seed=args.seed)
    bundle = profiling.run_pipeline(
        vsoc, timer=timer, ext_levels=args.ext_levels, fit_kind=args.fit)
    return bundle, vsoc


def _jax_bundle(args, timer):
    from repro import configs, profiling
    from repro.configs.base import ShapeCell
    from repro.profiling import probes

    cfg = configs.get(args.arch).reduced()
    cell = ShapeCell(f"{args.kind}_{args.seq}", args.seq, args.batch,
                     args.kind)
    platform = PLATFORMS[args.platform]()
    print(f"measuring {cfg.name} layer groups on the local JAX backend ...")
    measured = profiling.measure_arch(cfg, cell, backend=args.backend,
                                      timer=timer,
                                      max_groups=args.max_groups)
    for mg in measured:
        m = mg.measurement
        print(f"  {m.name}: {m.median_ms:.3f} ms "
              f"(n={len(m.kept_ms)}/{m.n_total}, std={m.std_ms:.3f})")
    graph = profiling.graph_from_measurements(
        f"{args.arch}:{cell.name}", platform, measured)

    print("calibrating from streaming-antagonist co-runs ...")
    usable_levels = [e for e in args.ext_levels if e <= 1.0]
    if not usable_levels:
        raise SystemExit(
            f"--executor jax sweeps the antagonist by duty cycle, so "
            f"every --ext-levels entry must be <= 1.0 (got "
            f"{args.ext_levels})")
    peak = probes.measure_peak_bandwidth(backend=args.backend, timer=timer)
    x, y = probes.make_buffers(8.0)
    base = profiling.measure_wallclock(
        lambda: probes.stream_once(x, y, backend=args.backend), timer=timer)
    own = min(1.0, (probes.stream_bytes(x)
                    / (base.median_ms * 1e-3)) / peak)
    samples = []
    for ext in usable_levels:
        with probes.MemoryProbe(demand=ext, backend=args.backend):
            co = profiling.measure_wallclock(
                lambda: probes.stream_once(x, y, backend=args.backend),
                timer=timer)
        samples.append((own, float(ext),
                        max(1.0, co.median_ms / base.median_ms)))
    result = profiling.fit(samples, args.fit)
    print(f"  peak={peak / 1e9:.2f} GB/s  {result.summary()}")
    bundle = profiling.ProfileBundle(
        platform=platform, graphs=(graph,), model=result.model,
        samples=tuple(samples),
        provenance={"executor": "jax-harness", "arch": args.arch,
                    "cell": cell.name, "backend": args.backend,
                    "timer": timer.to_dict(),
                    "peak_stream_bytes_per_s": peak,
                    "fit_kind": args.fit,
                    "fit": result.report.to_dict(),
                    **profiling.harness.local_device_provenance()})
    return bundle, None


def _measure_search_throughput(args, bundle):
    """Record this host's measured anneal-search candidates/s in the
    bundle provenance, so later ``budget_ms`` solves from the artifact
    skip the live probe.  Skipped quietly when the bundle's contention
    model has no lowerable surface (the search itself would refuse too).
    """
    import dataclasses

    from repro.core import solver_anneal
    try:
        cps = solver_anneal.measure_search_throughput(
            bundle.platform, list(bundle.graphs), bundle.model,
            max_transitions=args.max_transitions, devices=args.devices)
    except (ValueError, RuntimeError) as exc:
        print(f"(search-throughput probe skipped: {exc})")
        return bundle
    print(f"measured anneal-search throughput: {cps:,.0f} candidates/s"
          + (f" on {args.devices} device(s)" if args.devices else ""))
    prov = {**bundle.provenance, "search_cands_per_s": float(cps)}
    if args.devices:
        prov["search_devices"] = int(args.devices)
    return dataclasses.replace(bundle, provenance=prov)


def _anneal_knobs(args, bundle) -> dict:
    knobs = {}
    if args.solver != "anneal":
        return knobs
    if args.devices:
        knobs["devices"] = args.devices
    if args.search_budget_ms:
        knobs["budget_ms"] = args.search_budget_ms
        cps = bundle.provenance.get("search_cands_per_s")
        if cps:
            knobs["cands_per_s"] = float(cps)
    return knobs


def _solve_from_bundle(args, bundle, vsoc) -> int:
    from repro import profiling

    sched = profiling.scheduler_from_bundle(bundle)
    if len(bundle.platform.names) < 2:
        print("(platform has one accelerator: nothing to co-schedule)")
        return 0
    knobs = _anneal_knobs(args, bundle)
    plan = sched.solve(list(bundle.graphs), args.objective,
                       solver=args.solver,
                       max_transitions=args.max_transitions,
                       deadline_s=20.0, solver_knobs=knobs)
    print("solved from measured bundle:")
    print(plan.summary())
    if args.trace_out:
        from repro.obs import timeline
        print(timeline.plan_ascii(plan))
        path = timeline.write_chrome(timeline.plan_chrome(plan),
                                     args.trace_out)
        print(f"timeline: schedule gantt -> {path} "
              f"(open at https://ui.perfetto.dev)")
    if vsoc is not None:
        from repro.core import Scheduler
        truth_model = next(iter(vsoc.models.values()))
        truth = Scheduler(vsoc.platform, model=truth_model).solve(
            list(vsoc.graphs.values()), args.objective, solver=args.solver,
            max_transitions=args.max_transitions, deadline_s=20.0,
            solver_knobs=knobs)
        rel = (abs(plan.objective - truth.objective)
               / max(abs(truth.objective), 1e-12))
        print(f"generating-model objective={truth.objective:.4f}  "
              f"measured-bundle objective={plan.objective:.4f}  "
              f"rel-diff={rel:.2%}")
        if rel > args.solve_tolerance:
            print(f"ERROR: objective deviates more than "
                  f"{args.solve_tolerance:.0%} from the generating model")
            return 1
    return 0


def main(argv=None) -> int:
    from repro.profiling import ProfileBundle, TimerConfig

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--executor", choices=("virtual", "jax"),
                    default="virtual")
    ap.add_argument("--platform", default="xavier-agx",
                    choices=sorted(PLATFORMS))
    ap.add_argument("--dnns", nargs="+", default=["vgg19", "resnet101"],
                    help="paper-profile DNNs to characterize (virtual)")
    ap.add_argument("--true-model", default="piecewise",
                    choices=("piecewise", "proportional"),
                    help="generating contention model of the virtual SoC")
    ap.add_argument("--noise", type=float, default=0.003,
                    help="relative timing-noise sigma of the virtual SoC")
    ap.add_argument("--outlier-rate", type=float, default=0.05,
                    help="probability of a preemption-style timing outlier")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--arch", default="stablelm-1.6b",
                    help="model config measured by --executor jax")
    ap.add_argument("--kind", default="prefill",
                    choices=("prefill", "decode"))
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--backend", default="auto",
                    help="kernel backend (auto|xla|pallas|pallas_interpret)")
    ap.add_argument("--max-groups", type=int, default=None,
                    help="cap measured groups (jax executor)")
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--repeats", type=int, default=7)
    ap.add_argument("--ext-levels", type=_parse_levels,
                    default=[0.15, 0.3, 0.45, 0.6, 0.75, 0.9, 1.05],
                    metavar="F,F,...",
                    help="antagonist demand sweep (fractions of capacity)")
    ap.add_argument("--fit", default=None,
                    choices=("piecewise", "proportional"),
                    help="model class to calibrate (default: piecewise for "
                         "virtual, proportional for jax)")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="bundle path (default artifacts/profiles/"
                         "<platform-or-arch>.json)")
    ap.add_argument("--solve", action="store_true",
                    help="solve a schedule from the bundle; with the "
                         "virtual executor also compare against the "
                         "generating-model plan")
    ap.add_argument("--objective", default="latency")
    ap.add_argument("--solver", default="auto")
    ap.add_argument("--max-transitions", type=int, default=2)
    ap.add_argument("--solve-tolerance", type=float, default=0.05,
                    help="max generating-vs-measured objective deviation")
    ap.add_argument("--devices", type=int, default=None, metavar="N",
                    help="fan --solver anneal solves over N devices: "
                         "the first N accelerator devices, or on the CPU N "
                         "emulated host devices "
                         "(--xla_force_host_platform_device_count, applied "
                         "before jax initializes)")
    ap.add_argument("--search-budget-ms", type=float, default=None,
                    metavar="MS",
                    help="wall-clock budget per anneal solve: population/"
                         "steps auto-tune from the bundle-measured search "
                         "throughput (recorded in provenance as "
                         "search_cands_per_s); requires --solver anneal")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="with --solve: write the solved schedule as a "
                         "per-accelerator Gantt in Chrome-trace/Perfetto "
                         "JSON (contention intervals and transitions "
                         "annotated) and print its ASCII rendering; open "
                         "at https://ui.perfetto.dev")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a JSON snapshot of the metrics registry "
                         "(solver counters, search_compile_s, ...) to PATH")
    ap.add_argument("--log-level", default="info",
                    choices=("debug", "info", "warning", "error"))
    ap.add_argument("--log-json", action="store_true",
                    help="emit one JSON object per log line instead of "
                         "plain text")
    args = ap.parse_args(argv)

    from repro.obs import configure_logging
    configure_logging(args.log_level, json=args.log_json)
    if args.trace_out and not args.solve:
        ap.error("--trace-out renders the solved schedule; it requires "
                 "--solve")

    if (args.devices or args.search_budget_ms) and args.solver != "anneal":
        ap.error("--devices/--search-budget-ms tune the device-resident "
                 "search; they require --solver anneal")
    if args.devices:
        from repro.core import xla_env
        xla_env.apply(devices=args.devices)

    if args.fit is None:
        args.fit = "piecewise" if args.executor == "virtual" \
            else "proportional"
    timer = TimerConfig(warmup=args.warmup, repeats=args.repeats)
    if args.executor == "virtual":
        bundle, vsoc = _virtual_bundle(args, timer)
        default_out = f"artifacts/profiles/{args.platform}.json"
    else:
        bundle, vsoc = _jax_bundle(args, timer)
        default_out = f"artifacts/profiles/{args.arch}.json"

    if args.solver == "anneal" and len(bundle.platform.names) >= 2:
        bundle = _measure_search_throughput(args, bundle)

    path = bundle.save(args.out or default_out)
    # reload immediately: the tamper check re-verifies the content hash,
    # so a bundle that cannot round-trip never ships.
    reloaded = ProfileBundle.load(path)
    assert reloaded.bundle_hash() == bundle.bundle_hash()
    print(bundle.summary())
    print(f"bundle {bundle.bundle_hash()[:12]} saved to {path} "
          f"(round-trip verified)")

    rc = 0
    if args.solve:
        rc = _solve_from_bundle(args, bundle, vsoc)
    if args.metrics_out:
        from repro.obs import get_registry
        get_registry().write(args.metrics_out)
        print(f"metrics: registry snapshot -> {args.metrics_out}")
    return rc


if __name__ == "__main__":
    from repro.core import xla_env
    xla_env.enable_compile_cache()
    raise SystemExit(main())
