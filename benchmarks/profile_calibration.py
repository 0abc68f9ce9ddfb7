"""Calibration-quality benchmark: fit residuals on the virtual SoC.

Runs the measured characterize → calibrate pipeline against both
generating contention-model classes on two SoC platforms and records, per
scenario: fit residuals (rmse / max relative error vs the *training*
samples), agreement with the *generating* model across the sampled
(own, external) grid, pipeline wall time, and the end-to-end objective
deviation of a Table-6-style solve from the measured bundle vs the plan
under the generating model.

Writes ``BENCH_profile.json`` (repo root); CI's scheduled lane uploads it
and the schema guard (:mod:`benchmarks.schema_guard`) pins its columns.

    PYTHONPATH=src python -m benchmarks.profile_calibration [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

from repro import profiling
from repro.core import Scheduler
from repro.core.accelerators import PLATFORMS
from repro.core.contention import ProportionalShareModel
from repro.core.profiles import get_graph

from .common import emit, fmt_table

DEFAULT_OUT = pathlib.Path(__file__).resolve().parents[1] / \
    "BENCH_profile.json"

SCENARIOS = (
    # (platform, dnns, generating model kind, fit kind)
    ("xavier-agx", ("vgg19", "resnet101"), "piecewise", "piecewise"),
    ("xavier-agx", ("vgg19", "resnet101"), "proportional", "proportional"),
    ("agx-orin", ("inception", "resnet152"), "piecewise", "piecewise"),
)


def run_scenario(platform_name: str, dnns: tuple[str, ...],
                 true_kind: str, fit_kind: str, seed: int = 0) -> dict:
    platform = PLATFORMS[platform_name]()
    graphs = [get_graph(d, platform) for d in dnns]
    true_model = (ProportionalShareModel(capacity=1.0, sensitivity=3.0)
                  if true_kind == "proportional"
                  else profiling.paper_like_pccs())
    vsoc = profiling.VirtualSoC(platform, graphs, true_model, noise=0.003,
                                outlier_rate=0.05, seed=seed)
    t0 = time.perf_counter()
    bundle = profiling.run_pipeline(vsoc, fit_kind=fit_kind)
    pipeline_s = time.perf_counter() - t0

    fit = bundle.provenance["fit"]
    vs_truth = max(
        abs(bundle.model.slowdown(o, e) - vsoc.true_slowdown("GPU", o, e))
        / vsoc.true_slowdown("GPU", o, e)
        for o, e, _ in bundle.samples)

    plan = profiling.scheduler_from_bundle(bundle).solve(
        list(bundle.graphs), "latency", max_transitions=2, deadline_s=20.0)
    truth_plan = Scheduler(platform, model=true_model).solve(
        graphs, "latency", max_transitions=2, deadline_s=20.0)
    obj_rel = (abs(plan.objective - truth_plan.objective)
               / abs(truth_plan.objective))
    return {
        "platform": platform_name,
        "dnns": list(dnns),
        "generating_model": true_kind,
        "fit_kind": fit_kind,
        "n_samples": fit["n_samples"],
        "fit_rmse": fit["rmse"],
        "fit_max_rel_err": fit["max_rel_err"],
        "max_rel_err_vs_generating": vs_truth,
        "objective_rel_diff": obj_rel,
        "bundle_hash": bundle.bundle_hash(),
        "pipeline_s": round(pipeline_s, 4),
    }


def run(out_path: pathlib.Path) -> dict:
    rows = [run_scenario(*s) for s in SCENARIOS]
    data = {
        "benchmark": "profile_calibration",
        "timing": "one pipeline run per scenario (virtual SoC, seed 0)",
        "worst_fit_max_rel_err": max(r["fit_max_rel_err"] for r in rows),
        "worst_vs_generating": max(r["max_rel_err_vs_generating"]
                                   for r in rows),
        "worst_objective_rel_diff": max(r["objective_rel_diff"]
                                        for r in rows),
        "rows": rows,
    }
    out_path.write_text(json.dumps(data, indent=1))
    for r in rows:
        emit(f"profile_calibration.{r['platform']}.{r['generating_model']}",
             r["pipeline_s"] * 1e6,
             f"fit_max_rel={r['fit_max_rel_err']:.4f} "
             f"vs_gen={r['max_rel_err_vs_generating']:.4f} "
             f"obj_rel={r['objective_rel_diff']:.4f}")
    print(fmt_table(
        ["platform", "model", "samples", "fit rmse", "fit max-rel",
         "vs generating", "objective diff", "time"],
        [[r["platform"], r["generating_model"], r["n_samples"],
          f"{r['fit_rmse']:.4f}", f"{r['fit_max_rel_err']:.2%}",
          f"{r['max_rel_err_vs_generating']:.2%}",
          f"{r['objective_rel_diff']:.2%}", f"{r['pipeline_s']:.2f}s"]
         for r in rows]))
    print(f"wrote {out_path}")
    return data


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", type=pathlib.Path, default=DEFAULT_OUT)
    args = ap.parse_args(argv)
    data = run(args.out)
    # the acceptance gate: calibration must stay within 5% of the
    # generating model — fail the build if it drifts.
    if data["worst_vs_generating"] > 0.05:
        print(f"ERROR: calibration deviates "
              f"{data['worst_vs_generating']:.2%} (> 5%) from the "
              f"generating model")
        return 1
    return 0


if __name__ == "__main__":
    from repro.core import xla_env
    xla_env.enable_compile_cache()
    raise SystemExit(main())
