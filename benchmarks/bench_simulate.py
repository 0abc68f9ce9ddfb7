"""Candidate-evaluation throughput: batch/jax evaluators vs scalar simulator.

Reproduces the hot loop behind Table 8: for every unordered DNN pair of the
evaluation set on AGX Orin, enumerate the full exhaustive assignment
population (``max_transitions`` transitions per DNN, §5.4 iteration
balancing) and score every candidate schedule under the exact Eq. 2-8
timeline — through the scalar event-driven simulator (one timeline at a
time), the vectorized NumPy batch evaluator (the whole sweep as one
lockstep pass), and the XLA evaluator (:mod:`repro.core.simulate_jax`,
jit+vmap over the lowered :class:`~repro.core.lowering.ProblemSpec`).

Writes ``BENCH_simulate.json`` (repo root) with per-pair rows and the
aggregate candidates/second of all paths; the README performance table
quotes it, and CI uploads it as an artifact.  Every path records the
minimum over ``--repeats`` steady-state runs (the same protocol for the
scalar and vectorized paths), and the jax column records **jit compile
time separately from steady-state throughput**, so the Table-8 sweep
numbers stay honest: a one-shot solve pays the compile, a search loop
does not.  Agreement is asserted while
measuring — batch vs scalar to 1e-6, jax (float64) vs scalar to 1e-6 — so
the benchmark doubles as a coarse differential check.

    PYTHONPATH=src python -m benchmarks.bench_simulate [--pairs N]
    [--max-transitions T] [--out PATH] [--skip-jax]
"""
from __future__ import annotations

import argparse
import itertools
import json
import pathlib
import time

import numpy as np

from repro.core import Scheduler
from repro.core.lowering import lower_sweep
from repro.core.simulate import Workload, simulate
from repro.core.simulate_batch import simulate_spec
from repro.core.solver_bb import enumerate_assignments
from repro.core.profiles import DNN_SET

from .common import emit, fmt_table

from .table8_exhaustive import balanced_iterations

DEFAULT_OUT = pathlib.Path(__file__).resolve().parents[1] / \
    "BENCH_simulate.json"


def build_problems(sched: Scheduler, pairs, max_transitions: int):
    problems = []
    for a, b in pairs:
        graphs = sched.graphs([a, b])
        its = balanced_iterations(sched.platform, graphs)
        cands = [enumerate_assignments(g, sched.platform.names,
                                       max_transitions) for g in graphs]
        problems.append(((a, b), graphs, cands, its))
    return problems


def _best_of(fn, repeats: int) -> tuple[float, object]:
    """Steady-state wall time: min over ``repeats`` runs (the standard
    answer to scheduler/cache noise on shared boxes) + last result."""
    best, out = float("inf"), None
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best, out


def run(pairs_limit: int | None, max_transitions: int,
        out_path: pathlib.Path, skip_jax: bool = False,
        repeats: int = 3) -> dict:
    sched = Scheduler("agx-orin")
    plat, model = sched.platform, sched.model
    pairs = list(itertools.combinations(DNN_SET, 2))
    if pairs_limit:
        pairs = pairs[:pairs_limit]
    problems = build_problems(sched, pairs, max_transitions)
    sizes = [int(np.prod([len(c) for c in cands]))
             for _, _, cands, _ in problems]
    total = sum(sizes)
    print(f"Table-8 sweep: {len(problems)} pairs, {total} candidate "
          f"schedules (max_transitions={max_transitions})")

    # -- lowering: one ProblemSpec for the whole sweep (shared by both
    # vectorized paths; lowering cost is reported separately) -------------
    t0 = time.perf_counter()
    spec, slices = lower_sweep(
        plat,
        [(graphs, cands, its, None)
         for _pair, graphs, cands, its in problems],
        model, validate=False)
    t_lower = time.perf_counter() - t0

    # -- scalar path: one event-driven timeline per candidate.  Same
    # best-of-N protocol as the vectorized paths below, so the recorded
    # speedups compare steady states symmetrically (Workload construction
    # stays inside the timed loop: it is the scalar path's packing cost,
    # just as lowering — reported separately — is the vectorized paths').
    def scalar_sweep():
        makespans = []
        for _pair, graphs, cands, its in problems:
            for asgs in itertools.product(*cands):
                wls = [Workload(g, tuple(asg), iterations=it)
                       for g, asg, it in zip(graphs, asgs, its)]
                res = simulate(plat, wls, model, record_timeline=False)
                makespans.append(res.makespan)
        return np.asarray(makespans)

    t_scalar, scalar_makespans = _best_of(scalar_sweep, repeats)

    # -- batch path: the whole sweep in one lockstep NumPy pass -----------
    t_batch, bt = _best_of(lambda: simulate_spec(spec), repeats)

    diff = float(np.abs(bt.makespan - scalar_makespans).max())
    assert diff < 1e-6, f"batch/scalar disagreement: {diff}"

    # -- jax path: same spec through the XLA evaluator ---------------------
    jax_fields: dict = {}
    if not skip_jax:
        from repro.core import simulate_jax
        t0 = time.perf_counter()
        btj = simulate_jax.simulate_spec(spec)
        t_jax_first = time.perf_counter() - t0      # compile + run
        t_jax, btj = _best_of(                       # steady state
            lambda: simulate_jax.simulate_spec(spec), repeats)
        diff_jax = float(np.abs(btj.makespan - scalar_makespans).max())
        assert diff_jax < 1e-6, f"jax/scalar disagreement: {diff_jax}"
        jax_fields = {
            "jax_s": round(t_jax, 4),
            "jax_first_call_s": round(t_jax_first, 4),
            # compile time kept separate from steady-state throughput so
            # the sweep numbers stay honest (one-shot solves pay this once
            # per shape bucket; search loops do not).
            "jax_compile_s": round(max(0.0, t_jax_first - t_jax), 4),
            "jax_cands_per_s": round(total / t_jax, 1),
            "speedup_jax_vs_scalar": round(t_scalar / t_jax, 2),
            "speedup_jax_vs_batch": round(t_batch / t_jax, 2),
            "max_abs_makespan_diff_jax": diff_jax,
        }

    rows = []
    for (pair, _g, cands, its), size, sl in zip(problems, sizes, slices):
        rows.append({
            "pair": list(pair), "iterations": its,
            "candidates": size,
            "best_makespan_ms": float(bt.makespan[sl].min()),
        })
    result = {
        "benchmark": "table8_candidate_evaluation",
        "platform": "agx-orin",
        "max_transitions": max_transitions,
        "pairs": len(problems),
        "candidates": total,
        #: every path reports min-of-N steady-state wall time; one-time
        #: costs (lowering, jit compile) are separate fields.
        "repeats": max(1, repeats),
        "timing": "min over `repeats` runs per path; lowering_s (shared "
                  "by batch/jax) and jax compile time reported separately",
        "lowering_s": round(t_lower, 4),
        "scalar_s": round(t_scalar, 4),
        "batch_s": round(t_batch, 4),
        "scalar_cands_per_s": round(total / t_scalar, 1),
        "batch_cands_per_s": round(total / t_batch, 1),
        "speedup": round(t_scalar / t_batch, 2),
        "max_abs_makespan_diff": diff,
        **jax_fields,
        "rows": rows,
    }
    out_path.write_text(json.dumps(result, indent=1) + "\n")

    table_rows = [
        ["scalar", f"{t_scalar:.2f}", f"{total / t_scalar:.0f}", "-"],
        ["batch", f"{t_batch:.2f}", f"{total / t_batch:.0f}", "-"],
    ]
    if jax_fields:
        table_rows.append(["jax", f"{jax_fields['jax_s']:.2f}",
                           f"{jax_fields['jax_cands_per_s']:.0f}",
                           f"{jax_fields['jax_compile_s']:.2f}"])
    print(fmt_table(["path", "wall s", "candidates/s", "compile s"],
                    table_rows))
    print(f"batch speedup: {result['speedup']}x "
          f"(max |makespan diff| = {diff:.2e})")
    if jax_fields:
        print(f"jax speedup: {jax_fields['speedup_jax_vs_scalar']}x vs "
              f"scalar, {jax_fields['speedup_jax_vs_batch']}x vs batch "
              f"(max |makespan diff| = "
              f"{jax_fields['max_abs_makespan_diff_jax']:.2e})")
    print(f"wrote {out_path}")
    emit("bench_simulate.candidate_throughput", t_batch * 1e6,
         f"speedup={result['speedup']}x;candidates={total};"
         f"batch_cps={result['batch_cands_per_s']:.0f}")
    if jax_fields:
        emit("bench_simulate.jax_candidate_throughput",
             jax_fields["jax_s"] * 1e6,
             f"jax_cps={jax_fields['jax_cands_per_s']:.0f};"
             f"compile_s={jax_fields['jax_compile_s']}")
    return result


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--pairs", type=int, default=None,
                    help="limit the sweep to the first N pairs (default: "
                         "all 45)")
    ap.add_argument("--max-transitions", type=int, default=2,
                    help="transition budget per DNN for the candidate "
                         "population (default 2)")
    ap.add_argument("--out", type=pathlib.Path, default=DEFAULT_OUT)
    ap.add_argument("--skip-jax", action="store_true",
                    help="measure only the scalar/batch paths")
    ap.add_argument("--repeats", type=int, default=3,
                    help="steady-state runs per path (scalar included — "
                         "the dominant ~50s leg — as well as batch/jax); "
                         "the minimum is recorded (default 3)")
    args = ap.parse_args(argv)
    return run(args.pairs, args.max_transitions, args.out,
               skip_jax=args.skip_jax, repeats=args.repeats)


if __name__ == "__main__":
    from repro.core import xla_env
    xla_env.enable_compile_cache()
    main()
