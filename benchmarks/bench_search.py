"""Device-resident schedule-search throughput and solution quality.

Runs the ``anneal`` solver's compiled island search
(:mod:`repro.core.search_jax`) over the Table-8 pair spaces on AGX Orin
and reports:

* **throughput** — steady-state candidates/second of the annealing loop
  (mutation + full Eq. 2-8 timeline evaluation + Metropolis/incumbent
  selection per step), with jit compile time reported separately, per
  pair and aggregate.  ``speedup_vs_jax_eval`` relates the aggregate to
  the plain jit+vmap evaluator sweep recorded in ``BENCH_simulate.json``
  — the search adds mutation/selection work per candidate on top of
  evaluation, so parity-or-better here means the annealing machinery is
  effectively free.  Both loops are op-dispatch bound on a single-core
  CPU host; on an accelerator-backed deployment the same program scales
  with device parallelism instead.
* **quality** — per pair, the incumbent's scalar-re-simulated objective
  against the exact branch-and-bound optimum (``gap_rel``); plus the
  three golden Table-6 scenario shapes (concurrent pair, streaming
  pipeline, chain + third DNN) as an end-to-end ``anneal`` vs ``bb``
  solver comparison.

The search budget scales with each pair's exhaustive space size, so
small spaces are not over-sampled and large spaces are not starved.

``--device-sweep 1,2,4,8`` additionally measures the multi-device mesh
path (``shard_map`` fan-out + ring elite migration) at equal
*per-device* population.  On an accelerator every device count runs in
this process over its first N devices; on the CPU each count runs in a
fresh subprocess whose ``XLA_FLAGS`` emulate that many host devices
(:mod:`repro.core.xla_env`).
Every sweep point also re-runs a fixed-total-population search and
digests its incumbents — the digests must agree across device counts and
select-kernel backends (the determinism contract), and the scalar
re-simulated quality keeps its gap vs exact bb.  ``host_cores`` is
recorded because emulated devices time-share the host CPU: aggregate
scaling on a 1-core CI box is bounded by arithmetic intensity, not by
the fan-out (accelerator deployments scale with real device count).

Writes ``BENCH_search.json`` (repo root), guarded by
:mod:`benchmarks.schema_guard`; the README performance table quotes it
and the scheduled CI lane uploads it as an artifact.

    PYTHONPATH=src python -m benchmarks.bench_search [--pairs N]
    [--population P] [--repeats R] [--device-sweep 1,2,4,8] [--out PATH]
"""
from __future__ import annotations

import argparse
import hashlib
import itertools
import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np

from repro.core import Scheduler, search_jax, solver_anneal, xla_env
from repro.obs import Tracer, set_tracer
from repro.core.simulate import Workload, simulate
from repro.core.solver_bb import enumerate_assignments
from repro.core.profiles import DNN_SET

from .common import emit, fmt_table
from .table6_scenarios import EXPERIMENTS, build as build_scenario
from .table8_exhaustive import balanced_iterations

ROOT = pathlib.Path(__file__).resolve().parents[1]
DEFAULT_OUT = ROOT / "BENCH_search.json"

#: Table-6 experiments with golden bb plans (one per scenario shape).
SCENARIO_EXPS = (1, 4, 8)

#: fixed total population for the cross-device determinism digest: must
#: divide by island (32) x the largest swept device count.
DIGEST_POPULATION = 1024
DIGEST_STEPS = 24


def _best_of(fn, repeats: int) -> tuple[float, object]:
    """Min-of-N steady-state wall time + last result (the same protocol
    as bench_simulate, so the two artifacts compare symmetrically)."""
    best, out = float("inf"), None
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best, out


def _budget(space: int, population: int) -> int:
    """Annealing steps ∝ exhaustive-space size: ~4 evaluations per
    distinct candidate, clamped to a sane range (the stochastic search
    revisits states, so matching the exhaustive count would under-cover
    the space)."""
    return int(np.clip(round(4 * space / population), 48, 384))


def run_pairs(sched: Scheduler, pairs, population: int, seed: int,
              repeats: int) -> list[dict]:
    plat, model = sched.platform, sched.model
    rows = []
    for a, b in pairs:
        graphs = sched.graphs([a, b])
        its = balanced_iterations(plat, graphs)
        space = int(np.prod([len(enumerate_assignments(g, plat.names, 2))
                             for g in graphs]))
        tables = search_jax.build_tables(plat, graphs, model, 2,
                                         iterations=its)
        steps = _budget(space, population)
        kw = dict(objective="latency", seed=seed, population=population,
                  steps=steps)
        t0 = time.perf_counter()
        search_jax.anneal_search(tables, **kw)      # compile + run
        t_first = time.perf_counter() - t0
        t_search, out = _best_of(
            lambda: search_jax.anneal_search(tables, **kw), repeats)
        # compile attribution: an explicit AOT lower+compile of a fresh
        # executable, min-of-repeats — first_call_s - search_s is a
        # single sample and reads ~0 for every pair after the first in a
        # (w, gmax, amax) shape bucket (jit cache hit).  compile_seconds
        # measures internally (a "search.compile" trace span + the
        # search_compile_s gauge), so read the instrumented samples off
        # the tracer instead of re-timing the call from outside.
        tr = Tracer()
        prev = set_tracer(tr)
        try:
            for _ in range(max(1, repeats)):
                search_jax.compile_seconds(tables, objective="latency",
                                           population=population)
        finally:
            set_tracer(prev)
        t_compile = min(e["args"]["compile_s"] for e in tr.events()
                        if e["name"] == "search.compile")

        # scalar re-simulation is authoritative for the reported quality
        wls = [Workload(g, asg, iterations=it)
               for g, asg, it in zip(graphs, out.assignment, its)]
        obj = simulate(plat, wls, model,
                       record_timeline=False).objective("latency")
        bb = sched.solve(graphs, "latency", solver="bb", max_transitions=2,
                         iterations=its, evaluator="batch")
        gap = (obj - bb.objective) / abs(bb.objective)
        rows.append({
            "pair": [a, b], "iterations": its, "space": space,
            "population": out.population, "steps": out.steps,
            "evaluated": out.evaluated,
            "device_count": 1,
            "search_s": round(t_search, 4),
            "first_call_s": round(t_first, 4),
            "compile_s": round(t_compile, 4),
            "cands_per_s": round(out.evaluated / t_search, 1),
            "objective_ms": round(obj, 6),
            "bb_objective_ms": round(bb.objective, 6),
            "gap_rel": round(gap, 6),
        })
        print(f"  {a}+{b}: space={space} evaluated={out.evaluated} "
              f"{rows[-1]['cands_per_s']:.0f} cand/s "
              f"gap={gap:+.3%}")
    return rows


def run_scenarios(seed: int) -> list[dict]:
    """End-to-end solver comparison on the golden Table-6 shapes."""
    rows = []
    for no in SCENARIO_EXPS:
        plat_name, objective, spec, scenario, _pl, _pf = EXPERIMENTS[no]
        sched = Scheduler(plat_name)
        graphs, deps, its = build_scenario(sched.platform, spec, scenario)
        bb = sched.solve(graphs, objective, solver="bb", max_transitions=2,
                         iterations=its, depends_on=deps, evaluator="batch")
        t0 = time.perf_counter()
        sol = solver_anneal.solve(
            sched.platform, graphs, sched.model, objective=objective,
            max_transitions=2, iterations=its, depends_on=deps,
            seed=seed, population=1024, steps=192, evaluator="batch")
        t_anneal = time.perf_counter() - t0
        gap = (sol.objective - bb.objective) / abs(bb.objective)
        rows.append({
            "experiment": no, "platform": plat_name,
            "objective": objective, "scenario": scenario,
            "dnns": "+".join(str(s) for s in spec),
            "anneal_objective": round(sol.objective, 6),
            "bb_objective": round(bb.objective, 6),
            "gap_rel": round(gap, 6),
            "anneal_s": round(t_anneal, 4),
        })
        print(f"  exp{no} ({plat_name}, scenario {scenario}): "
              f"anneal={sol.objective:.4f} bb={bb.objective:.4f} "
              f"gap={gap:+.3%}")
    return rows


def _digest(out) -> str:
    """Content digest of a search incumbent (assignment + objective +
    winning chain): equal digests mean bit-identical outcomes."""
    blob = json.dumps([out.assignment, repr(out.objective), out.chain],
                      sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def sweep_worker(devices: int, per_device_population: int, seed: int,
                 n_pairs: int, steps: int, repeats: int) -> dict:
    """One device-sweep point, run inside a subprocess whose XLA_FLAGS
    emulate ``devices`` host devices.  Prints a single JSON dict."""
    avail = xla_env.device_count()
    if avail < devices:
        return {"devices": devices, "error":
                f"only {avail} device(s) visible (XLA_FLAGS not applied?)"}
    sched = Scheduler("agx-orin")
    plat, model = sched.platform, sched.model
    pairs = list(itertools.combinations(DNN_SET, 2))[:n_pairs]
    population = per_device_population * devices
    evaluated = 0
    wall = 0.0
    worst_gap = -np.inf
    for a, b in pairs:
        graphs = sched.graphs([a, b])
        its = balanced_iterations(plat, graphs)
        tables = search_jax.build_tables(plat, graphs, model, 2,
                                         iterations=its)
        kw = dict(objective="latency", seed=seed, population=population,
                  steps=steps, devices=devices)
        search_jax.anneal_search(tables, **kw)       # compile warm-up
        t, out = _best_of(
            lambda: search_jax.anneal_search(tables, **kw), repeats)
        evaluated += out.evaluated
        wall += t
        wls = [Workload(g, asg, iterations=it)
               for g, asg, it in zip(graphs, out.assignment, its)]
        obj = simulate(plat, wls, model,
                       record_timeline=False).objective("latency")
        bb = sched.solve(graphs, "latency", solver="bb", max_transitions=2,
                         iterations=its, evaluator="batch")
        worst_gap = max(worst_gap,
                        (obj - bb.objective) / abs(bb.objective))

    # determinism digest at a FIXED total population: must be identical
    # across device counts, select backends, and fan-outs.
    a, b = pairs[0]
    graphs = sched.graphs([a, b])
    its = balanced_iterations(plat, graphs)
    tables = search_jax.build_tables(plat, graphs, model, 2, iterations=its)
    dkw = dict(objective="latency", seed=seed,
               population=DIGEST_POPULATION, steps=DIGEST_STEPS,
               devices=devices)
    digest = _digest(search_jax.anneal_search(tables, **dkw))
    backend_ok = all(
        _digest(search_jax.anneal_search(tables, backend=bk, **dkw))
        == digest for bk in ("xla", "pallas_interpret"))
    fanout_ok = (devices == 1 or _digest(search_jax.anneal_search(
        tables, fanout="pmap", **dkw)) == digest)
    chunk_ok = True
    if devices == 1:
        # chunking exists only on the legacy (devices=None) path; its
        # incumbent must also match the mesh digest via migrate="island".
        leg = dict(dkw)
        leg.pop("devices")
        chunk_ok = (
            _digest(search_jax.anneal_search(tables, chunk=256, **leg))
            == _digest(search_jax.anneal_search(tables, chunk=1024, **leg)))
    return {
        "devices": devices,
        "per_device_population": per_device_population,
        "population": population,
        "steps": steps,
        "pairs": len(pairs),
        "evaluated": evaluated,
        "search_s": round(wall, 4),
        "cands_per_s": round(evaluated / wall, 1),
        "worst_gap_rel": round(float(worst_gap), 6),
        "digest": digest,
        "digest_backend_ok": bool(backend_ok),
        "digest_fanout_ok": bool(fanout_ok),
        "digest_chunk_ok": bool(chunk_ok),
    }


def run_device_sweep(device_counts, per_device_population: int, seed: int,
                     n_pairs: int, steps: int, repeats: int) -> list[dict]:
    """Run every sweep point.  On an accelerator all device counts run in
    this process over its first N devices: this process already holds the
    chips, so a child could not open them.  On the CPU each count runs in
    a subprocess, because the emulated-device flag is fixed at backend
    init."""
    points = []
    for d in sorted(device_counts):
        if not xla_env.runs_on_cpu():
            points.append(sweep_worker(d, per_device_population, seed,
                                       n_pairs, steps, repeats))
            continue
        cmd = [sys.executable, "-m", "benchmarks.bench_search",
               "--sweep-worker", str(d),
               "--sweep-per-dev", str(per_device_population),
               "--sweep-pairs", str(n_pairs),
               "--sweep-steps", str(steps),
               "--seed", str(seed), "--repeats", str(repeats)]
        env = xla_env.subprocess_env(d)
        env.setdefault("PYTHONPATH", "src")
        proc = subprocess.run(cmd, cwd=ROOT, env=env, text=True,
                              capture_output=True)
        if proc.returncode != 0:
            raise RuntimeError(
                f"device-sweep worker (devices={d}) failed:\n{proc.stderr}")
        point = json.loads(proc.stdout.strip().splitlines()[-1])
        points.append(point)
    for point in points:
        if "error" in point:
            raise RuntimeError(f"device-sweep point (devices="
                               f"{point['devices']}): {point['error']}")
        print(f"  devices={point['devices']}: {point['cands_per_s']:.0f} "
              f"cand/s (pop {point['population']}) digest="
              f"{point['digest']} gap={point['worst_gap_rel']:+.3%}")
    base = points[0]["cands_per_s"]
    for p in points:
        p["speedup_vs_1dev"] = round(p["cands_per_s"] / base, 3)
        p["digest_invariant"] = p["digest"] == points[0]["digest"]
    return points


def run(pairs_limit: int | None, population: int, seed: int,
        out_path: pathlib.Path, repeats: int = 2,
        device_sweep=None, sweep_per_dev: int = 1024,
        sweep_pairs: int = 2, sweep_steps: int = 64) -> dict:
    sched = Scheduler("agx-orin")
    pairs = list(itertools.combinations(DNN_SET, 2))
    if pairs_limit:
        pairs = pairs[:pairs_limit]
    print(f"Table-8 search sweep: {len(pairs)} pairs on agx-orin "
          f"(population={population}, budget ∝ space)")
    rows = run_pairs(sched, pairs, population, seed, repeats)
    print("Table-6 scenario quality (anneal vs bb):")
    scenarios = run_scenarios(seed)
    scaling = []
    if device_sweep:
        print(f"Device sweep ({sweep_per_dev} chains/device):")
        scaling = run_device_sweep(device_sweep, sweep_per_dev, seed,
                                   sweep_pairs, sweep_steps, repeats)

    total_eval = sum(r["evaluated"] for r in rows)
    total_wall = sum(r["search_s"] for r in rows)
    agg_cps = total_eval / total_wall
    worst_gap = max(r["gap_rel"] for r in rows + scenarios)

    jax_eval_cps = None
    sim_path = ROOT / "BENCH_simulate.json"
    if sim_path.exists():
        jax_eval_cps = json.loads(sim_path.read_text()).get(
            "jax_cands_per_s")

    result = {
        "benchmark": "device_resident_search",
        "platform": "agx-orin",
        "solver": "anneal",
        "max_transitions": 2,
        "pairs": len(rows),
        "population": population,
        "seed": seed,
        "repeats": max(1, repeats),
        "device_count": xla_env.device_count(),
        "host_cores": os.cpu_count(),
        "timing": "min over `repeats` steady-state runs per pair; "
                  "compile_s is an AOT lower+compile of a fresh "
                  "executable (min of repeats) — paid once per "
                  "(w, gmax, amax) shape bucket in real runs",
        "total_evaluated": total_eval,
        "search_cands_per_s": round(agg_cps, 1),
        #: plain-evaluator throughput from BENCH_simulate.json; the ratio
        #: is like-for-like on this host (both loops are op-dispatch
        #: bound on a single CPU core — accelerator deployments scale
        #: this with device parallelism).
        "jax_eval_cands_per_s": jax_eval_cps,
        "speedup_vs_jax_eval": (round(agg_cps / jax_eval_cps, 2)
                                if jax_eval_cps else None),
        "worst_gap_rel": round(worst_gap, 6),
        #: multi-device mesh scaling (one subprocess per emulated device
        #: count); empty unless --device-sweep is given.
        "scaling": scaling,
        "scenarios": scenarios,
        "rows": rows,
    }
    out_path.write_text(json.dumps(result, indent=1) + "\n")

    print(fmt_table(
        ["pairs", "evaluated", "cand/s", "vs jax eval", "worst gap"],
        [[len(rows), total_eval, f"{agg_cps:.0f}",
          (f"{result['speedup_vs_jax_eval']}x"
           if result["speedup_vs_jax_eval"] else "-"),
          f"{worst_gap:+.3%}"]]))
    print(f"wrote {out_path}")
    emit("bench_search.candidate_throughput", total_wall * 1e6,
         f"search_cps={agg_cps:.0f};evaluated={total_eval};"
         f"worst_gap={worst_gap:.4f}")
    return result


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--pairs", type=int, default=None,
                    help="limit the sweep to the first N pairs "
                         "(default: all 45)")
    ap.add_argument("--population", type=int, default=1024,
                    help="annealing chains per pair (default 1024)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeats", type=int, default=2,
                    help="steady-state runs per pair; min recorded")
    ap.add_argument("--device-sweep", type=str, default=None,
                    help="comma-separated device counts, e.g. 1,2,4,8 "
                         "— the first N accelerator devices, or on the CPU "
                         "a subprocess per count with "
                         "--xla_force_host_platform_device_count set")
    ap.add_argument("--sweep-per-dev", type=int, default=1024,
                    help="annealing chains per device in the sweep")
    ap.add_argument("--sweep-pairs", type=int, default=2,
                    help="Table-8 pairs timed per sweep point")
    ap.add_argument("--sweep-steps", type=int, default=64,
                    help="annealing steps per sweep-point search")
    ap.add_argument("--sweep-worker", type=int, default=None,
                    help=argparse.SUPPRESS)  # internal: one sweep point
    ap.add_argument("--out", type=pathlib.Path, default=DEFAULT_OUT)
    args = ap.parse_args(argv)
    if args.sweep_worker is not None:
        point = sweep_worker(args.sweep_worker, args.sweep_per_dev,
                             args.seed, args.sweep_pairs, args.sweep_steps,
                             args.repeats)
        print(json.dumps(point))
        return point
    sweep = ([int(s) for s in args.device_sweep.split(",")]
             if args.device_sweep else None)
    if sweep and sorted(sweep)[0] != 1:
        ap.error("--device-sweep must include 1 (the speedup baseline)")
    return run(args.pairs, args.population, args.seed, args.out,
               repeats=args.repeats, device_sweep=sweep,
               sweep_per_dev=args.sweep_per_dev,
               sweep_pairs=args.sweep_pairs, sweep_steps=args.sweep_steps)


if __name__ == "__main__":
    from repro.core import xla_env
    xla_env.enable_compile_cache()
    main()
