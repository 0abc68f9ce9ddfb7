"""Fleet gateway benchmark: SLO-aware routing vs round-robin at scale.

Replays one seeded million-request bursty arrival trace (hundreds of
tenants, open loop) through the virtual-time fleet gateway
(:mod:`repro.serve.fleet`) over a pool of heterogeneous solved SoC plans,
once per routing policy:

* ``round_robin`` — static tenant-hash placement over the pool (the
  baseline a contention-unaware fleet would run);
* ``slo`` — earliest-predicted-finish routing + SLO admission
  (:class:`~repro.serve.fleet.slo.AdmissionController`).

Reported per policy: sustained completions/s, p50/p99 end-to-end latency,
shed fraction and SLO violations.  The artifact additionally records the
sharded-PlanCache cold-start check: a second ``build_pool`` over the same
platforms from the same on-disk cache must perform **zero** solver
invocations.

    PYTHONPATH=src python -m benchmarks.bench_gateway             # 1M
    PYTHONPATH=src python -m benchmarks.bench_gateway --requests 1000

The trace is seeded and the replay is virtual-time, so every number except
the wall-clock throughput of the replay loop itself is bit-deterministic.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import tempfile

from repro import configs
from repro.core.accelerators import tpu_pod_split
from repro.core.plan import ShardedPlanCache
from repro.serve.fleet import (FleetConfig, FleetGateway, SLO, build_pool,
                               bursty_trace)
from repro.serve.gateway import GatewayConfig, TenantSpec

from .common import emit, fmt_table, timed

DEFAULT_OUT = pathlib.Path(__file__).resolve().parents[1] / "BENCH_gateway.json"

#: pool pod splits — heterogeneous placements of the same tenant mix, so
#: per-class service times differ across plans and routing has a choice
#: that matters.
SPLITS = ((4, 12), (8, 8), (12, 4))
TENANTS = (("stablelm", "stablelm-1.6b"), ("llama", "llama3.2-3b"))
SLOTS = 8
N_FLEET_TENANTS = 500
SEED = 7
#: offered load ~ pool capacity: enough pressure that routing quality
#: shows in the tail without the run being pure shedding.
BASE_RPS, BURST_RPS = 150.0, 1200.0
SLO_P99_MS = 400.0


def _specs() -> list[TenantSpec]:
    # full-size configs: the fleet loop bills service from the solved
    # schedule and never instantiates the models.
    return [TenantSpec(n, configs.get(a), max_slots=4, capacity=256,
                       prompt_len=64, max_new=16)
            for n, a in TENANTS]


def _build_pool(cache_root: pathlib.Path):
    cache = ShardedPlanCache(cache_root)
    plats = [tpu_pod_split(a, b, name=f"v5e-{a}x{b}-split")
             for a, b in SPLITS]
    pool = build_pool(_specs(), plats, GatewayConfig(), cache, slots=SLOTS)
    return pool, sum(pp.scheduler.solves for pp in pool)


def run(n_requests: int, out_path: pathlib.Path) -> dict:
    trace = bursty_trace(BASE_RPS, BURST_RPS, n_requests,
                         n_tenants=N_FLEET_TENANTS, seed=SEED)
    with tempfile.TemporaryDirectory() as tmp:
        cache_root = pathlib.Path(tmp) / "plancache"
        with timed() as t_plan:
            pool, cold_solves = _build_pool(cache_root)
        # cold-start check: rebuilding the pool from the sharded disk
        # cache (fresh Schedulers, fresh in-memory caches) is pure loads.
        with timed() as t_boot:
            pool2, warm_solves = _build_pool(cache_root)
        del pool2
    assert warm_solves == 0, \
        f"sharded-cache boot performed {warm_solves} fresh solve(s)"

    rows = []
    for policy in ("round_robin", "slo"):
        cfg = FleetConfig(policy=policy,
                          default_slo=SLO(p99_ms=SLO_P99_MS))
        gw = FleetGateway(pool, n_tenants=N_FLEET_TENANTS, cfg=cfg,
                          capacity_hint=len(trace))
        with timed() as t:
            rep = gw.replay(trace)
        slo = rep.slo_report()
        rows.append({
            "policy": policy,
            "requests": rep.n_requests,
            "completed": rep.completed,
            "shed": rep.shed,
            "p50_ms": round(rep.p50_ms, 3),
            "p99_ms": round(rep.p99_ms, 3),
            "sustained_rps": round(rep.sustained_rps, 1),
            "slo_p99_violations": slo["p99_violations"],
            "served_tenants": slo["served_tenants"],
            "replay_s": round(t["s"], 3),
            "replay_req_per_s": round(rep.n_requests / t["s"], 1),
        })
        emit(f"bench_gateway.{policy}", t["us"],
             f"p99={rep.p99_ms:.1f}ms;completed={rep.completed};"
             f"shed={rep.shed};sustained={rep.sustained_rps:.1f}rps")

    rr = next(r for r in rows if r["policy"] == "round_robin")
    slo_row = next(r for r in rows if r["policy"] == "slo")
    assert slo_row["p99_ms"] < rr["p99_ms"], \
        (f"SLO routing must beat round-robin on p99: "
         f"{slo_row['p99_ms']} vs {rr['p99_ms']}")

    result = {
        "benchmark": "fleet_gateway",
        "splits": [list(s) for s in SPLITS],
        "tenant_mix": [a for _, a in TENANTS],
        "fleet_tenants": N_FLEET_TENANTS,
        "requests": n_requests,
        "seed": SEED,
        "trace_kind": "bursty",
        "trace_hash": trace.trace_hash()[:16],
        "base_rps": BASE_RPS,
        "burst_rps": BURST_RPS,
        "slo_p99_ms": SLO_P99_MS,
        "plan_cold_solves": cold_solves,
        "plan_cold_s": round(t_plan["s"], 3),
        "cache_boot_solves": warm_solves,
        "cache_boot_s": round(t_boot["s"], 3),
        "p99_speedup": round(rr["p99_ms"] / slo_row["p99_ms"], 2),
        "rows": rows,
    }
    out_path.write_text(json.dumps(result, indent=1) + "\n")

    print()
    print(fmt_table(
        ["policy", "completed", "shed", "p50", "p99", "sustained",
         "replay"],
        [[r["policy"], r["completed"], r["shed"],
          f"{r['p50_ms']:.1f}ms", f"{r['p99_ms']:.1f}ms",
          f"{r['sustained_rps']:.0f} req/s", f"{r['replay_s']:.2f}s"]
         for r in rows]))
    print(f"slo vs round-robin p99: {result['p99_speedup']}x better; "
          f"cache boot {result['cache_boot_s']}s, "
          f"{result['cache_boot_solves']} solves")
    print(f"wrote {out_path}")
    return result


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=1_000_000,
                    help="trace length (default: one million requests)")
    ap.add_argument("--out", type=pathlib.Path, default=DEFAULT_OUT)
    args = ap.parse_args(argv)
    return run(args.requests, args.out)


if __name__ == "__main__":
    from repro.core import xla_env
    xla_env.enable_compile_cache()
    main()
