"""Observability overhead benchmark: tracing must be (nearly) free.

Replays one seeded bursty arrival trace through the virtual-time fleet
gateway (:mod:`repro.serve.fleet`) twice per repeat:

* ``disabled`` — the default :class:`~repro.obs.NullTracer` installed
  (every instrumentation point costs one attribute lookup);
* ``traced`` — a live :class:`~repro.obs.Tracer`, followed by the bulk
  per-request span export (``FleetGateway.export_trace``).

Gates (asserted, recorded in ``BENCH_obs.json``):

* replay overhead of enabled tracing < 3% wall-clock (min-of-repeats;
  asserted at >= ``GATE_MIN_REQUESTS`` requests — below that the replay
  is too short for the ratio to be meaningful, the number is recorded
  only);
* the disabled span path costs well under a microsecond per call;
* two identical virtual-clock replays export **byte-identical**
  Perfetto JSON (pinned on a load level with zero reschedules, so no
  wall-clock solver timings leak into span args);
* the exported trace is structurally valid Chrome-trace JSON.

    PYTHONPATH=src python -m benchmarks.bench_obs                 # 1M
    PYTHONPATH=src python -m benchmarks.bench_obs --requests 1000
"""
from __future__ import annotations

import argparse
import json
import pathlib
import tempfile
import time

from repro import configs
from repro.core.accelerators import tpu_pod_split
from repro.core.plan import ShardedPlanCache
from repro.obs import NULL_TRACER, Tracer, get_tracer, set_tracer
from repro.serve.fleet import (FleetConfig, FleetGateway, SLO, build_pool,
                               bursty_trace)
from repro.serve.gateway import GatewayConfig, TenantSpec

from .common import emit, fmt_table, timed

DEFAULT_OUT = pathlib.Path(__file__).resolve().parents[1] / "BENCH_obs.json"

SPLITS = ((4, 12), (8, 8), (12, 4))
TENANTS = (("stablelm", "stablelm-1.6b"), ("llama", "llama3.2-3b"))
N_FLEET_TENANTS = 500
SEED = 7
BASE_RPS, BURST_RPS = 150.0, 1200.0
SLO_P99_MS = 400.0
#: overhead is a ratio of wall times — below this many requests the
#: replay finishes in milliseconds and the ratio is dominated by noise.
GATE_MIN_REQUESTS = 100_000
OVERHEAD_GATE_PCT = 3.0
#: the disabled tracer must cost no more than this per span call.
DISABLED_GATE_NS = 1_000.0
#: determinism replay: gentle load so the fleet never re-solves (a
#: fresh solve stamps wall-clock ``solve_s`` into span args, which
#: byte-identity cannot survive).
DETERMINISM_REQUESTS = 5_000
DETERMINISM_BURST_RPS = 300.0


def _build_pool(cache_root: pathlib.Path):
    specs = [TenantSpec(n, configs.get(a), max_slots=4, capacity=256,
                        prompt_len=64, max_new=16)
             for n, a in TENANTS]
    plats = [tpu_pod_split(a, b, name=f"v5e-{a}x{b}-split")
             for a, b in SPLITS]
    return build_pool(specs, plats, GatewayConfig(),
                      ShardedPlanCache(cache_root), slots=8)


def _replay(pool, trace, tracer,
            slo_p99_ms: float = SLO_P99_MS) -> tuple[dict, "FleetGateway"]:
    prev = set_tracer(tracer)
    try:
        cfg = FleetConfig(policy="slo", default_slo=SLO(p99_ms=slo_p99_ms))
        gw = FleetGateway(pool, n_tenants=trace.n_tenants, cfg=cfg,
                          capacity_hint=len(trace))
        with timed() as t:
            rep = gw.replay(trace)
        return {"t": t, "rep": rep}, gw
    finally:
        set_tracer(prev)


def bench_disabled_span() -> float:
    """ns per ``get_tracer().span(...)`` call with the null tracer."""
    assert get_tracer() is NULL_TRACER
    n = 200_000
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            with get_tracer().span("noop", "bench", i=1):
                pass
        best = min(best, time.perf_counter() - t0)
    return best / n * 1e9


def validate_chrome(doc: dict) -> list[str]:
    """Structural problems with one Chrome-trace document ([] = valid)."""
    problems = []
    for key in ("traceEvents", "displayTimeUnit", "otherData"):
        if key not in doc:
            problems.append(f"missing top-level key {key!r}")
    for i, ev in enumerate(doc.get("traceEvents", [])):
        ph = ev.get("ph")
        if ph not in ("X", "i", "M", "C"):
            problems.append(f"traceEvents[{i}]: unknown ph {ph!r}")
            continue
        if "pid" not in ev or "name" not in ev:
            problems.append(f"traceEvents[{i}]: missing pid/name")
        if ph == "X" and not {"ts", "dur", "tid", "cat"} <= set(ev):
            problems.append(f"traceEvents[{i}]: X missing ts/dur/tid/cat")
        if ph == "i" and ev.get("s") != "t":
            problems.append(f"traceEvents[{i}]: instant missing s='t'")
    return problems


def run(n_requests: int, repeats: int, out_path: pathlib.Path) -> dict:
    trace = bursty_trace(BASE_RPS, BURST_RPS, n_requests,
                         n_tenants=N_FLEET_TENANTS, seed=SEED)
    with tempfile.TemporaryDirectory() as tmp:
        pool = _build_pool(pathlib.Path(tmp) / "plancache")

        disabled_ns = bench_disabled_span()
        assert disabled_ns < DISABLED_GATE_NS, \
            f"disabled span path costs {disabled_ns:.0f} ns/call"
        emit("bench_obs.disabled_span", disabled_ns / 1e3,
             f"ns_per_call={disabled_ns:.0f}")

        # warm-up: the first replay over a fresh pool re-solves on
        # monitor fires and mutates the shared pool plans — without it
        # the disabled arm would be measured against fresher state than
        # the traced arm ever sees.
        _replay(pool, trace, NULL_TRACER)

        base_s = traced_s = export_s = float("inf")
        events = spans = 0
        trace_bytes = 0
        for _ in range(repeats):
            out, _gw = _replay(pool, trace, NULL_TRACER)
            base_s = min(base_s, out["t"]["s"])

            tracer = Tracer()
            out, gw = _replay(pool, trace, tracer)
            traced_s = min(traced_s, out["t"]["s"])
            with timed() as t_exp:
                spans = gw.export_trace(tracer=tracer)
            export_s = min(export_s, t_exp["s"])
            events = len(tracer.events())
            trace_bytes = len(tracer.to_json()) + 1

        overhead_pct = (traced_s / base_s - 1.0) * 100.0
        gated = n_requests >= GATE_MIN_REQUESTS
        if gated:
            assert overhead_pct < OVERHEAD_GATE_PCT, \
                (f"enabled tracing adds {overhead_pct:.2f}% to the "
                 f"{n_requests}-request replay (gate {OVERHEAD_GATE_PCT}%)")

        doc = tracer.to_chrome()
        problems = validate_chrome(doc)
        assert not problems, f"invalid trace: {problems[:5]}"

        # byte-identity: two fresh gateways over the same pool, virtual
        # clock pinned, SLO relaxed so the fleet never re-solves (a
        # reschedule's fresh solve stamps wall-clock solve_s span args).
        dtrace = bursty_trace(BASE_RPS, DETERMINISM_BURST_RPS,
                              DETERMINISM_REQUESTS,
                              n_tenants=N_FLEET_TENANTS, seed=SEED)
        blobs = []
        for _ in range(2):
            tr = Tracer(clock=lambda: 0.0)
            out, gw = _replay(pool, dtrace, tr, slo_p99_ms=1e9)
            assert not out["rep"].reschedules, \
                "determinism replay re-solved despite the relaxed SLO"
            gw.export_trace(tracer=tr)
            blobs.append(tr.to_json())
        determinism_ok = blobs[0] == blobs[1]
        assert determinism_ok, "virtual-clock replays diverged byte-wise"

    rows = [
        {"mode": "disabled", "replay_s": round(base_s, 4),
         "replay_req_per_s": round(n_requests / base_s, 1),
         "events": 0, "exported_spans": 0},
        {"mode": "traced", "replay_s": round(traced_s, 4),
         "replay_req_per_s": round(n_requests / traced_s, 1),
         "events": events, "exported_spans": spans},
    ]
    emit("bench_obs.replay_disabled", base_s * 1e6,
         f"req_per_s={n_requests / base_s:.0f}")
    emit("bench_obs.replay_traced", traced_s * 1e6,
         f"overhead={overhead_pct:.2f}%;spans={spans}")

    result = {
        "benchmark": "obs_overhead",
        "requests": n_requests,
        "repeats": repeats,
        "seed": SEED,
        "trace_hash": trace.trace_hash()[:16],
        "disabled_ns_per_span": round(disabled_ns, 1),
        "replay_disabled_s": round(base_s, 4),
        "replay_traced_s": round(traced_s, 4),
        "overhead_pct": round(overhead_pct, 3),
        "overhead_gate_pct": OVERHEAD_GATE_PCT,
        "overhead_gated": gated,
        "export_s": round(export_s, 4),
        "exported_spans": spans,
        "trace_events": events,
        "trace_bytes": trace_bytes,
        "determinism_requests": DETERMINISM_REQUESTS,
        "determinism_ok": determinism_ok,
        "rows": rows,
    }
    out_path.write_text(json.dumps(result, indent=1) + "\n")

    print()
    print(fmt_table(
        ["mode", "replay", "req/s", "events", "spans"],
        [[r["mode"], f"{r['replay_s']:.3f}s",
          f"{r['replay_req_per_s']:.0f}", r["events"],
          r["exported_spans"]] for r in rows]))
    print(f"tracing overhead {overhead_pct:+.2f}% "
          f"({'gated' if gated else 'recorded only'}); disabled span "
          f"{disabled_ns:.0f} ns/call; export {export_s:.3f}s for "
          f"{spans} spans; determinism_ok={determinism_ok}")
    print(f"wrote {out_path}")
    return result


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=1_000_000,
                    help="trace length (default: one million requests)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", type=pathlib.Path, default=DEFAULT_OUT)
    args = ap.parse_args(argv)
    return run(args.requests, args.repeats, args.out)


if __name__ == "__main__":
    from repro.core import xla_env
    xla_env.enable_compile_cache()
    main()
