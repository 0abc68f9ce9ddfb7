"""Benchmark driver: one module per paper table/figure + roofline report.

Prints ``name,us_per_call,derived`` CSV rows per benchmark (interleaved with
human-readable tables) and persists all row dicts to
``artifacts/bench_results.json`` for EXPERIMENTS.md generation.

Usage:
    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run table6 fig7  # subset
"""
from __future__ import annotations

import json
import pathlib
import sys
import time
import traceback

from repro.obs import configure_logging, get_logger

ARTIFACTS = pathlib.Path(__file__).resolve().parent.parent / "artifacts"

log = get_logger(__name__)

SUITES = [
    "table2_characterization",
    "table5_standalone",
    "table6_scenarios",
    "table7_overhead",
    "table8_exhaustive",
    "fig5_scenario1",
    "fig6_contention",
    "fig7_dynamic",
    "serve_gateway",
]


def main(argv: list[str] | None = None) -> int:
    args = argv if argv is not None else sys.argv[1:]
    configure_logging("info")
    selected = [s for s in SUITES if not args or any(a in s for a in args)]
    ARTIFACTS.mkdir(exist_ok=True)
    results: dict[str, object] = {}
    failures: list[str] = []
    print("name,us_per_call,derived")
    for name in selected:
        mod_name = f"benchmarks.{name}"
        t0 = time.perf_counter()
        try:
            mod = __import__(mod_name, fromlist=["main"])
            results[name] = mod.main()
        except Exception:
            failures.append(name)
            log.error("[FAIL] %s:\n%s", mod_name, traceback.format_exc())
        print(f"# {name} finished in {time.perf_counter() - t0:.1f}s\n")
    out = ARTIFACTS / "bench_results.json"
    out.write_text(json.dumps(results, indent=1, default=str))
    print(f"# results -> {out}")
    if failures:
        print(f"# FAILURES: {failures}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    from repro.core import xla_env
    xla_env.enable_compile_cache()
    raise SystemExit(main())
