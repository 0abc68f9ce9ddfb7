"""Run one benchmark cell once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are found by the
names in ``BENCHMARK.json``.  Set-up (JAX start, weights or tables, the
warm-up of every program the window uses, from the persistent
compilation cache in ``.jax_cache/`` of the checkout) is timed as
``setup_s``; the window then runs for ``--seconds``; the check against
the plain reference runs after it, once the program's state is freed.
``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
records a profiler trace of the window and reports its per-layer
metrics.  The last line of standard output is one JSON object; the
numbers compared and their limits are the last lines of standard error.

``--control 1`` runs the cell's control, a step down in precision where
the cell's kind puts it (the planning search's tables in bfloat16; the
served tokens chosen by an int8 reference), and the check must then come
out not correct.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
if (ROOT / "src").is_dir():
    sys.path.insert(0, str(ROOT / "src"))
import harness  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", default=None,
                    help="also write the reduced trace as JSON to this path")
    return ap.parse_args(argv)


def run(args, *, bench=BENCH, require_tpu: bool = True, fault=None) -> dict:
    """One run of one cell of the benchmark in directory ``bench``;
    returns what ``harness.emit`` prints.  Tests pass ``require_tpu=False``
    to run on the CPU, and ``fault(driver)`` to break the timed path after
    set-up."""
    cell = harness.load_cell(args.workload, bench)
    kind = harness.load_module(bench / "kinds" / f"{cell.kind}.py",
                               f"bench_kind_{cell.kind}")
    readers = {m["name"]: harness.load_module(
        bench / "metrics" / f"{m['name']}.py", f"bench_metric_{i}")
        for i, m in enumerate(cell.per_layer)} if args.trace else {}

    # the persistent compilation cache lives at a fixed path of the
    # checkout; the program takes it from this variable (core/xla_env)
    cache = str(bench.parent / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = harness.devices(cell.chips, require_tpu)
    peak = harness.peak_of(cell, devs[0])
    compiles = harness.CompileCounter()

    tracing = harness.Tracing(bool(args.trace),
                              min(args.seconds, cell.traffic.get("trace_seconds",
                                                                 args.seconds)))
    drv = kind.Driver(cell, args.seed, args.seconds, tracing, bool(args.control))
    drv.setup()
    if fault is not None:
        fault(drv)
    setup_s = time.perf_counter() - T_START
    print(f"setup: {setup_s:.3f}s; executables {compiles.count('setup')} "
          f"({compiles.cache_hits} of {compiles.cache_requests} cache lookups "
          f"hit, cache {cache})", file=sys.stderr)

    compiles.phase = "window"
    drv.window()
    compiles.phase = "after"
    trace = tracing.read(len(devs)) if args.trace else None
    if trace is not None and args.dump_trace:
        import json
        pathlib.Path(args.dump_trace).write_text(json.dumps(trace))
    mem = harness.memory_peak_bytes(devs)
    attempted, failed = drv.attempted_failed()

    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if args.trace:
        import tracefile
        ctx = dict(drv.layer_context(), trace=trace, peak=peak,
                   compiles=compiles.count("window"), cell=cell)
        values = {n: r.read(ctx) for n, r in readers.items()}
        metrics = {n: {"value": v, "unit": units[n]}
                   for n, v in values.items() if v is not None}
        breakdown = tracefile.breakdown(trace)
    else:
        e2e = dict(drv.end_to_end(), setup_s=setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
        breakdown = None
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    if trace is not None:
        device.update(busy_s=tracefile.busy_s(trace),
                      window_s=tracefile.window_s(trace))
    print(f"window: {attempted} attempted, {failed} failed; executables "
          f"compiled or loaded inside it: {compiles.count('window')}",
          file=sys.stderr)

    drv.free()
    checks = drv.checks()
    correct = failed == 0 and all(c["value"] <= c["limit"] for c in checks.values())
    return dict(correct=correct, attempted=attempted, failed=failed,
                metrics=metrics, device=device, checks=checks,
                breakdown=breakdown)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        out = run(args)
    except harness.BenchError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    harness.emit(**out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
