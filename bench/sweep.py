"""Find the highest arrival rate a serving cell sustains: one set-up, then
one window per rate, on the chip.

    python bench/sweep.py --workload serve.stablelm.chat --seed 7 \\
        --seconds 30 --rates 1.0,1.5,2.0,2.5

Each window offers the cell's traffic mix at one rate (open loop) and
stops at its close, with no drain, so a backlog shows.  Per rate it
prints the tokens completed per second, time-to-first-token of the first
and the last third of the requests, and the requests still waiting for
a first token at the close.  A rate is sustained while the last third's
TTFT stays near the first third's and nothing piles up.  The cell's
traffic file then records four fifths of the highest such rate.  Not run
by the benchmark itself.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
import run  # noqa: E402,F401  (sets the import paths)
import harness  # noqa: E402
import generate  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]

    cell = harness.load_cell(args.workload)
    if cell.kind != "serve":
        raise SystemExit("the sweep is for serving cells")
    import os
    cache = str(BENCH.parent / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    harness.devices(cell.chips)
    kind = harness.load_module(BENCH / "kinds" / "serve.py", "bench_kind_serve")
    # set-up at the highest rate warms every prompt length any rate uses
    cell.traffic = dict(cell.traffic, rate_per_s=max(rates), drain_s=0.0)
    drv = kind.Driver(cell, args.seed, args.seconds, harness.Tracing(False, 0), False)
    drv.setup()
    for rate in rates:
        drv.mix = dict(cell.traffic, rate_per_s=rate)
        drv.reqs = [kind.ReqRecord(r["due"], r["prompt"], r["max_new"])
                    for r in generate.serve_requests(
                        drv.mix, args.seed, args.seconds,
                        vocab=drv.cfg["vocab_size"], capacity=drv.engine.capacity)]
        drv.window()
        eng = drv.engine
        third = max(1, len(drv.reqs) // 3)
        ttft = [(r.times[0] - r.due) * 1e3 if r.times else float("inf")
                for r in drv.reqs]
        row = {
            "rate_per_s": rate, "requests": len(drv.reqs),
            "out_tok_per_s": drv.end_to_end()["out_tok_per_s"],
            "ttft_p50_first_third_ms": harness.percentile(ttft[:third], 50),
            "ttft_p50_last_third_ms": harness.percentile(ttft[-third:], 50),
            "waiting_at_close": sum(not r.times for r in drv.reqs),
            "decode_step_ms": drv.decode_ms / max(1, drv.decode_steps),
        }
        print(json.dumps(row), flush=True)
        # empty the engine before the next rate
        eng.queue.clear()
        for slot in range(eng.max_slots):
            eng.slots[slot] = None
    return 0


if __name__ == "__main__":
    sys.exit(main())
