"""Proof that both device paths run on a TPU, through the repo's own entry
points, with random weights and generated problems.

    python chip_smoke.py              # one chip: planning + serving
    python chip_smoke.py --chips 4    # four chips: the search mesh only

One chip runs two phases:

* planning — ``Scheduler("agx-orin").solve(..., solver="anneal")`` at the
  default population (2,048 chains, which takes the Pallas select kernel
  on TPU) on a Table-8 pair and a Table-6 scenario, each held to the exact
  branch-and-bound objective within the gap ``tests/test_search.py``
  allows; then every baseline scored once with ``evaluator="jax"`` and
  held to the scalar simulator within the differential suite's tolerance;
* serving — ``ServingEngine`` over stablelm-1.6b at its published width,
  built by ``repro.launch.serve``'s single-model path: 8 requests with
  prompts of 8-64 tokens and 16 new tokens each on 4 slots of 128 tokens,
  then the prefill and first decode logits of the first four requests
  held to the same parameters served with ``backend="xla"``.

``--chips 4`` runs only ``anneal_search`` on four chips against one chip
at equal total population and requires identical incumbents.

Earlier lines report the device, the compile cache, each kernel's backend
and each phase's seconds; the last line is a JSON object naming the
device.  Any failed check or error exits nonzero; there is no CPU
fallback.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import re
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from repro.core import xla_env  # noqa: E402

#: the anneal objective may exceed exact bb by this fraction of |bb| — the
#: gap tests/test_search.py allows on the Table-6 and Table-8 problems.
BB_GAP = 0.02
#: jax-evaluator vs scalar-simulator agreement (ms), as in
#: tests/test_simulate_differential.py (JAX_TOL).
EVAL_TOL = 1e-5
#: Pallas vs XLA serving logits: both paths run bf16 activations through
#: 24 layers and differ only in the attention kernels, so logits may
#: differ by bf16 rounding carried through the stack.  The largest
#: difference must stay within this fraction of the largest |logit|.
LOGIT_TOL = 0.05
ARCH = "stablelm-1.6b"


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def dispatched() -> dict[str, dict[str, int]]:
    """kernel -> backend -> calls traced so far (the kernel_dispatch
    metric of repro.kernels.ops.resolve)."""
    from repro.obs import get_registry
    snap = get_registry().snapshot().get("repro_kernel_dispatch", {})
    out: dict[str, dict[str, int]] = {}
    for labels, n in snap.get("series", {}).items():
        kv = dict(re.findall(r'(\w+)="([^"]*)"', labels))
        out.setdefault(kv["kernel"], {})[kv["backend"]] = int(n)
    return out


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# planning
# ---------------------------------------------------------------------------

def planning_problems(sched):
    from benchmarks.table6_scenarios import EXPERIMENTS
    from benchmarks.table6_scenarios import build as build_scenario
    from benchmarks.table8_exhaustive import balanced_iterations
    graphs = sched.graphs(["vgg19", "inception"])
    yield ("table8 vgg19+inception", "latency", graphs,
           balanced_iterations(sched.platform, graphs), None)
    plat, objective, spec, scenario, _, _ = EXPERIMENTS[7]
    check(plat == sched.platform.name, f"Table-6 exp 7 runs on {plat}")
    graphs, deps, its = build_scenario(sched.platform, spec, scenario)
    yield ("table6 exp7 googlenet->resnet101 stream", objective, graphs,
           its, deps)


def planning() -> None:
    from repro.core import Scheduler
    from repro.core.scheduler import failed
    sched = Scheduler("agx-orin")
    for name, objective, graphs, its, deps in planning_problems(sched):
        kw = dict(max_transitions=2, iterations=its, depends_on=deps)
        # a fresh Scheduler has an empty plan cache: the second solve
        # re-runs the search on the executable the first one compiled.
        plan, first_s = timed(lambda: Scheduler("agx-orin").solve(
            graphs, objective, solver="anneal", **kw))
        again, run_s = timed(lambda: Scheduler("agx-orin").solve(
            graphs, objective, solver="anneal", **kw))
        bb, bb_s = timed(lambda: sched.solve(graphs, objective, solver="bb",
                                             **kw))
        gap = (plan.objective - bb.objective) / abs(bb.objective)
        params = plan.solution.params
        print(f"plan[{name}]: anneal {plan.objective:.6f} (population "
              f"{params['population']}, steps {params['steps']}) vs bb "
              f"{bb.objective:.6f}: gap {gap:+.4%} (limit "
              f"{BB_GAP:.0%}); first solve {first_s:.2f}s (compile + run), "
              f"run {run_s:.2f}s, bb {bb_s:.2f}s")
        check(plan.objective <= bb.objective + BB_GAP * abs(bb.objective),
              f"{name}: anneal objective outside the bb gap")
        check(again.objective == plan.objective,
              f"{name}: the same seeded search gave another objective")

        rows, eval_s = timed(lambda: sched.evaluate_baselines(
            graphs, iterations=its, depends_on=deps, evaluator="jax"))
        worst = 0.0
        for base, res in rows.items():
            if failed(res):
                continue
            _, ref = sched.evaluate_baseline(base, graphs, iterations=its,
                                             depends_on=deps)
            diff = max(abs(res.makespan - ref.makespan), *(
                abs(a - b) for a, b in zip(res.finish_times,
                                           ref.finish_times)))
            check(diff <= EVAL_TOL, f"{name}: baseline {base} jax vs scalar "
                                    f"differs by {diff:.3g} ms")
            worst = max(worst, diff)
        scored = sum(not failed(r) for r in rows.values())
        check(scored > 0, f"{name}: no baseline could be scored")
        print(f"plan[{name}]: {scored} baselines scored by evaluator=jax in "
              f"{eval_s:.2f}s (compile + run); worst |jax - scalar| "
              f"{worst:.3g} ms (limit {EVAL_TOL:g})")


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def serving(*, reduced: bool = False) -> None:
    import numpy as np
    from repro.launch import serve

    def recorder(store, keep):
        def on_logits(kind, logits):
            if len(store) < keep:
                store.append((kind, np.asarray(logits, np.float32)))
        return on_logits

    eng, init_s = timed(lambda: serve.build_engine(ARCH, reduced=reduced))
    cfg = eng.model.cfg
    print(f"serve: {cfg.name} {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads, vocab {cfg.vocab}; random parameters in "
          f"{init_s:.2f}s")
    # the first admission wave: 4 prefills, then the first decode step
    rec_p: list = []
    eng.on_logits = recorder(rec_p, 5)
    reqs = serve.submit_requests(eng, 8, max_new=16)
    lens = [len(r.prompt) for r in reqs]
    done, first_s = timed(eng.run_until_drained)
    eng.on_logits = None
    check(len(done) == 8, f"served {len(done)} of 8 requests")
    for r in done:
        check(len(r.tokens) == 16 and all(0 <= t < cfg.vocab
                                          for t in r.tokens),
              f"request {r.rid}: bad tokens {r.tokens}")
    # the same prompts again: every shape is compiled now
    for r in reqs:
        eng.submit(r.prompt, max_new=16)
    done2, run_s = timed(eng.run_until_drained)
    check(len(done2) == 16, "second pass did not drain")
    print(f"serve: 8 requests (prompts {min(lens)}-{max(lens)} tokens, 16 "
          f"new each) on {eng.max_slots} slots of {eng.capacity} tokens: "
          f"first pass {first_s:.2f}s (compile + run), second pass "
          f"{run_s:.2f}s, {eng.steps} decode steps in all")

    ref = serve.build_engine(ARCH, reduced=reduced, backend="xla",
                             params=eng.params)
    rec_x: list = []
    ref.on_logits = recorder(rec_x, 5)
    for r in reqs[:4]:
        ref.submit(r.prompt, max_new=16)
    _, ref_s = timed(ref.step)
    check([k for k, _ in rec_p] == ["prefill"] * 4 + ["decode"]
          and [k for k, _ in rec_x] == ["prefill"] * 4 + ["decode"],
          "logits were not observed in admission order")
    pre_p = np.stack([lg[0, -1] for _, lg in rec_p[:4]])
    pre_x = np.stack([lg[0, -1] for _, lg in rec_x[:4]])
    d_pre = float(np.abs(pre_p - pre_x).max())
    scale_pre = float(np.abs(pre_x).max())
    tok_p, tok_x = pre_p.argmax(-1), pre_x.argmax(-1)
    agree = tok_p == tok_x
    # the decode step is fed each path's own first token: compare the
    # slots where those agree
    dec_p, dec_x = rec_p[4][1][:, 0], rec_x[4][1][:, 0]
    d_dec = float(np.abs(dec_p - dec_x)[agree].max()) if agree.any() else 0.0
    scale_dec = float(np.abs(dec_x).max())
    top2 = np.sort(pre_x, axis=-1)[:, -2:]
    margin = top2[:, 1] - top2[:, 0]
    print(f"serve: pallas vs xla (xla reference step {ref_s:.2f}s): "
          f"prefill max|dlogit| {d_pre:.4g} of max|logit| {scale_pre:.4g}; "
          f"first decode max|dlogit| {d_dec:.4g} of {scale_dec:.4g} "
          f"(limit {LOGIT_TOL:g} x max|logit|); first tokens agree "
          f"{int(agree.sum())}/4")
    check(d_pre <= LOGIT_TOL * scale_pre, "prefill logits beyond tolerance")
    check(d_dec <= LOGIT_TOL * scale_dec, "decode logits beyond tolerance")
    # a first token may differ only where the reference's top two logits
    # are closer than the tolerance allows the paths to differ
    check(bool((agree | (margin <= 2 * LOGIT_TOL * scale_pre)).all()),
          f"first tokens disagree beyond a near-tie: {tok_p} vs {tok_x}")


# ---------------------------------------------------------------------------
# four chips: the search mesh
# ---------------------------------------------------------------------------

def mesh(devices: int) -> None:
    from benchmarks.table8_exhaustive import balanced_iterations
    from repro.core import Scheduler, search_jax
    sched = Scheduler("agx-orin")
    graphs = sched.graphs(["vgg19", "inception"])
    tables = search_jax.build_tables(
        sched.platform, graphs, sched.model, 2,
        iterations=balanced_iterations(sched.platform, graphs))
    kw = dict(objective="latency", seed=0, population=2048, steps=192)
    outs = {}
    for d in (1, devices):
        _, first_s = timed(lambda: search_jax.anneal_search(
            tables, devices=d, **kw))
        outs[d], run_s = timed(lambda: search_jax.anneal_search(
            tables, devices=d, **kw))
        o = outs[d]
        print(f"mesh[{d} chip(s)]: objective {o.objective!r} chain "
              f"{o.chain} fanout {o.fanout} migrate {o.migrate}; first "
              f"call {first_s:.2f}s (compile + run), run {run_s:.2f}s, "
              f"{o.evaluated / run_s:.0f} candidates/s")
    key = {d: (o.assignment, o.objective, o.chain) for d, o in outs.items()}
    check(key[1] == key[devices],
          f"incumbents differ between 1 and {devices} chips: {key}")
    print(f"mesh: incumbents identical at 1 and {devices} chips")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the search mesh on four chips "
                         "against one")
    args = ap.parse_args(argv)

    cache = xla_env.enable_compile_cache()
    import jax
    devs = jax.devices()
    dev = devs[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; "
          f"compile cache: {cache}")
    check(dev.platform == "tpu", f"no TPU: jax runs on {dev.platform}")
    check(len(devs) >= args.chips,
          f"{args.chips} chips asked for, {len(devs)} present")

    phases = [("mesh", lambda: mesh(args.chips))] if args.chips > 1 else [
        ("planning", planning), ("serving", serving)]
    for name, phase in phases:
        _, secs = timed(phase)
        print(f"phase {name}: ok in {secs:.2f}s")

    kernels = dispatched()
    for kernel, backends in sorted(kernels.items()):
        print(f"kernel {kernel}: " + ", ".join(
            f"{b} x{n}" for b, n in sorted(backends.items())))
    need = (["anneal_select"] if args.chips > 1 else
            ["anneal_select", "attention", "decode_attention"])
    for kernel in need:
        check(kernels.get(kernel, {}).get("pallas", 0) > 0,
              f"{kernel} never took the pallas backend")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
