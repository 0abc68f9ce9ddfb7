"""Tiny cells of both kinds, added to a copy of the benchmark by data
alone (a configuration, a traffic mix and ``BENCHMARK.json`` entries),
run end to end on the CPU: set-up, window, trace reduction, metric
readers and the check against the plain reference.  Then the control and
each fault the cells can have must come out not correct."""
import argparse
import dataclasses

import pytest

import run


def _args(cell, trace=0, control=0, seed=2**33 + 17, seconds=2.0):
    return argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=trace, control=control, dump_trace=None)


def _run(bench_dir, *a, fault=None, **kw):
    return run.run(_args(*a, **kw), bench=bench_dir / "bench",
                   require_tpu=False, fault=fault)


@pytest.mark.parametrize("cell", ["plan.tiny", "serve.tiny"])
def test_cell_runs_and_is_correct(bench_dir, cell):
    out = _run(bench_dir, cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    names = {"plan.tiny": {"plan_ms", "setup_s"},
             "serve.tiny": {"tbt_p95_ms", "setup_s"}}
    assert set(out["metrics"]) == names[cell]
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell", ["plan.tiny", "serve.tiny"])
def test_traced_run_reports_layers(bench_dir, cell):
    out = _run(bench_dir, cell, trace=1)
    assert out["correct"], out["checks"]
    want = {"plan.tiny": {"host_ms.plan", "search_ms.plan", "compiles.plan"},
            "serve.tiny": {"admit_ms", "decode_step_ms", "mfu.prefill",
                           "mfu.decode", "compiles.serve"}}[cell]
    assert want <= set(out["metrics"])
    assert out["metrics"][f"compiles.{cell.split('.')[0]}"]["value"] == 0
    assert "window_s" in out["device"] and "breakdown" in out


@pytest.mark.parametrize("cell", ["plan.tiny", "serve.tiny"])
def test_control_is_not_correct(bench_dir, cell):
    out = _run(bench_dir, cell, control=1)
    assert not out["correct"], out["checks"]


def _alter_token(drv):
    """Serve: every request's third token is replaced where the engine
    produces it, and fed back as the engine would."""
    eng = drv.engine
    step = eng.step

    def altered():
        n = step()
        for slot, req in enumerate(eng.slots):
            if req is not None and len(req.tokens) == 3:
                tok = (req.tokens[-1] + 1) % eng.model.cfg.vocab
                req.tokens[-1] = tok
                eng.last_tok[slot] = tok
        return n

    eng.step = altered


def _alter_plan(drv):
    """Plan: the solver's answer has one group moved to the other
    accelerator, with the objective it claims left as it was."""
    sched = drv.sched
    resolve = sched.resolve

    def altered(req, **kw):
        plan = resolve(req, **kw)
        wl = plan.solution.workloads[0]
        flip = {"GPU": "DLA", "DLA": "GPU"}
        asg = (flip[wl.assignment[0]],) + wl.assignment[1:]
        plan.solution.workloads[0] = dataclasses.replace(wl, assignment=asg)
        return plan

    sched.resolve = altered


def _alter_search(drv):
    """Plan: the device search reports its incumbent's objective 1% off."""
    inner = drv.search

    def altered(*a, **kw):
        out = inner(*a, **kw)
        return dataclasses.replace(out, objective=out.objective * 1.01)

    drv.search = altered


@pytest.mark.parametrize("cell,fault", [("serve.tiny", _alter_token),
                                        ("plan.tiny", _alter_plan),
                                        ("plan.tiny", _alter_search)],
                         ids=["token_altered", "plan_altered", "search_objective_altered"])
def test_fault_is_not_correct(bench_dir, cell, fault):
    from repro.core import search_jax
    keep = search_jax.anneal_search
    try:
        out = _run(bench_dir, cell, fault=fault)
    finally:
        search_jax.anneal_search = keep
    assert not out["correct"], out["checks"]
