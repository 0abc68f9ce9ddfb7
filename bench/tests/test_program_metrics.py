"""The per-layer metrics read from the program's own timing: the host
work inside a plan's device search (``anneal_search`` minus its
``anneal.wait`` spans) and the admission record each served request
carries.  Both kinds run small on the CPU, traced, as in
``test_cells.py``."""
import argparse
import math

import pytest

import run


def _traced(bench_dir, cell):
    args = argparse.Namespace(workload=cell, seed=2**33 + 29, seconds=2.0,
                              trace=1, control=0, dump_trace=None)
    out = run.run(args, bench=bench_dir / "bench", require_tpu=False)
    assert out["correct"], out["checks"]
    return {n: m["value"] for n, m in out["metrics"].items()}


@pytest.fixture(scope="module")
def plan_metrics(bench_dir):
    return _traced(bench_dir, "plan.tiny")


@pytest.fixture(scope="module")
def serve_metrics(bench_dir):
    return _traced(bench_dir, "serve.tiny")


def test_search_host_is_part_of_the_search(plan_metrics):
    host = plan_metrics["search_host_ms.plan"]
    assert math.isfinite(host) and 0 < host <= plan_metrics["search_ms.plan"]


@pytest.mark.parametrize("name", ["admit_dispatch_ms", "admit_wait_ms",
                                  "admit_copy_gb"])
def test_admission_readers_are_finite(serve_metrics, name):
    assert math.isfinite(serve_metrics[name]) and serve_metrics[name] > 0


def test_admission_parts_fit_in_its_step(serve_metrics):
    """Dispatch and wait lie inside the admitting step's wall time less
    its decode, which ``admit_ms`` averages over the same admissions."""
    m = serve_metrics
    assert m["admit_dispatch_ms"] + m["admit_wait_ms"] <= m["admit_ms"]
