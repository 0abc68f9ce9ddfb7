"""Search-correctness harness for the device-resident annealer.

Four layers of defense around :mod:`repro.core.search_jax`:

* **kernel parity** — the Metropolis/incumbent select step is bit-identical
  between the fused-XLA reference and the Pallas kernel body (interpret
  mode on CPU), including error-poisoned (non-finite) lanes;
* **seeded determinism** — the same ``(seed, population, steps, island,
  exchange_every)`` returns the bit-identical incumbent regardless of how
  the population is chunked across device calls, which selection-kernel
  backend ran, and (for well-separated optima) whether ranking used
  float32 or float64;
* **differential** — every device-search incumbent, re-simulated through
  the authoritative scalar simulator, matches its device-reported
  objective within dtype-scaled tolerances (the property runs over the
  same seeded scenario generator as the simulator differential suite);
* **optimality bounds** — on exhaustively enumerable problems the search
  finds the true optimum; on the golden Table-6 fixtures it is never
  worse than greedy and within 2% of the exact branch-and-bound plan.

The wide population sweep is ``@pytest.mark.slow`` (scheduled CI lane);
everything else is fast-lane smoke.
"""
from __future__ import annotations

import pathlib

import numpy as np
import pytest

from _prop import examples, given, search_problems, settings

from repro.core.accelerators import Accelerator, Platform
from repro.core.contention import ProportionalShareModel
from repro.core.graph import DNNGraph, LayerGroup
from repro.core.simulate import Workload, simulate
from repro.core.solver_bb import enumerate_assignments

from repro.core import search_jax

FIXTURES = sorted(
    (pathlib.Path(__file__).parent / "fixtures" / "plans").glob("*.json"))


# ---------------------------------------------------------------------------
# problems
# ---------------------------------------------------------------------------

def _acc(name: str, tin: float, tout: float) -> Accelerator:
    return Accelerator(name, peak_flops=1e12, mem_bw=1e11,
                       transition_in_ms=tin, transition_out_ms=tout)


def tiny_problem():
    """Two 3-group DNNs on two accelerators: 64 joint candidates, small
    enough to brute-force with the scalar simulator."""
    platform = Platform(
        name="tiny", accelerators=(_acc("GPU", 0.02, 0.03),
                                   _acc("DLA", 0.05, 0.01)),
        transition_bw=1e11, domains={"EMC": ("GPU", "DLA")},
        domain_bw={"EMC": 1e11})

    def grp(i, tg, td, dg, dd):
        return LayerGroup(name=f"g{i}", times={"GPU": tg, "DLA": td},
                          mem_demand={"GPU": dg, "DLA": dd},
                          out_bytes=2e7, can_transition_after=True)

    graphs = [
        DNNGraph("a", (grp(0, 1.0, 1.6, 0.7, 0.4),
                       grp(1, 2.0, 1.1, 0.5, 0.6),
                       grp(2, 0.8, 1.9, 0.9, 0.3))),
        DNNGraph("b", (grp(0, 1.4, 0.9, 0.6, 0.5),
                       grp(1, 0.7, 1.5, 0.8, 0.2),
                       grp(2, 1.8, 1.0, 0.4, 0.7))),
    ]
    model = ProportionalShareModel(capacity=1.0, sensitivity=2.0)
    return platform, graphs, model


def xavier_pair():
    from repro.core import Scheduler
    sched = Scheduler("xavier-agx")
    return sched.platform, sched.graphs(["googlenet", "resnet18"]), \
        sched.model


def scalar_objective(platform, graphs, model, assignment, objective,
                     its, deps, arr=None):
    arr = arr or [0.0] * len(graphs)
    wls = [Workload(g, tuple(a), iterations=it, depends_on=dep,
                    arrival_ms=a0)
           for g, a, it, dep, a0 in zip(graphs, assignment, its, deps, arr)]
    return simulate(platform, wls, model,
                    record_timeline=False).objective(objective)


def brute_force(platform, graphs, model, objective, mt, its, deps):
    best = np.inf
    cand = [enumerate_assignments(g, platform.names, mt) for g in graphs]
    import itertools
    for asgs in itertools.product(*cand):
        best = min(best, scalar_objective(platform, graphs, model, asgs,
                                          objective, its, deps))
    return best


# ---------------------------------------------------------------------------
# kernel parity
# ---------------------------------------------------------------------------

class TestSelectKernelParity:
    def _inputs(self, p=64, l=6, dtype=np.float32, seed=0):
        rng = np.random.default_rng(seed)
        cur = rng.integers(0, 3, size=(p, l)).astype(np.int32)
        prop = rng.integers(0, 3, size=(p, l)).astype(np.int32)
        best = rng.integers(0, 3, size=(p, l)).astype(np.int32)
        curo = rng.uniform(1, 10, p).astype(dtype)
        propo = rng.uniform(1, 10, p).astype(dtype)
        besto = rng.uniform(1, 10, p).astype(dtype)
        propo[3] = np.inf            # error-poisoned lane
        u = rng.uniform(0, 1, p).astype(dtype)
        temp = np.asarray(0.37, dtype)
        return cur, prop, best, curo, propo, besto, u, temp

    @pytest.mark.parametrize("p", [64, 600])   # one block; padded blocks
    @pytest.mark.parametrize("dtype", [np.float32, np.float64])
    def test_xla_matches_pallas_interpret_bitwise(self, dtype, p):
        from repro.kernels.search import anneal_select
        args = self._inputs(p=p, dtype=dtype)
        ref = anneal_select(*args, backend="xla")
        ker = anneal_select(*args, backend="pallas_interpret")
        for r, k in zip(ref, ker):
            np.testing.assert_array_equal(np.asarray(r), np.asarray(k))

    def test_nonfinite_proposals_always_reject(self):
        from repro.kernels.search import anneal_select
        cur, prop, best, curo, propo, besto, u, temp = self._inputs()
        propo[:] = -np.inf           # "better than anything" but poisoned
        ncur, ncuro, nbst, nbsto = anneal_select(
            cur, prop, best, curo, propo, besto, u, temp, backend="xla")
        np.testing.assert_array_equal(np.asarray(ncur), cur)
        np.testing.assert_array_equal(np.asarray(ncuro), curo)

    def test_strict_improvements_fold_into_incumbent(self):
        from repro.kernels.search import anneal_select
        cur, prop, best, curo, propo, besto, u, temp = self._inputs()
        better = propo < besto
        _, _, nbst, nbsto = anneal_select(
            cur, prop, best, curo, propo, besto, u, temp, backend="xla")
        np.testing.assert_array_equal(np.asarray(nbsto),
                                      np.where(better, propo, besto))
        np.testing.assert_array_equal(np.asarray(nbst)[better], prop[better])
        np.testing.assert_array_equal(np.asarray(nbst)[~better],
                                      best[~better])

    def test_unknown_backend_raises(self):
        from repro.kernels.search import anneal_select
        with pytest.raises(ValueError, match="backend"):
            anneal_select(*self._inputs(), backend="cuda")


# ---------------------------------------------------------------------------
# seeded determinism
# ---------------------------------------------------------------------------

class TestDeterminism:
    KW = dict(objective="latency", seed=7, population=32, steps=24,
              island=8, exchange_every=4)

    @pytest.fixture(scope="class")
    def tables(self):
        platform, graphs, model = xavier_pair()
        return search_jax.build_tables(platform, graphs, model, 2)

    def test_same_seed_bit_identical(self, tables):
        a = search_jax.anneal_search(tables, **self.KW)
        b = search_jax.anneal_search(tables, **self.KW)
        assert a.assignment == b.assignment
        assert a.objective == b.objective
        assert a.chain == b.chain

    @pytest.mark.parametrize("chunk", [8, 16, 32])
    def test_chunk_invariance(self, tables, chunk):
        ref = search_jax.anneal_search(tables, chunk=32, **self.KW)
        out = search_jax.anneal_search(tables, chunk=chunk, **self.KW)
        assert out.assignment == ref.assignment
        assert out.objective == ref.objective
        assert out.chain == ref.chain

    @pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
    def test_select_backend_invariance(self, tables, backend):
        ref = search_jax.anneal_search(tables, backend="xla", **self.KW)
        out = search_jax.anneal_search(tables, backend=backend, **self.KW)
        assert out.assignment == ref.assignment
        assert out.objective == ref.objective

    def test_precision_equivalent_quality(self, tables):
        kw = dict(self.KW, population=128, steps=64)
        f32 = search_jax.anneal_search(tables, precision="float32", **kw)
        f64 = search_jax.anneal_search(tables, precision="x64", **kw)
        # Metropolis deltas differ in the last ulp between precisions, so
        # trajectories may diverge to symmetric incumbents — but float32
        # ranking must not cost solution quality: both precisions land on
        # the same objective to single-precision accuracy, and each
        # incumbent survives a scalar re-simulation.
        assert f32.objective == pytest.approx(f64.objective, rel=1e-4)
        platform, graphs, model = xavier_pair()
        for out, rtol in ((f32, 1e-3), (f64, 1e-6)):
            host = scalar_objective(platform, graphs, model, out.assignment,
                                    "latency", [1, 1], [None, None])
            assert out.objective == pytest.approx(host, rel=rtol)

    def test_evaluated_counts_population_times_steps(self, tables):
        out = search_jax.anneal_search(tables, **self.KW)
        assert out.evaluated == out.population * (self.KW["steps"] + 1)
        assert out.population == 32


# ---------------------------------------------------------------------------
# differential: device incumbent vs authoritative scalar simulator
# ---------------------------------------------------------------------------

class TestDifferential:
    @given(prob=search_problems())
    @settings(max_examples=examples(6))
    def test_device_objective_matches_scalar_rerun(self, prob):
        platform, graphs, model, its, deps, arr = prob
        mt = max(len(g) for g in graphs)
        tables = search_jax.build_tables(
            platform, graphs, model, mt, iterations=its, depends_on=deps,
            arrival_ms=arr)
        for precision, rtol in (("x64", 1e-6), ("float32", 1e-3)):
            out = search_jax.anneal_search(
                tables, objective="latency", seed=3, population=16,
                steps=12, island=8, precision=precision)
            host = scalar_objective(platform, graphs, model, out.assignment,
                                    "latency", its, deps, arr)
            assert out.objective == pytest.approx(host, rel=rtol,
                                                  abs=rtol), precision


# ---------------------------------------------------------------------------
# optimality bounds
# ---------------------------------------------------------------------------

class TestOptimality:
    @pytest.mark.parametrize("objective", ["latency", "throughput"])
    def test_finds_bruteforce_optimum(self, objective):
        platform, graphs, model = tiny_problem()
        its, deps = [1, 2], [None, None]
        best = brute_force(platform, graphs, model, objective, 2, its, deps)
        tables = search_jax.build_tables(platform, graphs, model, 2,
                                         iterations=its)
        out = search_jax.anneal_search(tables, objective=objective, seed=0,
                                       population=64, steps=64, island=16)
        host = scalar_objective(platform, graphs, model, out.assignment,
                                objective, its, deps)
        assert host == pytest.approx(best, rel=1e-9, abs=1e-9)

    @pytest.mark.parametrize("path", FIXTURES, ids=lambda p: p.stem)
    def test_golden_fixtures_close_to_bb_and_never_worse_than_greedy(
            self, path):
        from repro.core import Plan
        from repro.core import solver_anneal, solver_greedy
        plan = Plan.load(path)
        req = plan.request
        sol = solver_anneal.solve(
            req.platform, list(req.graphs), req.model,
            objective=req.objective, max_transitions=req.max_transitions,
            iterations=list(req.iterations),
            depends_on=list(req.depends_on),
            population=1024, steps=192, evaluator="batch")
        greedy = solver_greedy.solve(
            req.platform, list(req.graphs), req.model,
            objective=req.objective, max_transitions=req.max_transitions,
            iterations=list(req.iterations),
            depends_on=list(req.depends_on), evaluator="batch")
        assert sol.objective <= greedy.objective + 1e-9
        # within 2% of the exact solver on every golden Table-6 scenario
        # (objectives may be negative: throughput is -fps).
        assert sol.objective <= plan.objective + 0.02 * abs(plan.objective)
        assert not sol.optimal
        assert sol.params["seed"] == 0


# ---------------------------------------------------------------------------
# validation and error surfaces
# ---------------------------------------------------------------------------

class TestValidation:
    @pytest.fixture(scope="class")
    def tables(self):
        platform, graphs, model = tiny_problem()
        return search_jax.build_tables(platform, graphs, model, 2)

    def test_rejects_unknown_objective(self, tables):
        with pytest.raises(ValueError, match="objective"):
            search_jax.anneal_search(tables, objective="energy")

    def test_rejects_unknown_precision(self, tables):
        with pytest.raises(ValueError, match="precision"):
            search_jax.anneal_search(tables, precision="bf16")

    def test_rejects_island_straddling_chunks(self, tables):
        with pytest.raises(ValueError, match="island"):
            search_jax.anneal_search(tables, island=32, chunk=48)

    def test_island_exceeding_population_names_nearest_legal(self, tables):
        with pytest.raises(ValueError, match="island=16"):
            search_jax.anneal_search(tables, population=16, island=32)

    def test_population_island_remainder_names_nearest_legal(self, tables):
        with pytest.raises(ValueError, match="population=96"):
            search_jax.anneal_search(tables, population=100, island=32)

    def test_chunk_exceeding_population_names_nearest_legal(self, tables):
        with pytest.raises(ValueError, match="chunk=64"):
            search_jax.anneal_search(tables, population=64, island=32,
                                     chunk=96)

    def test_rejects_illegal_init(self, tables):
        bad = np.zeros((tables.w, tables.gmax), dtype=np.int32)
        bad[0, 0] = 1  # transition budget: 3 groups alternating GPU/DLA
        bad[0, 2] = 1
        tables2 = search_jax.build_tables(*tiny_problem(),
                                          max_transitions=0)
        with pytest.raises(ValueError, match="legal"):
            search_jax.anneal_search(tables2, init_assignment=bad)

    def test_unlowerable_model_refused_with_guidance(self):
        platform, graphs, _model = tiny_problem()

        class Opaque:
            def slowdown(self, acc, own, ext):  # pragma: no cover
                return 1.0

        with pytest.raises(ValueError, match="surface"):
            search_jax.build_tables(platform, graphs, Opaque(), 2)

    def test_encode_decode_round_trip(self, tables):
        asg = (("GPU", "DLA", "DLA"), ("DLA", "GPU", "GPU"))
        row = tables.encode(asg)
        assert tables.decode(row) == asg
        assert tables.legal(row)


# ---------------------------------------------------------------------------
# wide sweep (scheduled lane)
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestWideSweep:
    def test_table8_pairs_match_bb_within_2pct(self):
        from repro.core import Scheduler
        from repro.core import solver_anneal
        from benchmarks.table8_exhaustive import balanced_iterations
        sched = Scheduler("agx-orin")
        for pair in (["googlenet", "resnet18"], ["vgg19", "inception"],
                     ["caffenet", "resnet50"]):
            graphs = sched.graphs(pair)
            its = balanced_iterations(sched.platform, graphs)
            bb = sched.solve(graphs, solver="bb", max_transitions=2,
                             iterations=its)
            sol = solver_anneal.solve(
                sched.platform, graphs, sched.model,
                max_transitions=2, iterations=its,
                population=2048, steps=160, evaluator="batch")
            assert sol.objective <= bb.objective + 0.02 * abs(bb.objective)

    def test_chunk_invariance_at_scale(self):
        platform, graphs, model = xavier_pair()
        tables = search_jax.build_tables(platform, graphs, model, 2)
        kw = dict(objective="latency", seed=11, population=1024, steps=64)
        a = search_jax.anneal_search(tables, chunk=1024, **kw)
        b = search_jax.anneal_search(tables, chunk=256, **kw)
        assert a.assignment == b.assignment
        assert a.objective == b.objective
        assert a.chain == b.chain
