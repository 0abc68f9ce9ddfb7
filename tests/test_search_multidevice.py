"""Multi-device mesh fan-out of the device-resident schedule search.

The determinism contract (docs/architecture.md): for a fixed ``(seed,
population, island)`` the search incumbent is **bit-identical** across

* the legacy chunked driver (``devices=None``) and the mesh driver at
  ``devices=1`` with ``migrate="island"``;
* every device count at equal *total* population (ring migration is a
  pure gather whose seam permutes with the device order);
* the ``shard_map`` and ``pmap`` fan-outs;
* the select-kernel backends (``xla`` / ``pallas_interpret`` — the
  ``auto`` threshold is judged on the *global* lane count so the backend
  choice itself is device-count invariant).

Run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI
mesh-smoke lane) the cross-device cases exercise real 8-way XLA
partitions; on a plain 1-device host they skip, and a subprocess test
(via :func:`repro.core.xla_env.subprocess_env`) still covers the
8-device path end-to-end.  The differential property re-checks the
scalar-simulator contract *under sharding* over the same seeded problem
generator as the single-device suite.
"""
from __future__ import annotations

import json
import pathlib
import subprocess
import sys
import textwrap

import pytest

from _prop import examples, given, search_problems, settings
# imported here, not inside a property test: importing a module of
# @given tests from within one trips hypothesis' nested-given check
from test_search import scalar_objective

from repro.core import search_jax

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _device_count() -> int:
    import jax
    return jax.device_count()


def _outcome_key(out):
    return (out.assignment, out.objective, out.chain)


def xavier_tables():
    from repro.core import Scheduler
    sched = Scheduler("xavier-agx")
    return search_jax.build_tables(
        sched.platform, sched.graphs(["googlenet", "resnet18"]),
        sched.model, 2)


KW = dict(objective="latency", seed=7, population=64, steps=24,
          island=8, exchange_every=4)


@pytest.fixture(scope="module")
def tables():
    return xavier_tables()


class TestMeshMatchesLegacy:
    """devices=1 mesh path vs the pre-mesh chunked driver."""

    def test_island_migrate_bit_identical_to_chunked(self, tables):
        legacy = search_jax.anneal_search(tables, **KW)
        mesh = search_jax.anneal_search(tables, devices=1,
                                        migrate="island", **KW)
        assert _outcome_key(mesh) == _outcome_key(legacy)
        assert mesh.devices == 1 and mesh.migrate == "island"
        assert legacy.devices is None and legacy.fanout is None

    def test_ring_at_one_device_is_self_consistent(self, tables):
        a = search_jax.anneal_search(tables, devices=1, **KW)
        b = search_jax.anneal_search(tables, devices=1, migrate="ring",
                                     **KW)
        # migrate="auto" resolves to "ring" on the mesh path
        assert a.migrate == "ring"
        assert _outcome_key(a) == _outcome_key(b)

    @pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
    def test_select_backend_invariance_on_mesh(self, tables, backend):
        ref = search_jax.anneal_search(tables, devices=1, **KW)
        out = search_jax.anneal_search(tables, devices=1, backend=backend,
                                       **KW)
        assert _outcome_key(out) == _outcome_key(ref)

    def test_compile_seconds_times_a_fresh_executable(self, tables):
        t = search_jax.compile_seconds(tables, objective="latency",
                                       population=64, devices=1)
        assert t > 0


class TestCrossDeviceDeterminism:
    """Equal total population, varying device count: bit-identical."""

    @pytest.fixture(scope="class")
    def ref(self, tables):
        return search_jax.anneal_search(tables, devices=1, **KW)

    @pytest.fixture(scope="class")
    def tables(self):
        return xavier_tables()

    @pytest.mark.parametrize("devices", [2, 4, 8])
    def test_device_count_invariance(self, tables, ref, devices):
        if _device_count() < devices:
            pytest.skip(f"needs {devices} jax devices "
                        f"(run under XLA_FLAGS="
                        f"--xla_force_host_platform_device_count=8)")
        out = search_jax.anneal_search(tables, devices=devices, **KW)
        assert _outcome_key(out) == _outcome_key(ref)
        assert out.devices == devices

    def test_pmap_matches_shard_map(self, tables, ref):
        if _device_count() < 2:
            pytest.skip("needs >= 2 jax devices")
        sm = search_jax.anneal_search(tables, devices=2,
                                      fanout="shard_map", **KW)
        pm = search_jax.anneal_search(tables, devices=2, fanout="pmap",
                                      **KW)
        assert _outcome_key(sm) == _outcome_key(pm) == _outcome_key(ref)
        assert sm.fanout == "shard_map" and pm.fanout == "pmap"

    @pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
    def test_backend_invariance_across_shards(self, tables, ref, backend):
        if _device_count() < 2:
            pytest.skip("needs >= 2 jax devices")
        out = search_jax.anneal_search(tables, devices=2, backend=backend,
                                       **KW)
        assert _outcome_key(out) == _outcome_key(ref)


# one subprocess emulating 8 host devices: covers the real multi-shard
# lowering even when this pytest process itself sees a single device.
_WORKER = textwrap.dedent("""\
    import json, sys
    sys.path.insert(0, {tests_dir!r})
    from test_search_multidevice import KW, xavier_tables, _outcome_key
    from repro.core import search_jax
    out = search_jax.anneal_search(xavier_tables(), devices=8, **KW)
    print(json.dumps({{"key": repr(_outcome_key(out)),
                       "fanout": out.fanout}}))
""")


def test_eight_emulated_devices_match_one(tables):
    from repro.core import xla_env
    ref = search_jax.anneal_search(tables, devices=1, **KW)
    env = xla_env.subprocess_env(8)
    env["PYTHONPATH"] = str(ROOT / "src")
    proc = subprocess.run(
        [sys.executable, "-c",
         _WORKER.format(tests_dir=str(ROOT / "tests"))],
        env=env, text=True, capture_output=True, timeout=600)
    assert proc.returncode == 0, proc.stderr
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["key"] == repr(_outcome_key(ref))
    assert got["fanout"] in ("shard_map", "pmap")


class TestDifferentialUnderSharding:
    """The scalar-simulator contract holds for mesh incumbents too."""

    @given(prob=search_problems())
    @settings(max_examples=examples(4))
    def test_device_objective_matches_scalar_rerun(self, prob):
        platform, graphs, model, its, deps, arr = prob
        mt = max(len(g) for g in graphs)
        tbl = search_jax.build_tables(
            platform, graphs, model, mt, iterations=its, depends_on=deps,
            arrival_ms=arr)
        ndev = min(_device_count(), 2)
        out = search_jax.anneal_search(
            tbl, objective="latency", seed=3, population=16 * ndev,
            steps=12, island=8, devices=ndev)
        host = scalar_objective(platform, graphs, model, out.assignment,
                                "latency", its, deps, arr)
        assert out.objective == pytest.approx(host, rel=1e-3, abs=1e-3)


class TestMeshKnobValidation:
    @pytest.fixture(scope="class")
    def tables(self):
        return xavier_tables()

    def test_devices_must_be_positive(self, tables):
        with pytest.raises(ValueError, match="devices"):
            search_jax.anneal_search(tables, devices=0, **KW)

    def test_devices_beyond_visible_names_xla_env(self, tables):
        with pytest.raises(ValueError, match="xla_env"):
            search_jax.anneal_search(tables, devices=4096, **KW)

    def test_unknown_migrate_lists_choices(self, tables):
        with pytest.raises(ValueError, match="island"):
            search_jax.anneal_search(tables, devices=1, migrate="bogus",
                                     **KW)

    def test_unknown_fanout_lists_choices(self, tables):
        with pytest.raises(ValueError, match="pmap"):
            search_jax.anneal_search(tables, devices=1, fanout="bogus",
                                     **KW)

    def test_fanout_without_devices_rejected(self, tables):
        with pytest.raises(ValueError, match="devices"):
            search_jax.anneal_search(tables, fanout="pmap", **KW)

    def test_ring_without_devices_rejected(self, tables):
        with pytest.raises(ValueError, match="migrate='island'"):
            search_jax.anneal_search(tables, migrate="ring", **KW)

    def test_population_quantum_names_nearest_legal(self, tables):
        kw = dict(KW, population=72)   # 72 % (8 islands * 2 devices) != 0
        if _device_count() < 2:
            pytest.skip("needs >= 2 jax devices")
        with pytest.raises(ValueError, match="population=64"):
            search_jax.anneal_search(tables, devices=2, **kw)
