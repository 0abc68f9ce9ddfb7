"""Integration: graph export -> scheduler -> serving plan; sharding rules."""
import jax
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro import configs
from repro.configs.base import SHAPES, ShapeCell
from repro.core import api as core_api
from repro.core.accelerators import tpu_pod_split
from repro.core.simulate import Workload, simulate
from repro.models import sharding
from repro.models.graph_export import export_graph
from repro.serve.concurrent import plan_concurrent_serving


class TestGraphExport:
    @pytest.mark.parametrize("arch", ["llama3.2-3b", "rwkv6-7b",
                                      "dbrx-132b", "recurrentgemma-9b"])
    @pytest.mark.parametrize("shape", ["decode_32k", "prefill_32k"])
    def test_exports_schedulable_graph(self, arch, shape):
        cfg = configs.get(arch)
        ok, _ = configs.cell_supported(cfg, shape)
        if not ok:
            pytest.skip("cell not supported")
        plat = tpu_pod_split()
        g = export_graph(cfg, SHAPES[shape], plat)
        assert len(g) >= 3                      # embed + layers + head
        for acc in plat.names:
            assert g.standalone_time(acc) > 0
        for grp in g:
            assert grp.flops >= 0 and grp.out_bytes >= 0
            for a, dem in grp.mem_demand.items():
                assert 0 <= dem <= 1.5

    def test_moe_decode_cheaper_than_dense_of_same_total_size(self):
        """Active-params accounting: qwen3 (235B total, ~22B active) decode
        groups must be far cheaper than a hypothetical dense 235B."""
        plat = tpu_pod_split()
        cfg = configs.get("qwen3-moe-235b-a22b")
        g = export_graph(cfg, SHAPES["decode_32k"], plat)
        t = g.standalone_time("MESH_A")
        assert t < 100.0                        # ms; dense-235B would be ~4x


class TestConcurrentPlanning:
    def test_plan_never_worse_than_baselines(self):
        plan = plan_concurrent_serving(
            [configs.get("llama3.2-3b"), configs.get("stablelm-1.6b")],
            ["decode_32k", "decode_32k"], objective="latency",
            deadline_s=5.0)
        assert plan.plan is not None            # provenance artifact
        assert plan.plan.solver in ("z3", "bb", "greedy")
        for name, res in plan.baselines.items():
            if not core_api.failed(res):
                assert (plan.solution.result.latency_ms
                        <= res.latency_ms + 1e-9), name

    def test_schedule_executes_in_simulator(self):
        plan = plan_concurrent_serving(
            [configs.get("rwkv6-7b"), configs.get("nemotron-4-15b")],
            [ShapeCell("s", 2048, 64, "decode")] * 2,
            objective="throughput", deadline_s=5.0)
        res = simulate(plan.platform, plan.solution.workloads,
                       core_api.default_model(plan.platform))
        assert res.makespan == pytest.approx(
            plan.solution.result.makespan, rel=1e-9)


class TestShardingRules:
    MESH_AXES = ("data", "model")

    def make_mesh(self):
        dev = np.array(jax.devices()[:1]).reshape(1, 1)
        return Mesh(dev, self.MESH_AXES)

    def test_axis_used_once(self):
        rules = {"batch": ("pod", "data"), "embed": "data", "seq": None}
        s = sharding.spec(rules, ("batch", "seq", "embed"),
                          self.make_mesh())
        # data consumed by batch; embed falls back to None
        assert s == P("data")

    def test_missing_mesh_axis_dropped(self):
        rules = {"batch": ("pod", "data")}           # no 'pod' axis in mesh
        s = sharding.spec(rules, ("batch",), self.make_mesh())
        assert s == P("data")

    def test_divisibility_fallback(self):
        rules = {"heads": "model"}
        mesh = self.make_mesh()
        ns = sharding.named_sharding(mesh, rules, ("heads", None),
                                     shape=(40, 128))
        # 40 % 1 == 0 on this 1-device mesh -> kept; logic exercised at
        # scale in the dry-run (40 heads over 16 -> dropped)
        assert isinstance(ns.spec, P)

    def test_zero3_rules_have_no_tensor_axes(self):
        rules = dict(configs.RULES_ZERO3)
        for name in ("heads", "mlp", "vocab", "kv_heads"):
            s = sharding.spec(rules, (name,), self.make_mesh())
            assert s == P()

