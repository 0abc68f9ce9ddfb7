"""A four-chip planning cell is data: the plan driver passes the cell's
``chips`` to the solver as ``devices``, and leaves it unset at one chip.
Rehearsed on four virtual CPU devices in a process of its own (the
device count is fixed when JAX starts)."""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

HERE = pathlib.Path(__file__).resolve().parent

SCRIPT = textwrap.dedent("""
    import argparse, json, pathlib, sys
    sys.path[:0] = [{tests!r}, {bench!r}, {src!r}]
    import conftest, run
    root = conftest.tiny_bench(pathlib.Path({tmp!r}))
    cfg = dict(conftest.TINY_PLANNER, name="tiny-anneal-mesh4")
    cfg["solver_knobs"] = dict(cfg["solver_knobs"], population=128)
    (root / "bench" / "configs" / "tiny-anneal-mesh4.json").write_text(json.dumps(cfg))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({{"name": "tiny-anneal-mesh4", "source": "test",
        "file": "bench/configs/tiny-anneal-mesh4.json", "reduced": [], "why": "test"}})
    spec["workloads"] += [
        {{"name": "plan.tiny.mesh4", "config": "tiny-anneal-mesh4",
          "traffic": "tiny-pairs", "chips": 4, "why": "test"}}]
    for m in spec["end_to_end"]:
        if "workloads" in m and "plan.tiny" in m["workloads"]:
            m["workloads"].append("plan.tiny.mesh4")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    seen = {{}}
    for cell in ("plan.tiny.mesh4", "plan.tiny"):
        box = []
        args = argparse.Namespace(workload=cell, seed=5, seconds=1.0, trace=0,
                                  control=0, dump_trace=None)
        out = run.run(args, bench=root / "bench", require_tpu=False,
                      fault=box.append)
        rec = box[0].records[0]
        seen[cell] = dict(correct=out["correct"], count=out["device"]["count"],
                          devices=rec.plan.solution.params.get("devices"),
                          search_devices=rec.outcome.devices)
    print(json.dumps(seen))
""")


def test_four_chip_cell_passes_devices(tmp_path):
    src = HERE.parents[1] / "src"
    code = SCRIPT.format(tests=str(HERE), bench=str(HERE.parent), src=str(src),
                         tmp=str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    seen = json.loads(proc.stdout.strip().splitlines()[-1])
    assert seen["plan.tiny.mesh4"] == {"correct": True, "count": 4, "devices": 4,
                                       "search_devices": 4}
    assert seen["plan.tiny"] == {"correct": True, "count": 1, "devices": None,
                                 "search_devices": None}
