"""Shared fixtures: a copy of the benchmark in a temporary directory with
tiny cells of each kind, added by data alone (new configuration, traffic
and ``BENCHMARK.json`` entries), that runs on the CPU."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MODEL = {
    "name": "tiny-dense", "kind": "serve", "arch": "stablelm-1.6b",
    "num_hidden_layers": 2, "hidden_size": 128, "intermediate_size": 256,
    "num_attention_heads": 4, "num_key_value_heads": 4, "vocab_size": 512,
    "max_position_embeddings": 512, "rope_theta": 10000, "hidden_act": "silu",
    "tie_word_embeddings": False, "partial_rotary_factor": 1.0,
    "use_qkv_bias": False, "rms_norm_eps": 1e-6, "torch_dtype": "float32",
    "activation_dtype": "bfloat16", "kv_cache_dtype": "bfloat16",
    "engine": {"max_slots": 2, "capacity": 128},
    "limits": {"served_logit_gap": 0.02},
}
TINY_CHAT = {
    "kind": "serve", "rate_per_s": 6.0,
    "prompt_tokens": {"median": 32, "sigma": 0.5, "min": 16, "max": 64, "grid": 16},
    "output_tokens": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
    "drain_s": 60, "check_requests": 4, "trace_seconds": 1,
}
TINY_PLANNER = {
    "name": "tiny-anneal", "kind": "plan", "platform": "agx-orin",
    "contention": {"capacity": 1.0, "sensitivity": 3.0}, "solver": "anneal",
    "solver_knobs": {"population": 64, "steps": 8, "island": 32,
                     "precision": "float32", "backend": "auto"},
    "matmul_precision": "float32",
    "limits": {"plan_obj_err": 1e-9, "device_obj_err": 1e-5},
}
TINY_PAIRS = {
    "kind": "plan",
    "problems": [
        {"dnns": ["caffenet", "resnet50"], "objective": "throughput",
         "max_transitions": 1, "iterations": "balanced"},
        {"dnns": ["resnet18", "vgg19"], "objective": "latency",
         "max_transitions": 1, "iterations": [1, 1]}],
    "strata": 2, "trace_seconds": 1,
}


def tiny_bench(tmp: pathlib.Path) -> pathlib.Path:
    """A checkout-shaped directory: the benchmark copied, plus tiny cells
    made only of new data files and entries."""
    shutil.copytree(BENCH, tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    b = tmp / "bench"
    (b / "configs" / "tiny-dense.json").write_text(json.dumps(TINY_MODEL))
    (b / "configs" / "tiny-anneal.json").write_text(json.dumps(TINY_PLANNER))
    (b / "traffic" / "tiny-chat.json").write_text(json.dumps(TINY_CHAT))
    (b / "traffic" / "tiny-pairs.json").write_text(json.dumps(TINY_PAIRS))
    peaks = json.loads((b / "peaks.json").read_text())
    peaks["cpu"] = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                    "hbm_bytes": 1 << 34, "source": "test stand-in, not a device"}
    (b / "peaks.json").write_text(json.dumps(peaks))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] += [
        {"name": "tiny-dense", "source": "test", "file": "bench/configs/tiny-dense.json",
         "reduced": [], "why": "test"},
        {"name": "tiny-anneal", "source": "test", "file": "bench/configs/tiny-anneal.json",
         "reduced": [], "why": "test"}]
    spec["workloads"] += [
        {"name": "serve.tiny", "config": "tiny-dense", "traffic": "tiny-chat",
         "chips": 1, "why": "test"},
        {"name": "plan.tiny", "config": "tiny-anneal", "traffic": "tiny-pairs",
         "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            kind = m["workloads"][0].split(".")[0]
            m["workloads"].append(f"{kind}.tiny")
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


@pytest.fixture(scope="session")
def bench_dir(tmp_path_factory):
    return tiny_bench(tmp_path_factory.mktemp("checkout"))
