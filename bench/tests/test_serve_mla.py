"""The latent-attention MoE serving kind (``kinds/serve_mla.py``) end to
end on the CPU: a tiny configuration of the DeepSeek-V2 shape, added by
data alone beside the benchmark's copy, runs its window, reads its
metrics and passes its check, and its int8 control fails the check."""
from __future__ import annotations

import argparse
import json
import pathlib

import pytest

from conftest import tiny_bench

TINY_MLA = {
    "name": "tiny-mla", "kind": "serve_mla", "arch": "deepseek-v2-lite",
    "first_k_dense_replace": 1, "hidden_size": 64, "intermediate_size": 128,
    "kv_lora_rank": 32, "moe_intermediate_size": 32, "n_routed_experts": 4,
    "n_shared_experts": 1, "norm_topk_prob": False, "num_attention_heads": 4,
    "num_experts_per_tok": 3, "num_hidden_layers": 3, "num_key_value_heads": 4,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 32, "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1,
    "tie_word_embeddings": False, "v_head_dim": 16, "vocab_size": 256,
    "torch_dtype": "float32", "activation_dtype": "float32",
    "kv_cache_dtype": "float32",
    "engine": {"max_slots": 2, "capacity": 128},
    "deployment": {"router_experts": 8, "first_held": 2},
    "limits": {"served_logit_gap": 0.01},
}
TINY_LONGDOC = {
    "kind": "serve", "rate_per_s": 6.0,
    "prompt_tokens": {"median": 40, "sigma": 0.3, "min": 16, "max": 96,
                      "grid": 16},
    "output_tokens": {"median": 8, "sigma": 0.5, "min": 2, "max": 16},
    "drain_s": 60, "check_requests": 3, "trace_seconds": 1,
}
CELL = "serve.tiny-mla"


@pytest.fixture(scope="module")
def mla_bench(tmp_path_factory):
    root = tiny_bench(tmp_path_factory.mktemp("checkout"))
    b = root / "bench"
    (b / "configs" / "tiny-mla.json").write_text(json.dumps(TINY_MLA))
    (b / "traffic" / "tiny-longdoc.json").write_text(json.dumps(TINY_LONGDOC))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-mla", "source": "test",
                            "file": "bench/configs/tiny-mla.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny-mla",
                              "traffic": "tiny-longdoc", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "serve.dsv2lite.longdoc" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return b


def _run(bench, **kw):
    import run
    args = argparse.Namespace(workload=CELL, seed=2**33 + 5, seconds=1.5,
                              trace=0, control=0, dump_trace=None)
    for k, v in kw.items():
        setattr(args, k, v)
    return run.run(args, bench=bench, require_tpu=False)


def test_cell_runs_and_is_correct(mla_bench):
    out = _run(mla_bench)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert set(out["metrics"]) == {"tbt_p95_ms", "setup_s"}


def test_traced_run_reads_the_expert_metrics(mla_bench):
    out = _run(mla_bench, trace=1)
    m = out["metrics"]
    assert {"moe_rows_per_expert", "mfu.decode.mla", "decode_step_ms",
            "admit_ms", "compiles.serve"} <= set(m)
    # 2 slots x 3 of 8 experts: 0.75 rows per expert on average
    assert 0 < m["moe_rows_per_expert"]["value"] < 2


def test_control_fails_the_check(mla_bench):
    out = _run(mla_bench, control=1)
    assert not out["correct"]


def _published_sizes():
    from reference import mla_ref
    cfg = json.loads((pathlib.Path(__file__).resolve().parents[1] / "configs"
                      / "deepseek-v2-lite.json").read_text())
    return mla_ref.sizes(cfg)


def test_published_sizes_give_the_memory_reckoning():
    """3.111 B parameters on the chip's share; the published 15.7 B uncut."""
    from repro import configs
    import dataclasses
    s = _published_sizes()
    assert (s["E"], s["held"], s["k"], s["r"], s["rope"]) == (64, 8, 6, 512, 64)
    full = configs.get("deepseek-v2-lite")
    share = dataclasses.replace(full, moe=dataclasses.replace(full.moe,
                                                              n_held=8))
    assert round(full.n_params() / 1e9, 2) == 15.71
    assert round(share.n_params() / 1e9, 3) == 3.111


def test_roofline_costs():
    from rooflines import mla_decode, moe_gmm
    s = _published_sizes()
    f, b = mla_decode.cost([100, 3000], s)
    assert f == 2 * 16 * (576 + 512) * 3100
    assert b == (3100 * 576 + 2 * 16 * (576 + 512)) * 2
    f, b = moe_gmm.cost(9, 5, s)
    assert f == 9 * 2 * 3 * 2048 * 1408
    assert b == 5 * 3 * 2048 * 1408 * 2 + 9 * 2048 * 6


def test_decode_token_flops():
    import flops_mla
    s = _published_sizes()
    base = flops_mla.decode_token(s, 0, 0)
    # absorbed attention grows with the context, the routed rows with rows
    assert flops_mla.decode_token(s, 10, 0) - base == 10 * 2 * 27 * 16 * 1088
    assert flops_mla.decode_token(s, 0, 2) - base == 2 * 6 * 2048 * 1408
    # the dense and shared weights a token passes through, twice
    assert 2.1e9 < base < 2.3e9


def test_expert_staging_is_matched_by_its_shape():
    """The slices of the held experts' weights that feed the kernel count
    with it; other ops, and the kernel itself, do not match as staging."""
    from rooflines import moe_gmm
    s = _published_sizes()
    pred = moe_gmm.staging(s)
    hlo = "%dynamic-slice_bitcast_fusion.9 = bf16[8,2048,1408]{2,1,0:T(8,128)}"
    assert pred("dynamic-slice_bitcast_fusion.9", {"long_name": hlo})
    assert pred("fusion.3", {"long_name": "%fusion.3 = bf16[8,1408,2048]{2,1,0}"})
    assert not pred("fusion.4", {"long_name": "%fusion.4 = bf16[8,2048,2816]{2,1,0}"})
    assert not pred("moe_gmm.3", {"long_name": "%moe_gmm.3 = bf16[8,2048,1408]{2,1,0}"})
    assert moe_gmm.match("moe_gmm.31", {}) and not moe_gmm.match("gmm.3", {})
