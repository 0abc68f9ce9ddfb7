"""Operations and bytes of each kernel and of the model, at shapes
checked by hand."""
import pytest

import flops
from reference import lm_ref
from rooflines import anneal_select, common, decode_attention, flash_attention

STABLELM = lm_ref.sizes({
    "num_hidden_layers": 24, "hidden_size": 2048, "num_attention_heads": 32,
    "num_key_value_heads": 32, "intermediate_size": 5632, "vocab_size": 100352,
    "rope_theta": 10000, "rms_norm_eps": 1e-6})


def test_out_shape_from_hlo_text():
    st = {"long_name": "%flash_attention.3 = bf16[1,32,1024,64]{3,2,1,0} custom-call(...)"}
    assert common.out_shape(st) == ("bf16", (1, 32, 1024, 64))
    tup = {"long_name": "%_pallas_select.1 = (s32[2048,32]{1,0}, f32[2048,1]{1,0}) custom-call"}
    assert common.out_shape(tup) == ("s32", (2048, 32))
    assert common.out_shape({}) is None


def test_flash_attention_cost():
    st = {"long_name": "%flash_attention.1 = bf16[1,32,1024,64]{3,2,1,0} custom-call()"}
    f, b = flash_attention.cost(st)
    # QK and PV over the causal triangle: 4 * 32 * 64 * 1024 * 1025 / 2
    assert f == 4 * 32 * 64 * 1024 * 1025 // 2 == 4_299_161_600
    # q, k, v read and o written once, bf16
    assert b == 4 * 32 * 1024 * 64 * 2 == 16_777_216
    assert flash_attention.match("flash_attention.12", {})
    assert not flash_attention.match("decode_attention.1", {})


def test_decode_attention_cost():
    f, b = decode_attention.cost([100, 300], STABLELM)
    assert f == 4 * 32 * 64 * 400 == 3_276_800
    # keys and values of 400 positions, plus q and o of 2 tokens, bf16
    assert b == (2 * 400 * 32 * 64 + 2 * 2 * 32 * 64) * 2 == 3_293_184
    assert decode_attention.match("decode_attention.1", {})


def test_anneal_select_cost():
    st = {"long_name": "%_pallas_select.1 = (s32[2048,32]{1,0}) custom-call"}
    f, b = anneal_select.cost(st)
    assert b == (3 * 2048 * 32 * 4 + 4 * 2048 * 4 + 4) + (2 * 2048 * 32 * 4 + 2 * 2048 * 4)
    assert f == 2 * 2048 * 32 + 8 * 2048
    assert anneal_select.match("_pallas_select.7", {})


def test_share_and_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    # 200 flops -> 2 s, 10 bytes -> 1 s: least time 2 s over 4 s taken
    assert common.share([(200, 10, 4.0)], peak) == pytest.approx(50.0)
    assert common.bound([(200, 10, 4.0)], peak) == "compute"
    assert common.share([], peak) is None


def test_model_flops_stablelm():
    per_token = 2 * 24 * (2048 * 64 * 128 + 3 * 2048 * 5632)
    assert per_token == 2 * 24 * 51_380_224 == 2_466_250_752
    head = 2 * 2048 * 100352
    assert flops.decode_token(STABLELM, 1000) == per_token + head + 4 * 24 * 32 * 64 * 1000
    assert flops.prefill(STABLELM, 10) == (per_token * 10 + head
                                           + 4 * 24 * 32 * 64 * 55)
