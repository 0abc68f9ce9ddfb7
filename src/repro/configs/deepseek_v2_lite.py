"""deepseek-v2-lite [moe]: multi-head latent attention, 64 routed experts
top-6 plus 2 shared experts, one leading dense layer.

27L d_model=2048 16H, q 192 wide (128 + 64 rotary; no q LoRA), latent
512 with its own RMSNorm, shared rotary key 64, values 128; YaRN rotary
(base 10,000, factor 40 over 4,096 positions, mscale = mscale_all_dim =
0.707); dense FFN 10,944 in layer 0, then experts of 1,408 with softmax
scores, greedy top-6 and no renormalisation; vocab 102,400, untied head
[hf:deepseek-ai/DeepSeek-V2-Lite, DeepSeek-V2 arXiv:2405.04434].

Departures that random weights cannot tell apart: the rotary pairs are
the halves of each rotary vector, as the repo's ``rope`` rotates them,
where the published code pairs interleaved columns (a fixed permutation
of the rotary columns of ``W_q`` and ``W_kva``); RMSNorm scales by
``1 + g`` where the published one scales by ``g`` (a reparameterisation
of ``g``).  All 64 experts are held here; a serving deployment states
its own share (``MoEConfig.first_held``/``n_held``).
"""
from .base import MLAConfig, MoEConfig, ModelConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_head=128,
    d_ff=10944,
    vocab=102400,
    moe=MoEConfig(n_experts=64, top_k=6, d_ff=1408, n_shared=2,
                  norm_topk=False),
    block_pattern=("mla",),
    mla=MLAConfig(kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128),
    rope_theta=10000.0,
    rope_scaling=YarnConfig(factor=40.0, original_max_position=4096,
                            beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                            mscale_all_dim=0.707),
    first_dense=1,
    act="swiglu",
    param_dtype="bfloat16",
)
