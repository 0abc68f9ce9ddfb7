"""DeepSeek-V2's latent attention and drop-free MoE, served on the CPU at
a small size with seeded random weights, against the benchmark's plain
reference (``bench/reference/mla_ref.py``, loaded by path so the
repository keeps one reference).

Everything runs in float32, so the program and the reference differ by
summation order alone: the tolerances below are a few hundred float32
ulps of the values compared.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import MoEConfig, YarnConfig
from repro.models import build, layers, mla, moe
from repro.serve.engine import ServingEngine

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _load_ref():
    spec = importlib.util.spec_from_file_location(
        "mla_ref_under_test", ROOT / "bench" / "reference" / "mla_ref.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_ref()

#: a configuration file of the DeepSeek-V2 shape at a small size: 16
#: experts scored, this chip holding 4 of them (from expert 4)
FILE = {
    "num_hidden_layers": 3, "hidden_size": 64, "num_attention_heads": 4,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "kv_lora_rank": 32, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_experts_per_tok": 4, "n_routed_experts": 4, "n_shared_experts": 1,
    "first_k_dense_replace": 1, "vocab_size": 256, "rope_theta": 10000,
    "rms_norm_eps": 1e-6, "norm_topk_prob": False, "torch_dtype": "float32",
    "deployment": {"router_experts": 16, "first_held": 4},
    "rope_scaling": {"factor": 4, "original_max_position_embeddings": 32,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                     "mscale_all_dim": 0.707},
}
S = ref.sizes(FILE)
SEED = 2**33 + 15


def tiny_cfg(first_held=4, n_held=4):
    y = FILE["rope_scaling"]
    return dataclasses.replace(
        configs.get("deepseek-v2-lite").reduced(), n_layers=3,
        moe=MoEConfig(n_experts=16, top_k=4, d_ff=32, n_shared=1,
                      norm_topk=False, first_held=first_held, n_held=n_held),
        rope_scaling=YarnConfig(
            factor=y["factor"],
            original_max_position=y["original_max_position_embeddings"],
            beta_fast=y["beta_fast"], beta_slow=y["beta_slow"],
            mscale=y["mscale"], mscale_all_dim=y["mscale_all_dim"]))


@pytest.fixture(scope="module")
def served():
    cfg = tiny_cfg()
    model = build(cfg, backend="xla")
    return cfg, model, ref.make_params(SEED, S)


def _serve(model, params, prompts, max_new, slots=3):
    """Serve ``prompts``; returns the requests, the logits each request's
    tokens were sampled from (prefill's, then each step's), and the
    engine."""
    eng = ServingEngine(model, params, max_slots=slots, capacity=64)
    reqs = [eng.submit(p, max_new=max_new) for p in prompts]
    logits = {r.rid: [] for r in reqs}
    admitted = iter(reqs)               # prefills run in submission order

    def seen(kind, lg):
        lg = np.asarray(lg)
        if kind == "prefill":
            logits[next(admitted).rid].append(lg[0, -1])
        else:
            for slot, r in enumerate(eng.slots):
                if r is not None:
                    logits[r.rid].append(lg[slot, 0])

    eng.on_logits = seen
    eng.run_until_drained()
    return reqs, logits, eng


def test_make_params_tree_is_the_programs(served):
    cfg, model, params = served
    shape = lambda t: jax.tree.map(lambda a: (a.shape, str(a.dtype)), t)
    assert shape(params) == shape(model.abstract_params())


def test_served_logits_match_the_reference_forward(served):
    """Prefill, then decode through the latent cache, against the
    reference's full forward over prompt plus served tokens."""
    cfg, model, params = served
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 256, 21).astype(np.int32)
    reqs, logits, _ = _serve(model, params, [prompt], max_new=7)
    toks = reqs[0].tokens
    ids = np.concatenate([prompt, toks[:-1]])
    want = np.asarray(ref.forward_logits(SEED, S, ids))[len(prompt) - 1:]
    got = np.stack(logits[reqs[0].rid])
    assert got.shape == want.shape == (7, 256)
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max(),
                               rtol=0)


def test_logits_do_not_depend_on_batch_neighbours(served):
    """Drop-free dispatch: a request's logits are the same served alone
    or beside others, and no assignment is dropped."""
    cfg, model, params = served
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (17, 9, 30)]
    alone, la, _ = _serve(model, params, prompts[:1], max_new=6)
    crowd, lc, eng = _serve(model, params, prompts, max_new=6)
    assert alone[0].tokens == crowd[0].tokens
    np.testing.assert_allclose(np.stack(lc[crowd[0].rid]),
                               np.stack(la[alone[0].rid]), atol=1e-5, rtol=0)
    c = eng.counters
    assert c.moe_dropped_rows == 0
    assert c.moe_rows_total > 0
    # 2 MoE layers x 4 held experts per prefill and per step
    assert c.moe_expert_steps == 8 * (3 + c.steps)


def test_absorbed_decode_matches_the_expanded_form(served):
    """One layer's decode over the latent cache (absorbed: q W_uk^T against
    the cached latents, W_uv after the weighted sum) equals the prefill's
    expanded form (keys and values rebuilt per head) at the same position."""
    cfg, model, _ = served
    p, _ = mla.init_mla(cfg, jax.random.PRNGKey(3))
    p = jax.tree.map(lambda a: a + 0.05 * jax.random.normal(
        jax.random.PRNGKey(4), a.shape), p)     # non-zero norm gains
    B, T = 2, 13
    x = jax.random.normal(jax.random.PRNGKey(5), (B, T, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    y_full, rows = mla.mla_block(cfg, p, {}, x, pos, backend="xla")
    from repro.models import kvcache
    cache = kvcache.latent_from_prefill(rows[:, :T - 1], 32, "float32")
    lengths = jnp.full((B,), T - 1, jnp.int32)
    y_dec, _ = mla.mla_block(cfg, p, {}, x[:, T - 1:], lengths[:, None],
                             cache=cache, lengths=lengths, backend="xla")
    want = np.asarray(y_full[:, T - 1])
    np.testing.assert_allclose(np.asarray(y_dec[:, 0]), want,
                               atol=2e-5 * np.abs(want).max(), rtol=0)


def test_yarn_frequencies_follow_the_closed_form():
    """At DeepSeek-V2-Lite's published values the ramp runs from rotary
    pair 10 to 23, and the frequencies are f_i/40 above it, f_i below."""
    y = configs.get("deepseek-v2-lite").rope_scaling
    assert y.ramp_bounds(64, 10000.0) == (10, 23)
    assert y.cos_sin_scale == 1.0
    assert y.attn_scale == pytest.approx((0.1 * 0.707 * np.log(40) + 1) ** 2)
    i = np.arange(32)
    f = 10000.0 ** (-2 * i / 64)
    ramp = np.clip((i - 10) / 13, 0, 1)
    want = f / 40 * ramp + f * (1 - ramp)
    np.testing.assert_allclose(np.asarray(layers.yarn_freq(64, 10000.0, y)),
                               want, rtol=1e-6)
    np.testing.assert_allclose(
        ref.yarn_inv_freq(ref.sizes(dict(FILE, qk_rope_head_dim=64,
                                         rope_scaling=dict(
                                             FILE["rope_scaling"], factor=40,
                                             original_max_position_embeddings=4096)))),
        want, rtol=1e-6)


def test_plain_rope_is_unchanged():
    """Without YaRN, ``rope`` is bit for bit the plain rotary embedding."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 3, 64))
    pos = jnp.broadcast_to(jnp.arange(9)[None], (2, 9)) + 5
    half = 32
    freq = 10000.0 ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None] * freq
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    want = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    assert np.array_equal(np.asarray(layers.rope(x, pos, 10000.0)),
                          np.asarray(want))


@pytest.mark.parametrize("layer_index", [1, 2])
def test_expert_shares_sum_to_the_uncut_layer(layer_index):
    """Four shares of a layer's 16 routed experts, plus the shared expert
    counted once, give the uncut reference layer; the program's served
    block over each share gives that share's part."""
    key = ref.base_key(SEED)
    s = dict(S)
    x = jax.random.normal(jax.random.PRNGKey(7), (11, s["d"]))
    whole = ref.layer_weights(key, layer_index, s, False, held=(0, 16))
    c = whole["c"]
    h = ref._norm(x, c["ln"], s["eps"])
    uncut = ref.routed(h, c, s, (0, 16)) + ref._swiglu(h, c["shared"], None)
    total = ref._swiglu(h, c["shared"], None)
    for share in range(4):
        w = ref.layer_weights(key, layer_index, s, False, held=(4 * share, 4))
        part = ref.routed(h, w["c"], s, (4 * share, 4))
        total = total + part
        cfg = tiny_cfg(4 * share, 4)
        y, _ = moe.moe_block(cfg, w["c"], {}, x[None], backend="xla")
        np.testing.assert_allclose(
            np.asarray(y[0] - x), np.asarray(
                part + ref._swiglu(h, w["c"]["shared"], None)),
            atol=2e-5 * float(jnp.abs(uncut).max()), rtol=0)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=1e-5 * float(jnp.abs(uncut).max()), rtol=0)
