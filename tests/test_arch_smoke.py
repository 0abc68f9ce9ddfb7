"""Per-architecture smoke tests (reduced configs, CPU, 1 device).

For every assigned arch: instantiate the reduced sibling config, run the
full-sequence forward, assert output shapes and finiteness, and check that
the walker's modes agree: the prefill's last logits are the forward's, and
its caches are laid out as ``init_cache`` lays them.  For decode-capable
archs additionally check prefill+decode consistency: decoding token S after
a prefill of [0, S) must reproduce the full-sequence forward logits at
position S — this exercises every cache type (full KV, local ring buffer,
RG-LRU state + conv carry, RWKV matrix state + token shift).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import build

ARCHS = list(configs.ARCHS)


def make_batch(cfg, key, B=2, S=16):
    ks = jax.random.split(key, 2)
    batch = {}
    if cfg.embeds_only:
        batch["embeds"] = jax.random.normal(ks[0], (B, S, cfg.d_model),
                                            jnp.float32)
    else:
        batch["token_ids"] = jax.random.randint(ks[0], (B, S), 0, cfg.vocab)
        if cfg.mm_prefix:
            batch["mm_embeds"] = jax.random.normal(
                ks[1], (B, cfg.mm_prefix, cfg.mm_embed_dim), jnp.float32)
    return batch


@pytest.fixture(scope="module")
def built():
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = configs.get(arch).reduced()
            m = build(cfg, backend="xla")
            params = m.init(jax.random.PRNGKey(0))
            cache[arch] = (cfg, m, params)
        return cache[arch]

    return get


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_matches_assignment(arch):
    cfg = configs.get(arch)
    # exact numbers from the assignment table
    expect = {
        "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
        "rwkv6-7b": (32, 4096, 64, 64, 14336, 65536),
        "internvl2-2b": (24, 2048, 16, 8, 8192, 92553),
        "stablelm-1.6b": (24, 2048, 32, 32, 5632, 100352),
        "nemotron-4-15b": (32, 6144, 48, 8, 24576, 256000),
        "qwen1.5-32b": (64, 5120, 40, 40, 27392, 152064),
        "llama3.2-3b": (28, 3072, 24, 8, 8192, 128256),
        "hubert-xlarge": (48, 1280, 16, 16, 5120, 504),
        "dbrx-132b": (40, 6144, 48, 8, 10752, 100352),
        "qwen3-moe-235b-a22b": (94, 4096, 64, 4, 1536, 151936),
        "deepseek-v2-lite": (27, 2048, 16, 16, 10944, 102400),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.d_ff, cfg.vocab)
    assert got == expect
    if arch == "dbrx-132b":
        assert (cfg.moe.n_experts, cfg.moe.top_k) == (16, 4)
    if arch == "qwen3-moe-235b-a22b":
        assert (cfg.moe.n_experts, cfg.moe.top_k) == (128, 8)
    if arch == "deepseek-v2-lite":
        assert (cfg.moe.n_experts, cfg.moe.top_k, cfg.moe.d_ff,
                cfg.moe.n_shared, cfg.first_dense) == (64, 6, 1408, 2, 1)
        assert (cfg.mla.kv_rank, cfg.mla.q_dim, cfg.mla.v_dim) == (512, 192,
                                                                   128)


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_logit_shapes(built, arch):
    cfg, m, params = built(arch)
    B, S = 2, 16
    batch = make_batch(cfg, jax.random.PRNGKey(2), B, S)
    logits, _ = m.forward(params, batch)
    assert logits.shape == (B, S, cfg.vocab)
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_matches_prefill_last_logits(built, arch):
    """The walker without a cache and emitting one computes the same
    last-position logits."""
    cfg, m, params = built(arch)
    batch = make_batch(cfg, jax.random.PRNGKey(5), 2, 16)
    logits, _ = m.forward(params, batch)
    last, _, _ = m.prefill(params, batch, capacity=32)
    assert last.shape == (2, 1, cfg.vocab)
    np.testing.assert_allclose(np.asarray(last[:, 0]),
                               np.asarray(logits[:, -1]), atol=1e-5,
                               rtol=1e-5)


DECODE_ARCHS = [a for a in ARCHS if configs.get(a).has_decode]


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_prefill_caches_match_init_cache(built, arch):
    """Prefill's caches at capacity C have the tree, shapes and dtypes of
    ``init_cache(B, C)``: the engine splices one into the other."""
    cfg, m, params = built(arch)
    B, S, C = 2, 12, 48
    batch = make_batch(cfg, jax.random.PRNGKey(6), B, S)
    _, caches, _ = m.prefill(params, batch, capacity=C)
    layout = lambda t: jax.tree.map(lambda a: (a.shape, a.dtype), t)
    assert (jax.tree.structure(caches)
            == jax.tree.structure(m.init_cache(B, C)))
    assert layout(caches) == layout(jax.eval_shape(
        lambda: m.init_cache(B, C)))


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_prefill_decode_consistency(built, arch):
    """decode(token_S | prefill[0:S)) == forward[0:S+1)[S]."""
    cfg, m, params = built(arch)
    B, S = 2, 12
    key = jax.random.PRNGKey(3)
    full = make_batch(cfg, key, B, S + 1)
    prefix = dict(full)
    if cfg.embeds_only:
        pytest.skip("encoder-only")
    prefix["token_ids"] = full["token_ids"][:, :S]

    # ground truth: full forward, logits at position S
    logits_full, _ = m.forward(params, full)
    want = logits_full[:, S]

    # prefill [0, S) then decode token S
    last_logits, caches, _ = m.prefill(params, prefix)
    # prefill's last logits equal forward position S-1
    np.testing.assert_allclose(np.asarray(last_logits[:, 0]),
                               np.asarray(logits_full[:, S - 1]),
                               atol=2e-3, rtol=2e-3)
    step = {"token_ids": full["token_ids"][:, S:S + 1],
            "lengths": jnp.full((B,), S, jnp.int32)}
    got, _, _ = m.decode_step(params, caches, step)
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(want),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("arch", DECODE_ARCHS[:3])
def test_multi_step_decode_consistency(built, arch):
    """Three consecutive decode steps track the full forward."""
    cfg, m, params = built(arch)
    B, S, N = 1, 8, 3
    key = jax.random.PRNGKey(4)
    full = make_batch(cfg, key, B, S + N)
    logits_full, _ = m.forward(params, full)

    prefix = {k: (v[:, :S] if k == "token_ids" else v)
              for k, v in full.items()}
    _, caches, _ = m.prefill(params, prefix)
    for t in range(N):
        step = {"token_ids": full["token_ids"][:, S + t:S + t + 1],
                "lengths": jnp.full((B,), S + t, jnp.int32)}
        got, caches, _ = m.decode_step(params, caches, step)
        np.testing.assert_allclose(
            np.asarray(got[:, 0]), np.asarray(logits_full[:, S + t]),
            atol=3e-3, rtol=3e-3, err_msg=f"{arch} step {t}")


@pytest.mark.parametrize("arch", ARCHS)
def test_cell_support_matrix(arch):
    cfg = configs.get(arch)
    for shape in configs.SHAPES:
        ok, why = configs.cell_supported(cfg, shape)
        if shape == "prefill_32k":
            assert ok
        if shape == "long_500k":
            assert ok == (arch in ("recurrentgemma-9b", "rwkv6-7b")), why
        if shape == "decode_32k":
            assert ok == (arch != "hubert-xlarge")


def test_int8_kv_cache_decode_close(built):
    """int8-quantized KV cache stays within quantization tolerance."""
    import dataclasses
    cfg = configs.get("llama3.2-3b").reduced(n_layers=2)
    cfg8 = dataclasses.replace(cfg, kv_cache_dtype="int8")
    m = build(cfg, backend="xla")
    m8 = build(cfg8, backend="xla")
    params = m.init(jax.random.PRNGKey(0))
    B, S = 1, 12
    full = make_batch(cfg, jax.random.PRNGKey(7), B, S + 1)
    prefix = {"token_ids": full["token_ids"][:, :S]}
    step = {"token_ids": full["token_ids"][:, S:S + 1],
            "lengths": jnp.full((B,), S, jnp.int32)}
    _, c32, _ = m.prefill(params, prefix)
    got32, _, _ = m.decode_step(params, c32, step)
    _, c8, _ = m8.prefill(params, prefix)
    got8, _, _ = m8.decode_step(params, c8, step)
    # int8 absmax quantization: ~1% relative error on logits
    np.testing.assert_allclose(np.asarray(got8), np.asarray(got32),
                               atol=0.15, rtol=0.1)
    assert c8["groups"][0]["k"]["data"].dtype == jnp.int8
