"""Ahead-of-time compiles of every Pallas kernel for a TPU v5e.

Nothing runs: each kernel is lowered and compiled by the TPU compiler for
a described (not attached) v5e chip at the widths the main path uses, so
Mosaic's tiling rules, scalar-prefetch use and VMEM budget are checked on
every change without a chip.  Each compiled program must contain the
kernel (``tpu_custom_call``); a kernel that fell back to XLA would not.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and under pytest-xdist
every worker imports this file.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import decode_attention, flash_attention, mla_decode
from repro.kernels import moe_gmm, rglru, rwkv6, search, slowdown
from repro.profiling import probes

#: stablelm-1.6b attention widths: 32 heads (MHA) of 64 dims
HEADS, HEAD_DIM = 32, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back here: keep
        # the persistent cache out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _kv(seq, dtype=jnp.bfloat16):
    return _sds((1, seq, HEADS, HEAD_DIM), dtype)


#: name -> (function, argument shapes); every function calls the Pallas
#: kernel itself, with interpret=False.
CASES = {
    "anneal_select_p8192": (
        lambda c, p, b, co, po, bo, u, t: search._pallas_select(
            c, p, b, co, po, bo, u, t, block=256, interpret=False),
        (_sds((8192, 32), jnp.int32),) * 3 + (_sds((8192,)),) * 4
        + (_sds(()),)),
    "piecewise_slowdown_n16384": (
        lambda o, e, ok, ek, t: slowdown._pallas_piecewise(
            o, e, ok, ek, t, block=8192, interpret=False),
        (_sds((16384,)), _sds((16384,)), _sds((6,)), _sds((8,)),
         _sds((6, 8)))),
    "flash_attention_prompt8": (
        lambda q, k, v: flash_attention.flash_attention(q, k, v),
        (_kv(8),) * 3),
    "flash_attention_prompt512": (
        lambda q, k, v: flash_attention.flash_attention(q, k, v),
        (_kv(512),) * 3),
    "decode_attention_4slots_cap128": (
        lambda q, k, v, n, i: decode_attention.decode_attention(q, k, v, n,
                                                                i),
        (_sds((4, 1, HEADS, HEAD_DIM), jnp.bfloat16),
         _sds((3, 4, 128, HEADS * HEAD_DIM), jnp.bfloat16),
         _sds((3, 4, 128, HEADS * HEAD_DIM), jnp.bfloat16),
         _sds((4,), jnp.int32), _sds((), jnp.int32))),
    "decode_attention_gqa_cap2048": (
        lambda q, k, v, n, i: decode_attention.decode_attention(q, k, v, n,
                                                                i),
        (_sds((4, 1, 24, 128), jnp.bfloat16),
         _sds((3, 4, 2048, 8 * 128), jnp.bfloat16),
         _sds((3, 4, 2048, 8 * 128), jnp.bfloat16),
         _sds((4,), jnp.int32), _sds((), jnp.int32))),
    "flash_attention_mla_prompt512": (
        lambda q, k, v: flash_attention.flash_attention(q, k, v),
        (_sds((1, 512, 16, 192), jnp.bfloat16),) * 2
        + (_sds((1, 512, 16, 128), jnp.bfloat16),)),
    "mla_decode_12slots_cap1024": (
        lambda q, c, n, i: mla_decode.mla_decode(q, c, n, i, value_width=512),
        (_sds((12, 16, 576), jnp.bfloat16),
         _sds((3, 12, 1024, 576), jnp.bfloat16),
         _sds((12,), jnp.int32), _sds((), jnp.int32))),
    "moe_gmm_decode_rows": (
        lambda x, g, i, o, n: moe_gmm.moe_gmm(x, g, i, o, n),
        (_sds((72, 2048), jnp.bfloat16), _sds((8, 2048, 1408), jnp.bfloat16),
         _sds((8, 2048, 1408), jnp.bfloat16),
         _sds((8, 1408, 2048), jnp.bfloat16), _sds((8,), jnp.int32))),
    "moe_gmm_prefill_rows": (
        lambda x, g, i, o, n: moe_gmm.moe_gmm(x, g, i, o, n),
        (_sds((3072, 2048), jnp.bfloat16),
         _sds((8, 2048, 1408), jnp.bfloat16),
         _sds((8, 2048, 1408), jnp.bfloat16),
         _sds((8, 1408, 2048), jnp.bfloat16), _sds((8,), jnp.int32))),
    "rglru_scan_prefill": (
        lambda a, b: rglru.rglru_scan(a, b),
        (_sds((2, 300, 2560), jnp.bfloat16),) * 2),
    "rglru_scan_decode": (
        lambda a, b: rglru.rglru_scan(a, b),
        (_sds((4, 1, 2560), jnp.bfloat16),) * 2),
    "rwkv6_scan_prefill": (
        lambda r, k, v, w, u: rwkv6.rwkv6_scan(r, k, v, w, u),
        (_sds((1, 200, 64, 64), jnp.bfloat16),) * 4
        + (_sds((64, 64), jnp.bfloat16),)),
    "rwkv6_scan_decode": (
        lambda r, k, v, w, u: rwkv6.rwkv6_scan(r, k, v, w, u),
        (_sds((4, 1, 64, 64), jnp.bfloat16),) * 4
        + (_sds((64, 64), jnp.bfloat16),)),
    "probe_stream_32mb": (
        lambda x, y: probes._pallas_stream(x, y, block=65536,
                                           interpret=False),
        (_sds((2_666_666,)),) * 2),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _shapes(hlo: str) -> dict[str, list[str]]:
    """Each instruction's result dims, by name."""
    return {m.group(1): m.group(2).split(",") for m in re.finditer(
        r"%([\w.-]+) = \w+\[([\d,]*)\]", hlo)}


def _whole_layer_moves(hlo: str, slots: int, capacity: int) -> list[str]:
    """The ``copy``, ``dynamic-slice`` and ``dynamic-update-slice``
    instructions of ``hlo`` (fused ones too) that move a whole cache layer
    or stack: a result (for an update, the update written) with dims of
    both ``slots`` and ``capacity``.  A one-row insert moves neither."""
    whole = {str(slots), str(capacity)}
    shapes = _shapes(hlo)
    out = []
    for line in hlo.splitlines():
        m = re.search(r"= \w+\[([\d,]*)\]\S*\s+(copy|dynamic-slice|"
                      r"dynamic-update-slice)\(([^)]*)\)", line)
        if not m:
            continue
        dims = m.group(1).split(",")
        if m.group(2) == "dynamic-update-slice":
            update = m.group(3).split(",")[1].strip().lstrip("%")
            dims = shapes.get(update, dims)
        if whole <= set(dims):
            out.append(line.strip())
    return out


def _decode_step_hlo(one_chip, model, slots, capacity):
    """The v5e-compiled decode step as ``ServingEngine`` jits it, with
    the cache donated; and the number of cache leaves."""
    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = place(model.abstract_params())
    caches = place(jax.eval_shape(lambda: model.init_cache(slots, capacity)))
    batch = place({"token_ids": _sds((slots, 1), jnp.int32),
                   "lengths": _sds((slots,), jnp.int32)})
    hlo = jax.jit(model.decode_step, donate_argnums=1).lower(
        params, caches, batch).compile().as_text()
    return hlo, len(jax.tree.leaves(caches))


def _assert_cache_updated_in_place(hlo, n_leaves, slots, capacity):
    """Every cache leaf is an input the output aliases, and no whole
    cache layer or stack is copied, sliced out or written back."""
    cache_params = re.findall(r"%caches\S*\.\d+ = \S+ parameter\((\d+)\)",
                              hlo)
    assert len(cache_params) == n_leaves
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo).group(1)
    aliased = set(re.findall(r"\((\d+), \{\}", alias))
    assert set(cache_params) <= aliased
    assert _whole_layer_moves(hlo, slots, capacity) == []


def _served_config(arch):
    """``arch`` at published widths, 3 layers and vocab 1,024, holding
    8 experts where it has MoE: the engine programs compiled below."""
    import dataclasses

    from repro import configs
    base = configs.get(arch)
    cfg = dataclasses.replace(base, n_layers=3, vocab=1024)
    if base.moe is not None:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(base.moe,
                                                               n_held=8))
    return cfg


def test_decode_step_keeps_the_cache_layout(one_chip):
    """stablelm-1.6b at published widths (3 layers, so the scan runs
    three groups; 2 slots of 512): the compiled decode step, its cache
    donated as the engine donates it, writes each new row into the
    stored stacks in place and the kernel reads its layer there, with no
    whole-layer ``copy``, slice or write-back."""
    from repro.models import build

    slots, capacity = 2, 512
    model = build(_served_config("stablelm-1.6b"), backend="pallas")
    hlo, n_leaves = _decode_step_hlo(one_chip, model, slots, capacity)
    names = re.findall(r"%(\w+)\.\d+ = \S+ custom-call\(", hlo)
    assert "decode_attention" in names
    _assert_cache_updated_in_place(hlo, n_leaves, slots, capacity)


def test_mla_decode_step_compiles_with_its_kernels(one_chip):
    """deepseek-v2-lite at published widths (the dense layer and two MoE
    layers, so the scan runs two groups; 8 of 64 experts held, vocab
    1,024, 2 slots of 384): the compiled decode step, its cache donated,
    runs the latent-attention and expert kernels under their names and
    updates every latent cache, the leading layer's too, in place."""
    from repro.models import build

    slots, capacity = 2, 384
    model = build(_served_config("deepseek-v2-lite"), backend="pallas")
    hlo, n_leaves = _decode_step_hlo(one_chip, model, slots, capacity)
    names = re.findall(r"%(\w+)\.\d+ = \S+ custom-call\(", hlo)
    assert "mla_decode" in names and "moe_gmm" in names
    _assert_cache_updated_in_place(hlo, n_leaves, slots, capacity)


@pytest.mark.parametrize("arch,kernels", [
    ("stablelm-1.6b", {"flash_attention"}),
    ("deepseek-v2-lite", {"flash_attention", "moe_gmm"})],
    ids=["stablelm-1.6b", "deepseek-v2-lite"])
def test_prefill_compiles_with_its_kernels(one_chip, arch, kernels):
    """The prefill as ``ServingEngine`` jits it (one prompt of 256 into a
    cache of 384) compiles for a v5e with its kernels under their names,
    and its caches come out in the engine's cache layout: the shapes and
    dtypes of ``init_cache(1, 384)``."""
    from repro.models import build

    capacity = 384
    model = build(_served_config(arch), backend="pallas")
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), model.abstract_params())
    batch = {"token_ids": jax.ShapeDtypeStruct((1, 256), jnp.int32,
                                               sharding=one_chip)}
    prefill = jax.jit(lambda p, b: model.prefill(p, b, capacity=capacity))
    hlo = prefill.lower(params, batch).compile().as_text()
    names = set(re.findall(r"%(\w+)\.\d+ = \S+ custom-call\(", hlo))
    assert kernels <= names
    layout = lambda t: jax.tree.map(lambda a: (a.shape, a.dtype), t)
    _, caches, _ = jax.eval_shape(prefill, params, batch)
    assert layout(caches) == layout(jax.eval_shape(
        lambda: model.init_cache(1, capacity)))
