"""The decode step updates the stored cache in place.

``transformer.decode_step`` carries the stacked group caches through its
scan and writes one row per layer and sequence into them; the kernels
read their layer from the stack by index; ``ServingEngine`` donates the
cache to the step.  Pinned here on the CPU against a plain unrolled
reference that applies each layer to its own slice of the stack: the
logits agree, and the new cache is the old one with exactly one row per
layer and sequence written at its position, every other byte unchanged.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import build, layers, transformer
from repro.obs import Tracer, get_tracer, set_tracer
from repro.serve.engine import ServingEngine

SLOTS, CAPACITY = 3, 16
#: float32 throughout (the reduced configurations): the scan and the
#: unrolled reference may fuse differently, not compute differently
TOL = dict(rtol=1e-5, atol=1e-5)


def _cfg(case):
    if case == "attn":
        return configs.get("llama3.2-3b").reduced(n_layers=3, vocab=64)
    if case == "int8":
        return configs.get("llama3.2-3b").reduced(
            n_layers=3, vocab=64, kv_cache_dtype="int8")
    if case == "local":       # two (rglru, rglru, local) groups + a tail
        return configs.get("recurrentgemma-9b").reduced(
            n_layers=7, vocab=64, local_window=8)
    if case == "mla":         # the dense leading layer + two scanned
        return configs.get("deepseek-v2-lite").reduced(n_layers=3, vocab=64)
    if case == "rwkv":
        return configs.get("rwkv6-7b").reduced(n_layers=3, vocab=64)
    raise ValueError(case)


CASES = ["attn", "int8", "local", "mla", "rwkv"]
#: tokens cached per sequence; the ring (window 8) wraps for 11 and 8
LENGTHS = np.array([11, 5, 8], np.int32)


def _filled_cache(model, key):
    """A cache of the engine's shapes holding random values, so that a
    byte the step should not touch cannot be zero by chance."""
    caches = model.init_cache(SLOTS, CAPACITY)
    leaves, tree = jax.tree.flatten(caches)
    keys = jax.random.split(key, len(leaves))
    out = []
    for a, k in zip(leaves, keys):
        if a.dtype == jnp.int8:
            out.append(jax.random.randint(k, a.shape, -127, 128).astype(
                jnp.int8))
        else:
            out.append((0.5 * jax.random.normal(k, a.shape)).astype(a.dtype))
    return jax.tree.unflatten(tree, out)


def _unrolled_decode(model, params, caches, batch):
    """One decode step layer by layer, each on its own cache slice: the
    step as it was before the scan carried the cache."""
    cfg, rules = model.cfg, model.rules
    lengths = batch["lengths"]
    x = layers.embed_tokens(cfg, params["emb"], rules, batch)
    positions = lengths[:, None]
    kinds = cfg.layer_kinds
    P = len(cfg.block_pattern)

    def one(x, kind, lp, cache):
        x, cache, _ = transformer.apply_layer(
            cfg, kind, lp, rules, x, positions, cache=cache,
            lengths=lengths, backend=model.backend)
        return x, cache

    new = {}
    if "lead" in params:
        new["lead"] = []
        for i, lp in enumerate(params["lead"]):
            x, c = one(x, kinds[i], lp, caches["lead"][i])
            new["lead"].append(c)
        new["lead"] = tuple(new["lead"])
    per_group = []
    n_groups = jax.tree.leaves(params["groups"])[0].shape[0]
    for g in range(n_groups):
        row = []
        for pos in range(P):
            x, c = one(x, cfg.block_pattern[pos],
                       _take(params["groups"][pos], g),
                       _take(caches["groups"][pos], g))
            row.append(c)
        per_group.append(tuple(row))
    new["groups"] = jax.tree.map(lambda *xs: jnp.stack(xs), *per_group)
    tail = []
    n_scanned = len(kinds) - len(params["tail"])
    for i, lp in enumerate(params["tail"]):
        x, c = one(x, kinds[n_scanned + i], lp, caches["tail"][i])
        tail.append(c)
    new["tail"] = tuple(tail)
    return layers.logits_head(cfg, params["emb"], rules, x), new


def _host(tree):
    """Host copies of a tree's arrays.  (A NumPy view of a CPU array
    shares its buffer, which JAX then cannot donate.)"""
    return jax.tree.map(lambda a: np.asarray(jnp.copy(a)), tree)


def _take(tree, g):
    """Layer ``g`` of a stacked tree."""
    return jax.tree.map(lambda a: a[g], tree)


def _written_rows(kind, leaf_shape):
    """Mask of the rows one decode step may write in a stored attention
    leaf, ``(L, B, S, ·)`` stacked or ``(B, S, ·)``: per layer and
    sequence the row at its position (modulo the ring for ``local``)."""
    mask = np.zeros(leaf_shape, bool)
    cap = leaf_shape[-2]
    slots = LENGTHS % cap if kind == "local" else LENGTHS
    for b, s in enumerate(slots):
        mask[..., b, s, :] = True
    return mask


def _cache_leaves(cfg, caches):
    """(kind, part, leaf) of every cache leaf, its layer's kind beside it."""
    kinds = cfg.layer_kinds
    out = [(kinds[i], "lead", c) for i, c in enumerate(caches.get("lead", ()))]
    out += [(cfg.block_pattern[pos], "groups", c)
            for pos, c in enumerate(caches["groups"])]
    n_scanned = len(kinds) - len(caches["tail"])
    out += [(kinds[n_scanned + i], "tail", c)
            for i, c in enumerate(caches["tail"])]
    return [(k, part, leaf) for k, part, c in out
            for leaf in jax.tree.leaves(c)]


@pytest.fixture(scope="module", params=CASES)
def stepped(request):
    """(cfg, old cache, reference (logits, cache), step's (logits, cache))
    with the step jitted and donated as the engine runs it."""
    cfg = _cfg(request.param)
    model = build(cfg, backend="xla")
    params = model.init(jax.random.PRNGKey(1))
    caches = _filled_cache(model, jax.random.PRNGKey(2))
    batch = {"token_ids": jnp.array([[3], [7], [11]], jnp.int32),
             "lengths": jnp.asarray(LENGTHS)}
    old = _host(caches)
    want = jax.jit(lambda p, c, b: _unrolled_decode(model, p, c, b))(
        params, caches, batch)
    want = jax.tree.map(np.asarray, want)
    logits, new, _ = jax.jit(model.decode_step, donate_argnums=1)(
        params, caches, batch)
    assert all(a.is_deleted() for a in jax.tree.leaves(caches))
    return cfg, old, want, (np.asarray(logits), jax.tree.map(np.asarray, new))


def test_logits_match_each_layer_on_its_slice(stepped):
    _, _, (want_logits, _), (logits, _) = stepped
    np.testing.assert_allclose(logits, want_logits, **TOL)


def test_new_cache_matches_each_layer_on_its_slice(stepped):
    cfg, _, (_, want), (_, new) = stepped
    assert jax.tree.structure(new) == jax.tree.structure(want)
    for got, ref in zip(jax.tree.leaves(new), jax.tree.leaves(want)):
        assert got.shape == ref.shape and got.dtype == ref.dtype
        if got.dtype == np.int8:     # a rounding step may land either side
            assert np.abs(got.astype(int) - ref.astype(int)).max() <= 1
        else:
            np.testing.assert_allclose(got, ref, **TOL)


def test_one_row_per_layer_and_sequence_written(stepped):
    """Outside the new rows every byte of every attention leaf is the old
    one, and each new row was written (it differs from the random old
    one).  A recurrent layer's state, small, is rewritten whole: for
    every layer and sequence."""
    cfg, old, _, (_, new) = stepped
    for (kind, part, a), (_, _, b) in zip(_cache_leaves(cfg, old),
                                          _cache_leaves(cfg, new)):
        if kind in ("rglru", "rwkv"):
            lead = 2 if part == "groups" else 1
            per_state = (a != b).reshape(*a.shape[:lead], -1).any(axis=-1)
            assert per_state.all(), (kind, part)
            continue
        mask = _written_rows(kind, a.shape)
        np.testing.assert_array_equal(np.where(mask, 0, a),
                                      np.where(mask, 0, b),
                                      err_msg=f"{kind} {part}")
        rows = (a != b).reshape(-1, a.shape[-1]).any(axis=-1)
        assert rows.sum() == mask[..., 0].sum(), (kind, part)


# ---------------------------------------------------------------------------
# the engine donates the cache
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_lm():
    cfg = configs.get("llama3.2-3b").reduced(n_layers=3, vocab=64)
    model = build(cfg, backend="xla")
    return cfg, model, model.init(jax.random.PRNGKey(0))


PROMPTS = [(np.arange(4 + 2 * i) * (i + 1)) % 64 for i in range(4)]


def _serve(tiny_lm, prompts, decode=None):
    _, model, params = tiny_lm
    eng = ServingEngine(model, params, max_slots=2, capacity=32)
    if decode is not None:
        eng._decode = decode
    reqs = [eng.submit(p, max_new=5) for p in prompts]
    eng.run_until_drained()
    return eng, reqs


def test_every_step_is_in_place(tiny_lm):
    eng, _ = _serve(tiny_lm, PROMPTS)
    assert eng.counters.steps > 0
    assert eng.counters.cache_inplace_steps == eng.counters.steps


def test_tokens_match_an_undonated_step(tiny_lm):
    """The same run through a step that donates nothing, jitted here:
    the same tokens, and no step counted in place."""
    _, model, _ = tiny_lm
    donated, reqs = _serve(tiny_lm, PROMPTS)
    kept, reqs_kept = _serve(tiny_lm, PROMPTS, jax.jit(model.decode_step))
    assert [r.tokens for r in reqs] == [r.tokens for r in reqs_kept]
    assert kept.counters.steps == donated.counters.steps
    assert kept.counters.cache_inplace_steps == 0


def test_admission_after_donated_steps_splices(tiny_lm):
    """A request admitted after donated steps lands in the cache the
    steps left: its slot holds its prompt's prefill rows, the other slot
    keeps its own, and both requests decode as they do alone."""
    _, model, params = tiny_lm
    eng = ServingEngine(model, params, max_slots=2, capacity=32)
    first = eng.submit(PROMPTS[3], max_new=8)
    for _ in range(3):
        eng.step()
    assert eng.counters.cache_inplace_steps == 3
    before = _host(eng.caches)
    second = eng.submit(PROMPTS[1], max_new=5)
    eng._admit(get_tracer())
    assert eng.slots[1] is second
    _, cache1, _ = eng._prefill(params, {"token_ids": jnp.asarray(
        PROMPTS[1])[None]})
    n = len(PROMPTS[1])
    for got, one, was in zip(jax.tree.leaves(_host(eng.caches["groups"])),
                             jax.tree.leaves(cache1["groups"]),
                             jax.tree.leaves(before["groups"])):
        np.testing.assert_array_equal(got[:, 1, :n], np.asarray(one)[:, 0, :n])
        np.testing.assert_array_equal(got[:, 0], was[:, 0])
    eng.run_until_drained()
    _, (alone_first,) = _serve(tiny_lm, [PROMPTS[3]])
    _, (alone_second,) = _serve(tiny_lm, [PROMPTS[1]])
    assert first.tokens[:5] == alone_first.tokens
    assert second.tokens == alone_second.tokens
    assert eng.counters.cache_inplace_steps == eng.counters.steps


def test_decode_span_carries_the_count(tiny_lm):
    tr = Tracer()
    prev = set_tracer(tr)
    try:
        eng, _ = _serve(tiny_lm, PROMPTS[:2])
    finally:
        set_tracer(prev)
    counts = [e["args"]["cache_inplace_steps"] for e in tr.events()
              if e["name"] == "engine.decode"]
    assert counts == list(range(1, eng.counters.steps + 1))


def test_config_cases_cover_every_cache_kind():
    kinds = {k for case in CASES for k in _cfg(case).layer_kinds}
    assert {"attn", "local", "mla", "rglru", "rwkv"} <= kinds
    assert _cfg("int8").kv_cache_dtype == "int8"
