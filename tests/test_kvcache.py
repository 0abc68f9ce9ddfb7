"""The KV cache's lane-dense layout ``(B, S, Hkv·D)`` against a 4-D
``(B, S, Hkv, D)`` reference of the same operations."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import kvcache

B, S, H, D = 2, 16, 2, 8
WINDOW = 8                         # ring layout: capacity min(window, S)
KEYS = jax.random.split(jax.random.PRNGKey(3), 4)
DTYPES = ["bfloat16", "int8", "float32"]
WINDOWS = [None, WINDOW]


def _quant4(x):
    """Per (position, head) absmax over D, kept 4-D."""
    s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    s = jnp.maximum(s, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127, 127)
    return q.astype(jnp.int8), s


def _ref_layer(x, pos, cap, dtype):
    """A 4-D cache of capacity ``cap`` with x (B, n, H, D) at ``pos``."""
    b, _, h, d = x.shape
    if dtype == "int8":
        q, s = _quant4(x)
        return {"data": jnp.zeros((b, cap, h, d), jnp.int8).at[:, pos].set(q),
                "scale": jnp.zeros((b, cap, h, 1), jnp.float32
                                   ).at[:, pos].set(s)}
    return {"data": jnp.zeros((b, cap, h, d), jnp.dtype(dtype)
                              ).at[:, pos].set(x.astype(dtype))}


def _assert_same(layer, ref4):
    """The lane-dense layer holds the 4-D reference's values, row for row."""
    data4 = ref4["data"]
    np.testing.assert_array_equal(
        np.asarray(layer["data"]),
        np.asarray(data4.reshape(*data4.shape[:2], -1)))
    assert set(layer) == set(ref4)
    if "scale" in ref4:
        np.testing.assert_array_equal(np.asarray(layer["scale"]),
                                      np.asarray(ref4["scale"][..., 0]))


def _prompt(n):
    return jax.random.normal(KEYS[0], (B, n, H, D), jnp.float32)


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_init_layer_is_lane_dense(dtype, window):
    cap = S if window is None else window
    layer = kvcache.init_layer(B, cap, H, D, dtype)
    assert layer["data"].shape == (B, cap, H * D)
    assert kvcache.size(layer) == cap
    if dtype == "int8":
        assert layer["scale"].shape == (B, cap, H)


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_from_prefill_matches_4d(dtype, window):
    n = 12                          # longer than the ring window
    k = _prompt(n)
    kc, vc = kvcache.from_prefill(k, -k, S, dtype, window)
    if window is None:
        cap, pos, take = S, jnp.arange(n), k
    else:
        cap = min(window, S)
        pos, take = jnp.arange(n - cap, n) % cap, k[:, n - cap:]
    _assert_same(kc, _ref_layer(take, pos, cap, dtype))
    _assert_same(vc, _ref_layer(-take, pos, cap, dtype))


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_insert_matches_4d(dtype, window):
    n = 12
    k = _prompt(n)
    layer, _ = kvcache.from_prefill(k, k, S, dtype, window)
    cap = kvcache.size(layer)
    new = jax.random.normal(KEYS[1], (B, H, D), jnp.float32)
    lengths = jnp.array([n, n - 3], jnp.int32)
    got = kvcache.insert(layer, new, lengths, window)
    # the 4-D reference: the prefilled layer, then the new row at its slot
    want = {name: a.reshape(B, cap, H, -1) for name, a in layer.items()}
    slot = lengths % cap if window is not None else lengths
    rows = jnp.arange(B)
    if dtype == "int8":
        q, s = _quant4(new)
        want = {"data": want["data"].at[rows, slot].set(q),
                "scale": want["scale"].at[rows, slot].set(s)}
    else:
        want = {"data": want["data"].at[rows, slot].set(new.astype(dtype))}
    _assert_same(got, want)


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_dequant_matches_4d(dtype, window):
    k = _prompt(12)
    layer, _ = kvcache.from_prefill(k, k, S, dtype, window)
    got = kvcache.dequant(layer)
    four = {name: a.reshape(*a.shape[:2], H, -1) for name, a in layer.items()}
    if dtype == "int8":
        want = (four["data"].astype(jnp.float32) * four["scale"]
                ).astype(jnp.bfloat16)
        assert got.dtype == jnp.bfloat16
    else:
        want = four["data"]
    assert got.shape == layer["data"].shape
    np.testing.assert_array_equal(
        np.asarray(got.astype(jnp.float32)),
        np.asarray(want.reshape(got.shape).astype(jnp.float32)))

