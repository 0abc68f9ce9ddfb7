"""Kernel validation: shape/dtype sweeps, every backend vs the jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEYS = jax.random.split(jax.random.PRNGKey(42), 8)


def rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


def tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


ATTN_SHAPES = [
    # (B, Sq, Skv, Hq, Hkv, D)
    (1, 128, 128, 4, 4, 64),      # MHA
    (2, 256, 256, 8, 2, 64),      # GQA 4:1
    (1, 64, 64, 4, 1, 128),       # MQA
    (2, 96, 96, 4, 2, 32),        # non-128 seq (masked tail tiles)
]


class TestAttention:
    @pytest.mark.parametrize("shape", ATTN_SHAPES)
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
    @pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                               (True, 48)])
    def test_vs_oracle(self, shape, dtype, backend, causal, window):
        B, Sq, Skv, Hq, Hkv, D = shape
        q = rand(KEYS[0], (B, Sq, Hq, D), dtype)
        k = rand(KEYS[1], (B, Skv, Hkv, D), dtype)
        v = rand(KEYS[2], (B, Skv, Hkv, D), dtype)
        got = ops.attention(q, k, v, causal=causal, window=window,
                            backend=backend, block_q=64, block_kv=64)
        want = ref.attention(q, k, v, causal=causal, window=window)
        np.testing.assert_allclose(
            got.astype(jnp.float32), want.astype(jnp.float32), **tol(dtype))

    def test_decode_offset_queries(self):
        """Sq < Skv: queries are the last Sq positions (chunked prefill)."""
        q = rand(KEYS[0], (2, 32, 4, 64), jnp.float32)
        k = rand(KEYS[1], (2, 128, 4, 64), jnp.float32)
        v = rand(KEYS[2], (2, 128, 4, 64), jnp.float32)
        got = ops.attention(q, k, v, causal=True, backend="xla", block_kv=32)
        want = ref.attention(q, k, v, causal=True)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    def test_grad_flows_xla(self):
        q = rand(KEYS[0], (1, 64, 2, 32), jnp.float32)
        k = rand(KEYS[1], (1, 64, 2, 32), jnp.float32)
        v = rand(KEYS[2], (1, 64, 2, 32), jnp.float32)
        g = jax.grad(lambda q_: ops.attention(
            q_, k, v, backend="xla", block_kv=16).sum())(q)
        assert np.isfinite(np.asarray(g)).all()


class TestDecodeAttention:
    @pytest.mark.parametrize("shape", [(2, 128, 8, 2, 64), (1, 96, 4, 4, 32),
                                       (3, 256, 4, 1, 128)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("backend", ["xla", "pallas_interpret", "ref"])
    def test_vs_oracle(self, shape, dtype, backend):
        """Every backend reads the caches lane-dense, (B, S, Hkv·D), as
        the model stores them; the oracle gets them 4-D."""
        B, S, Hq, Hkv, D = shape
        q = rand(KEYS[0], (B, 1, Hq, D), dtype)
        k = rand(KEYS[1], (B, S, Hkv, D), dtype)
        v = rand(KEYS[2], (B, S, Hkv, D), dtype)
        lengths = jnp.array([S // 2 + 7 * i + 1 for i in range(B)],
                            jnp.int32) % S + 1
        got = ops.decode_attention(q, k.reshape(B, S, -1),
                                   v.reshape(B, S, -1), lengths,
                                   backend=backend)
        want = ref.attention(q, k, v, causal=True, lengths=lengths)
        np.testing.assert_allclose(
            got.astype(jnp.float32), want.astype(jnp.float32), **tol(dtype))


    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_kv_tiles_past_length_are_skipped(self, dtype):
        """Several kv tiles per sequence: tiles past a sequence's length
        are clamped to its last live tile and never computed."""
        from repro.kernels.decode_attention import decode_attention
        B, S, Hq, Hkv, D = 4, 128, 8, 2, 64
        q = rand(KEYS[0], (B, 1, Hq, D), dtype)
        k = rand(KEYS[1], (B, S, Hkv, D), dtype)
        v = rand(KEYS[2], (B, S, Hkv, D), dtype)
        lengths = jnp.array([1, 31, 32, 128], jnp.int32)
        got = decode_attention(q, k.reshape(1, B, S, -1),
                               v.reshape(1, B, S, -1), lengths, 0,
                               block_kv=32, interpret=True)
        want = ref.attention(q, k, v, causal=True, lengths=lengths)
        np.testing.assert_allclose(
            got.astype(jnp.float32), want.astype(jnp.float32), **tol(dtype))

    @pytest.mark.parametrize("layer", [0, 1, 2])
    @pytest.mark.parametrize("backend", ["xla", "pallas_interpret", "ref"])
    def test_stacked_leaf_reads_its_layer(self, layer, backend):
        """Over a stacked leaf (L, B, S, Hkv·D) and a layer index, every
        backend reads that layer, as the oracle does on the layer alone;
        the kernel takes the index as a traced scalar, several kv tiles."""
        from repro.kernels import decode_attention as dec
        L, B, S, Hq, Hkv, D = 3, 2, 96, 4, 2, 32
        q = rand(KEYS[0], (B, 1, Hq, D), jnp.float32)
        k = rand(KEYS[1], (L, B, S, Hkv, D), jnp.float32)
        v = rand(KEYS[2], (L, B, S, Hkv, D), jnp.float32)
        lengths = jnp.array([40, 96], jnp.int32)
        index = jnp.asarray(layer, jnp.int32)
        got = ops.decode_attention(q, k.reshape(L, B, S, -1),
                                   v.reshape(L, B, S, -1), lengths,
                                   layer=index, backend=backend)
        want = ref.attention(q, k[layer], v[layer], causal=True,
                             lengths=lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **tol(jnp.float32))
        if backend == "pallas_interpret":
            tiled = dec.decode_attention(
                q, k.reshape(L, B, S, -1), v.reshape(L, B, S, -1), lengths,
                index, block_kv=32, interpret=True)
            np.testing.assert_allclose(np.asarray(tiled), np.asarray(want),
                                       **tol(jnp.float32))


class TestLinearScan:
    @pytest.mark.parametrize("shape", [(2, 64, 32), (1, 100, 256),
                                       (3, 33, 128), (1, 300, 128)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
    @pytest.mark.parametrize("with_h0", [False, True])
    def test_vs_oracle(self, shape, dtype, backend, with_h0):
        B, S, D = shape
        a = jax.nn.sigmoid(rand(KEYS[0], shape, jnp.float32)).astype(dtype)
        b = rand(KEYS[1], shape, dtype)
        h0 = rand(KEYS[2], (B, D), dtype) if with_h0 else None
        h, hT = ops.linear_scan(a, b, h0, backend=backend)
        h_ref, hT_ref = ref.linear_scan(a, b, h0)
        np.testing.assert_allclose(h.astype(jnp.float32),
                                   h_ref.astype(jnp.float32), **tol(dtype))
        np.testing.assert_allclose(np.asarray(hT, np.float32),
                                   np.asarray(hT_ref, np.float32),
                                   **tol(dtype))

    def test_decay_composition_property(self):
        """Scanning [0:k) then [k:S) with carried state == one scan."""
        B, S, D = 2, 48, 16
        a = jax.nn.sigmoid(rand(KEYS[0], (B, S, D), jnp.float32))
        b = rand(KEYS[1], (B, S, D), jnp.float32)
        h_full, hT_full = ops.linear_scan(a, b, backend="xla")
        k = 20
        _, h1 = ops.linear_scan(a[:, :k], b[:, :k], backend="xla")
        h2_all, h2 = ops.linear_scan(a[:, k:], b[:, k:], h1, backend="xla")
        np.testing.assert_allclose(np.asarray(hT_full), np.asarray(h2),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(h_full[:, k:]),
                                   np.asarray(h2_all), atol=1e-5, rtol=1e-5)


class TestRWKV6:
    @pytest.mark.parametrize("shape", [(1, 32, 2, 16, 16), (2, 17, 4, 32, 32),
                                       (1, 64, 1, 64, 64), (1, 150, 2, 16, 16)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
    def test_vs_oracle(self, shape, dtype, backend):
        B, T, H, D, Dv = shape
        r = rand(KEYS[0], (B, T, H, D), dtype)
        k = rand(KEYS[1], (B, T, H, D), dtype) * 0.3
        v = rand(KEYS[2], (B, T, H, Dv), dtype)
        w = jax.nn.sigmoid(rand(KEYS[3], (B, T, H, D), jnp.float32) + 2.0
                           ).astype(dtype)
        u = rand(KEYS[4], (H, D), dtype) * 0.3
        s0 = rand(KEYS[5], (B, H, D, Dv), jnp.float32) * 0.1
        y, sT = ops.rwkv6(r, k, v, w, u, s0, backend=backend)
        y_ref, sT_ref = ref.rwkv6(r, k, v, w, u, s0)
        np.testing.assert_allclose(y.astype(jnp.float32),
                                   y_ref.astype(jnp.float32),
                                   atol=5e-2 if dtype == jnp.bfloat16
                                   else 1e-4, rtol=5e-2)
        np.testing.assert_allclose(np.asarray(sT), np.asarray(sT_ref),
                                   atol=5e-2 if dtype == jnp.bfloat16
                                   else 1e-4, rtol=5e-2)

    def test_state_streaming_property(self):
        """Chunked evaluation with carried state == full evaluation."""
        B, T, H, D, Dv = 1, 40, 2, 16, 16
        r = rand(KEYS[0], (B, T, H, D), jnp.float32)
        k = rand(KEYS[1], (B, T, H, D), jnp.float32) * 0.3
        v = rand(KEYS[2], (B, T, H, Dv), jnp.float32)
        w = jax.nn.sigmoid(rand(KEYS[3], (B, T, H, D), jnp.float32) + 2.0)
        u = rand(KEYS[4], (H, D), jnp.float32) * 0.3
        y_full, s_full = ops.rwkv6(r, k, v, w, u, backend="xla")
        cut = 23
        y1, s1 = ops.rwkv6(r[:, :cut], k[:, :cut], v[:, :cut], w[:, :cut],
                           u, backend="xla")
        y2, s2 = ops.rwkv6(r[:, cut:], k[:, cut:], v[:, cut:], w[:, cut:],
                           u, s1, backend="xla")
        np.testing.assert_allclose(np.asarray(y_full[:, cut:]),
                                   np.asarray(y2), atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(s_full), np.asarray(s2),
                                   atol=1e-5, rtol=1e-5)


class TestSlowdownSurfaceKernel:
    """The batched PCCS slowdown kernel vs the scalar contention model and
    the NumPy surface path (repro.core.lowering.slowdown_array)."""

    def _model(self):
        from repro.core.contention import PiecewiseModel
        return PiecewiseModel(
            (0.2, 0.6, 1.0), (0.2, 0.5, 0.8, 1.1),
            ((1.0, 1.1, 1.3, 1.5), (1.1, 1.4, 1.7, 1.9),
             (1.3, 1.7, 2.2, 2.5)))

    @pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
    def test_vs_numpy_surface_and_scalar_model(self, backend):
        from repro.core.lowering import slowdown_array
        from repro.kernels.slowdown import piecewise_slowdown
        m = self._model()
        rng = np.random.default_rng(0)
        own = rng.uniform(-0.1, 1.4, size=2048)
        ext = rng.uniform(-0.1, 1.4, size=2048)
        want = slowdown_array(m, own, ext)
        got = np.asarray(piecewise_slowdown(
            own.astype(np.float32), ext.astype(np.float32),
            m.own_knots, m.ext_knots, m.table, backend=backend))
        np.testing.assert_allclose(got, want, atol=5e-6, rtol=5e-6)
        # spot-check the scalar model directly, incl. exact knots/corners
        for o, e in [(0.2, 0.5), (0.6, 1.1), (0.0, 0.9), (0.9, 0.0),
                     (2.0, 2.0), (0.05, 0.05), (1.0, 1.1)]:
            g = float(np.asarray(piecewise_slowdown(
                jnp.float32(o)[None], jnp.float32(e)[None],
                m.own_knots, m.ext_knots, m.table, backend=backend))[0])
            assert g == pytest.approx(m.slowdown(o, e), abs=5e-6)

    def test_zero_demand_is_identity(self):
        from repro.kernels.slowdown import piecewise_slowdown
        m = self._model()
        own = jnp.asarray([0.0, 0.5, -1.0])
        ext = jnp.asarray([0.7, 0.0, 0.7])
        out = np.asarray(piecewise_slowdown(own, ext, m.own_knots,
                                            m.ext_knots, m.table,
                                            backend="xla"))
        np.testing.assert_allclose(out, [1.0, 1.0, 1.0])

    def test_nonmultiple_block_padding(self):
        from repro.kernels.slowdown import piecewise_slowdown
        m = self._model()
        rng = np.random.default_rng(1)
        # 2777 points in blocks of 8 rows x 128 lanes: three blocks, the
        # last one padded
        own = rng.uniform(0.05, 1.3, size=2777).astype(np.float32)
        ext = rng.uniform(0.05, 1.3, size=2777).astype(np.float32)
        a = np.asarray(piecewise_slowdown(own, ext, m.own_knots,
                                          m.ext_knots, m.table,
                                          backend="pallas_interpret",
                                          block=1024))
        b = np.asarray(piecewise_slowdown(own, ext, m.own_knots,
                                          m.ext_knots, m.table,
                                          backend="xla"))
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6)


class TestSlowdownAutoDispatch:
    """``backend="auto"``: the tiny-batch XLA fallback below the pallas
    launch threshold, and xla/interpret agreement at the boundary."""

    _model = TestSlowdownSurfaceKernel._model

    def _demands(self, n):
        rng = np.random.default_rng(n)
        return (rng.uniform(0.05, 1.3, size=n).astype(np.float32),
                rng.uniform(0.05, 1.3, size=n).astype(np.float32))

    @pytest.mark.parametrize("delta", [-1, 0, +1])
    def test_paths_agree_at_threshold_boundary(self, delta):
        from repro.kernels import ref
        from repro.kernels.slowdown import (_MIN_PALLAS_ELEMS,
                                            piecewise_slowdown)
        m = self._model()
        own, ext = self._demands(_MIN_PALLAS_ELEMS + delta)
        want = np.asarray(ref.piecewise_slowdown(
            own, ext, np.asarray(m.own_knots, np.float32),
            np.asarray(m.ext_knots, np.float32),
            np.asarray(m.table, np.float32)))
        for backend in ("auto", "xla", "pallas_interpret"):
            got = np.asarray(piecewise_slowdown(
                own, ext, m.own_knots, m.ext_knots, m.table,
                backend=backend))
            np.testing.assert_allclose(got, want, atol=5e-6, rtol=5e-6,
                                       err_msg=f"backend={backend} "
                                               f"n={len(own)}")

    def test_auto_prefers_xla_below_threshold_on_tpu(self, monkeypatch):
        """Even on TPU, auto must not pay a pallas launch for a tiny
        batch — below _MIN_PALLAS_ELEMS it stays on the fused XLA path."""
        from repro.kernels import slowdown
        calls = []
        real = slowdown._pallas_piecewise

        def recording(*args, **kwargs):
            calls.append(kwargs.get("interpret"))
            # run interpreted so the dispatch decision is testable on CPU
            kwargs["interpret"] = True
            return real(*args, **kwargs)

        monkeypatch.setattr(slowdown, "_pallas_piecewise", recording)
        monkeypatch.setattr(slowdown.jax, "default_backend",
                            lambda: "tpu")
        m = self._model()
        small = self._demands(slowdown._MIN_PALLAS_ELEMS - 1)
        slowdown.piecewise_slowdown(*small, m.own_knots, m.ext_knots,
                                    m.table, backend="auto")
        assert not calls, "tiny batch must take the XLA fallback"
        big = self._demands(slowdown._MIN_PALLAS_ELEMS)
        slowdown.piecewise_slowdown(*big, m.own_knots, m.ext_knots,
                                    m.table, backend="auto")
        assert len(calls) == 1, "at-threshold batch must launch pallas"

    def test_auto_is_xla_off_tpu_regardless_of_size(self, monkeypatch):
        from repro.kernels import slowdown
        monkeypatch.setattr(
            slowdown, "_pallas_piecewise",
            lambda *a, **k: pytest.fail("pallas launched off-TPU"))
        m = self._model()
        own, ext = self._demands(slowdown._MIN_PALLAS_ELEMS * 2)
        out = slowdown.piecewise_slowdown(own, ext, m.own_knots,
                                          m.ext_knots, m.table,
                                          backend="auto")
        assert out.shape == own.shape


class TestMLADecode:
    """Absorbed latent attention over (B, S, W) latent rows."""

    @pytest.mark.parametrize("shape", [(2, 128, 4, 64, 16), (3, 96, 16, 576, 64),
                                       (1, 64, 8, 160, 32)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
    def test_vs_oracle(self, shape, dtype, backend):
        B, S, H, W, rope = shape
        q = rand(KEYS[0], (B, H, W), dtype)
        cache = rand(KEYS[1], (B, S, W), dtype)
        lengths = jnp.array([S // 2 + 7 * i + 1 for i in range(B)],
                            jnp.int32) % S + 1
        got = ops.mla_decode(q * W ** -0.5, cache, lengths,
                             value_width=W - rope, backend=backend)
        want = ref.mla_decode(q * W ** -0.5, cache, lengths, W - rope)
        np.testing.assert_allclose(
            got.astype(jnp.float32), want.astype(jnp.float32), **tol(dtype))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_kv_tiles_past_length_are_skipped(self, dtype):
        """Several kv tiles per sequence; lengths at and across tile
        edges."""
        from repro.kernels.mla_decode import mla_decode
        B, S, H, W = 4, 128, 8, 96
        q = rand(KEYS[2], (B, H, W), dtype) * W ** -0.5
        cache = rand(KEYS[3], (B, S, W), dtype)
        lengths = jnp.array([1, 31, 32, 128], jnp.int32)
        got = mla_decode(q, cache[None], lengths, 0, value_width=64,
                         block_kv=32, interpret=True)
        want = ref.mla_decode(q, cache, lengths, 64)
        np.testing.assert_allclose(
            got.astype(jnp.float32), want.astype(jnp.float32), **tol(dtype))

    @pytest.mark.parametrize("layer", [0, 1, 2])
    @pytest.mark.parametrize("backend", ["xla", "pallas_interpret", "ref"])
    def test_stacked_leaf_reads_its_layer(self, layer, backend):
        """Over stacked latent rows (L, B, S, W) and a layer index, every
        backend reads that layer, as the oracle does on the layer alone;
        the kernel takes the index as a traced scalar, several kv tiles."""
        from repro.kernels.mla_decode import mla_decode
        L, B, S, H, W = 3, 2, 96, 8, 160
        q = rand(KEYS[2], (B, H, W), jnp.float32) * W ** -0.5
        cache = rand(KEYS[3], (L, B, S, W), jnp.float32)
        lengths = jnp.array([40, 96], jnp.int32)
        index = jnp.asarray(layer, jnp.int32)
        got = ops.mla_decode(q, cache, lengths, value_width=128, layer=index,
                             backend=backend)
        want = ref.mla_decode(q, cache[layer], lengths, 128)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **tol(jnp.float32))
        if backend == "pallas_interpret":
            tiled = mla_decode(q, cache, lengths, index, value_width=128,
                               block_kv=32, interpret=True)
            np.testing.assert_allclose(np.asarray(tiled), np.asarray(want),
                                       **tol(jnp.float32))


class TestMoEGmm:
    """Grouped SwiGLU over rows sorted by expert; rows past the groups
    come back as zeros."""

    @pytest.mark.parametrize("sizes", [(3, 0, 5, 2), (0, 0, 0, 9), (1, 1, 1, 1),
                                       (40, 30, 0, 50)])
    @pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
    def test_vs_oracle(self, sizes, backend):
        E, d, ff = 4, 128, 96
        m = 24 if sum(sizes) < 24 else 136
        x = rand(KEYS[0], (m, d), jnp.float32)
        wg = rand(KEYS[1], (E, d, ff), jnp.float32) * d ** -0.5
        wi = rand(KEYS[2], (E, d, ff), jnp.float32) * d ** -0.5
        wo = rand(KEYS[3], (E, ff, d), jnp.float32) * ff ** -0.5
        gs = jnp.array(sizes, jnp.int32)
        got = ops.moe_gmm(x, wg, wi, wo, gs, backend=backend)
        want = ref.moe_gmm(x, wg, wi, wo, gs)
        assert got.shape == (m, d)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-4)
        assert not np.asarray(got[sum(sizes):]).any()
