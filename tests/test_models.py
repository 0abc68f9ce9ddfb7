"""Model-component unit tests: norms, rope, MoE dispatch."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _prop import given, settings, st

from repro import configs
from repro.models import moe as moe_mod
from repro.models.layers import rmsnorm, rope


class TestRMSNorm:
    def test_unit_scale_normalizes(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 32)) * 7.0
        y = rmsnorm(x, jnp.zeros((32,)))
        rms = jnp.sqrt(jnp.mean(y * y, axis=-1))
        np.testing.assert_allclose(np.asarray(rms), 1.0, atol=1e-3)


class TestRoPE:
    def test_preserves_norm(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 4, 64))
        pos = jnp.broadcast_to(jnp.arange(16)[None], (2, 16))
        y = rope(x, pos, 10000.0)
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(y), axis=-1),
            np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)

    def test_relative_property(self):
        """<rope(q,i), rope(k,j)> depends only on i - j."""
        q = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, 64))
        k = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 1, 64))

        def dot(i, j):
            qi = rope(q, jnp.array([[i]]), 10000.0)
            kj = rope(k, jnp.array([[j]]), 10000.0)
            return float(jnp.sum(qi * kj))

        assert dot(5, 3) == pytest.approx(dot(12, 10), rel=1e-4)
        assert dot(0, 0) == pytest.approx(dot(9, 9), rel=1e-4)


MOE_ARCHS = ["dbrx-132b", "qwen3-moe-235b-a22b", "deepseek-v2-lite"]


class TestMoE:
    @pytest.mark.parametrize("seed", range(3))
    @pytest.mark.parametrize("arch", MOE_ARCHS)
    def test_matches_dense_reference(self, arch, seed):
        """The drop-free dispatch == per-token dense expert evaluation,
        shared experts included."""
        cfg = configs.get(arch).reduced()
        p, _ = moe_mod.init_moe(cfg, jax.random.PRNGKey(seed))
        x = jax.random.normal(jax.random.PRNGKey(seed + 10),
                              (2, 7, cfg.d_model))

        def swiglu(h, wg, wi, wo):
            return (jax.nn.silu(h @ wg) * (h @ wi)) @ wo

        def ref(x):
            B, S, d = x.shape
            h = rmsnorm(x, p["ln"]).reshape(B * S, d)
            probs = jax.nn.softmax(h @ p["router"], -1)
            gv, ei = jax.lax.top_k(probs, cfg.moe.top_k)
            if cfg.moe.norm_topk:
                gv = gv / gv.sum(-1, keepdims=True)
            out = jnp.zeros_like(h)
            for t in range(B * S):
                for j in range(cfg.moe.top_k):
                    e = int(ei[t, j])
                    out = out.at[t].add(gv[t, j] * swiglu(
                        h[t], p["wi_gate"][e], p["wi"][e], p["wo"][e]))
            if "shared" in p:
                sh = p["shared"]
                out = out + swiglu(h, sh["wi_gate"], sh["wi"], sh["wo"])
            return x + out.reshape(B, S, d)

        got, stats = moe_mod.moe_block(cfg, p, {}, x, backend="xla")
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref(x)),
                                   atol=1e-5, rtol=1e-5)
        assert int(stats["moe_dropped"]) == 0

    @pytest.mark.parametrize("arch", MOE_ARCHS)
    def test_served_rows_count_every_held_assignment(self, arch):
        """With every expert held, each of the T·k assignments is one
        computed row, and none is dropped."""
        cfg = configs.get(arch).reduced()
        assert cfg.moe.n_held == cfg.moe.n_experts
        p, _ = moe_mod.init_moe(cfg, jax.random.PRNGKey(0))
        B, S = 3, 5
        x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))
        _, stats = moe_mod.moe_block(cfg, p, {}, x, backend="xla")
        assert stats["moe_rows"].shape == (cfg.moe.n_held,)
        assert int(stats["moe_rows"].sum()) == B * S * cfg.moe.top_k
        assert int(stats["moe_dropped"]) == 0

    def test_experts_must_be_swiglu(self):
        """The one dispatch runs SwiGLU experts; a config asking for
        another activation is refused when it is built."""
        cfg = configs.get("dbrx-132b").reduced()
        with pytest.raises(ValueError, match="SwiGLU"):
            dataclasses.replace(cfg, act="gelu")


@given(b1=st.floats(0.0, 5.0), b2=st.floats(0.0, 5.0))
@settings(max_examples=30, deadline=None)
def test_rmsnorm_scale_equivariance(b1, b2):
    """rmsnorm(a*x) == rmsnorm(x) for a > 0 (scale invariance)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 16)) + b1
    a = 1.0 + b2
    y1 = rmsnorm(a * x, jnp.zeros((16,)))
    y2 = rmsnorm(x, jnp.zeros((16,)))
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=1e-4, rtol=1e-4)
