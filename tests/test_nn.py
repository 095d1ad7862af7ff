import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import nn
from repro.nn.attention import build_mask, NEG_INF


KEY = jax.random.PRNGKey(0)


class TestLinearNorms:
    def test_linear_shapes_bias(self):
        p = nn.linear_init(KEY, 8, 12)
        y = nn.linear_apply(p, jnp.ones((3, 8)))
        assert y.shape == (3, 12)

    def test_rmsnorm_unit_scale(self):
        p = nn.rmsnorm_init(16)
        x = jax.random.normal(KEY, (4, 16)) * 10
        y = nn.rmsnorm_apply(p, x)
        rms = jnp.sqrt(jnp.mean(y ** 2, axis=-1))
        np.testing.assert_allclose(rms, 1.0, rtol=1e-3)

    def test_layernorm_zero_mean(self):
        p = nn.layernorm_init(16)
        x = jax.random.normal(KEY, (4, 16)) + 3.0
        y = nn.layernorm_apply(p, x)
        np.testing.assert_allclose(jnp.mean(y, -1), 0.0, atol=1e-4)

    def test_batchnorm_stats(self):
        p = nn.batchnorm_init(3)
        x = jax.random.normal(KEY, (8, 4, 4, 3)) * 5 + 2
        y = nn.batchnorm_apply(p, x)
        np.testing.assert_allclose(y.mean((0, 1, 2)), 0.0, atol=1e-4)
        np.testing.assert_allclose(y.std((0, 1, 2)), 1.0, atol=1e-2)

    def test_norm_dtype_preserved(self):
        p = nn.rmsnorm_init(8)
        y = nn.rmsnorm_apply(p, jnp.ones((2, 8), dtype=jnp.bfloat16))
        assert y.dtype == jnp.bfloat16


class TestRoPE:
    def test_rope_preserves_norm(self):
        inv = nn.rope_frequencies(8)
        x = jax.random.normal(KEY, (2, 5, 3, 8))
        pos = jnp.broadcast_to(jnp.arange(5), (2, 5))
        y = nn.apply_rope(x, pos, inv)
        np.testing.assert_allclose(
            jnp.linalg.norm(y, axis=-1), jnp.linalg.norm(x, axis=-1),
            rtol=1e-5)

    def test_rope_relative_shift(self):
        """Rotating q and k by the same offset keeps their dot product."""
        inv = nn.rope_frequencies(16)
        q = jax.random.normal(KEY, (1, 1, 1, 16))
        k = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, 16))
        def dot_at(pq, pk):
            qq = nn.apply_rope(q, jnp.full((1, 1), pq), inv)
            kk = nn.apply_rope(k, jnp.full((1, 1), pk), inv)
            return float(jnp.sum(qq * kk))
        assert dot_at(3, 1) == pytest.approx(dot_at(13, 11), rel=1e-4)


class TestMasks:
    def test_causal(self):
        pos = jnp.arange(4)[None]
        m = build_mask(pos, pos, causal=True, window=None)
        expect = np.triu(np.full((4, 4), NEG_INF), k=1)
        np.testing.assert_allclose(m[0], expect)

    def test_window(self):
        pos = jnp.arange(6)[None]
        m = build_mask(pos, pos, causal=True, window=2)
        allowed = np.asarray(m[0] == 0)
        for i in range(6):
            for j in range(6):
                assert allowed[i, j] == (j <= i and j > i - 2)

    def test_k_valid(self):
        qpos = jnp.arange(3)[None]
        kpos = jnp.arange(3)[None]
        valid = jnp.asarray([[True, False, True]])
        m = build_mask(qpos, kpos, causal=False, window=None, k_valid=valid)
        assert (np.asarray(m[0][:, 1]) == NEG_INF).all()


class TestAttention:
    def test_gqa_shapes(self):
        p = nn.attention_init(KEY, 32, 8, 2)
        y = nn.attention_apply(p, jnp.ones((2, 6, 32)), n_heads=8,
                               n_kv_heads=2)
        assert y.shape == (2, 6, 32)

    def test_causality(self):
        """Changing a future token must not change earlier outputs."""
        p = nn.attention_init(KEY, 32, 4, 4)
        x = jax.random.normal(KEY, (1, 8, 32))
        y1 = nn.attention_apply(p, x, n_heads=4, n_kv_heads=4)
        x2 = x.at[:, -1].add(10.0)
        y2 = nn.attention_apply(p, x2, n_heads=4, n_kv_heads=4)
        np.testing.assert_allclose(y1[:, :-1], y2[:, :-1], atol=1e-5)

    def test_qk_norm_finite_large_inputs(self):
        p = nn.attention_init(KEY, 32, 4, 2, qk_norm=True)
        x = jax.random.normal(KEY, (1, 8, 32)) * 1e3
        y = nn.attention_apply(p, x, n_heads=4, n_kv_heads=2, qk_norm=True)
        assert jnp.isfinite(y).all()


class TestMLPConv:
    def test_swiglu(self):
        p = nn.mlp_init(KEY, 16, 32)
        assert nn.mlp_apply(p, jnp.ones((2, 16))).shape == (2, 16)
        assert "w_gate" in p

    def test_gelu_bias(self):
        p = nn.mlp_init(KEY, 16, 32, gated=False, use_bias=True)
        assert "w_gate" not in p and "b_in" in p
        assert nn.mlp_apply(p, jnp.ones((2, 16))).shape == (2, 16)

    def test_conv_updown(self):
        pc = nn.conv2d_init(KEY, 3, 8, 4)
        pt = nn.conv_transpose2d_init(KEY, 8, 3, 4)
        img = jax.random.normal(KEY, (2, 16, 16, 3))
        down = nn.conv2d_apply(pc, img)
        assert down.shape == (2, 8, 8, 8)
        up = nn.conv_transpose2d_apply(pt, down)
        assert up.shape == (2, 16, 16, 3)

    @pytest.mark.parametrize("image_size", [8, 64])
    def test_dcgan_disc_head_is_the_valid_conv(self, image_size):
        """The DCGAN discriminator's last layer (a valid 4x4 conv to one
        channel, written as a contraction) equals the conv it replaces."""
        from repro.configs.dcgan import DCGANConfig
        from repro.models import dcgan
        cfg = DCGANConfig(nz=8, ngf=8, ndf=8, nc=3, image_size=image_size)
        params = dcgan.discriminator_init(KEY, cfg)
        img = jax.random.normal(KEY, (3, image_size, image_size, 3))
        x = jax.nn.leaky_relu(
            nn.conv2d_apply(params["layers"][0]["conv"], img), 0.2)
        for layer in params["layers"][1:-1]:
            x = nn.conv2d_apply(layer["conv"], x)
            x = jax.nn.leaky_relu(nn.batchnorm_apply(layer["bn"], x), 0.2)
        with jax.default_matmul_precision("highest"):
            conv = nn.conv2d_apply(params["layers"][-1]["conv"], x,
                                   stride=1, padding=0)
            out = dcgan.discriminator_apply(params, cfg, img)
        assert conv.shape == (3, 1, 1, 1)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(conv).reshape(3), rtol=1e-5,
                                   atol=1e-5)


class TestTensorParallel:
    """Megatron column/row-parallel paths (nn/tp.py, linear, mlp) must
    reproduce the dense math — forward AND gradients — with the model
    axis simulated by `jax.vmap(axis_name=...)` (the real shard_map
    execution is pinned by the TP equivalence matrix)."""

    AXIS = "model"
    TP = 2

    def _split(self, x, dim):
        return jnp.stack(jnp.split(x, self.TP, axis=dim))

    def _rep(self, x):
        return jnp.broadcast_to(x[None], (self.TP,) + x.shape)

    def test_linear_column_then_row_matches_dense(self):
        k1, k2, kx = jax.random.split(KEY, 3)
        w1 = jax.random.normal(k1, (8, 12))
        w2 = jax.random.normal(k2, (12, 6))
        x = jax.random.normal(kx, (3, 8))
        ref = jnp.tanh(x @ w1) @ w2

        def tp_fn(w1s, w2s):
            h = jnp.tanh(nn.linear_apply({"w": w1s}, x,
                                         tp_axis=self.AXIS,
                                         tp_mode="column"))
            return nn.linear_apply({"w": w2s}, h, tp_axis=self.AXIS,
                                   tp_mode="row")

        out = jax.vmap(tp_fn, axis_name=self.AXIS)(
            self._split(w1, -1), self._split(w2, 0))
        for r in range(self.TP):
            np.testing.assert_allclose(out[r], ref, atol=1e-5)

    def test_linear_gather_output_matches_dense(self):
        kw, kx = jax.random.split(KEY)
        w = jax.random.normal(kw, (8, 12))
        x = jax.random.normal(kx, (3, 8))
        ref = x @ w
        out = jax.vmap(
            lambda ws: nn.linear_apply({"w": ws}, x, tp_axis=self.AXIS,
                                       tp_mode="column",
                                       gather_output=True),
            axis_name=self.AXIS)(self._split(w, -1))
        for r in range(self.TP):
            np.testing.assert_allclose(out[r], ref, atol=1e-5)

    def test_linear_tp_requires_mode(self):
        p = nn.linear_init(KEY, 8, 12, use_bias=False)
        with pytest.raises(ValueError, match="tp_mode"):
            jax.vmap(lambda w: nn.linear_apply({"w": w}, jnp.ones((2, 8)),
                                               tp_axis=self.AXIS),
                     axis_name=self.AXIS)(self._rep(p["w"]))

    def _shard_mlp(self, p):
        sh = {"w_in": self._split(p["w_in"], -1),
              "w_out": self._split(p["w_out"], 0)}
        if "w_gate" in p:
            sh["w_gate"] = self._split(p["w_gate"], -1)
        if "b_in" in p:
            sh["b_in"] = self._split(p["b_in"], -1)
        if "b_out" in p:
            sh["b_out"] = self._rep(p["b_out"])
        return sh

    @pytest.mark.parametrize("gated,use_bias", [(True, False),
                                                (False, True)])
    def test_mlp_tp_matches_dense_forward_and_grad(self, gated, use_bias):
        p = nn.mlp_init(KEY, 16, 32, gated=gated, use_bias=use_bias)
        x = jax.random.normal(jax.random.fold_in(KEY, 1), (4, 16))

        def loss_dense(p):
            return jnp.sum(nn.mlp_apply(p, x) ** 2)

        def loss_tp(ps):
            return jnp.sum(nn.mlp_apply(ps, x, tp_axis=self.AXIS) ** 2)

        np.testing.assert_allclose(
            jax.vmap(lambda ps: nn.mlp_apply(ps, x, tp_axis=self.AXIS),
                     axis_name=self.AXIS)(self._shard_mlp(p))[0],
            nn.mlp_apply(p, x), atol=1e-4)

        g_dense = self._shard_mlp(jax.grad(loss_dense)(p))
        g_tp = jax.vmap(jax.grad(loss_tp),
                        axis_name=self.AXIS)(self._shard_mlp(p))
        # replicated b_out grads are identical per rank (each rank sees
        # the full replicated cotangent), matching the dense grad
        for name in g_dense:
            ref = (g_dense[name] if name != "b_out"
                   else self._rep(jax.grad(loss_dense)(p)["b_out"]))
            np.testing.assert_allclose(np.asarray(g_tp[name]),
                                       np.asarray(ref), atol=1e-3,
                                       rtol=1e-4)

    def test_fused_gate_rejects_tp(self):
        p = nn.mlp_init(KEY, 16, 32, fuse_gate=True)
        with pytest.raises(ValueError, match="fuse_gate"):
            jax.vmap(lambda ps: nn.mlp_apply(ps, jnp.ones((2, 16)),
                                             tp_axis=self.AXIS),
                     axis_name=self.AXIS)(
                jax.tree.map(self._rep, p))

    def test_tp_helpers_identity_without_axis(self):
        x = jnp.arange(6.0).reshape(2, 3)
        np.testing.assert_array_equal(nn.copy_to_tp(x, None), x)
        np.testing.assert_array_equal(nn.reduce_from_tp(x, None), x)
        np.testing.assert_array_equal(nn.gather_from_tp(x, None), x)
        assert nn.tp_rank(None) == 0
