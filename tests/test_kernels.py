"""Per-kernel validation: shape/dtype sweeps, assert_allclose against the
pure-jnp oracles, in interpret mode (CPU executes the kernel body)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

KEY = jax.random.PRNGKey(0)


class TestInterpretMode:
    """The one platform probe every kernel wrapper asks at call time."""

    def test_cpu_backend_interprets(self):
        from repro.kernels import interpret_mode
        assert jax.default_backend() == "cpu"
        assert interpret_mode() is True

    def test_tpu_compiles(self, monkeypatch):
        from repro.kernels import interpret_mode
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert interpret_mode() is False

    def test_explicit_choice_wins(self, monkeypatch):
        from repro.kernels import interpret_mode
        assert interpret_mode(False) is False
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert interpret_mode(True) is True

    @pytest.mark.parametrize("platform", ["gpu", "cuda", "rocm", "metal"])
    def test_unknown_platform_raises(self, monkeypatch, platform):
        from repro.kernels import interpret_mode
        monkeypatch.setattr(jax, "default_backend", lambda: platform)
        with pytest.raises(RuntimeError, match=platform):
            interpret_mode()

    def test_wrapper_asks_at_call_time(self, monkeypatch):
        """A wrapper reads the platform when it is called, not when its
        module was imported: on an unknown platform it refuses."""
        from repro.kernels.wavg.ops import weighted_average
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeError, match="gpu"):
            weighted_average(jnp.ones((2, 8)), jnp.full((2,), 0.5))


class TestWavg:
    @pytest.mark.parametrize("k,n", [(2, 64), (10, 2048), (16, 5000),
                                     (3, 1)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, k, n, dtype):
        from repro.kernels.wavg.ops import weighted_average
        from repro.kernels.wavg.ref import wavg_ref
        x = jax.random.normal(KEY, (k, n), dtype=dtype)
        w = jax.random.uniform(jax.random.PRNGKey(1), (k,))
        w = w / w.sum()
        out = weighted_average(x, w, interpret=True)
        ref = wavg_ref(x, w)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=1e-5 if dtype == jnp.float32 else 0.02)

    def test_nd_tensor(self):
        from repro.kernels.wavg.ops import weighted_average
        x = jax.random.normal(KEY, (4, 3, 5, 7))
        w = jnp.asarray([0.1, 0.2, 0.3, 0.4])
        out = weighted_average(x, w, interpret=True)
        ref = jnp.einsum("k,kabc->abc", w, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    @pytest.mark.parametrize("n", [1, 2047, 2048, 2049, 4096, 4097])
    def test_padded_output_slicing_at_block_edges(self, n):
        """The wrapper pads N up to BLOCK_N and slices the kernel output
        back to n — exact at 1 element, exactly-BLOCK_N, and BLOCK_N+1
        (and the 2-block edges), with no padding garbage leaking in."""
        from repro.kernels.wavg.kernel import BLOCK_N
        from repro.kernels.wavg.ops import weighted_average
        from repro.kernels.wavg.ref import wavg_ref
        assert BLOCK_N == 2048, "parametrization assumes BLOCK_N=2048"
        k = 4
        x = jax.random.normal(KEY, (k, n))
        w = jax.random.uniform(jax.random.PRNGKey(1), (k,))
        w = w / w.sum()
        out = weighted_average(x, w, interpret=True)
        assert out.shape == (n,)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(wavg_ref(x, w)), atol=1e-5)

    def test_single_device_row(self):
        """K=1 (one mesh slice's contribution) must reduce to w*x."""
        from repro.kernels.wavg.ops import weighted_average
        x = jax.random.normal(KEY, (1, 37))
        out = weighted_average(x, jnp.ones(1), interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x[0]),
                                   atol=1e-6)

    def test_matches_protocol_averaging(self):
        """The kernel path must agree with core.averaging (impl='jnp')."""
        from repro.core.averaging import weighted_average as core_avg
        tree = {"a": jax.random.normal(KEY, (5, 33)),
                "b": {"c": jax.random.normal(KEY, (5, 4, 9))}}
        w = jnp.asarray([1.0, 2.0, 0.0, 4.0, 1.5])
        ref = core_avg(tree, w, impl="jnp")
        out = core_avg(tree, w, impl="pallas")
        for a, b in zip(jax.tree_util.tree_leaves(ref),
                        jax.tree_util.tree_leaves(out)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)

    def test_psum_pallas_flat_path_matches_jnp(self):
        """weighted_average_psum impl='pallas' (flat all-gather + one
        kernel, the mesh-round hot path) == the per-leaf psum impl, on a
        1-slice shard_map so the fast lane covers it without a forced
        multi-device host."""
        from repro.core.averaging import weighted_average_psum
        from repro.core.shard_round import _shard_map
        from repro.launch.mesh import make_host_mesh
        from jax.sharding import PartitionSpec as P

        mesh = make_host_mesh(1, 1)
        tree = {"a": jax.random.normal(KEY, (6, 5)),
                "b": {"c": jax.random.normal(KEY, (3, 2, 4))}}
        w = jnp.float32(4.0)
        specs = jax.tree.map(lambda _: P(), tree)

        def run(impl):
            body = lambda t, lw: weighted_average_psum(
                t, lw, axis_names=("data",), impl=impl)
            return _shard_map(body, mesh=mesh, in_specs=(specs, P()),
                              out_specs=specs)(tree, w)

        ref, out = run("jnp"), run("pallas")
        for a, b in zip(jax.tree_util.tree_leaves(ref),
                        jax.tree_util.tree_leaves(out)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)


class TestTrimmedWavg:
    """The robust-aggregation kernel (kernels/robust_avg): coordinate
    trimmed mean with participation-mask-aware trimming, against the
    numpy ref twin."""

    @pytest.mark.parametrize("k,n", [(4, 64), (8, 2048), (10, 3000),
                                     (3, 1), (16, 2049)])
    @pytest.mark.parametrize("trim", [0, 1, 2])
    def test_matches_ref(self, k, n, trim):
        from repro.kernels.robust_avg.ops import trimmed_average
        from repro.kernels.robust_avg.ref import trimmed_mean_ref
        x = jax.random.normal(KEY, (k, n))
        w = jax.random.uniform(jax.random.PRNGKey(1), (k,))
        w = jnp.where(w < 0.2, 0.0, w)      # some dropped workers
        out = trimmed_average(x, w, trim=trim, interpret=True)
        ref = trimmed_mean_ref(np.asarray(x, np.float64),
                               np.asarray(w, np.float64), trim=trim)
        assert out.shape == (n,)
        np.testing.assert_allclose(np.asarray(out),
                                   ref.astype(np.float32), atol=2e-5)

    @pytest.mark.parametrize("n", [2047, 2048, 2049])
    def test_block_edges(self, n):
        """BLOCK_N padding must not leak pad columns into the trim
        statistics (pad entries are excluded like dropped workers)."""
        from repro.kernels.robust_avg.ops import trimmed_average
        from repro.kernels.robust_avg.ref import trimmed_mean_ref
        x = jax.random.normal(KEY, (6, n))
        w = jnp.ones(6)
        out = trimmed_average(x, w, trim=1, interpret=True)
        ref = trimmed_mean_ref(np.asarray(x, np.float64),
                               np.ones(6), trim=1)
        np.testing.assert_allclose(np.asarray(out),
                                   ref.astype(np.float32), atol=2e-5)

    def test_trim_actually_removes_extremes(self):
        """Plant one +1000 and one -1000 row: trim=1 must recover the
        honest coordinate means."""
        from repro.kernels.robust_avg.ops import trimmed_average
        honest = jax.random.normal(KEY, (6, 128))
        x = jnp.concatenate(
            [honest, jnp.full((1, 128), 1000.0),
             jnp.full((1, 128), -1000.0)])
        out = trimmed_average(x, jnp.ones(8), trim=1, interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(honest.mean(0)), atol=1e-4)

    def test_psum_robust_path_matches_tree_level(self):
        """weighted_average_psum(robust=...) — the mesh robust hot path
        (flat all-gather + ONE kernel) — must agree with the stacked
        tree-level `weighted_average(robust=...)` on the same payload,
        for every robust method, on a 1-slice shard_map."""
        from repro.core.averaging import (weighted_average,
                                          weighted_average_psum)
        from repro.core.shard_round import _shard_map
        from repro.kernels.robust_avg import RobustConfig
        from repro.launch.mesh import make_host_mesh
        from jax.sharding import PartitionSpec as P

        mesh = make_host_mesh(1, 1)
        tree = {"a": jax.random.normal(KEY, (6, 5)),
                "b": {"c": jax.random.normal(KEY, (3, 2, 4))}}
        w = jnp.float32(4.0)
        w_full = jnp.full((1,), 4.0)
        specs = jax.tree.map(lambda _: P(), tree)

        # the tree-level API takes a STACKED tree (leading K axis); the
        # 1-slice psum path sees the same payload as a K=1 stack
        stacked = jax.tree.map(lambda x: x[None], tree)
        for method in ("trimmed_mean", "norm_clip", "krum"):
            cfg = RobustConfig(method=method, trim=0, krum_f=0)
            body = lambda t, lw: weighted_average_psum(
                t, lw, axis_names=("data",), robust=cfg)
            out = _shard_map(body, mesh=mesh, in_specs=(specs, P()),
                             out_specs=specs)(tree, w)
            ref = weighted_average(stacked, w_full, robust=cfg)
            for a, b in zip(jax.tree_util.tree_leaves(out),
                            jax.tree_util.tree_leaves(ref)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-5, err_msg=method)

    def test_robust_psum_hot_path_is_one_gather_one_kernel(self):
        """Acceptance criterion: every robust reducer keeps the
        Algorithm-2 hot path at ONE payload all-gather (+ the (K,)
        weight gather) and ONE Pallas kernel call per round — counted
        in the traced jaxpr of `weighted_average_psum`."""
        from repro.core.averaging import weighted_average_psum
        from repro.kernels.robust_avg import RobustConfig

        tree = {"a": jnp.zeros((33,)), "b": {"c": jnp.zeros((2, 17))}}
        w = jnp.float32(1.0)

        def counts(robust, impl="pallas"):
            fn = lambda t, lw: weighted_average_psum(
                t, lw, axis_names=("data",), impl=impl, robust=robust)
            jaxpr = str(jax.make_jaxpr(
                fn, axis_env=[("data", 4)])(tree, w))
            # count eqns, not substrings: every all_gather eqn also
            # prints an `all_gather_dimension=` param
            return (jaxpr.count("all_gather["),
                    jaxpr.count("pallas_call["))

        for method in ("trimmed_mean", "norm_clip", "krum"):
            gathers, kernels = counts(RobustConfig(method=method))
            assert kernels == 1, (method, kernels)
            assert gathers == 2, (method, gathers)   # payload + weights
        # the plain pallas path has the same collective budget
        gathers, kernels = counts(None)
        assert kernels == 1 and gathers == 2


class TestSSDScan:
    @pytest.mark.parametrize("s,chunk", [(32, 8), (40, 16), (16, 16),
                                         (7, 8)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, s, chunk, dtype):
        from repro.kernels.ssd_scan.ops import ssd_scan
        from repro.nn.ssm import ssd_scan_ref
        ks = jax.random.split(KEY, 5)
        b, h, p, g, n = 2, 4, 16, 2, 8
        x = jax.random.normal(ks[0], (b, s, h, p), dtype=dtype)
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
        A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.4)
        B = jax.random.normal(ks[3], (b, s, g, n))
        C = jax.random.normal(ks[4], (b, s, g, n))
        y_k = ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
        y_r = ssd_scan_ref(x, dt, A, B, C, chunk=chunk)
        np.testing.assert_allclose(
            np.asarray(y_k, np.float32), np.asarray(y_r, np.float32),
            atol=1e-4 if dtype == jnp.float32 else 0.05)

    def test_final_state_handoff(self):
        """Kernel prefill state must seed the decode recurrence exactly."""
        from repro.kernels.ssd_scan.ops import ssd_scan
        from repro.nn.ssm import ssd_scan_ref
        ks = jax.random.split(KEY, 5)
        b, s, h, p, n = 1, 24, 2, 8, 4
        x = jax.random.normal(ks[0], (b, s, h, p))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
        A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.4)
        B = jax.random.normal(ks[3], (b, s, 1, n))
        C = jax.random.normal(ks[4], (b, s, 1, n))
        _, st_k = ssd_scan(x, dt, A, B, C, chunk=8, return_final_state=True,
                           interpret=True)
        _, st_r = ssd_scan_ref(x, dt, A, B, C, chunk=8,
                               return_final_state=True)
        np.testing.assert_allclose(np.asarray(st_k), np.asarray(st_r),
                                   atol=1e-4)

    def test_mixer_integration(self):
        """scan_impl hook: the mixer with the Pallas path == reference."""
        from repro import nn
        from repro.kernels.ssd_scan import ops as ssd_ops
        p = nn.ssd_mixer_init(KEY, 32, d_state=8, head_dim=16)
        x = jax.random.normal(KEY, (2, 24, 32))
        kw = dict(d_state=8, head_dim=16, chunk=8)
        y_ref = nn.ssd_mixer_apply(p, x, **kw)
        y_ker = nn.ssd_mixer_apply(
            p, x, scan_impl=lambda *a, **k: ssd_ops.ssd_scan(
                *a, **{**k, "interpret": True}), **kw)
        np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_ref),
                                   atol=1e-4)


class TestFlashAttn:
    @pytest.mark.parametrize("s,window", [(32, None), (40, 9), (64, 16),
                                          (24, None)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_naive(self, s, window, dtype):
        from repro.kernels.flash_attn.ops import flash_attention
        from repro.kernels.flash_attn.ref import naive_ref
        ks = jax.random.split(KEY, 3)
        b, nh, nkv, hd = 2, 4, 2, 16
        q = jax.random.normal(ks[0], (b, s, nh, hd), dtype=dtype)
        k = jax.random.normal(ks[1], (b, s, nkv, hd), dtype=dtype)
        v = jax.random.normal(ks[2], (b, s, nkv, hd), dtype=dtype)
        out = flash_attention(q, k, v, n_kv_heads=nkv, window=window,
                              bq=16, bk=16, interpret=True)
        g = nh // nkv
        kr = jnp.repeat(k, g, axis=2)
        vr = jnp.repeat(v, g, axis=2)
        qf = jnp.moveaxis(q, 2, 1).reshape(b * nh, s, hd)
        kf = jnp.moveaxis(kr, 2, 1).reshape(b * nh, s, hd)
        vf = jnp.moveaxis(vr, 2, 1).reshape(b * nh, s, hd)
        ref = naive_ref(qf, kf, vf, scale=hd ** -0.5, causal=True,
                        window=window)
        ref = jnp.moveaxis(ref.reshape(b, nh, s, hd), 1, 2)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=2e-5 if dtype == jnp.float32 else 0.05)

    def test_agrees_with_model_attention(self):
        """Kernel output == the model's attention (flash_ref path)."""
        from repro.kernels.flash_attn.ops import flash_attention
        from repro.kernels.flash_attn.ref import flash_ref
        ks = jax.random.split(KEY, 3)
        b, s, h, hd = 1, 48, 2, 8
        q = jax.random.normal(ks[0], (b, s, h, hd))
        k = jax.random.normal(ks[1], (b, s, h, hd))
        v = jax.random.normal(ks[2], (b, s, h, hd))
        out = flash_attention(q, k, v, n_kv_heads=h, bq=16, bk=16,
                              interpret=True)
        qf = jnp.moveaxis(q, 2, 1).reshape(b * h, s, hd)
        kf = jnp.moveaxis(k, 2, 1).reshape(b * h, s, hd)
        vf = jnp.moveaxis(v, 2, 1).reshape(b * h, s, hd)
        ref = flash_ref(qf, kf, vf, scale=hd ** -0.5)
        ref = jnp.moveaxis(ref.reshape(b, h, s, hd), 1, 2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
