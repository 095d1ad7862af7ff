"""Protocol mechanics: Algorithms 1-3, schedules, averaging, micro-batching."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ProtocolConfig
from repro.configs.dcgan import DCGANConfig
from repro.core import protocol
from repro.core.averaging import weighted_average, broadcast_like
from repro.models import dcgan
from repro.models.specs import make_dcgan_spec

KEY = jax.random.PRNGKey(0)
CFG = DCGANConfig(nz=8, ngf=8, ndf=8, nc=1, image_size=16)
SPEC = make_dcgan_spec(CFG)


def make_data(k_dev=4, n_k=8):
    return jax.random.normal(jax.random.PRNGKey(9),
                             (k_dev, n_k, 16, 16, 1))


def make_state(pcfg, k_dev=4):
    return protocol.make_train_state(
        KEY, lambda k: dcgan.gan_init(k, CFG), pcfg, k_dev)


def leaves_close(a, b, atol=1e-6):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), atol=atol)


class TestAveraging:
    def test_equal_weights_is_mean(self):
        tree = {"a": jnp.arange(12.0).reshape(4, 3)}
        avg = weighted_average(tree, jnp.ones(4))
        np.testing.assert_allclose(avg["a"], tree["a"].mean(0))

    def test_weights_exclude(self):
        tree = {"a": jnp.stack([jnp.zeros(3), jnp.ones(3) * 7])}
        avg = weighted_average(tree, jnp.asarray([0.0, 5.0]))
        np.testing.assert_allclose(avg["a"], 7.0)

    def test_mk_weighting(self):
        """phi = sum m_k phi_k / sum m_k (Algorithm 2 exactly)."""
        phis = jnp.asarray([[1.0], [4.0], [10.0]])
        m = jnp.asarray([1.0, 2.0, 3.0])
        avg = weighted_average({"p": phis}, m)["p"]
        np.testing.assert_allclose(avg, (1 + 8 + 30) / 6.0)

    def test_broadcast_like(self):
        t = broadcast_like({"x": jnp.ones((2, 2))}, 5)
        assert t["x"].shape == (5, 2, 2)


class TestRound:
    def test_round_runs_and_moves_params(self):
        pcfg = ProtocolConfig(n_devices=4, n_d=2, n_g=2, sample_size=4,
                              server_sample_size=4, lr_d=1e-3, lr_g=1e-3)
        state = make_state(pcfg)
        data = make_data()
        w = jnp.full((4,), 4.0)
        new_state, metrics = protocol.gan_round(SPEC, pcfg, state, data, w,
                                                KEY)
        for leaf in jax.tree_util.tree_leaves(new_state):
            assert jnp.isfinite(leaf).all()
        # params actually moved
        d0 = jax.tree_util.tree_leaves(state["gen"])[0]
        d1 = jax.tree_util.tree_leaves(new_state["gen"])[0]
        assert float(jnp.abs(d0 - d1).max()) > 0
        assert metrics["participation"] == 1.0

    def test_zero_weight_device_excluded(self):
        """A device with weight 0 must not influence the global disc."""
        pcfg = ProtocolConfig(n_devices=2, n_d=1, n_g=1, sample_size=4,
                              server_sample_size=4)
        state = make_state(pcfg, 2)
        data = make_data(2)
        poisoned = jax.tree.map(lambda x: x, data)
        poisoned = poisoned.at[1].set(1e3)   # garbage on device 1
        w = jnp.asarray([4.0, 0.0])
        s1, _ = protocol.gan_round(SPEC, pcfg, state, data, w, KEY)
        s2, _ = protocol.gan_round(SPEC, pcfg, state, poisoned, w, KEY)
        leaves_close(s1["disc"], s2["disc"])

    def test_parallel_vs_serial_disc_identical_gen_differs(self):
        """Both schedules produce the same averaged discriminator; the
        generator differs because serial uses the fresh phi^{t+1}."""
        common = dict(n_devices=4, n_d=2, n_g=2, sample_size=4,
                      server_sample_size=4, lr_d=5e-3, lr_g=5e-3)
        p_ser = ProtocolConfig(schedule="serial", **common)
        p_par = ProtocolConfig(schedule="parallel", **common)
        state = make_state(p_ser)
        data = make_data()
        w = jnp.full((4,), 4.0)
        s_ser, _ = protocol.gan_round(SPEC, p_ser, state, data, w, KEY)
        s_par, _ = protocol.gan_round(SPEC, p_par, state, data, w, KEY)
        leaves_close(s_ser["disc"], s_par["disc"])
        g1 = jax.tree_util.tree_leaves(s_ser["gen"])
        g2 = jax.tree_util.tree_leaves(s_par["gen"])
        assert any(float(jnp.abs(a - b).max()) > 1e-7 for a, b in zip(g1, g2))

    def test_parallel_gen_update_ignores_device_updates(self):
        """Parallel schedule: generator update depends only on phi^t, so
        corrupting the device data must not change the new generator."""
        pcfg = ProtocolConfig(schedule="parallel", n_devices=2, n_d=3,
                              n_g=2, sample_size=4, server_sample_size=4)
        state = make_state(pcfg, 2)
        data = make_data(2)
        w = jnp.full((2,), 4.0)
        s1, _ = protocol.gan_round(SPEC, pcfg, state, data, w, KEY)
        s2, _ = protocol.gan_round(SPEC, pcfg, state, data * -3.0, w, KEY)
        leaves_close(s1["gen"], s2["gen"])

    def test_centralized_equals_k1_round(self):
        # quantize_bits=32: centralized training has no uplink, so the
        # K=1 round must run with the float32-identity uplink to match.
        pcfg = ProtocolConfig(n_devices=1, n_d=2, n_g=2, sample_size=4,
                              server_sample_size=4, quantize_bits=32)
        state = make_state(pcfg, 1)
        data = make_data(1)
        s_round, _ = protocol.gan_round(SPEC, pcfg, state, data,
                                        jnp.asarray([4.0]), KEY)
        s_cent, _ = protocol.centralized_step(SPEC, pcfg, state, data[0], KEY)
        leaves_close(s_round["gen"], s_cent["gen"])
        leaves_close(s_round["disc"], s_cent["disc"])

    def test_microbatch_invariance(self):
        """Gradient accumulation must not change the result (SGD linear)."""
        common = dict(n_devices=2, n_d=1, n_g=1, sample_size=8,
                      server_sample_size=8)
        p_full = ProtocolConfig(**common)
        p_micro = ProtocolConfig(micro_batch_d=2, micro_batch_g=4, **common)
        state = make_state(p_full, 2)
        data = make_data(2)
        w = jnp.full((2,), 8.0)
        s1, _ = protocol.gan_round(SPEC, p_full, state, data, w, KEY)
        s2, _ = protocol.gan_round(SPEC, p_micro, state, data, w, KEY)
        # DCGAN BatchNorm normalizes per microbatch, so equality is only
        # approximate here; BN-free backbones accumulate exactly.
        leaves_close(s1["gen"], s2["gen"], atol=5e-4)
        leaves_close(s1["disc"], s2["disc"], atol=5e-4)

    def test_shared_seed_consistency(self):
        """Parallel schedule seed contract: the server's noise at step j
        equals every device's noise at step j (Section III-A)."""
        from repro.core.protocol import _SALT_SHARED_Z
        kz_server = jax.random.fold_in(jax.random.fold_in(KEY, _SALT_SHARED_Z), 0)
        kz_device = jax.random.fold_in(jax.random.fold_in(KEY, _SALT_SHARED_Z), 0)
        np.testing.assert_array_equal(
            jax.random.key_data(kz_server), jax.random.key_data(kz_device))


class TestStackedAlgorithm1:
    @pytest.mark.parametrize("optimizer", ["sgd", "adam"])
    def test_devices_round_equals_per_worker_oracle(self, optimizer):
        """The stacked layout's Algorithm 1 (one worker after another, the
        shared fakes made once) against `device_update` called worker by
        worker on its own shard: discs, opt states and objectives to f32
        round-off."""
        pcfg = ProtocolConfig(n_devices=3, n_d=3, n_g=1, sample_size=4,
                              server_sample_size=4, lr_d=1e-2,
                              optimizer=optimizer)
        state = make_state(pcfg, 3)
        state["disc_opt"] = jax.tree.map(
            lambda a: a + 0.01 * jnp.arange(3.0).reshape((3,) + (1,) * (
                a.ndim - 1)).astype(a.dtype), state["disc_opt"])
        data = make_data(3)
        got = jax.jit(lambda s, d: protocol.devices_round(
            SPEC, pcfg, s["gen"], s["disc"], s["disc_opt"], d, KEY))(
                state, data)

        def oracle(s, d):
            one = jax.jit(lambda o, x, k: protocol.device_update(
                SPEC, pcfg, s["gen"], s["disc"], o, x, KEY, k))
            outs = [one(jax.tree.map(lambda a: a[k], s["disc_opt"]), d[k],
                        jnp.int32(k)) for k in range(3)]
            return jax.tree.map(lambda *xs: jnp.stack(xs), *outs)

        want = oracle(state, data)
        leaves_close(got, want)
        assert any(float(jnp.abs(a - b).max()) > 0 for a, b in zip(
            jax.tree_util.tree_leaves(got[0]),
            jax.tree_util.tree_leaves(broadcast_like(state["disc"], 3))))

    def test_sharded_worker_axis_round_equals_the_loop(self):
        """`constrain_stacked` keeps the vmapped Algorithm 1; its round
        equals the unsharded round's loop to f32 round-off."""
        pcfg = ProtocolConfig(n_devices=3, n_d=2, n_g=2, sample_size=4,
                              server_sample_size=4, lr_d=1e-2, lr_g=1e-2,
                              quantize_bits=32)
        state = make_state(pcfg, 3)
        data = make_data(3)
        w = jnp.asarray([4.0, 0.0, 4.0])
        loop = protocol.gan_round(SPEC, pcfg, state, data, w, KEY)
        vmapped = protocol.gan_round(SPEC, pcfg, state, data, w, KEY,
                                     constrain_stacked=lambda t: t)
        leaves_close(loop, vmapped)


class TestOptimizers:
    def test_adam_state_threads_through_round(self):
        pcfg = ProtocolConfig(n_devices=2, n_d=1, n_g=1, sample_size=4,
                              server_sample_size=4, optimizer="adam")
        state = make_state(pcfg, 2)
        data = make_data(2)
        w = jnp.full((2,), 4.0)
        s1, _ = protocol.gan_round(SPEC, pcfg, state, data, w, KEY)
        assert int(s1["gen_opt"]["t"]) == 1
        assert np.asarray(s1["disc_opt"]["t"]).tolist() == [1, 1]
        s2, _ = protocol.gan_round(SPEC, pcfg, s1, data, w,
                                   jax.random.fold_in(KEY, 1))
        assert int(s2["gen_opt"]["t"]) == 2
