"""Fused-driver equivalence: the compiled multi-round scan must
reproduce the per-round host loop, and the pure-JAX scheduler/channel
twins must agree with their numpy oracles.

Contract (see core/engine.py, core/protocol.py docstrings), for BOTH
the proposed protocol and FedGAN (the unified engine):
  * params/metrics: float32 round-off agreement, any scheduler
  * scheduler masks: BITWISE agreement for deterministic policies
  * wallclock: float32 round-off agreement when fading=False (with
    fading the streams differ, distribution-level only)
  * the quantized uplink (bits < 32) draws per-device streams from the
    round key alone, so both drivers quantize bitwise-identically

The full FedGAN matrix (schedules x fading x bits) is `slow`-marked and
runs in CI's slow lane; one representative combo stays in the fast lane.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ProtocolConfig
from repro.configs.dcgan import DCGANConfig
from repro.core import Trainer, protocol
from repro.core.channel import ChannelConfig, ChannelSimulator, round_wallclock
from repro.core.jax_channel import JaxChannel
from repro.core.jax_channel import round_wallclock as jax_round_wallclock
from repro.core.jax_scheduling import JaxScheduler, schedule_step
from repro.core.scheduling import SchedulerState, schedule_round
from repro.models import dcgan
from repro.models.specs import make_dcgan_spec

KEY = jax.random.PRNGKey(0)
# 8x8 two-stage DCGAN: small enough that many-round runs stay cheap
CFG = DCGANConfig(nz=8, ngf=8, ndf=8, nc=1, image_size=8)
SPEC = make_dcgan_spec(CFG)
K = 4
DATA = jax.random.normal(jax.random.PRNGKey(9), (K, 8, 8, 8, 1))


def make_trainer(driver, *, algorithm="proposed", schedule="serial",
                 scheduler="all", ratio=1.0, bits=16, channel_kw=None):
    pcfg = ProtocolConfig(n_devices=K, n_d=1, n_g=1, sample_size=4,
                          server_sample_size=4, lr_d=1e-3, lr_g=1e-3,
                          schedule=schedule, scheduler=scheduler,
                          scheduling_ratio=ratio, quantize_bits=bits)
    chan = ChannelConfig(n_devices=K, seed=3, **(channel_kw or {}))
    return Trainer(SPEC, pcfg, lambda k: dcgan.gan_init(k, CFG), DATA, KEY,
                   channel_cfg=chan, driver=driver, algorithm=algorithm)


def assert_trees_close(a, b, atol=2e-5):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), atol=atol)


def assert_histories_match(host_hist, fused_hist, *, wallclock=False):
    assert len(host_hist) == len(fused_hist)
    for rh, rf in zip(host_hist, fused_hist):
        assert rh.round == rf.round
        np.testing.assert_array_equal(rh.mask, rf.mask)   # bitwise
        for k in rh.metrics:
            assert abs(rh.metrics[k] - rf.metrics[k]) < 1e-4, \
                (rh.round, k, rh.metrics[k], rf.metrics[k])
        if wallclock:
            np.testing.assert_allclose(rh.wallclock_s, rf.wallclock_s,
                                       rtol=1e-5)


class TestFusedVsHostLoop:
    @pytest.mark.parametrize("schedule", ["serial", "parallel"])
    def test_fused_matches_host_over_rounds(self, schedule):
        """Satellite (a): >=5 rounds, params + per-round metrics + masks."""
        th = make_trainer("host", schedule=schedule)
        tf = make_trainer("fused", schedule=schedule)
        h, f = th.run(6), tf.run(6)
        assert_trees_close(th.state, tf.state)
        assert_histories_match(h, f)

    def test_round_robin_masks_and_wallclock_fading_off(self):
        """Deterministic channel: masks bitwise AND wallclock to f32
        round-off, while the cursor wraps (K=4, n=2 -> period 2)."""
        kw = dict(scheduler="round_robin", ratio=0.5,
                  channel_kw={"fading": False})
        th = make_trainer("host", **kw)
        tf = make_trainer("fused", **kw)
        h, f = th.run(5), tf.run(5)
        assert_trees_close(th.state, tf.state)
        assert_histories_match(h, f, wallclock=True)
        # the rotating window actually rotated
        assert (h[0].mask != h[1].mask).any()
        np.testing.assert_array_equal(h[0].mask, h[2].mask)

    def test_chunked_fused_run_matches_one_shot(self):
        """run(2) + run(4) must equal run(6): the scheduler carry and the
        absolute round index survive chunk boundaries."""
        ta = make_trainer("fused", scheduler="round_robin", ratio=0.5)
        tb = make_trainer("fused", scheduler="round_robin", ratio=0.5)
        ta.run(2)
        ta.run(4)
        tb.run(6)
        assert_trees_close(ta.state, tb.state)
        assert_histories_match(ta.history, tb.history)

    def test_fused_straggler_exclusion_matches_weights(self):
        """A sub-round deadline makes every scheduled device a straggler:
        weights go to zero and wallclock is the broadcast-only path —
        identically in both drivers."""
        kw = dict(channel_kw={"fading": False,
                              "straggler_deadline_s": 1e-9})
        th = make_trainer("host", **kw)
        tf = make_trainer("fused", **kw)
        h, f = th.run(3), tf.run(3)
        assert_histories_match(h, f, wallclock=True)
        assert all(r.metrics["participation"] == 0.0 for r in f)
        assert_trees_close(th.state, tf.state)


class TestFedganFusedVsHost:
    """The FedGAN baseline gets the SAME pinning the proposed protocol
    has: bitwise masks, float32-tolerance params/metrics, wallclock
    parity with fading off — across schedules, fading, and uplink
    quantization widths."""

    def _run_pair(self, *, schedule, fading, bits, rounds=4):
        kw = dict(algorithm="fedgan", schedule=schedule, bits=bits,
                  scheduler="round_robin", ratio=0.5,
                  channel_kw={"fading": fading})
        th = make_trainer("host", **kw)
        tf = make_trainer("fused", **kw)
        h, f = th.run(rounds), tf.run(rounds)
        assert_trees_close(th.state, tf.state)
        assert_histories_match(h, f, wallclock=not fading)
        return th, tf

    def test_fedgan_fused_matches_host_fast_lane(self):
        """Fast-lane representative of the matrix below."""
        self._run_pair(schedule="serial", fading=False, bits=16)

    @pytest.mark.slow
    @pytest.mark.parametrize("schedule", ["serial", "parallel"])
    @pytest.mark.parametrize("fading", [False, True])
    @pytest.mark.parametrize("bits", [16, 32])
    def test_fedgan_fused_matches_host_matrix(self, schedule, fading,
                                              bits):
        self._run_pair(schedule=schedule, fading=fading, bits=bits)

    def test_fedgan_quantized_uplink_actually_quantizes(self):
        """bits=8 must change the trajectory vs bits=32 (the uplink is
        exercised, not a no-op) while both drivers still agree."""
        t8 = make_trainer("fused", algorithm="fedgan", bits=8,
                          channel_kw={"fading": False})
        t32 = make_trainer("fused", algorithm="fedgan", bits=32,
                           channel_kw={"fading": False})
        t8.run(2), t32.run(2)
        l8 = jax.tree_util.tree_leaves(t8.state["disc"])
        l32 = jax.tree_util.tree_leaves(t32.state["disc"])
        assert any(float(jnp.abs(a - b).max()) > 1e-7
                   for a, b in zip(l8, l32))

    def test_fedgan_uplink_payload_drives_timing(self):
        """FedGAN's two-net upload must cost more upload time than the
        proposed one-net upload on the same channel, and lower bit
        widths must shrink it."""
        wall = {}
        for alg, bits in (("fedgan", 16), ("fedgan", 8), ("proposed", 16)):
            tr = make_trainer("fused", algorithm=alg, bits=bits,
                              channel_kw={"fading": False})
            wall[alg, bits] = tr.run(1)[0].wallclock_s
        assert wall["fedgan", 16] > wall["proposed", 16]
        assert wall["fedgan", 8] < wall["fedgan", 16]


class TestTrainerCheckpointResume:
    """Satellite: `Trainer.save_checkpoint`/`restore` serialize
    `_round_index`, `_clock`, and the scheduler carry alongside params,
    so a resumed fused run continues masks, params, AND the wallclock
    curve exactly."""

    @pytest.mark.parametrize("algorithm", ["proposed", "fedgan"])
    def test_fused_save_restore_continues_exactly(self, tmp_path,
                                                  algorithm):
        """Kill mid-run, restore, and the wallclock curve and mask
        sequence continue exactly — for BOTH fused algorithms (the
        FedGAN case additionally round-trips the per-device gen_opt
        stack its state carries)."""
        kw = dict(scheduler="round_robin", ratio=0.5, algorithm=algorithm)
        ta = make_trainer("fused", **kw)
        ta.run(3)
        ta.save_checkpoint(str(tmp_path))
        tb = make_trainer("fused", **kw)
        assert tb.restore(str(tmp_path)) == 3
        tb.run(3)
        tc = make_trainer("fused", **kw)
        tc.run(6)
        for a, b in zip(jax.tree_util.tree_leaves(tb.state),
                        jax.tree_util.tree_leaves(tc.state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert tb._clock == tc._clock
        assert_histories_match(tc.history[3:], tb.history, wallclock=True)
        # resumed records continue the cumulative wallclock curve exactly
        for rb, rc in zip(tb.history, tc.history[3:]):
            assert rb.cumulative_s == rc.cumulative_s

    def test_fused_resume_carries_fault_state(self, tmp_path):
        """Checkpoint round-trip under a fault program: the stale-upload
        cache (`state["fault"]`) must survive the trip so a resumed run
        reproduces the free-rider replays, masks, and wallclock exactly
        (the fault matrix itself lives in test_faults_equivalence.py)."""
        from repro.core.faults import FaultConfig
        faults = FaultConfig(n_devices=K, dropout_prob=0.3,
                             n_free_riders=1, straggler_factor=2.0)
        pcfg = ProtocolConfig(n_devices=K, n_d=1, n_g=1, sample_size=4,
                              server_sample_size=4, lr_d=1e-3, lr_g=1e-3,
                              scheduler="round_robin",
                              scheduling_ratio=0.5)
        chan = ChannelConfig(n_devices=K, seed=3, fading=False)

        def make():
            return Trainer(SPEC, pcfg, lambda k: dcgan.gan_init(k, CFG),
                           DATA, KEY, channel_cfg=chan, driver="fused",
                           faults=faults)

        ta = make()
        ta.run(3)
        ta.save_checkpoint(str(tmp_path))
        tb = make()
        assert tb.restore(str(tmp_path)) == 3
        assert "fault" in tb.state
        tb.run(3)
        tc = make()
        tc.run(6)
        for a, b in zip(jax.tree_util.tree_leaves(tb.state),
                        jax.tree_util.tree_leaves(tc.state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert tb._clock == tc._clock
        assert_histories_match(tc.history[3:], tb.history, wallclock=True)

    def test_restore_resumes_scheduler_carry(self, tmp_path):
        """round_robin cursor must survive the round-trip (a fresh carry
        would restart the rotation and change the masks)."""
        kw = dict(scheduler="round_robin", ratio=0.5)
        ta = make_trainer("fused", **kw)
        ta.run(1)                      # cursor now mid-rotation
        ta.save_checkpoint(str(tmp_path))
        tb = make_trainer("fused", **kw)
        tb.restore(str(tmp_path))
        assert int(tb._sched_carry["rr_cursor"]) == \
            int(ta._sched_carry["rr_cursor"]) != 0


class TestMeshLayoutSelection:
    """Fast-lane validation of the layout axis (construction only — the
    8-device execution matrix runs in the mesh lane below)."""

    def test_unknown_layout_raises(self):
        with pytest.raises(ValueError, match="layout"):
            Trainer(SPEC, ProtocolConfig(n_devices=K),
                    lambda k: dcgan.gan_init(k, CFG), DATA, KEY,
                    layout="warp")

    def test_mesh_layout_rejects_centralized(self):
        """centralized has no device structure, so mesh raises — but
        BOTH protocol algorithms are mesh-capable now (the layout x
        algorithm matrix is complete)."""
        from repro.core.engine import MESH_ALGORITHMS
        assert set(MESH_ALGORITHMS) == {"proposed", "fedgan"}
        with pytest.raises(ValueError, match="mesh"):
            Trainer(SPEC, ProtocolConfig(n_devices=K),
                    lambda k: dcgan.gan_init(k, CFG), DATA, KEY,
                    algorithm="centralized", layout="mesh")

    def test_mesh_algorithms_have_fused_entries(self):
        from repro.core.engine import _ALGORITHMS
        for name in ("proposed", "fedgan"):
            algo = _ALGORITHMS[name]
            assert algo.mesh_round is not None
            assert algo.mesh_rounds_scan is not None


class TestRingAvgImplSelection:
    """Fast-lane validation of the `avg_impl` axis (construction only —
    the 8-device ring execution matrix runs in the mesh lane below)."""

    def _trainer(self, **kw):
        return Trainer(SPEC, ProtocolConfig(n_devices=K),
                       lambda k: dcgan.gan_init(k, CFG), DATA, KEY, **kw)

    def test_unknown_avg_impl_raises(self):
        with pytest.raises(ValueError, match="avg_impl"):
            self._trainer(avg_impl="warp")

    def test_ring_requires_mesh_layout(self):
        with pytest.raises(ValueError, match="mesh"):
            self._trainer(avg_impl="ring", layout="stacked")

    def test_ring_rejects_robust_reducer(self):
        with pytest.raises(NotImplementedError, match="robust"):
            self._trainer(avg_impl="ring", layout="mesh",
                          reducer="trimmed_mean")

    def test_ring_rejects_corrupting_faults(self):
        from repro.core.faults import FaultConfig
        with pytest.raises(NotImplementedError, match="corrupt"):
            self._trainer(avg_impl="ring", layout="mesh",
                          faults=FaultConfig(n_devices=K, n_byzantine=1))
        # dropout-only fault programs compose: they only zero weights
        from repro.core import shard_round
        shard_round.check_ring_support(
            "ring", ("data",), None, 1,
            FaultConfig(n_devices=K, dropout_prob=0.5), None)

    def test_ring_rejects_tp_and_multi_axis(self):
        from repro.core import shard_round
        with pytest.raises(NotImplementedError, match="tensor parallel"):
            shard_round.check_ring_support("ring", ("data",), "model", 2,
                                           None, None)
        with pytest.raises(NotImplementedError, match="single device"):
            shard_round.check_ring_support("ring", ("rows", "cols"),
                                           None, 1, None, None)


class TestShardRoundBuilderMemo:
    """The shard_map builders memoize on their full (mesh, config)
    signature, so repeated Trainer constructions in one process reuse
    the jitted closures (and their compiles) instead of rebuilding per
    call. A 1x1 host mesh suffices — construction only."""

    def _args(self):
        from repro.launch.mesh import make_host_mesh
        pcfg = ProtocolConfig(n_devices=1, n_d=1, n_g=1, sample_size=2,
                              server_sample_size=2)
        return SPEC, pcfg, make_host_mesh(1, 1)

    def test_single_round_builders_memoize(self):
        from repro.core import shard_round
        spec, pcfg, mesh = self._args()
        a = shard_round.shard_map_round(spec, pcfg, mesh)
        b = shard_round.shard_map_round(spec, pcfg, mesh)
        assert a is b
        c = shard_round.fedgan_shard_map_round(spec, pcfg, mesh)
        assert c is shard_round.fedgan_shard_map_round(spec, pcfg, mesh)
        assert c is not a

    def test_scan_builders_memoize_and_key_on_config(self):
        import dataclasses as dc
        from repro.core import shard_round
        from repro.core.jax_channel import JaxChannel
        from repro.core.jax_scheduling import JaxScheduler
        spec, pcfg, mesh = self._args()
        chan_cfg = ChannelConfig(n_devices=1, seed=3)
        sched = JaxScheduler(policy="all", n_devices=1)
        kw = dict(channel=JaxChannel(chan_cfg), scheduler=sched)
        a = shard_round.shard_rounds_scan(spec, pcfg, mesh, 2, **kw)
        # a DIFFERENT JaxChannel instance with an EQUAL config still hits
        b = shard_round.shard_rounds_scan(spec, pcfg, mesh, 2,
                                          channel=JaxChannel(chan_cfg),
                                          scheduler=sched)
        assert a is b
        # any config change misses: round count, pcfg, channel config
        assert shard_round.shard_rounds_scan(spec, pcfg, mesh, 3,
                                             **kw) is not a
        pcfg2 = dc.replace(pcfg, quantize_bits=8)
        assert shard_round.shard_rounds_scan(spec, pcfg2, mesh, 2,
                                             **kw) is not a
        chan2 = JaxChannel(ChannelConfig(n_devices=1, seed=4))
        assert shard_round.shard_rounds_scan(spec, pcfg, mesh, 2,
                                             channel=chan2,
                                             scheduler=sched) is not a

    def test_eval_fn_closures_never_memoized(self):
        from repro.core import shard_round
        from repro.core.jax_channel import JaxChannel
        from repro.core.jax_scheduling import JaxScheduler
        spec, pcfg, mesh = self._args()
        kw = dict(channel=JaxChannel(ChannelConfig(n_devices=1, seed=3)),
                  scheduler=JaxScheduler(policy="all", n_devices=1),
                  eval_fn=lambda g, t, k: 0.0, eval_every=2)
        a = shard_round.shard_rounds_scan(spec, pcfg, mesh, 2, **kw)
        assert shard_round.shard_rounds_scan(spec, pcfg, mesh, 2,
                                             **kw) is not a

    def test_memoized_trainer_reuses_mesh_round(self):
        """Two Trainers sharing spec/pcfg/mesh config reuse ONE mesh
        round builder — the satellite's actual target."""
        pcfg = ProtocolConfig(n_devices=1, n_d=1, n_g=1, sample_size=2,
                              server_sample_size=2)
        data = DATA[:1]
        chan = ChannelConfig(n_devices=1, seed=3)
        ta = Trainer(SPEC, pcfg, lambda k: dcgan.gan_init(k, CFG), data,
                     KEY, channel_cfg=chan, driver="host", layout="mesh")
        tb = Trainer(SPEC, pcfg, lambda k: dcgan.gan_init(k, CFG), data,
                     KEY, channel_cfg=chan, driver="host", layout="mesh")
        assert ta._round is tb._round

    @pytest.mark.parametrize("driver", ["fused", "host"])
    def test_mesh_trainer_compiles_once(self, driver):
        """Later calls feed the mesh-sharded outputs of earlier ones
        back in; the builders place every call's inputs on the same
        shardings, so a second run compiles nothing."""
        from jax import monitoring
        pcfg = ProtocolConfig(n_devices=1, n_d=1, n_g=1, sample_size=2,
                              server_sample_size=2)
        t = Trainer(SPEC, pcfg, lambda k: dcgan.gan_init(k, CFG),
                    DATA[:1], KEY, channel_cfg=ChannelConfig(n_devices=1,
                                                             seed=3),
                    driver=driver, layout="mesh")
        t.run(2)
        compiles = []

        def listener(event, secs, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles.append(secs)

        monitoring.register_event_duration_secs_listener(listener)
        try:
            t.run(2)
        finally:
            monitoring.unregister_event_duration_listener(listener)
        assert compiles == []


class TestMeshFusedEquivalence:
    """Satellite: the FULL layout x algorithm matrix — mesh-fused vs
    stacked-fused vs host oracle, for BOTH the proposed protocol and
    FedGAN, over schedules x quantize_bits, on a forced 8-device host
    mesh. The whole matrix runs in ONE subprocess (the jax startup
    dominates); masks must agree BITWISE across all three drivers and
    params to float32 tolerance. Resume is checked for both algorithms
    on the mesh layout. Runs in CI's mesh lane."""

    @pytest.mark.slow
    def test_mesh_matrix_and_resume_on_8_device_mesh(self):
        from conftest import run_on_host_mesh
        run_on_host_mesh("""
            import itertools, tempfile
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs.base import ProtocolConfig
            from repro.configs.dcgan import DCGANConfig
            from repro.core import Trainer
            from repro.core.channel import ChannelConfig
            from repro.models import dcgan
            from repro.models.specs import make_dcgan_spec

            KEY = jax.random.PRNGKey(0)
            CFG = DCGANConfig(nz=8, ngf=8, ndf=8, nc=1, image_size=8)
            SPEC = make_dcgan_spec(CFG)
            K = 8
            DATA = jax.random.normal(jax.random.PRNGKey(9),
                                     (K, 8, 8, 8, 1))

            def make(driver, layout, schedule, bits, algorithm):
                pcfg = ProtocolConfig(
                    n_devices=K, n_d=1, n_g=1, sample_size=4,
                    server_sample_size=4, lr_d=1e-3, lr_g=1e-3,
                    schedule=schedule, scheduler="round_robin",
                    scheduling_ratio=0.5, quantize_bits=bits)
                chan = ChannelConfig(n_devices=K, seed=3, fading=False)
                return Trainer(SPEC, pcfg,
                               lambda k: dcgan.gan_init(k, CFG), DATA,
                               KEY, channel_cfg=chan, driver=driver,
                               layout=layout, algorithm=algorithm)

            def leaves(t):
                return jax.tree_util.tree_leaves(t.state)

            for algorithm, schedule, bits in itertools.product(
                    ("proposed", "fedgan"), ("serial", "parallel"),
                    (16, 32)):
                th = make("host", "stacked", schedule, bits, algorithm)
                ts = make("fused", "stacked", schedule, bits, algorithm)
                tm = make("fused", "mesh", schedule, bits, algorithm)
                h, s, m = th.run(4), ts.run(4), tm.run(4)
                for rh, rs, rm in zip(h, s, m):
                    np.testing.assert_array_equal(rh.mask, rs.mask)
                    np.testing.assert_array_equal(rh.mask, rm.mask)
                    for k in rh.metrics:
                        assert abs(rh.metrics[k] - rm.metrics[k]) < 1e-4
                    np.testing.assert_allclose(rh.wallclock_s,
                                               rm.wallclock_s, rtol=1e-5)
                for a, b in zip(leaves(th), leaves(tm)):
                    np.testing.assert_allclose(
                        np.asarray(a, np.float32),
                        np.asarray(b, np.float32), atol=2e-5)
                for a, b in zip(leaves(ts), leaves(tm)):
                    np.testing.assert_allclose(
                        np.asarray(a, np.float32),
                        np.asarray(b, np.float32), atol=2e-5)
                print(f"matrix OK algorithm={algorithm} "
                      f"schedule={schedule} bits={bits}")

            # mesh+host (per-round shard_map dispatch) agrees too —
            # one representative per algorithm
            for algorithm in ("proposed", "fedgan"):
                th = make("host", "stacked", "serial", 16, algorithm)
                tm = make("host", "mesh", "serial", 16, algorithm)
                h, m = th.run(3), tm.run(3)
                for rh, rm in zip(h, m):
                    np.testing.assert_array_equal(rh.mask, rm.mask)
                for a, b in zip(leaves(th), leaves(tm)):
                    np.testing.assert_allclose(
                        np.asarray(a, np.float32),
                        np.asarray(b, np.float32), atol=2e-5)
                print(f"mesh host driver OK algorithm={algorithm}")

            # resumed mesh runs continue the wallclock curve and mask
            # sequence exactly — both algorithms
            for algorithm in ("proposed", "fedgan"):
                d = tempfile.mkdtemp()
                ta = make("fused", "mesh", "serial", 16, algorithm)
                ta.run(2)
                ta.save_checkpoint(d)
                tb = make("fused", "mesh", "serial", 16, algorithm)
                tb.restore(d)
                tb.run(2)
                tc = make("fused", "mesh", "serial", 16, algorithm)
                tc.run(4)
                for a, b in zip(leaves(tb), leaves(tc)):
                    np.testing.assert_array_equal(np.asarray(a),
                                                  np.asarray(b))
                assert tb._clock == tc._clock
                for rb, rc in zip(tb.history, tc.history[2:]):
                    assert rb.cumulative_s == rc.cumulative_s
                    np.testing.assert_array_equal(rb.mask, rc.mask)
                print(f"mesh resume OK algorithm={algorithm}")
        """)

    @pytest.mark.slow
    def test_mesh_ring_avg_impl_matches_host_and_flat(self):
        """PR 9 tentpole acceptance: `avg_impl="ring"` on the fused mesh
        engine reproduces the host oracle and the flat pallas mesh path
        for BOTH algorithms x bits in {16, 32} — masks BITWISE, params
        to float32 round-off (the ring changes reduction ORDER, so the
        tolerance covers cross-rank accumulation rotation, not values:
        the quantized wire realizes the same `quantize_tree` streams).
        Also pins the mesh twin of tests/test_no_survivor.py: ring +
        FaultConfig(dropout_prob=1.0) freezes the disc exactly."""
        from conftest import run_on_host_mesh
        run_on_host_mesh("""
            import itertools
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs.base import ProtocolConfig
            from repro.configs.dcgan import DCGANConfig
            from repro.core import Trainer
            from repro.core.channel import ChannelConfig
            from repro.core.faults import FaultConfig
            from repro.models import dcgan
            from repro.models.specs import make_dcgan_spec

            KEY = jax.random.PRNGKey(0)
            CFG = DCGANConfig(nz=8, ngf=8, ndf=8, nc=1, image_size=8)
            SPEC = make_dcgan_spec(CFG)
            K = 8
            DATA = jax.random.normal(jax.random.PRNGKey(9),
                                     (K, 8, 8, 8, 1))

            def make(driver, layout, bits, algorithm, avg_impl="pallas",
                     faults=None):
                pcfg = ProtocolConfig(
                    n_devices=K, n_d=1, n_g=1, sample_size=4,
                    server_sample_size=4, lr_d=1e-3, lr_g=1e-3,
                    scheduler="round_robin", scheduling_ratio=0.5,
                    quantize_bits=bits)
                chan = ChannelConfig(n_devices=K, seed=3, fading=False)
                return Trainer(SPEC, pcfg,
                               lambda k: dcgan.gan_init(k, CFG), DATA,
                               KEY, channel_cfg=chan, driver=driver,
                               layout=layout, algorithm=algorithm,
                               avg_impl=avg_impl, faults=faults)

            def leaves(t):
                return jax.tree_util.tree_leaves(t.state)

            for algorithm, bits in itertools.product(
                    ("proposed", "fedgan"), (16, 32)):
                th = make("host", "stacked", bits, algorithm)
                tp = make("fused", "mesh", bits, algorithm)
                tr = make("fused", "mesh", bits, algorithm,
                          avg_impl="ring")
                h, p, r = th.run(4), tp.run(4), tr.run(4)
                for rh, rp, rr in zip(h, p, r):
                    np.testing.assert_array_equal(rh.mask, rr.mask)
                    np.testing.assert_array_equal(rp.mask, rr.mask)
                    for k in rh.metrics:
                        assert abs(rh.metrics[k] - rr.metrics[k]) < 1e-4
                    np.testing.assert_allclose(rh.wallclock_s,
                                               rr.wallclock_s, rtol=1e-5)
                for a, b in zip(leaves(th), leaves(tr)):
                    np.testing.assert_allclose(
                        np.asarray(a, np.float32),
                        np.asarray(b, np.float32), atol=1e-4)
                for a, b in zip(leaves(tp), leaves(tr)):
                    np.testing.assert_allclose(
                        np.asarray(a, np.float32),
                        np.asarray(b, np.float32), atol=1e-4)
                print(f"ring matrix OK algorithm={algorithm} bits={bits}")

            # no-survivor on the mesh: ring + dropout=1.0 freezes disc
            for avg_impl in ("pallas", "ring"):
                tr = make("fused", "mesh", 16, "proposed",
                          avg_impl=avg_impl,
                          faults=FaultConfig(n_devices=K,
                                             dropout_prob=1.0))
                disc0 = jax.tree.map(np.asarray, tr.state["disc"])
                hist = tr.run(3)
                assert all(not rec.mask.any() for rec in hist)
                for a, f in zip(
                        jax.tree_util.tree_leaves(tr.state["disc"]),
                        jax.tree_util.tree_leaves(disc0)):
                    np.testing.assert_array_equal(np.asarray(a), f)
                print(f"mesh no-survivor OK avg_impl={avg_impl}")
        """)


class TestDriverSelection:
    """Regression for the silent driver coercion fixed in PR 2:
    requesting the fused driver for an unsupported algorithm raises."""

    def test_fused_centralized_raises(self):
        with pytest.raises(ValueError, match="fused"):
            make_trainer("fused", algorithm="centralized")

    def test_auto_resolves_per_algorithm(self):
        assert make_trainer("auto").driver == "fused"
        assert make_trainer("auto", algorithm="fedgan").driver == "fused"
        assert make_trainer("auto",
                            algorithm="centralized").driver == "host"

    def test_explicit_host_always_allowed(self):
        assert make_trainer("host", algorithm="centralized").driver == "host"

    def test_unknown_driver_raises(self):
        with pytest.raises(ValueError):
            make_trainer("warp")


class TestSchedulerTwinParity:
    """Satellite (b): each JAX policy selects the same device sets as its
    numpy twin under identical rates."""

    @pytest.mark.parametrize("policy", ["all", "round_robin",
                                        "best_channel", "prop_fair"])
    def test_policy_matches_numpy_twin(self, policy):
        k, ratio, rounds = 5, 0.4, 12          # n=2: cursor wraps at 5
        rng = np.random.default_rng(11)
        np_state = SchedulerState(policy, k, ratio=ratio)
        jx = JaxScheduler(policy=policy, n_devices=k, ratio=ratio)
        carry = jx.init_carry()
        assert jx.n_scheduled == np_state.n_scheduled
        for t in range(rounds):
            rates = rng.uniform(0.5, 10.0, k)   # distinct w.p. 1
            np_mask = schedule_round(np_state, rates, rng)
            jx_mask, carry = schedule_step(
                jx, carry, jnp.asarray(rates, jnp.float32),
                jax.random.fold_in(KEY, t))
            np.testing.assert_array_equal(np_mask, np.asarray(jx_mask))
            np.testing.assert_allclose(np.asarray(carry["ewma_rate"]),
                                       np_state.ewma_rate, rtol=1e-5)
        if policy == "round_robin":
            # 12 rounds x n=2 -> cursor 24 % 5 == 4 in both twins
            assert int(carry["rr_cursor"]) == np_state.rr_cursor == 4

    def test_prop_fair_ewma_drives_rotation(self):
        """Served devices' EWMA rises, shifting priority to unserved
        ones — the numpy twin's rotation property, on the JAX side."""
        jx = JaxScheduler(policy="prop_fair", n_devices=4, ratio=0.5)
        carry = jx.init_carry()
        rates = jnp.ones(4)
        m1, carry = schedule_step(jx, carry, rates, KEY)
        m2, carry = schedule_step(jx, carry, rates, KEY)
        assert (np.asarray(m1) != np.asarray(m2)).any()

    def test_random_policy_counts_and_coverage(self):
        """`random` matches in distribution: always exactly n scheduled,
        every device selected eventually."""
        jx = JaxScheduler(policy="random", n_devices=6, ratio=0.34)
        carry = jx.init_carry()
        seen = np.zeros(6, dtype=bool)
        for t in range(60):
            mask, carry = schedule_step(jx, carry, jnp.ones(6),
                                        jax.random.fold_in(KEY, t))
            mask = np.asarray(mask)
            assert mask.sum() == jx.n_scheduled
            seen |= mask
        assert seen.all()

    def test_unknown_policy_raises(self):
        jx = JaxScheduler(policy="nope", n_devices=4)
        with pytest.raises(ValueError):
            schedule_step(jx, jx.init_carry(), jnp.ones(4), KEY)


class TestChannelTwinParity:
    def _pair(self, **kw):
        cfg = ChannelConfig(n_devices=6, seed=3, **kw)
        return ChannelSimulator(cfg), JaxChannel(cfg)

    def test_placement_and_static_rates_match(self):
        np_sim, jx_sim = self._pair(fading=False)
        np.testing.assert_allclose(np.asarray(jx_sim.dist_km),
                                   np_sim.dist_km, rtol=1e-6)
        for n_sched in (1, 3, 6):
            np.testing.assert_allclose(
                np.asarray(jx_sim.uplink_rates(KEY, n_sched)),
                np_sim.uplink_rates(n_sched), rtol=1e-5)
        np.testing.assert_allclose(jx_sim.downlink_rate_s,
                                   np_sim.downlink_rate(), rtol=1e-6)

    @pytest.mark.parametrize("schedule,fedgan", [("serial", False),
                                                 ("parallel", False),
                                                 ("serial", True)])
    def test_round_timing_and_wallclock_match(self, schedule, fedgan):
        np_sim, jx_sim = self._pair(fading=False)
        mask = np.array([True, True, False, True, False, True])
        kw = dict(disc_params=10_000, gen_params=12_000,
                  disc_step_flops=1e9, gen_step_flops=1e9, n_d=2, n_g=2,
                  fedgan=fedgan)
        t_np = np_sim.round_timing(mask=mask, **kw)
        t_jx = jx_sim.round_timing(KEY, jnp.asarray(mask), **kw)
        np.testing.assert_allclose(np.asarray(t_jx.upload_s), t_np.upload_s,
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(t_jx.compute_dev_s),
                                   t_np.compute_dev_s, rtol=1e-5)
        np.testing.assert_allclose(float(t_jx.compute_srv_s),
                                   t_np.compute_srv_s, rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(t_jx.stragglers),
                                      t_np.stragglers)
        w_np = round_wallclock(t_np, mask, schedule=schedule, fedgan=fedgan)
        w_jx = jax_round_wallclock(t_jx, jnp.asarray(mask),
                                   schedule=schedule, fedgan=fedgan)
        np.testing.assert_allclose(float(w_jx), w_np, rtol=1e-5)

    def test_all_stragglers_falls_back_to_broadcast(self):
        np_sim, jx_sim = self._pair(fading=False,
                                    straggler_deadline_s=1e-12)
        mask = np.ones(6, dtype=bool)
        kw = dict(disc_params=10_000, gen_params=12_000,
                  disc_step_flops=1e9, gen_step_flops=1e9, n_d=2, n_g=2)
        t_np = np_sim.round_timing(mask=mask, **kw)
        t_jx = jx_sim.round_timing(KEY, jnp.asarray(mask), **kw)
        assert np.asarray(t_jx.stragglers).all()
        w_np = round_wallclock(t_np, mask, schedule="serial")
        w_jx = jax_round_wallclock(t_jx, jnp.asarray(mask),
                                   schedule="serial")
        np.testing.assert_allclose(float(w_jx), w_np, rtol=1e-5)
        np.testing.assert_allclose(float(w_jx), t_np.broadcast_s, rtol=1e-5)

    def test_fading_rates_match_in_distribution(self):
        """jax.random vs numpy Exp(1) streams: per-device mean uplink
        rate over many draws agrees (the twins share every deterministic
        factor, so only the fading marginal is being compared)."""
        np_sim, jx_sim = self._pair(fading=True)
        n = 2000
        np_rates = np.stack([np_sim.uplink_rates(3) for _ in range(n)])
        keys = jax.random.split(jax.random.PRNGKey(42), n)
        jx_rates = np.asarray(
            jax.vmap(lambda k: jx_sim.uplink_rates(k, 3))(keys))
        np.testing.assert_allclose(jx_rates.mean(0), np_rates.mean(0),
                                   rtol=0.1)
        np.testing.assert_allclose(jx_rates.std(0), np_rates.std(0),
                                   rtol=0.15)


class TestGanRoundsScanApi:
    def test_scan_returns_stacked_outputs(self):
        pcfg = ProtocolConfig(n_devices=K, n_d=1, n_g=1, sample_size=4,
                              server_sample_size=4)
        state = protocol.make_train_state(
            KEY, lambda k: dcgan.gan_init(k, CFG), pcfg, K)
        chan_cfg = ChannelConfig(n_devices=K, seed=3)
        state, carry, out = protocol.gan_rounds_scan(
            SPEC, pcfg, state, DATA, KEY, 3,
            channel=JaxChannel(chan_cfg),
            scheduler=JaxScheduler(policy="all", n_devices=K))
        assert out["wallclock_s"].shape == (3,)
        assert out["mask"].shape == (3, K) and out["mask"].dtype == bool
        assert out["weights"].shape == (3, K)
        for v in out["metrics"].values():
            assert v.shape == (3,)
        assert set(carry) == {"rr_cursor", "ewma_rate"}
        for leaf in jax.tree_util.tree_leaves(state):
            assert bool(jnp.isfinite(leaf).all())
