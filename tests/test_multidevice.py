"""Multi-device host-mesh tests, run in subprocesses so the main pytest
process keeps the default single-device view (per the dry-run contract,
XLA_FLAGS must not be set globally)."""
import pytest

from conftest import run_on_host_mesh as run_sub


@pytest.mark.slow
def test_shard_map_round_matches_vmap_round():
    """The explicit-psum (shard_map) protocol round must agree with the
    stacked/vmap (pjit) round on a real 4-device mesh."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import ProtocolConfig
        from repro.configs.dcgan import DCGANConfig
        from repro.core import protocol
        from repro.core.shard_round import shard_map_round
        from repro.models import dcgan
        from repro.models.specs import make_dcgan_spec
        from repro.launch.mesh import make_host_mesh

        cfg = DCGANConfig(nz=8, ngf=8, ndf=8, nc=1, image_size=16)
        spec = make_dcgan_spec(cfg)
        pcfg = ProtocolConfig(n_devices=4, n_d=2, n_g=1, sample_size=4,
                              server_sample_size=4)
        key = jax.random.PRNGKey(0)
        state = protocol.make_train_state(
            key, lambda k: dcgan.gan_init(k, cfg), pcfg, 4)
        data = jax.random.normal(key, (4, 8, 16, 16, 1))
        w = jnp.asarray([4.0, 4.0, 0.0, 4.0])

        ref_state, ref_metrics = jax.jit(
            lambda s, d, ww, kk: protocol.gan_round(spec, pcfg, s, d, ww, kk)
        )(state, data, w, key)

        mesh = make_host_mesh(4, 1)
        run = shard_map_round(spec, pcfg, mesh, device_axes=("data",))
        sm_state, sm_metrics = run(state, data, w, key)

        for a, b in zip(jax.tree_util.tree_leaves(ref_state),
                        jax.tree_util.tree_leaves(sm_state)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), atol=2e-5)
        assert abs(float(ref_metrics["disc_objective"])
                   - float(sm_metrics["disc_objective"])) < 1e-4
        print("shard_map == vmap round OK")
    """)


@pytest.mark.slow
def test_mini_dryrun_train_and_decode_lower_on_mesh():
    """End-to-end mini dry-run: a reduced arch lowers + compiles on a
    (2, 4) host mesh through the production step builders."""
    run_sub("""
        import dataclasses, math
        import jax, jax.numpy as jnp
        from repro.configs import get_arch_config
        from repro.configs.base import MeshConfig, ShapeConfig
        from repro.launch import steps as steps_mod
        from repro.launch.analysis import analyze_compiled

        cfg = dataclasses.replace(get_arch_config('qwen3-1.7b').reduced(),
                                  vocab=512)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ('data', 'model'))
        mesh_cfg = MeshConfig()
        train_shape = ShapeConfig('mini_train', 32, 8, 'train')
        step, args = steps_mod.build_train_step(cfg, train_shape, mesh,
                                                mesh_cfg)
        with jax.sharding.set_mesh(mesh):
            compiled = step.lower(*args).compile()
            r = analyze_compiled(compiled, 8)
        assert r['roofline']['flops'] > 0
        assert r['collectives']['total_bytes'] > 0, 'averaging must show up'
        print('train lowers OK', r['roofline']['dominant'])

        dec_shape = ShapeConfig('mini_decode', 64, 8, 'decode')
        step, args = steps_mod.build_decode_step(cfg, dec_shape, mesh,
                                                 mesh_cfg)
        with jax.sharding.set_mesh(mesh):
            compiled = step.lower(*args).compile()
        print('decode lowers OK')

        pre_shape = ShapeConfig('mini_prefill', 64, 8, 'prefill')
        step, args = steps_mod.build_prefill_step(cfg, pre_shape, mesh,
                                                  mesh_cfg)
        with jax.sharding.set_mesh(mesh):
            compiled = step.lower(*args).compile()
        print('prefill lowers OK')
    """)


@pytest.mark.slow
def test_mesh_layout_train_step_executes():
    """launch/steps.build_train_step(layout='mesh'): the fused shard_map
    rounds-scan executes on a real 8-device mesh for BOTH mesh
    algorithms, including a shorter remainder chunk through a second
    compile (any round count works). Three backbone-scale shard_map
    compiles in one subprocess — give it headroom over the default
    timeout."""
    run_sub(timeout=1100, code="""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_arch_config
        from repro.configs.base import MeshConfig, ShapeConfig
        from repro.core import protocol
        from repro.core.fedgan import make_fedgan_state
        from repro.launch import steps as steps_mod
        from repro.launch.mesh import make_mesh
        from repro.models import gan as gan_model

        cfg = dataclasses.replace(get_arch_config('qwen3-1.7b').reduced(),
                                  vocab=256)
        mesh = make_mesh((8, 1), ('data', 'model'))
        shape = ShapeConfig('mesh_train', 16, 16, 'train')
        over = {'n_d': 1, 'n_g': 1}
        step2, args = steps_mod.build_train_step(
            cfg, shape, mesh, MeshConfig(), fuse_rounds=2, layout='mesh',
            pcfg_overrides=over)
        step1, _ = steps_mod.build_train_step(
            cfg, shape, mesh, MeshConfig(), fuse_rounds=1, layout='mesh',
            pcfg_overrides=over)
        state_abs, carry_abs, tokens_abs, key_abs, _ = args
        from repro.configs.base import ProtocolConfig
        pcfg = ProtocolConfig(n_devices=8, sample_size=2,
                              server_sample_size=8)
        state = protocol.make_train_state(
            jax.random.PRNGKey(0), lambda k: gan_model.gan_init(k, cfg),
            pcfg, 8)
        state = jax.tree.map(lambda x, a: jnp.asarray(x, a.dtype), state,
                             state_abs)
        carry = {'rr_cursor': jnp.int32(0),
                 'ewma_rate': jnp.ones(8, jnp.float32)}
        assert jax.eval_shape(lambda: carry) == carry_abs
        tokens = jnp.zeros(tokens_abs.shape, tokens_abs.dtype)
        key = jax.random.PRNGKey(0)
        with jax.sharding.set_mesh(mesh):
            state, carry, out = step2(state, carry, tokens, key,
                                      jnp.int32(0))
            state, carry, out2 = step1(state, carry, tokens, key,
                                       jnp.int32(2))   # remainder chunk
        assert out['wallclock_s'].shape == (2,)
        assert out2['mask'].shape == (1, 8)
        for leaf in jax.tree_util.tree_leaves(state):
            assert bool(jnp.isfinite(leaf.astype(jnp.float32)).all())
        print('mesh layout train step OK')

        # FedGAN through the SAME builder: two-net fused shard_map scan
        fstep, fargs = steps_mod.build_train_step(
            cfg, shape, mesh, MeshConfig(), fuse_rounds=2, layout='mesh',
            algorithm='fedgan', pcfg_overrides=over)
        fstate_abs = fargs[0]
        fstate = make_fedgan_state(
            jax.random.PRNGKey(0), lambda k: gan_model.gan_init(k, cfg),
            pcfg, 8)
        fstate = jax.tree.map(lambda x, a: jnp.asarray(x, a.dtype),
                              fstate, fstate_abs)
        # gen_opt is per-device on FedGAN (every device trains both nets)
        gen_opt_leaves = jax.tree_util.tree_leaves(fstate['gen_opt'])
        assert all(l.shape[0] == 8 for l in gen_opt_leaves)
        carry = {'rr_cursor': jnp.int32(0),
                 'ewma_rate': jnp.ones(8, jnp.float32)}
        with jax.sharding.set_mesh(mesh):
            fstate, carry, fout = fstep(fstate, carry, tokens, key,
                                        jnp.int32(0))
        assert fout['wallclock_s'].shape == (2,)
        assert set(fout['metrics']) == {'participation'}
        for leaf in jax.tree_util.tree_leaves(fstate):
            assert bool(jnp.isfinite(leaf.astype(jnp.float32)).all())
        print('mesh layout fedgan train step OK')

        # stacked builder stays proposed-only (FedGAN stacked runs via
        # the Trainer, not the pod-scale builder)
        try:
            steps_mod.build_train_step(cfg, shape, mesh, MeshConfig(),
                                       layout='stacked',
                                       algorithm='fedgan')
        except ValueError as e:
            assert 'proposed' in str(e)
        else:
            raise AssertionError('stacked fedgan builder must raise')
        print('stacked builder algorithm guard OK')
    """)


@pytest.mark.slow
def test_mesh_layout_tp2_backbone_matches_tp1():
    """launch/steps.build_train_step(layout='mesh', tp=2) on a 16-device
    (8 data x 2 model) host mesh: the backbone's feed-forward blocks run
    Megatron column/row-parallel inside each worker slice and the fused
    scan reproduces the tp=1 run to bf16 round-off from the same initial
    state. Two backbone-scale shard_map compiles in one subprocess."""
    run_sub(n_devices=16, timeout=1100, code="""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_arch_config
        from repro.configs.base import MeshConfig, ProtocolConfig, ShapeConfig
        from repro.core import protocol
        from repro.launch import steps as steps_mod
        from repro.launch.mesh import make_mesh
        from repro.models import gan as gan_model
        from repro.sharding import rules

        cfg = dataclasses.replace(get_arch_config('qwen3-1.7b').reduced(),
                                  vocab=256)
        shape = ShapeConfig('mesh_tp', 16, 16, 'train')
        over = {'n_d': 1, 'n_g': 1}
        mesh2 = make_mesh((8, 2), ('data', 'model'))
        step2, args = steps_mod.build_train_step(
            cfg, shape, mesh2, MeshConfig(), fuse_rounds=2, layout='mesh',
            tp=2, pcfg_overrides=over)
        mesh1 = make_mesh((8, 1), ('data', 'model'))
        step1, _ = steps_mod.build_train_step(
            cfg, shape, mesh1, MeshConfig(), fuse_rounds=2, layout='mesh',
            tp=1, pcfg_overrides=over)

        state_abs, carry_abs, tokens_abs, key_abs, _ = args
        pcfg = ProtocolConfig(n_devices=8, sample_size=2,
                              server_sample_size=8)
        state = protocol.make_train_state(
            jax.random.PRNGKey(0), lambda k: gan_model.gan_init(k, cfg),
            pcfg, 8)
        state = jax.tree.map(lambda x, a: jnp.asarray(x, a.dtype), state,
                             state_abs)

        # the name rules actually shard the ff weights at this config
        dims = rules.tp_tree_dims(state['disc'], 2)
        assert any(d is not None for d in dims), 'nothing TP-sharded'
        assert rules.tp_local_size(state['disc'], 2) < sum(
            x.size for x in jax.tree_util.tree_leaves(state['disc']))

        def make_carry():   # fresh buffers: the steps donate their carry
            return {'rr_cursor': jnp.int32(0),
                    'ewma_rate': jnp.ones(8, jnp.float32)}
        tokens = jnp.zeros(tokens_abs.shape, tokens_abs.dtype)
        key = jax.random.PRNGKey(0)
        with jax.sharding.set_mesh(mesh2):
            s2, c2, out2 = step2(jax.tree.map(jnp.copy, state),
                                 make_carry(), tokens, key, jnp.int32(0))
        with jax.sharding.set_mesh(mesh1):
            s1, c1, out1 = step1(jax.tree.map(jnp.copy, state),
                                 make_carry(), tokens, key, jnp.int32(0))
        np.testing.assert_array_equal(np.asarray(out1['mask']),
                                      np.asarray(out2['mask']))
        np.testing.assert_allclose(np.asarray(out1['wallclock_s']),
                                   np.asarray(out2['wallclock_s']),
                                   rtol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(s1),
                        jax.tree_util.tree_leaves(s2)):
            a32 = np.asarray(a, np.float32)
            b32 = np.asarray(b, np.float32)
            assert np.isfinite(b32).all()
            # bf16 state: TP changes only matmul reduction order
            np.testing.assert_allclose(a32, b32, atol=0.03,
                                       rtol=0.02)
        print('mesh tp=2 backbone matches tp=1 OK')
    """)


@pytest.mark.slow
def test_protocol_round_executes_on_mesh():
    """Actually EXECUTE (not just compile) one protocol round with the
    stacked axis sharded over a 4-device data axis."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.base import ProtocolConfig
        from repro.configs.dcgan import DCGANConfig
        from repro.core import protocol
        from repro.models import dcgan
        from repro.models.specs import make_dcgan_spec
        from repro.launch.mesh import make_host_mesh

        cfg = DCGANConfig(nz=8, ngf=8, ndf=8, nc=1, image_size=16)
        spec = make_dcgan_spec(cfg)
        pcfg = ProtocolConfig(n_devices=4, n_d=1, n_g=1, sample_size=4,
                              server_sample_size=4)
        key = jax.random.PRNGKey(0)
        mesh = make_host_mesh(4, 1)
        state = protocol.make_train_state(
            key, lambda k: dcgan.gan_init(k, cfg), pcfg, 4)
        data = jax.device_put(
            jax.random.normal(key, (4, 8, 16, 16, 1)),
            NamedSharding(mesh, P('data')))
        w = jnp.full((4,), 4.0)
        with jax.sharding.set_mesh(mesh):
            new_state, metrics = jax.jit(
                lambda s, d, ww, kk: protocol.gan_round(spec, pcfg, s, d,
                                                        ww, kk)
            )(state, data, w, key)
        assert all(bool(jnp.isfinite(x).all())
                   for x in jax.tree_util.tree_leaves(new_state))
        print('executed round on mesh OK')
    """)
