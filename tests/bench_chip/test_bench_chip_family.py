"""A model family is taken from new modules alone.

This file defines a second family, the program's two-layer MLP-GAN
(`models/gan.mlp_gan_spec`): its reference (data, weights, a tanh MLP at
`Precision.HIGHEST` and its fp8 form), its program spec and its FLOP
count, registered as `benchmarks.chip.families.mlp.{reference,program}`
and `benchmarks.chip.flops.mlp`, the names a configuration with
`"family": "mlp"` makes the harness look up. A cell of it runs through
`run.run_cell` on the CPU: a sound run is `correct`, and a program whose
round returns the state unchanged is not. Its configuration names its
layers in a list and its initialisation in a dict, as a published
config names a layer pattern or RoPE settings, and counts its samples
under a name of its own.

The reference's Algorithm 2, a running sum over the workers, is held
against the stacked average it replaced, and its memory against K."""
from __future__ import annotations

import functools
import json
import os
import subprocess
import sys
import textwrap
import types
from pathlib import Path
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import reference, run, spec  # noqa: E402
from test_bench_chip_static import check_cell  # noqa: E402

CONFIG = dict(name="mlp-test", family="mlp", d_z=8, d_hidden=16, d_data=64,
              layer_types=["tanh", "linear"], init={"std": 0.1},
              n_samples=256)
TRAFFIC = dict(algorithm="proposed", workers=2, n_d=2, n_g=2, m_k=16, M=16,
               lr_d=2e-2, lr_g=2e-2, optimizer="sgd", schedule="serial",
               scheduler="all", scheduling_ratio=1.0, quantize_bits=16,
               layout="stacked", avg_impl="pallas", rounds_per_dispatch=2)
SEED = 2 ** 31 + 29
E2E = ({"name": "rounds_per_s", "unit": "rounds/s"},
       {"name": "setup_s", "unit": "s"})


# ---------------------------------------------------------------------------
# the family, as a later change would add it in files of its own
# ---------------------------------------------------------------------------

ACTIVATIONS = {"tanh": jnp.tanh, "linear": lambda h: h}


def samples(cfg):
    return cfg["n_samples"]


def make_shards(key, workers, cfg, mesh=None):
    n = samples(cfg) // workers
    x = jax.random.uniform(key, (workers, n, cfg["d_data"]), minval=-1.0,
                           maxval=1.0)
    return x if mesh is None else jax.device_put(
        x, NamedSharding(mesh, P("data")))


def init_params(key, cfg):
    ks = jax.random.split(key, 4)
    w = lambda k, shape: cfg["init"]["std"] * jax.random.normal(k, shape)
    z, h, d = cfg["d_z"], cfg["d_hidden"], cfg["d_data"]
    return {"gen": {"w_in": w(ks[0], (z, h)), "w_out": w(ks[1], (h, d))},
            "disc": {"w_in": w(ks[2], (d, h)), "w_out": w(ks[3], (h, 1))}}


def noise(key, n, cfg):
    return jax.random.normal(key, (n, cfg["d_z"]))


def _mlp(p, x, cfg, variant):
    dot = lambda a, w: jnp.dot(a, w, precision=reference.HI)
    act_in, act_out = (ACTIVATIONS[t] for t in cfg["layer_types"])
    h = act_in(reference.mxu(dot, x, p["w_in"], variant))
    return act_out(reference.mxu(dot, h, p["w_out"], variant))


def generator(gen, z, cfg, variant=None):
    return jnp.tanh(_mlp(gen, z, cfg, variant))


def discriminator(disc, x, cfg, variant=None):
    return _mlp(disc, x.reshape(x.shape[0], -1), cfg, variant)[:, 0]


def program_spec(cfg):
    from repro.models import gan
    return gan.mlp_gan_spec(d_z=cfg["d_z"])


def round_flops(cfg, traffic, chips):
    d = 2.0 * cfg["d_hidden"] * (cfg["d_data"] + 1)
    g = 2.0 * cfg["d_hidden"] * (cfg["d_z"] + cfg["d_data"])
    k, n_d, n_g = traffic["workers"], traffic["n_d"], traffic["n_g"]
    m, big_m = traffic["m_k"], traffic["M"]
    parts = {"algorithm1": k * n_d * 2 * m * 3 * d,
             "fakes": min(chips, k) * n_d * m * g,
             "algorithm3": n_g * big_m * (3 * g + 2 * d)}
    parts["total"] = sum(parts.values())
    return parts


def wavg_bytes(cfg, traffic):
    n = cfg["d_hidden"] * (cfg["d_data"] + 1)
    return 4.0 * (traffic["workers"] * n + n + traffic["workers"])


def _module(name, **attrs):
    m = types.ModuleType(name)
    m.__dict__.update(attrs)
    return m


def family_modules():
    """The family's three modules, under the names the harness looks up."""
    return {
        "benchmarks.chip.families.mlp.reference": _module(
            "reference", MODEL_KEYS=("d_z", "d_hidden", "d_data",
                                     "layer_types", "init"),
            samples=samples, make_shards=make_shards,
            init_params=init_params, noise=noise, generator=generator,
            discriminator=discriminator),
        "benchmarks.chip.families.mlp.program": _module(
            "program", spec=program_spec),
        "benchmarks.chip.flops.mlp": _module(
            "flops", round_flops=round_flops, wavg_bytes=wavg_bytes),
    }


@pytest.fixture
def mlp_family(monkeypatch):
    modules = family_modules()
    for name, module in modules.items():
        monkeypatch.setitem(sys.modules, name, module)
    return modules


def _cell():
    limits = spec.cell("dcgan32-cifar10.k10-stacked").limits
    return spec.Cell("mlp-test.k2", 1, CONFIG, TRAFFIC, limits, E2E, ())


def _frozen_round(monkeypatch):
    from repro.core import protocol
    original = protocol.gan_round

    def frozen(spec_, pcfg, state, *args, **kwargs):
        return state, original(spec_, pcfg, state, *args, **kwargs)[1]

    monkeypatch.setattr(protocol, "gan_round", frozen)


@pytest.mark.parametrize("fault,correct", [(None, True),
                                           (_frozen_round, False)],
                         ids=["sound", "state_unchanged"])
def test_a_family_from_new_modules_runs_through_run_cell(
        mlp_family, monkeypatch, fault, correct):
    if fault is not None:
        fault(monkeypatch)
    result = run.run_cell(_cell(), SEED, 0.01, False, jax.devices()[:1])
    assert result["correct"] is correct, result["checks"]
    assert result["attempted"] >= TRAFFIC["rounds_per_dispatch"]


def _named_cell(tmp_path):
    """A cell of the family through `spec.cell`, from a configuration
    file, with the traffic and limits files of a benchmark cell."""
    cfg = tmp_path / "mlp-test.json"
    cfg.write_text(json.dumps(CONFIG))
    name = "dcgan32-cifar10.k10-stacked"
    bench = {**spec.benchmark(),
             "configs": [{"name": "mlp-test", "file": str(cfg)}],
             "workloads": [{"name": name, "config": "mlp-test",
                            "traffic": "k10-stacked", "chips": 1}]}
    return spec.cell(name, bench)


def test_spec_cell_takes_the_family_from_its_modules(mlp_family, tmp_path):
    cell = _named_cell(tmp_path)
    assert cell.config == CONFIG
    assert cell.flops_module() is mlp_family["benchmarks.chip.flops.mlp"]
    assert spec.family_module("mlp", "reference") is mlp_family[
        "benchmarks.chip.families.mlp.reference"]


def test_a_family_without_train_images_passes_the_static_checks(
        mlp_family, tmp_path):
    cell = _named_cell(tmp_path)
    assert "train_images" not in cell.config
    check_cell(cell)


def test_model_keys_reach_the_family_as_loaded(mlp_family, monkeypatch):
    """A list and a dict among the model keys key the reference's jit
    and reach the family's networks as they were loaded."""
    seen = []
    module = mlp_family["benchmarks.chip.families.mlp.reference"]

    def discriminator_seen(disc, x, cfg, variant=None):
        seen.append(cfg)
        return discriminator(disc, x, cfg, variant)

    monkeypatch.setattr(module, "discriminator", discriminator_seen)
    # a key of its own, so that no earlier test's trace is reused
    cfg = {**CONFIG, "init": {"std": 0.1, "seen": True}}
    cell = spec.Cell("mlp-test.k2", 1, cfg, TRAFFIC, _cell().limits, E2E, ())
    result = run.run_cell(cell, SEED, 0.01, False, jax.devices()[:1])
    assert result["correct"], result["checks"]
    assert seen
    for got in seen:
        assert type(got["layer_types"]) is list
        assert got["layer_types"] == cfg["layer_types"]
        assert type(got["init"]) is dict and got["init"] == cfg["init"]
    static = reference.statics(cfg, TRAFFIC)[0]
    reordered = reference.Static(dict(reversed(list(static.value.items()))))
    assert static.value["init"] is cfg["init"]
    assert hash(static) == hash(reordered) and static == reordered
    assert static != reference.Static({**static.value, "layer_types": []})


# ---------------------------------------------------------------------------
# Algorithm 2 in the reference: a running sum over the workers
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg_items", "traffic_items",
                                             "variant"))
def stacked_round(params, real, round_key, *, cfg_items, traffic_items,
                  variant=None):
    """`reference.reference_round` with Algorithm 2 as it was first
    written: every worker's upload kept, stacked, then one tensordot."""
    cfg, tr = cfg_items.value, dict(traffic_items)
    gen, disc = params["gen"], params["disc"]
    fakes = reference.shared_fakes(gen, round_key, cfg, tr, variant)
    uploads, objs = jax.lax.map(
        lambda a: reference.local_round(disc, fakes, a[1], a[0], round_key,
                                        cfg, tr, variant),
        (jnp.arange(tr["workers"]), real))
    if variant == "no_exchange":
        disc = jax.tree.map(lambda u: u[0], uploads)
    else:
        w = reference.average_weights(tr)
        disc = jax.tree.map(
            lambda u: jnp.tensordot(w, u, 1, precision=reference.HI), uploads)
    gen, loss = reference.server_round(gen, disc, round_key, cfg, tr,
                                       variant)
    return {"gen": gen, "disc": disc}, (jnp.mean(objs), loss)


def oracle_gaps(traffic, devices, rounds=2):
    """{variant: the worst leaf's ||running - stacked|| / ||stacked||}
    after `rounds` reference rounds on the cell's shards."""
    cell = spec.Cell("mlp-test.oracle", len(devices), CONFIG, traffic, {},
                     E2E, ())
    prep = run.prepare(cell, SEED, devices)

    def params_after(variant):
        return reference.run(CONFIG, traffic, prep.params0, prep.shards,
                             prep.key, rounds, record_after=(rounds,),
                             variant=variant)[1][rounds]

    gaps = {}
    for variant in reference.VARIANTS:
        got = params_after(variant)
        with mock.patch.object(reference, "reference_round", stacked_round):
            want = params_after(variant)
        gaps[str(variant)] = max(
            float(np.linalg.norm(np.float64(a) - b)
                  / np.linalg.norm(np.float64(b)))
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))
    return gaps


def test_the_running_sum_matches_the_stacked_average(mlp_family):
    gaps = oracle_gaps({**TRAFFIC, "workers": 4}, jax.devices()[:1])
    assert set(gaps) == {str(v) for v in reference.VARIANTS}
    assert max(gaps.values()) <= 1e-6, gaps


def test_the_running_sum_matches_the_stacked_average_on_a_mesh():
    """The same on a 2-device mesh, where each worker's data is its
    chip's own shard."""
    code = textwrap.dedent(f"""
        import json, sys
        sys.path[:0] = [{str(HERE)!r}]
        import jax
        import test_bench_chip_family as t
        from benchmarks.chip import reference
        sys.modules.update(t.family_modules())
        traffic = {{**t.TRAFFIC, "layout": "mesh"}}
        prep = t.run.prepare(t.spec.Cell("m", 2, t.CONFIG, traffic, {{}},
                                         t.E2E, ()), 1, jax.devices()[:2])
        print(len(reference.worker_sources(prep.shards)[0][0].devices()))
        print(json.dumps(t.oracle_gaps(traffic, jax.devices()[:2])))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=HERE, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    one_device, line = out.stdout.split("\n")[-3:-1]
    assert one_device == "1"
    gaps = json.loads(line)
    assert set(gaps) == {str(v) for v in reference.VARIANTS}
    assert max(gaps.values()) <= 1e-6, gaps


def test_the_reference_round_memory_is_flat_in_workers(mlp_family):
    """From 2 to 8 workers the compiled round's temporaries grow by less
    than one f32 discriminator; kept stacked they grew by six."""
    cfg = {**CONFIG, "d_hidden": 1024, "d_data": 1024}
    disc_bytes = 4 * cfg["d_hidden"] * (cfg["d_data"] + 1)

    def temporaries(workers):
        traffic = {**TRAFFIC, "workers": workers}
        cfg_items, tr_items = reference.statics(cfg, traffic)
        params = jax.eval_shape(lambda k: init_params(k, cfg),
                                jax.random.PRNGKey(0))
        real = jax.ShapeDtypeStruct(
            (workers, traffic["n_d"], traffic["m_k"], cfg["d_data"]),
            jnp.float32)
        compiled = reference.reference_round.lower(
            params, real, jax.random.PRNGKey(0), cfg_items=cfg_items,
            traffic_items=tr_items).compile()
        return compiled.memory_analysis().temp_size_in_bytes

    assert temporaries(8) - temporaries(2) < disc_bytes
