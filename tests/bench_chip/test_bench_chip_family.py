"""A model family is taken from new modules alone.

This file defines a second family, the program's two-layer MLP-GAN
(`models/gan.mlp_gan_spec`): its reference (data, weights, a tanh MLP at
`Precision.HIGHEST` and its fp8 form), its program spec and its FLOP
count, registered as `benchmarks.chip.families.mlp.{reference,program}`
and `benchmarks.chip.flops.mlp`, the names a configuration with
`"family": "mlp"` makes the harness look up. A cell of it runs through
`run.run_cell` on the CPU: a sound run is `correct`, and a program whose
round returns the state unchanged is not."""
from __future__ import annotations

import json
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import reference, run, spec  # noqa: E402

CONFIG = dict(name="mlp-test", family="mlp", d_z=8, d_hidden=16, d_data=64,
              train_images=256)
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

def make_shards(key, workers, cfg, mesh=None):
    n = cfg["train_images"] // workers
    return jax.random.uniform(key, (workers, n, cfg["d_data"]), minval=-1.0,
                              maxval=1.0)


def init_params(key, cfg):
    ks = jax.random.split(key, 4)
    w = lambda k, shape: 0.1 * jax.random.normal(k, shape)
    z, h, d = cfg["d_z"], cfg["d_hidden"], cfg["d_data"]
    return {"gen": {"w_in": w(ks[0], (z, h)), "w_out": w(ks[1], (h, d))},
            "disc": {"w_in": w(ks[2], (d, h)), "w_out": w(ks[3], (h, 1))}}


def noise(key, n, cfg):
    return jax.random.normal(key, (n, cfg["d_z"]))


def _mlp(p, x, variant):
    dot = lambda a, w: jnp.dot(a, w, precision=reference.HI)
    h = jnp.tanh(reference.mxu(dot, x, p["w_in"], variant))
    return reference.mxu(dot, h, p["w_out"], variant)


def generator(gen, z, cfg, variant=None):
    return jnp.tanh(_mlp(gen, z, variant))


def discriminator(disc, x, cfg, variant=None):
    return _mlp(disc, x.reshape(x.shape[0], -1), variant)[:, 0]


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


@pytest.fixture
def mlp_family(monkeypatch):
    """The family's three modules, under the names the harness looks up."""
    modules = {
        "benchmarks.chip.families.mlp.reference": _module(
            "reference", MODEL_KEYS=("d_z", "d_hidden", "d_data"),
            make_shards=make_shards, init_params=init_params, noise=noise,
            generator=generator, discriminator=discriminator),
        "benchmarks.chip.families.mlp.program": _module(
            "program", spec=program_spec),
        "benchmarks.chip.flops.mlp": _module(
            "flops", round_flops=round_flops, wavg_bytes=wavg_bytes),
    }
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


def test_spec_cell_takes_the_family_from_its_modules(mlp_family, tmp_path):
    cfg = tmp_path / "mlp-test.json"
    cfg.write_text(json.dumps(CONFIG))
    name = "dcgan32-cifar10.k10-stacked"      # its traffic and limits files
    bench = {**spec.benchmark(),
             "configs": [{"name": "mlp-test", "file": str(cfg)}],
             "workloads": [{"name": name, "config": "mlp-test",
                            "traffic": "k10-stacked", "chips": 1}]}
    cell = spec.cell(name, bench)
    assert cell.config == CONFIG
    assert cell.flops_module() is mlp_family["benchmarks.chip.flops.mlp"]
    assert spec.family_module("mlp", "reference") is mlp_family[
        "benchmarks.chip.families.mlp.reference"]
