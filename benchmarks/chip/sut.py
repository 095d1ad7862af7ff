"""The system under test: the repository's distributed-GAN trainer,
`repro.core.engine.Trainer` with the fused driver, driven as a user
drives it, on the model that the configuration's family names
(`families/<family>/program.py`). Those and this are the only modules
of the benchmark that import the program."""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.chip import spec
from repro.configs.base import ProtocolConfig
from repro.core import Trainer


def make_mesh(devices):
    """One paper worker per chip: a (chips, 1) ("data", "model") mesh."""
    auto = (jax.sharding.AxisType.Auto,) * 2
    return jax.make_mesh((len(devices), 1), ("data", "model"),
                         axis_types=auto, devices=devices)


def trainer(cfg: dict, traffic: dict, init_params, shards, key, mesh=None):
    """A fused Trainer over pre-sharded (K, n_k, ...) `shards`, its
    weights from the zero-argument `init_params`."""
    pcfg = ProtocolConfig(
        n_devices=traffic["workers"], n_d=traffic["n_d"], n_g=traffic["n_g"],
        sample_size=traffic["m_k"], server_sample_size=traffic["M"],
        lr_d=traffic["lr_d"], lr_g=traffic["lr_g"],
        schedule=traffic["schedule"], scheduler=traffic["scheduler"],
        scheduling_ratio=traffic["scheduling_ratio"],
        quantize_bits=traffic["quantize_bits"],
        optimizer=traffic["optimizer"])
    model = spec.family_module(cfg["family"], "program").spec(cfg)
    layout = {"layout": traffic["layout"]}
    if traffic["layout"] == "mesh":
        layout.update(mesh=mesh, avg_impl=traffic["avg_impl"])
    return Trainer(model, pcfg, lambda _key: init_params(),
                   shards, key, algorithm=traffic["algorithm"],
                   driver="fused", partition=None, **layout)


def run_chunk(t, rounds: int):
    """One dispatch of `rounds` rounds, waited for on the device."""
    t.run(rounds)
    jax.block_until_ready(t.state)


def params(t):
    """The global generator and discriminator, on the host."""
    return jax.device_get({"gen": t.state["gen"], "disc": t.state["disc"]})


def objectives(t) -> np.ndarray:
    """(rounds, 2): each round's discriminator and generator objective."""
    return np.array([[r.metrics["disc_objective"], r.metrics["gen_objective"]]
                     for r in t.history], np.float64)
