"""Algorithm 1 on the stacked layout, read from the compiled program: an
unsharded stacked round runs the workers one after another on plain
convolutions (`protocol.devices_round`) and makes the shared fakes once
a local step; a round whose worker axis is sharded (`constrain_stacked`)
keeps the vmap and its K-way grouped convolutions."""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ProtocolConfig
from repro.configs.dcgan import DCGANConfig
from repro.core import Trainer, protocol, stages
from repro.launch.hlo_costs import HloModule
from repro.models import dcgan
from repro.models.specs import make_dcgan_spec

CFG = DCGANConfig(nz=8, ngf=4, ndf=4, nc=1, image_size=16)
SPEC = make_dcgan_spec(CFG)
K, N_K, N_D, ROUNDS = 3, 8, 2, 2
DATA = jnp.zeros((K, N_K, 16, 16, 1))
PCFG = ProtocolConfig(n_devices=K, n_d=N_D, n_g=1, sample_size=4,
                      server_sample_size=4)


def _convolutions(hlo: str):
    """(line, runs per call of the program) of every convolution."""
    module = HloModule(hlo)
    runs = module.runs()
    return [(ins.line, runs.get(name, 0))
            for name, instrs in module.computations.items()
            for ins in instrs if ins.op == "convolution"]


def _stage(line: str) -> str:
    name = re.search(r'op_name="([^"]*)"', line)
    found = re.findall(r"round\.\w+", name.group(1) if name else "")
    return found[-1] if found else ""


def _a1_convolutions(convs):
    """Algorithm 1's convolutions split by pass: the fakes' generator
    forward is the only one outside the discriminator's jvp and its
    transpose (the local steps differentiate D only)."""
    a1 = [(line, n) for line, n in convs if _stage(line) == stages.A1_LOCAL]
    fakes = [(line, n) for line, n in a1 if "jvp(" not in line]
    return fakes, [(line, n) for line, n in a1 if "jvp(" in line]


@pytest.fixture(scope="module")
def stacked_chunk():
    t = Trainer(SPEC, PCFG, lambda k: dcgan.gan_init(k, CFG), DATA,
                jax.random.PRNGKey(0), driver="fused", partition=None)
    fn = t._chunk_fn(ROUNDS)
    return fn.lower(t.state, t._sched_carry, t.data, t.key,
                    jnp.int32(0)).compile().as_text()


def test_stacked_chunk_has_no_grouped_convolution(stacked_chunk):
    convs = _convolutions(stacked_chunk)
    grouped = [line for line, _ in convs if re.search(
        r"(?:feature|batch)_group_count=(?!1\b)\d+", line)]
    assert convs and not grouped
    assert {_stage(line) for line, _ in convs} == {stages.A1_LOCAL,
                                                   stages.A3_SERVER}


def test_stacked_chunk_loops_over_workers_and_makes_fakes_once(
        stacked_chunk):
    """Each discriminator convolution runs once per worker and local
    step, each of the fakes' generator convolutions once per local step."""
    fakes, disc = _a1_convolutions(_convolutions(stacked_chunk))
    assert len(fakes) == 3 and disc                 # G's three layers
    assert {n for _, n in fakes} == {ROUNDS * N_D}
    assert {n for _, n in disc} == {ROUNDS * K * N_D}


def test_stacked_chunk_copies_no_worker_shard(stacked_chunk):
    """Each local step gathers its m_k rows from all the shards at
    (worker, rows): nothing in the program has a shard's shape."""
    module = HloModule(stacked_chunk)
    shapes = [ins.type_str for instrs in module.computations.values()
              for ins in instrs]
    assert shapes and not any(f"[{N_K},16,16,1]" in s for s in shapes)


def test_sharded_worker_axis_keeps_the_vmap():
    """With `constrain_stacked` (the GSPMD pod path, whose worker axis is
    spread over devices) Algorithm 1 stays vmapped: K-way grouped
    discriminator convolutions, one pass per local step."""
    state = protocol.make_train_state(
        jax.random.PRNGKey(0), lambda k: dcgan.gan_init(k, CFG), PCFG, K)
    weights = jnp.full((K,), 4.0)
    fn = jax.jit(lambda s, d: protocol.gan_round(
        SPEC, PCFG, s, d, weights, jax.random.PRNGKey(1),
        constrain_stacked=lambda tree: tree))
    convs = _convolutions(fn.lower(state, DATA).compile().as_text())
    fakes, disc = _a1_convolutions(convs)
    assert fakes and {n for _, n in fakes} == {N_D}
    assert any(f"feature_group_count={K}" in line for line, _ in disc)
    assert {n for _, n in disc} == {N_D}
