"""CPU tests of the comparison that decides a chip run's `correct`.

A sound run passes. The fp8 control (the reference computed one
precision step below the configuration's bf16 MXU passes) fails, and so
does the program with each fault a training cell can have: a round that
returns its state unchanged, half of each local batch left out, and (on
a mesh) the Algorithm-2 exchange left out. The limits are those of the
benchmark's cells; the size is the 32x32 DCGAN at its real widths with
small batches, where the gaps the limits were set from show within a few
rounds."""
from __future__ import annotations

import dataclasses
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import check, run, spec  # noqa: E402

CONFIG = dict(name="test", family="dcgan", nz=100, ngf=64, ndf=64, nc=3,
              image_size=32, train_images=64, n_modes=10)
TRAFFIC = dict(algorithm="proposed", workers=2, n_d=2, n_g=2, m_k=16, M=16,
               lr_d=2e-4, lr_g=2e-4, optimizer="sgd", schedule="serial",
               scheduler="all", scheduling_ratio=1.0, quantize_bits=16,
               layout="stacked", avg_impl="pallas", rounds_per_dispatch=2)
SEED = 2 ** 31 + 11
E2E = ({"name": "rounds_per_s", "unit": "rounds/s"},
       {"name": "setup_s", "unit": "s"})


def _cell(limits_of: str, **traffic):
    limits = spec.cell(limits_of).limits
    return spec.Cell("test", 1, CONFIG, {**TRAFFIC, **traffic}, limits, E2E,
                     ())


def _run(cell, devices=None):
    import jax
    return run.run_cell(cell, SEED, 0.01, False,
                        devices or jax.devices()[:cell.chips])


@pytest.fixture(scope="module")
def cell():
    return _cell("dcgan32-cifar10.k10-stacked")


def test_a_sound_run_is_correct(cell):
    result = _run(cell)
    assert result["correct"], result["checks"]
    assert list(result)[-1] == "checks"
    assert result["attempted"] >= TRAFFIC["rounds_per_dispatch"]


def test_the_fp8_control_is_not_correct(cell):
    import jax
    prep = run.prepare(cell, SEED, jax.devices()[:1])
    r = TRAFFIC["rounds_per_dispatch"]
    ref_obj, ref_after = run.reference_steps(cell, prep)
    obj, after = run.reference_steps(cell, prep, "fp8")
    last = run.CHECK_STEPS * r
    values = check.readings(prep.params0, after, ref_after, obj, ref_obj,
                            r, last)
    ok, table = check.verdict(values, cell.limits)
    assert not ok, table


def _frozen_round(monkeypatch):
    from repro.core import protocol
    original = protocol.gan_round

    def frozen(spec_, pcfg, state, *args, **kwargs):
        return state, original(spec_, pcfg, state, *args, **kwargs)[1]

    monkeypatch.setattr(protocol, "gan_round", frozen)


def _half_batch(monkeypatch):
    from repro.core import protocol
    original = protocol.device_update

    def half(spec_, pcfg, *args):
        return original(spec_, dataclasses.replace(
            pcfg, sample_size=pcfg.sample_size // 2), *args)

    monkeypatch.setattr(protocol, "device_update", half)


@pytest.mark.parametrize("fault", [_frozen_round, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_a_broken_program_is_not_correct(cell, monkeypatch, fault):
    fault(monkeypatch)
    result = _run(cell)
    assert not result["correct"], result["checks"]


def test_a_mesh_without_the_exchange_is_not_correct():
    """The mesh cell's fault: each chip keeps its own discriminator."""
    code = textwrap.dedent(f"""
        import sys
        sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / "src")!r}]
        import jax
        from repro.core import shard_round
        from benchmarks.chip import run, spec
        cell = spec.Cell("test", 2, {CONFIG!r},
                         {{**{TRAFFIC!r}, "layout": "mesh"}},
                         spec.cell("dcgan64-celeba.k4-mesh").limits,
                         {E2E!r}, ())
        sound = run.run_cell(cell, {SEED}, 0.01, False, jax.devices()[:2])
        shard_round.weighted_average_psum = lambda local, w, **kw: local
        broken = run.run_cell(cell, {SEED}, 0.01, False, jax.devices()[:2])
        print(sound["correct"], broken["correct"], broken["checks"])
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    sound, broken = out.stdout.split()[:2]
    assert (sound, broken) == ("True", "False"), out.stdout
