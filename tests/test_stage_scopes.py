"""The stage names on a round's device ops and the fused driver's host
spans (core/stages.py): where the compiled chunk files each op, that the
names leave the compiled program as it was, and how the spans nest in a
profiler trace."""
from __future__ import annotations

import contextlib
import glob
import json
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ProtocolConfig
from repro.configs.dcgan import DCGANConfig
from repro.core import Trainer, stages
from repro.models import dcgan
from repro.models.specs import make_dcgan_spec

CFG = DCGANConfig(nz=8, ngf=4, ndf=4, nc=1, image_size=16)
SPEC = make_dcgan_spec(CFG)
K = 2
DATA = jnp.zeros((K, 8, 16, 16, 1))
_INSTR = re.compile(r"^\s*(?:ROOT )?%?\S+ = (?:\([^=]*\)|\S+) ([a-z][\w-]*)\(")


def _trainer(algorithm="proposed", **pcfg):
    pcfg = ProtocolConfig(n_devices=K, n_d=1, n_g=1, sample_size=4,
                          server_sample_size=4, **pcfg)
    return Trainer(SPEC, pcfg, lambda k: dcgan.gan_init(k, CFG), DATA,
                   jax.random.PRNGKey(0), driver="fused", partition=None,
                   algorithm=algorithm)


def _chunk_hlo(t, n=2) -> str:
    fn = t._chunk_fn(n)
    return fn.lower(t.state, t._sched_carry, t.data, t.key,
                    jnp.int32(0)).compile().as_text()


def _ops(hlo: str):
    """(opcode, stage) of every instruction of a compiled module; the
    stage is the deepest `round.*` scope of its op_name, else ''."""
    out = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m:
            name = re.search(r'op_name="([^"]*)"', line)
            found = re.findall(r"round\.\w+", name.group(1) if name else "")
            out.append((m.group(1), found[-1] if found else ""))
    return out


@pytest.mark.parametrize("algorithm,pcfg,conv_stages,expected", [
    ("proposed", {}, {stages.A1_LOCAL, stages.A3_SERVER}, set(stages.STAGES)),
    ("proposed", {"hoist_fakes": True}, {stages.A1_LOCAL, stages.A3_SERVER},
     set(stages.STAGES)),
    ("fedgan", {}, {stages.A1_LOCAL},
     {stages.A1_LOCAL, stages.UPLINK, stages.A2_AVERAGE}),
], ids=["proposed", "proposed-hoisted", "fedgan"])
def test_stacked_chunk_files_each_convolution_under_its_stage(
        algorithm, pcfg, conv_stages, expected):
    ops = _ops(_chunk_hlo(_trainer(algorithm, **pcfg)))
    convs = [s for op, s in ops if op == "convolution"]
    assert convs and set(convs) == conv_stages
    assert {s for _, s in ops if s} == expected


def test_stage_names_leave_the_compiled_chunk_unchanged(monkeypatch):
    """The same chunk compiled with the scopes stubbed out, each from
    cleared caches: as many fusions, and every instruction the same once
    metadata is dropped and names are numbered by first use."""
    jax.clear_caches()
    scoped = _chunk_hlo(_trainer())
    jax.clear_caches()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _chunk_hlo(_trainer())
    assert "round.a1_local" in scoped and "round.a1_local" not in plain

    def instructions(hlo):
        lines = [re.sub(r", metadata=\{[^}]*\}", "", line)
                 for line in hlo.splitlines() if _INSTR.match(line)]
        names = {}
        number = lambda m: names.setdefault(m.group(0), f"%v{len(names)}")
        return [re.sub(r"%[\w.\-]+", number, line) for line in lines]

    count = lambda hlo: len(re.findall(r" fusion\(", hlo))
    assert count(scoped) == count(plain) > 0
    assert instructions(scoped) == instructions(plain)


def _spans(trace_dir):
    """Program spans of the host planes: (start, dur, name, stats)."""
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [(e.start_ns, e.duration_ns, e.name, dict(e.stats))
                    for e in line.events
                    if e.name.startswith(("trainer.", "shard_round."))]
    return sorted(out)


def _children(spans, parent):
    s, d = parent[0], parent[1]
    return [c for c in spans if c is not parent and s <= c[0]
            and c[0] + c[1] <= s + d]


def test_fused_dispatches_record_nested_host_spans(tmp_path):
    t = _trainer()
    t.run(2)                                   # compiles outside the trace
    with jax.profiler.trace(str(tmp_path)):
        t.run(2)
        t.run(2)
    spans = _spans(tmp_path)
    dispatches = [s for s in spans if s[2] == stages.DISPATCH]
    assert [d[3].get("step_num") for d in dispatches] == [2, 4]
    for d in dispatches:
        assert [c[2] for c in _children(spans, d)] == [
            stages.ENQUEUE, stages.WAIT, stages.READBACK, stages.RECORDS]
    assert len(spans) == 10


MESH_CODE = """
import json, re
import jax, jax.numpy as jnp
from repro.configs.base import ProtocolConfig
from repro.configs.dcgan import DCGANConfig
from repro.core import Trainer, shard_round
from repro.models import dcgan
from repro.models.specs import make_dcgan_spec

jitted = []
placed = shard_round._placed
def spy(fn, mesh, in_specs):            # keep the chunk's jitted program
    jitted.append(fn)
    return placed(fn, mesh, in_specs)
shard_round._placed = spy

CFG = DCGANConfig(nz=8, ngf=4, ndf=4, nc=1, image_size=8)
INSTR = re.compile(r"^\\s*(?:ROOT )?%?\\S+ = (?:\\([^=]*\\)|\\S+) ([a-z][\\w-]*)\\(")
out = {}
for algorithm in ("proposed", "fedgan"):
    for impl in ("pallas", "ring"):
        pcfg = ProtocolConfig(n_devices=4, n_d=1, n_g=1, sample_size=4,
                              server_sample_size=4)
        t = Trainer(make_dcgan_spec(CFG), pcfg,
                    lambda k: dcgan.gan_init(k, CFG),
                    jnp.zeros((4, 8, 8, 8, 1)), jax.random.PRNGKey(0),
                    driver="fused", layout="mesh", algorithm=algorithm,
                    avg_impl=impl, partition=None)
        t.run(1)
        hlo = jitted[-1].lower(t.state, t._sched_carry, t.data, t.key,
                               jnp.int32(1)).compile().as_text()
        ops = []
        for line in hlo.splitlines():
            m = INSTR.match(line)
            if m:
                name = re.search(r'op_name="([^"]*)"', line)
                name = name.group(1) if name else ""
                found = re.findall(r"round\\.\\w+", name)
                ops.append([m.group(1), "pallas" in name,
                            found[-1] if found else ""])
        out[algorithm + "-" + impl] = ops
with jax.profiler.trace(TRACE_DIR):
    t.run(1)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_ops(tmp_path_factory):
    """Per algorithm x avg impl, (opcode, is a kernel op, stage) of the
    fused mesh chunk on a 4-device host mesh; the trace dir of one
    dispatch of the last of them (FedGAN, ring)."""
    from conftest import run_on_host_mesh
    trace_dir = str(tmp_path_factory.mktemp("mesh_trace"))
    out = run_on_host_mesh(f"TRACE_DIR = {trace_dir!r}\n" + MESH_CODE,
                           n_devices=4)
    return json.loads(out.strip().splitlines()[-1]), trace_dir


@pytest.mark.parametrize("case", ["proposed-pallas", "proposed-ring",
                                  "fedgan-pallas", "fedgan-ring"])
def test_mesh_exchange_sits_under_a2_average(mesh_ops, case):
    ops = mesh_ops[0][case]
    exchange = [s for op, kernel, s in ops
                if kernel or op in ("all-gather", "collective-permute")]
    assert any(op == "all-gather" for op, _, _ in ops)
    assert any(kernel for _, kernel, _ in ops)
    if case.endswith("ring"):
        assert any(op == "collective-permute" for op, _, _ in ops)
    assert exchange and set(exchange) == {stages.A2_AVERAGE}


def test_mesh_enqueue_holds_signature_and_placement(mesh_ops):
    spans = _spans(mesh_ops[1])
    (dispatch,) = [s for s in spans if s[2] == stages.DISPATCH]
    (enqueue,) = [s for s in spans if s[2] == stages.ENQUEUE]
    assert [c[2] for c in _children(spans, enqueue)] == [
        stages.SIGNATURE, stages.PLACE]
    assert enqueue in _children(spans, dispatch)
