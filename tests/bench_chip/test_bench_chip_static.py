"""CPU tests of the chip benchmark's yardstick (benchmarks/chip): its
files, its operation counts, its trace reduction and its refusals."""
from __future__ import annotations

import ast
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import check, spec, tracereduce, xplane  # noqa: E402
from benchmarks.chip.flops import dcgan as dcgan_flops  # noqa: E402

BENCH = spec.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


# ---------------------------------------------------------------------------
# the benchmark's files
# ---------------------------------------------------------------------------

def check_cell(cell):
    """What a cell's files give, whatever its model family."""
    assert cell.chips in (1, 4)
    assert set(cell.limits) == set(check.NUMBERS)
    assert all(0 < v < 1 for v in cell.limits.values())
    for key in ("workers", "n_d", "n_g", "m_k", "M", "layout",
                "rounds_per_dispatch", "quantize_bits"):
        assert key in cell.traffic, key
    family = spec.family_module(cell.config["family"], "reference")
    assert family.samples(cell.config) // cell.traffic["workers"] > 0
    names = {m["name"] for m in cell.end_to_end}
    assert {"rounds_per_s", "setup_s"} <= names
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert callable(spec.reader(m["name"]))
        assert m["moves"] in names
    fl = cell.flops_module()
    assert fl.round_flops(cell.config, cell.traffic, cell.chips)["total"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_load_and_name_known_metrics(name):
    check_cell(spec.cell(name, BENCH))


def test_benchmark_names_the_files_it_uses():
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    for m in BENCH["per_layer"]:
        assert (ROOT / "benchmarks/chip/layer_metrics" /
                f"{m['name']}.py").is_file()
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


def _imports(path: Path) -> set:
    tree = ast.parse(path.read_text())
    names = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names}
    return names | {n.module for n in ast.walk(tree)
                    if isinstance(n, ast.ImportFrom) and n.module}


def test_only_the_system_under_test_imports_the_program():
    """The yardstick reads the program only through `sut.py` and each
    family's `program.py`."""
    chip = ROOT / "benchmarks/chip"
    allowed = {chip / "sut.py", *chip.glob("families/*/program.py")}
    assert len(allowed) >= 2
    for path in sorted(chip.rglob("*.py")):
        program = {m for m in _imports(path)
                   if m == "repro" or m.startswith("repro.")}
        if path in allowed:
            assert program, path
        else:
            assert not program, (path, program)


def test_an_unknown_family_fails_at_cell_naming_the_files(tmp_path):
    cfg = tmp_path / "c.json"
    cfg.write_text(json.dumps({"name": "c", "family": "nosuch"}))
    bench = {**BENCH, "configs": [{"name": "c", "file": str(cfg)}],
             "workloads": [{"name": CELLS[0], "config": "c",
                            "traffic": "k10-stacked", "chips": 1}]}
    with pytest.raises(KeyError) as err:
        spec.cell(CELLS[0], bench)
    for f in ("benchmarks/chip/families/nosuch/reference.py",
              "benchmarks/chip/families/nosuch/program.py",
              "benchmarks/chip/flops/nosuch.py"):
        assert f in str(err.value)


def test_peaks_lookup_refuses_an_unknown_device_kind():
    assert spec.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        spec.peaks("TPU v99")


def test_run_refuses_a_cpu_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks/chip/run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "cpu" in out.stderr


# ---------------------------------------------------------------------------
# operation counts
# ---------------------------------------------------------------------------

DCGAN64 = dict(nz=100, ngf=64, ndf=64, nc=3, image_size=64)
DCGAN32 = dict(DCGAN64, image_size=32)


def _hlo_flops(fn, *args):
    import jax
    from repro.launch.hlo_costs import hlo_costs
    return hlo_costs(jax.jit(fn).lower(*args).compile().as_text())["flops"]


@pytest.mark.parametrize("cfg,d_mflop,g_mflop,g_dense_mflop", [
    (DCGAN64, 207.634432, 209.256448, 856.686592),
    (DCGAN32, 35.135488, 35.946496, 153.616384),
])
def test_analytic_flops_at_the_real_widths(cfg, d_mflop, g_mflop,
                                           g_dense_mflop):
    """The discriminator's count matches the compiled forward's dot and
    convolution FLOPs. The generator's transposed convolutions are
    counted by INPUT pixels x C_in x C_out x k^2 x 2; the compiled
    lhs-dilated convolution counts its OUTPUT pixels, the inserted
    zeros included, about stride^2 = 4 times as much."""
    import jax
    import jax.numpy as jnp
    from repro.configs.dcgan import DCGANConfig
    from repro.models import dcgan

    dcfg = DCGANConfig(**cfg)
    params = dcgan.gan_init(jax.random.PRNGKey(0), dcfg)
    x = jnp.zeros((2, cfg["image_size"], cfg["image_size"], 3))
    z = jnp.zeros((2, cfg["nz"]))
    d_hlo = _hlo_flops(lambda p, x: dcgan.discriminator_apply(p, dcfg, x),
                       params["disc"], x) / 2
    g_hlo = _hlo_flops(lambda p, z: dcgan.generator_apply(p, dcfg, z),
                       params["gen"], z) / 2
    assert dcgan_flops.disc_forward_flops(cfg) == pytest.approx(d_mflop * 1e6)
    assert d_hlo == pytest.approx(d_mflop * 1e6, rel=1e-6)
    assert dcgan_flops.gen_forward_flops(cfg) == pytest.approx(g_mflop * 1e6)
    assert g_hlo == pytest.approx(g_dense_mflop * 1e6, rel=1e-6)
    assert dcgan_flops.disc_params(cfg) == sum(
        x.size for x in jax.tree_util.tree_leaves(params["disc"]))


def test_round_flops_of_the_cells():
    tr = dict(workers=10, n_d=5, n_g=5, m_k=128, M=128)
    assert dcgan_flops.round_flops(DCGAN64, tr, 1)["total"] == \
        pytest.approx(8.7746e12, rel=1e-4)
    assert dcgan_flops.round_flops(DCGAN32, tr, 1)["total"] == \
        pytest.approx(1.4862e12, rel=1e-4)
    mesh = dcgan_flops.round_flops(DCGAN64, dict(tr, workers=4), 4)
    assert mesh["total"] == pytest.approx(4.3925e12, rel=1e-4)
    assert mesh["fakes"] == 4 * 5 * 128 * dcgan_flops.gen_forward_flops(
        DCGAN64)
    assert dcgan_flops.wavg_bytes(DCGAN64, dict(tr, workers=4)) == \
        4 * (4 * 2765568 + 2765568 + 4)


# ---------------------------------------------------------------------------
# trace reading and reduction
# ---------------------------------------------------------------------------

def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _field(num, payload):
    if isinstance(payload, int):
        return _varint(num << 3) + _varint(payload)
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def test_xplane_event_metadata_reads_stats(tmp_path):
    stat = lambda sid, **v: (_field(1, sid) + (
        _field(5, v["s"].encode()) if "s" in v else _field(4, v["i"])))
    event = (_field(1, 7) + _field(2, b"%fusion.1 = f32[8] fusion(x)")
             + _field(5, stat(3, s="convolution fusion"))
             + _field(5, stat(4, i=99)))
    plane = (_field(2, b"/device:TPU:0")
             + _field(4, _field(1, 7) + _field(2, event))
             + _field(5, _field(1, 3) + _field(2, _field(1, 3)
                                               + _field(2, b"hlo_category")))
             + _field(5, _field(1, 4) + _field(2, _field(1, 4)
                                               + _field(2, b"flops")))
             + _field(6, b"\x08\x01"))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, plane) + _field(1, _field(2, b"/host:CPU")))
    meta = xplane.event_metadata(str(path))
    assert meta == {"/device:TPU:0": {"%fusion.1 = f32[8] fusion(x)": {
        "hlo_category": "convolution fusion", "flops": 99}}}


def _recorded():
    data = json.loads((ROOT / "benchmarks/chip/testdata/"
                       "trace_v5e_dcgan32_gap.json").read_text())
    ops = [tracereduce.Op(s, d, n, c, t) for s, d, n, c, t in data["ops"]]
    return data, ops


def test_trace_reduction_on_a_recorded_trace():
    data, ops = _recorded()
    dev = tracereduce.reduce_device("/device:TPU:0", data["modules"], ops)
    lo = min(s for s, _ in data["modules"])
    hi = max(s + d for s, d in data["modules"])
    assert dev.window_ns == (lo, hi)
    # busy: a timeline at 1 ns resolution, loops left out
    line = np.zeros(int(hi - lo) + 1, bool)
    for o in ops:
        if o.category != "while":
            a, b = max(o.start_ns, lo), min(o.start_ns + o.dur_ns, hi)
            if b > a:
                line[int(round(a - lo)):int(round(b - lo))] = True
    assert dev.busy_ns == pytest.approx(line.sum(), abs=len(ops))
    # the gap between the two dispatches is the longest idle gap
    longest = max(dev.gaps, key=lambda g: g[1])
    assert longest[1] > 4e6
    idle = 1 - dev.busy_ns / (hi - lo)
    assert 0.5 < idle < 1
    assert dev.mxu_ns == pytest.approx(sum(
        o.dur_ns for o in ops if o.category == "convolution fusion"))
    assert dev.collective_ns == 0
    assert not any("wavg" in k for k in dev.kernel_ns)
    assert not any(k.startswith("while") for k in dev.op_ns)
    named = tracereduce.host_activity(
        [tuple(h) for h in data["host"]], dev.gaps, top=3)
    assert len(named) == 3 and named[0][1] == pytest.approx(longest[1] * 1e-9)


def test_trace_reduction_classifies_and_clips():
    Op = tracereduce.Op
    ops = [
        Op(0, 100, "%while.1 = ...", "while"),
        Op(5, 10, "%fusion.2 = ... kind=kOutput", "convolution fusion"),
        Op(10, 10, "%fusion.3 = ...", "loop fusion"),        # overlaps
        Op(30, 5, "%all-gather.4 = ...", "all-gather"),
        Op(40, 10, "%wavg_pallas.5 = custom-call(...)", "custom-call"),
        Op(60, 4, "%dot.6 = ...", "dot"),
        Op(95, 20, "%fusion.7 = ...", "loop fusion"),         # past the end
    ]
    dev = tracereduce.reduce_device("d", [(0, 50), (50, 50)], ops)
    assert dev.window_ns == (0, 100)
    assert dev.busy_ns == 15 + 5 + 10 + 4 + 5
    assert dev.mxu_ns == 14 and dev.collective_ns == 5
    assert dev.kernel_ns == {"wavg_pallas": 10}
    assert dev.kernel_calls == {"wavg_pallas": 1}
    assert [g for g in dev.gaps if g[1] > 0][0] == (0, 5)
