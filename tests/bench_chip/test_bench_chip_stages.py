"""CPU tests of the stage and host-span reduction (benchmarks/chip/
stagetrace.py) and its readers, on made-up events and on traces recorded
on the chip."""
from __future__ import annotations

import json
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import spec, stagetrace, tracereduce  # noqa: E402

TESTDATA = ROOT / "benchmarks/chip/testdata"
SHARES = ("a1_local_share", "uplink_share", "a2_average_share",
          "a3_server_share")
READERS = SHARES + ("host_overhead_ms",)
Op = tracereduce.Op


@pytest.mark.parametrize("tf_op,stage", [
    ("jit(run_chunk)/while/body/closed_call/vmap(round.a1_local)/while/"
     "body/closed_call/transpose(jvp())/conv_general_dilated:",
     "round.a1_local"),
    ("vmap(transpose(jvp(round.a1_local)))/conv_general_dilated",
     "round.a1_local"),
    ("jit(body)/shard_map/while/body/closed_call/round.a2_average/"
     "jit(wavg_pallas)/while/body/dot_general", "round.a2_average"),
    ("jit(f)/round.a2_average/vmap(round.uplink)/floor", "round.uplink"),
    ("jit(f)/transpose(jvp(round.a3_server))/round.a3_server/mul",
     "round.a3_server"),
    ("jit(run_chunk)/while/body/jit(gan_round)/round", "unscoped"),
    ("jit(run_chunk)/while/body/dynamic_update_slice:", "unscoped"),
    ("", "unscoped"),
], ids=["vmapped", "wrapped", "kernel", "deepest", "repeated",
        "function-name", "no-scope", "empty"])
def test_stage_of_unwraps_and_picks_the_deepest(tf_op, stage):
    assert stagetrace.stage_of(tf_op) == stage


def test_scope_ns_buckets_leaf_ops_and_leaves_out_loops():
    ops = [Op(0, 100, "%while.1", "while", "jit(f)/round.a1_local/while"),
           Op(5, 10, "%fusion.2", "convolution fusion",
              "jit(f)/vmap(round.a1_local)/conv_general_dilated"),
           Op(20, 4, "%fusion.3", "loop fusion", "jit(f)/round.uplink/add"),
           Op(30, 6, "%copy.4", "data formatting", "jit(f)/copy"),
           Op(40, 2, "%fusion.5", "loop fusion", "")]
    assert stagetrace.scope_ns(ops) == {"round.a1_local": 10,
                                        "round.uplink": 4, "unscoped": 8}


# two dispatches of 5 rounds: 100 ns long each, waiting 70 and 80 ns
HOST = [(0, 100, "trainer.dispatch"), (0, 10, "trainer.enqueue"),
        (10, 70, "trainer.wait"), (80, 15, "trainer.readback"),
        (95, 5, "trainer.records"), (120, 100, "trainer.dispatch"),
        (125, 80, "trainer.wait"), (130, 3, "PjitFunction(run_chunk)")]


def test_host_overhead_is_each_dispatch_less_its_wait():
    assert stagetrace.dispatch_host_ns(HOST) == [(0, 100, 30),
                                                 (120, 100, 20)]
    assert stagetrace.host_overhead_ms(HOST, 10) == pytest.approx(5e-6)
    assert stagetrace.host_overhead_ms(HOST[2:5], 10) is None
    assert stagetrace.host_overhead_ms(HOST, 0) is None


def test_gaps_are_named_by_program_span_and_host_share():
    # idle 60-70 lies in a wait; 80-130 runs from the first dispatch's
    # read-back past its end into the second's enqueue and wait; 85-105
    # has its middle in the first dispatch's records
    found = stagetrace.gaps(HOST, [(85, 20), (80, 50), (60, 10), (300, 5)],
                            min_ns=10)
    assert found == [(60, 10, "trainer.wait", 0.0),
                     (80, 50, "no program span", 0.5),
                     (85, 20, "trainer.records", 0.75)]


def _ctx(devices, rounds=10, host=None):
    return types.SimpleNamespace(devices=devices, rounds=rounds, host=host)


def _device(scope=None):
    d = tracereduce.reduce_device("/device:TPU:0", [(0, 10)],
                                  [Op(0, 10, "%f.1", "loop fusion")])
    if scope is not None:
        d.scope_ns = scope
    return d


def test_shares_are_of_all_leaf_op_time_on_all_chips():
    chips = [_device({"round.a1_local": 60, "unscoped": 20}),
             _device({"round.a1_local": 10, "round.a3_server": 10})]
    ctx = _ctx(chips)
    read = {m: spec.reader(m)(ctx) for m in SHARES}
    assert read == {"a1_local_share": 70.0, "uplink_share": 0.0,
                    "a2_average_share": 0.0, "a3_server_share": 10.0}


def test_readers_find_nothing_without_scopes_or_spans():
    """Devices on which no op runs under a stage and a context without
    host events, as a program without stage scopes and dispatch spans
    gives: no value, no error."""
    ctx = _ctx([_device(), _device({"unscoped": 5})], host=None)
    assert {m: spec.reader(m)(ctx) for m in READERS} == dict.fromkeys(
        READERS)
    assert spec.reader("host_overhead_ms")(_ctx([], host=[])) is None


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


def _plane(pid, name, lines, metadata, stat_names=()):
    """An XPlane: lines of (name, [(metadata id, offset ns, ns)]),
    event metadata {id: (name, {stat id: str})}, stat names by id."""
    out = _field(1, pid) + _field(2, name.encode())
    for lid, (lname, events) in enumerate(lines, 1):
        body = _field(1, lid) + _field(2, lname.encode()) + _field(3, 1000)
        for mid, offset, dur in events:
            body += _field(4, _field(1, mid) + _field(2, offset * 1000)
                           + _field(3, dur * 1000))
        out += _field(3, body)
    for mid, (ename, stats) in metadata.items():
        body = _field(1, mid) + _field(2, ename.encode()) + b"".join(
            _field(5, _field(1, sid) + _field(5, v.encode()))
            for sid, v in stats.items())
        out += _field(4, _field(1, mid) + _field(2, body))
    for sid, sname in enumerate(stat_names, 1):
        out += _field(5, _field(1, sid) + _field(
            2, _field(1, sid) + _field(2, sname.encode())))
    return _field(1, out)


CONV = "%fusion.1 = f32[8] fusion(x)"


def _hand_made_trace(tmp_path):
    """A TPU plane with one program and two ops, a host plane with one
    dispatch span; the directory that holds it."""
    tpu = _plane(1, "/device:TPU:0",
                 [("XLA Modules", [(1, 0, 100)]),
                  ("XLA Ops", [(2, 10, 30), (3, 50, 20)])],
                 {1: ("jit_run_chunk", {}),
                  2: (CONV, {1: "convolution fusion",
                             2: "jit(f)/vmap(round.a1_local)/conv"}),
                  3: ("%fusion.2 = f32[8] fusion(y)",
                      {1: "loop fusion", 2: "jit(f)/add"})},
                 ("hlo_category", "tf_op"))
    host = _plane(2, "/host:CPU", [("python", [(1, 0, 200)])],
                  {1: ("trainer.dispatch", {})})
    (tmp_path / "t.xplane.pb").write_bytes(tpu + host)
    return str(tmp_path)


def test_read_planes_reads_programs_and_tf_ops(tmp_path):
    """read_trace keeps each device's programs and ops, each op with its
    `tf_op`, and buckets the leaf ops by stage."""
    (dev,), host_events = tracereduce.read_trace(_hand_made_trace(tmp_path))
    assert dev.modules == [(1000, 100)]
    assert [(o.start_ns, o.dur_ns, o.name, o.category, o.op_name)
            for o in dev.ops] == [
        (1010, 30, CONV, "convolution fusion",
         "jit(f)/vmap(round.a1_local)/conv"),
        (1050, 20, "%fusion.2 = f32[8] fusion(y)", "loop fusion",
         "jit(f)/add")]
    assert dev.scope_ns == {"round.a1_local": 30, "unscoped": 20}
    assert dev.busy_ns == 50 and dev.mxu_ns == 30
    assert host_events == [(1000, 200, "trainer.dispatch")]


def test_read_trace_puts_the_scopes_of_its_ops_on_each_device(tmp_path):
    """A device read through read_trace carries the `scope_ns` and
    `named_ns` that stagetrace gives for its own ops."""
    (dev,), _ = tracereduce.read_trace(_hand_made_trace(tmp_path))
    assert dev.scope_ns == stagetrace.scope_ns(dev.ops)
    assert dev.named_ns == stagetrace.named_ns(dev.ops)
    assert dev.named_ns == {"f": 50, "round.a1_local": 30}


@pytest.mark.parametrize("tf_op,scopes", [
    ("jit(run_chunk)/while/body/closed_call/vmap(round.a1_local)/while/"
     "body/closed_call/transpose(jvp())/conv_general_dilated:",
     {"run_chunk", "while", "body", "closed_call", "round.a1_local"}),
    ("vmap(transpose(jvp(round.a1_local)))/ssm.scan/jvp(moe.dispatch)/dot",
     {"round.a1_local", "ssm.scan", "moe.dispatch"}),
    ("jit(f)/transpose(jvp(round.a3_server))/round.a3_server/mul",
     {"f", "round.a3_server"}),
    ("round.uplink", set()),
    ("", set()),
], ids=["vmapped", "nested", "repeated", "op-only", "empty"])
def test_scopes_of_unwraps_every_component_but_the_op(tf_op, scopes):
    assert stagetrace.scopes_of(tf_op) == scopes


def test_named_ns_counts_an_op_under_every_scope_once():
    """Nested and transform-wrapped scopes: an op counts for each scope
    in its stack, once, and loops are left out."""
    ops = [Op(0, 100, "%while.1", "while", "jit(f)/round.a1_local/while"),
           Op(5, 10, "%fusion.2", "loop fusion",
              "jit(f)/vmap(round.a1_local)/ssm.scan/transpose(jvp("
              "ssm.scan))/mul"),
           Op(20, 4, "%fusion.3", "convolution fusion",
              "jit(f)/round.a1_local/vmap(moe.dispatch)/dot_general"),
           Op(30, 6, "%copy.4", "data formatting", "jit(f)/copy"),
           Op(40, 2, "%fusion.5", "loop fusion", "")]
    assert stagetrace.named_ns(ops) == {"f": 20, "round.a1_local": 14,
                                        "ssm.scan": 10, "moe.dispatch": 4}
    assert stagetrace.scope_ns(ops) == {"round.a1_local": 14,
                                        "unscoped": 8}


def test_named_share_is_of_all_leaf_op_time_and_none_without_the_scope():
    chips = [_device(), _device()]
    chips[0].named_ns = {"ssm.scan": 5, "round.a1_local": 8}
    chips[1].named_ns = {"ssm.scan": 3}
    ctx = _ctx(chips)
    assert stagetrace.named_share(ctx, "ssm.scan") == pytest.approx(40.0)
    assert stagetrace.named_share(ctx, "round.a1_local") == \
        pytest.approx(40.0)
    assert stagetrace.named_share(ctx, "moe.dispatch") is None


def _recorded(name):
    data = json.loads((TESTDATA / name).read_text())
    ops = [Op(s, d, n, c, t) for s, d, n, c, t in data["ops"]]
    return data, ops, [tuple(h) for h in data["host"]]


def test_a_trace_without_scopes_reads_all_unscoped():
    """The recorded trace of the program before the scopes: every leaf op
    is unscoped, and the buckets hold all leaf-op time."""
    data, ops, host = _recorded("trace_v5e_dcgan32_gap.json")
    leaf = sum(o.dur_ns for o in ops if o.category != "while")
    assert stagetrace.scope_ns(ops) == {"unscoped": pytest.approx(leaf)}
    assert stagetrace.host_overhead_ms(host, 10) is None


def test_probe_records_the_window_around_the_longest_gap(tmp_path):
    from benchmarks.chip import stage_probe
    ops = [Op(-4e6, 5e6, "%fusion.1 = f32[8] fusion(x)", "loop fusion",
              "jit(f)/round.a3_server/add"),
           Op(6e6, 2e6, "%fusion.2", "convolution fusion",
              "jit(f)/vmap(round.a1_local)/conv_general_dilated"),
           Op(8e6, 2e6, "%fusion.3", "loop fusion", "")]
    modules = [(-5e6, 6e6), (6e6, 4e6)]            # idle from 1 to 6 ms
    dev = tracereduce.reduce_device("/device:TPU:0", modules, ops)
    host = [(-1e6, 2.5e6, "trainer.dispatch"), (-1e6, 1.8e6, "trainer.wait"),
            (1.5e6, 1e3, "PjitFunction(run_chunk)"),
            (5e6, 6e6, "trainer.dispatch"), (30e6, 1e6, "trainer.dispatch")]
    path = tmp_path / "window.json"
    stage_probe._record(path, dev, host, "made up")
    data = json.loads(path.read_text())
    lo = 1e6 - stage_probe.RECORD_BEFORE_NS
    assert data["source"] == "made up"
    assert data["modules"] == [[0, 1e6 - lo], [6e6 - lo, 4e6]]
    assert [[o[0], o[2], o[4]] for o in data["ops"]] == [
        [o.start_ns - lo, o.name, o.op_name] for o in ops]
    assert data["host"] == sorted([s - lo, d, n] for s, d, n in host[:4])


@pytest.fixture(scope="module")
def scoped_window():
    """The recorded window of the scoped program around one dispatch gap:
    the tail of one 10-round dispatch, the gap, and the first round of
    the next."""
    data, ops, host = _recorded("trace_v5e_dcgan32_scopes.json")
    dev = tracereduce.reduce_device("/device:TPU:0", data["modules"], ops)
    return _ctx([dev], rounds=20, host=host)


def test_readers_read_the_recorded_scoped_trace(scoped_window):
    read = {m: spec.reader(m)(scoped_window) for m in READERS}
    assert all(isinstance(v, float) for v in read.values()), read
    (dev,) = scoped_window.devices
    unscoped = 100.0 * dev.scope_ns["unscoped"] / sum(dev.scope_ns.values())
    assert sum(read[m] for m in SHARES) + unscoped == pytest.approx(100.0)
    assert read["a1_local_share"] > max(
        [read[m] for m in SHARES[1:]] + [unscoped])
    assert set(dev.scope_ns) == set(stagetrace.STAGES) | {"unscoped"}
    # two dispatches overlap the window, each ~5 ms of host work
    assert 0.3 < read["host_overhead_ms"] < 1.0


def test_recorded_idle_gaps_fall_in_host_work_of_a_dispatch(scoped_window):
    """Each idle gap over 1 ms has its middle in a dispatch's host work
    (not its wait), and most of it lies there."""
    (dev,) = scoped_window.devices
    found = stagetrace.gaps(scoped_window.host, dev.gaps)
    assert found
    for _, length, span, host_share in found:
        assert span in ("trainer.enqueue", "trainer.readback",
                        "trainer.records", "shard_round.signature",
                        "shard_round.place"), span
        assert host_share > 0.5, (length, span, host_share)


def test_a_named_stage_holds_at_least_its_deepest_ops(scoped_window):
    """By every scope in the stack, a stage holds its own ops and those
    of any stage nested inside it."""
    for stage in stagetrace.STAGES:
        assert stagetrace.named_share(scoped_window, stage) >= \
            stagetrace.share(scoped_window, stage) - 1e-9, stage


def test_breakdown_names_idle_gaps_after_program_spans(scoped_window):
    """The longest gap lies in a dispatch's read-back: the breakdown
    names it so, where the innermost host event is a numpy call; a gap
    that no program span covers keeps its host event's name."""
    from benchmarks.chip import run
    (dev,) = scoped_window.devices
    gaps = run._breakdown([dev], scoped_window.host)["idle_gaps"]
    plain = tracereduce.host_activity(scoped_window.host, dev.gaps)
    assert gaps[0] == ["trainer.readback", plain[0][1]]
    assert plain[0][0] != "trainer.readback"
    outside = tracereduce.host_activity(
        [(0, 10, "PjitFunction(f)"), (0, 100, "trainer.dispatch")],
        [(20, 4), (2, 6)], prefer=stagetrace.PROGRAM_SPANS)
    assert outside == [("trainer.dispatch", pytest.approx(6e-9)),
                       ("trainer.dispatch", pytest.approx(4e-9))]
    bare = tracereduce.host_activity([(0, 10, "PjitFunction(f)")],
                                     [(2, 6), (50, 4)],
                                     prefer=stagetrace.PROGRAM_SPANS)
    assert bare == [("PjitFunction(f)", pytest.approx(6e-9)),
                    ("no host event", pytest.approx(4e-9))]
