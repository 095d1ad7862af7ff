"""From a profiler trace to per-device busy time, op classes and idle
gaps.

Each device plane (`/device:TPU:<i>`) has an "XLA Modules" line, one
event per executed program, and an "XLA Ops" line, one event per HLO
op, with loops ("while") enclosing the ops of their bodies. The TPU
runtime files each op's HLO category in its event metadata
(`xplane.event_metadata`): `convolution fusion`, `loop fusion`,
`all-gather`, `custom-call`, ... The reduction uses the categories and
the ops' own names only, not the program's source, so it reads the same
after the program is refactored.

- window: from the start of the first program on the device to the end
  of the last one;
- busy: the union of the intervals of all ops but loops, clipped to the
  window; idle is the rest;
- mxu: ops whose category is a convolution or a dot;
- collective: ops whose category is a collective (all-gather,
  all-reduce, reduce-scatter, all-to-all, collective-permute);
- kernels: custom calls (Pallas kernels among them) by their HLO name
  without its numeric suffix, which the program's jitted kernel wrapper
  gives them (`wavg_pallas.9` -> `wavg_pallas`);
- by name: each op keeps the JAX name stack it came from (its `tf_op`),
  and leaf-op time is summed by the round's stage and by every named
  scope in the stack (`stagetrace.scope_ns`, `stagetrace.named_ns`).
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import re

CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
_NAME = re.compile(r"%([\w.\-]+) = ")


@dataclasses.dataclass(frozen=True)
class Op:
    start_ns: float
    dur_ns: float
    name: str          # the HLO op's text as the trace names it
    category: str      # its HLO category ("" where none is filed)
    op_name: str = ""  # the JAX op it came from, where filed


def is_mxu(category: str) -> bool:
    return "convolution" in category or re.search(r"\bdot\b", category) \
        is not None


def is_collective(category: str) -> bool:
    return any(c in category for c in COLLECTIVES)


def kernel_name(op: Op) -> str | None:
    """A custom call's HLO name without its numeric suffix, else None."""
    if "custom" not in op.category:
        return None
    m = _NAME.match(op.name)
    return re.sub(r"\.\d+$", "", m.group(1)) if m else None


def short_name(op: Op) -> str:
    m = _NAME.match(op.name)
    base = m.group(1) if m else op.name[:60]
    where = op.op_name.rstrip(":").split("/")[-1] if op.op_name else ""
    return f"{op.category or '?'}: {base}" + (f" ({where})" if where else "")


def _union(intervals):
    total, end = 0.0, float("-inf")
    gaps = []
    for s, e in sorted(intervals):
        if s > end:
            if end > float("-inf"):
                gaps.append((end, s - end))
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total, gaps


@dataclasses.dataclass
class Device:
    name: str
    window_ns: tuple       # (start, end)
    busy_ns: float
    mxu_ns: float
    collective_ns: float
    kernel_ns: dict        # kernel name -> total
    kernel_calls: dict     # kernel name -> number of calls
    op_ns: dict            # short op name -> total
    gaps: list             # (start, length) of idle gaps inside the window
    modules: list          # (start, length) of each program run
    ops: list              # every `Op`, loops too, with its `tf_op`
    scope_ns: dict         # stage -> leaf-op time (`stagetrace.scope_ns`)
    named_ns: dict         # named scope -> leaf-op time (`.named_ns`)

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9


def reduce_device(name: str, modules, ops) -> Device:
    """modules: (start_ns, dur_ns) of the programs; ops: `Op`s."""
    from benchmarks.chip import stagetrace   # which imports this module

    if not modules:
        raise ValueError(f"{name}: no program ran in the traced window")
    lo = min(s for s, _ in modules)
    hi = max(s + d for s, d in modules)
    leaf = [o for o in ops if o.category not in CONTAINERS]
    busy, gaps = _union([(max(o.start_ns, lo), min(o.start_ns + o.dur_ns, hi))
                         for o in leaf if o.start_ns + o.dur_ns > lo
                         and o.start_ns < hi])
    first = min((o.start_ns for o in leaf), default=lo)
    last = max((o.start_ns + o.dur_ns for o in leaf), default=hi)
    if first > lo:
        gaps.insert(0, (lo, first - lo))
    if last < hi:
        gaps.append((last, hi - last))
    op_ns, kernel_ns, kernel_calls = (collections.Counter(),
                                      collections.Counter(),
                                      collections.Counter())
    mxu = coll = 0.0
    for o in leaf:
        op_ns[short_name(o)] += o.dur_ns
        if is_mxu(o.category):
            mxu += o.dur_ns
        elif is_collective(o.category):
            coll += o.dur_ns
        k = kernel_name(o)
        if k is not None:
            kernel_ns[k] += o.dur_ns
            kernel_calls[k] += 1
    return Device(name, (lo, hi), busy, mxu, coll, dict(kernel_ns),
                  dict(kernel_calls), dict(op_ns), gaps, list(modules),
                  list(ops), stagetrace.scope_ns(ops),
                  stagetrace.named_ns(ops))


def host_activity(host_events, gaps, top: int = 10, prefer=()):
    """The `top` longest idle gaps, each named by the innermost host
    event that covers its middle, among those whose names start with one
    of `prefer` where one does. host_events: (start_ns, dur_ns, name)."""
    named = []
    for start, length in sorted(gaps, key=lambda g: -g[1])[:top]:
        mid = start + length / 2
        covering = [(d, n) for s, d, n in host_events if s <= mid <= s + d]
        preferred = [(d, n) for d, n in covering if n.startswith(prefer)]
        named.append((min(preferred or covering,
                          default=(0, "no host event"))[1], length * 1e-9))
    return named


def read_trace(trace_dir: str):
    """(devices, host events) of the one `*.xplane.pb` under trace_dir."""
    import jax
    from benchmarks.chip import xplane

    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one trace under {trace_dir}, found "
                         f"{len(files)}")
    meta = xplane.event_metadata(files[0])
    data = jax.profiler.ProfileData.from_file(files[0])
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            stats = meta.get(plane.name, {})
            modules, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = [(e.start_ns, e.duration_ns)
                               for e in line.events]
                elif line.name == "XLA Ops":
                    for e in line.events:
                        st = stats.get(e.name, {})
                        ops.append(Op(e.start_ns, e.duration_ns, e.name,
                                      str(st.get("hlo_category", "")),
                                      str(st.get("tf_op", ""))))
            devices.append(reduce_device(plane.name, modules, ops))
        elif plane.name.startswith("/host:CPU"):
            host += [(e.start_ns, e.duration_ns, e.name)
                     for line in plane.lines for e in line.events]
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    return devices, host
