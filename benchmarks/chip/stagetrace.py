"""From a profiler trace to a round's device time by stage and the fused
driver's host time per dispatch.

The program names the stages of a round on the device ops (a
`jax.named_scope` around each stage's work) and records host spans
around each fused dispatch (`jax.profiler.TraceAnnotation`), so both
land in the trace the benchmark already reads, on one clock:

- stage: the deepest `round.*` component of an op's `tf_op` name stack,
  with transform wrappers taken off
  (`vmap(transpose(jvp(round.a1_local)))/conv_general_dilated` ->
  `round.a1_local`); ops with none are `unscoped`;
- scope_ns: device time of the leaf ops (loops left out, as
  `tracereduce.reduce_device` leaves them out) by stage, so the stages
  and `unscoped` sum to all leaf-op time;
- named_ns: the same time by every named scope in the stack, at any
  depth, wrappers taken off, so an op under `round.a1_local/ssm.scan`
  counts for both: the scopes a model of any family puts on its own
  layers, for readers of their own (`named_share`);
- host overhead: the length of each `trainer.dispatch` span less its
  `trainer.wait` children, the part of a dispatch in which the host and
  not the device is the one working.

Like `tracereduce`, this reads names from the trace only and imports
nothing of the program.
"""
from __future__ import annotations

import collections
import re

from benchmarks.chip import tracereduce

STAGES = ("round.a1_local", "round.uplink", "round.a2_average",
          "round.a3_server")
UNSCOPED = "unscoped"
DISPATCH = "trainer.dispatch"
WAIT = "trainer.wait"
PROGRAM_SPANS = ("trainer.", "shard_round.")
_STAGE = re.compile(r"(?:^|[/(])(round\.[A-Za-z0-9_]+)")
_WRAPPER = re.compile(r"[A-Za-z_]\w*\((.*)\)")


def stage_of(tf_op: str) -> str:
    """The deepest `round.*` scope in an op's name stack, else
    `unscoped`."""
    found = _STAGE.findall(tf_op)
    return found[-1] if found else UNSCOPED


def scope_ns(ops) -> dict:
    """Device time of the leaf ops (`tracereduce.Op`s) by stage."""
    out = collections.Counter()
    for o in ops:
        if o.category not in tracereduce.CONTAINERS:
            out[stage_of(o.op_name)] += o.dur_ns
    return dict(out)


def scopes_of(tf_op: str) -> set:
    """Every named component of an op's name stack but the op's own
    (the last), each wrapper such as `vmap(...)` or `jit(...)` taken off
    (`vmap(transpose(jvp(round.a1_local)))` -> `round.a1_local`). The
    structural ones (`while`, `body`, ...) are among them; no reader asks
    for those."""
    out = set()
    for part in tf_op.split("/")[:-1]:
        while (m := _WRAPPER.fullmatch(part)) is not None:
            part = m.group(1)
        if part:
            out.add(part)
    return out


def named_ns(ops) -> dict:
    """Device time of the leaf ops by every named scope they run under."""
    out, stacks = collections.Counter(), {}
    for o in ops:
        if o.category not in tracereduce.CONTAINERS:
            if o.op_name not in stacks:
                stacks[o.op_name] = scopes_of(o.op_name)
            for scope in stacks[o.op_name]:
                out[scope] += o.dur_ns
    return dict(out)


def _named(host_events, name):
    return [(s, d) for s, d, n in host_events if n == name]


def dispatch_host_ns(host_events):
    """(start, length, host length) of each `trainer.dispatch` span: the
    host length is the span less its `trainer.wait` children.
    host_events: (start_ns, dur_ns, name)."""
    waits = _named(host_events, WAIT)
    out = []
    for s, d in sorted(_named(host_events, DISPATCH)):
        waited = sum(wd for ws, wd in waits if s <= ws and ws + wd <= s + d)
        out.append((s, d, d - waited))
    return out


def host_overhead_ms(host_events, rounds: int):
    """Host time of the dispatches in the trace per round, in ms; None
    where the trace holds no dispatch span."""
    spans = dispatch_host_ns(host_events)
    if not spans or rounds <= 0:
        return None
    return sum(h for _, _, h in spans) * 1e-6 / rounds


def host_share(host_events, start: float, length: float) -> float:
    """The share of [start, start + length) inside a `trainer.dispatch`
    span and outside its `trainer.wait`: time in which the device waits
    on the fused driver's own host work."""
    lo, hi = start, start + length
    cover = 0.0
    for s, d in _named(host_events, DISPATCH):
        cover += max(0.0, min(hi, s + d) - max(lo, s))
    for s, d in _named(host_events, WAIT):
        cover -= max(0.0, min(hi, s + d) - max(lo, s))
    return cover / length if length > 0 else 0.0


def program_span_at(host_events, t: float) -> str:
    """The innermost program span (`trainer.*`, `shard_round.*`) that
    covers time t, else "no program span"."""
    covering = [(d, n) for s, d, n in host_events
                if n.startswith(PROGRAM_SPANS) and s <= t <= s + d]
    return min(covering)[1] if covering else "no program span"


def gaps(host_events, device_gaps, min_ns: float = 1e6):
    """Each idle gap of at least min_ns: (start, length, the program
    span at its middle, its host share)."""
    return [(s, n, program_span_at(host_events, s + n / 2),
             host_share(host_events, s, n))
            for s, n in sorted(device_gaps) if n >= min_ns]


def _leaf_ns(ctx) -> float:
    return sum(sum(d.scope_ns.values()) for d in ctx.devices)


def share(ctx, stage: str):
    """`stage`'s share of all leaf-op time on all chips, in %; None where
    no op on any chip runs under a stage (a program without the
    scopes)."""
    total = _leaf_ns(ctx)
    if total <= 0 or all(set(d.scope_ns) <= {UNSCOPED}
                         for d in ctx.devices):
        return None
    return 100.0 * sum(d.scope_ns.get(stage, 0.0)
                       for d in ctx.devices) / total


def named_share(ctx, scope: str):
    """The share of all leaf-op time on all chips that runs under the
    named scope `scope` at any depth, in %; None where no op does."""
    total = _leaf_ns(ctx)
    under = sum(d.named_ns.get(scope, 0.0) for d in ctx.devices)
    return 100.0 * under / total if total > 0 and under > 0 else None
