#!/usr/bin/env python3
"""Stage shares, host overhead and the cost of tracing of one cell, on
the chip, read from the program's stage scopes and dispatch spans.

    python3 benchmarks/chip/stage_probe.py --workload <cell> --seed <n> \
        [--seconds 10] [--record <file.json>]

Set-up as in run.py (images, weights, trainer, three dispatches). Then
the same trainer runs an untraced window of --seconds and a traced
window of run.TRACE_SECONDS, both in whole dispatches. The last line of
standard output is one JSON object:

- rounds_per_s of each window, so the cost of tracing when it is on;
- the cell's per-layer metrics (`BENCHMARK.json`), the stage shares
  and `host_overhead_ms` among them, read from the traced window as
  run.py reads them;
- the device time a round by stage, summed over chips, and the longest
  unscoped ops;
- every idle gap of 1 ms or more on each chip: its length, the program
  span at its middle, and the share of it in which the fused driver's
  own host work (a dispatch outside its wait) held the device.

--record writes the window around the longest idle gap of the first chip
(3 ms before it, 16 ms after) in the form of `testdata/*.json`: the
programs, the ops with their `tf_op`, and the host events. No `correct`
check is made: run.py makes it. Refuses any platform but TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

RECORD_BEFORE_NS, RECORD_AFTER_NS = 3e6, 16e6


def _record(path, device, host, source):
    """The window around `device`'s longest idle gap, times from its
    start; programs clipped to it, ops and host events whole."""
    ops, modules = device.ops, device.modules
    start, length = max(device.gaps, key=lambda g: g[1])
    lo, hi = start - RECORD_BEFORE_NS, start + length + RECORD_AFTER_NS
    inside = lambda s, d: s < hi and s + d > lo
    other = sorted((h for h in host if inside(h[0], h[1])
                    and not h[2].startswith(("trainer.", "shard_round."))),
                   key=lambda h: -h[1])[:300]
    spans = [h for h in host if inside(h[0], h[1])
             and h[2].startswith(("trainer.", "shard_round."))]
    data = {
        "source": source,
        "modules": [[max(s, lo) - lo, min(s + d, hi) - max(s, lo)]
                    for s, d in modules if inside(s, d)],
        "ops": [[o.start_ns - lo, o.dur_ns, o.name[:100], o.category,
                 o.op_name] for o in ops if inside(o.start_ns, o.dur_ns)],
        "host": [[s - lo, d, n] for s, d, n in sorted(spans + other)],
    }
    Path(path).parent.mkdir(parents=True, exist_ok=True)
    Path(path).write_text(json.dumps(data))


def probe(cell, seed: int, seconds: float, devices, record=None) -> dict:
    import jax

    from benchmarks.chip import run, spec, stagetrace, tracereduce

    r = cell.traffic["rounds_per_dispatch"]
    prep = run.prepare(cell, seed, devices)
    with jax.default_device(devices[0]):
        trainer, _ = run.program_steps(cell, prep)
        rounds_off, off_s = run._window(trainer, r, seconds)
        with tempfile.TemporaryDirectory() as trace_dir:
            jax.profiler.start_trace(trace_dir)
            rounds, window_s = run._window(trainer, r, run.TRACE_SECONDS)
            jax.profiler.stop_trace()
            traced, host = tracereduce.read_trace(trace_dir)

    ctx = run.reader_ctx(cell, devices, rounds, window_s, traced, host)
    metrics = {m["name"]: spec.reader(m["name"])(ctx)
               for m in cell.per_layer}

    by_stage = {}
    for d in traced:
        for k, ns in d.scope_ns.items():
            by_stage[k] = by_stage.get(k, 0.0) + ns * 1e-6 / rounds
    unscoped = {}
    for d in traced:
        for o in d.ops:
            if (o.category not in tracereduce.CONTAINERS
                    and stagetrace.stage_of(o.op_name)
                    == stagetrace.UNSCOPED):
                key = tracereduce.short_name(o)
                unscoped[key] = unscoped.get(key, 0.0) + o.dur_ns * 1e-6
    if record and traced:
        _record(record, traced[0], host,
                f"{devices[0].device_kind}, {cell.name}, seed {seed}: a "
                f"window around the longest idle gap of the first chip")
    return {
        "cell": cell.name, "seed": seed,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)},
        "rounds_per_s": {"untraced": rounds_off / off_s,
                         "traced": rounds / window_s},
        "metrics": metrics,
        "stage_ms_per_round": by_stage,
        "unscoped_top_ms": sorted(([k, v] for k, v in unscoped.items()),
                                  key=lambda x: -x[1])[:10],
        "gaps": {d.name: [[n * 1e-6, span, host_share] for _, n, span,
                          host_share in stagetrace.gaps(host, d.gaps)]
                 for d in traced},
        "busy_s": [d.busy_s for d in traced],
        "window_s": [d.window_s for d in traced],
    }


def main(argv=None) -> int:
    t0 = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--record", default=None)
    args = ap.parse_args(argv)

    from benchmarks.chip import spec
    cell = spec.cell(args.workload)

    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"stage_probe.py: {cell.name} needs {cell.chips} TPU chips, "
              f"JAX found {len(devices)} {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    result = probe(cell, args.seed, args.seconds, devices[:cell.chips],
                   args.record)
    result["total_s"] = time.perf_counter() - t0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
