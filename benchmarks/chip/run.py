#!/usr/bin/env python3
"""Chip benchmark of the distributed-GAN training path: one cell, once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Set-up makes the cell's data shards on the device and the weights in
one jitted call, both from --seed and by the configuration's model
family (`families/<family>/reference.py`), builds the program's fused
Trainer, and drives it through the first three dispatches (the first
compiles, or loads from the compile cache in `.jax_cache/` at the
checkout root).
The window then calls `Trainer.run(rounds_per_dispatch)` and waits for
the state, in whole dispatches, until --seconds have passed. After the
window the program is freed and the plain f32 reference
(`reference.py`) replays the set-up's three dispatches from the same
weights, data and keys; `check.py` compares them and decides `correct`.

--trace 0 reports the cell's end-to-end metrics, rounds_per_s and
setup_s. --trace 1 traces a shorter window (the first TRACE_SECONDS of
dispatches) with the profiler on and reports the per-layer metrics,
read by `layer_metrics/<metric>.py` from the trace, with the device's
busy and window seconds and a breakdown. The last line of standard
output is one JSON object; the compared numbers and their limits are
also the last lines of standard error. The run refuses any platform but
TPU, and a cell that asks for more chips than JAX finds.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CHECK_STEPS = 3          # dispatches the reference follows
TRACE_SECONDS = 3.0      # length of the traced window of --trace 1


def seed_key(seed: int):
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _window(trainer, rounds_per_dispatch: int, seconds: float):
    """Whole dispatches until `seconds` have passed; (rounds, seconds)."""
    from benchmarks.chip import sut
    rounds, start = 0, time.perf_counter()
    while True:
        sut.run_chunk(trainer, rounds_per_dispatch)
        rounds += rounds_per_dispatch
        elapsed = time.perf_counter() - start
        if elapsed >= seconds:
            return rounds, elapsed


def _breakdown(devices, host):
    """The device ops that took most time, and the longest idle gaps of
    the idlest chip, each named by the program span at its middle, else
    by the innermost host event there."""
    from benchmarks.chip import stagetrace, tracereduce
    ops = {}
    for d in devices:
        for name, ns in d.op_ns.items():
            ops[name] = ops.get(name, 0.0) + ns * 1e-9 / len(devices)
    idlest = max(devices, key=lambda d: 1.0 - d.busy_s / d.window_s)
    return {"device_ops": sorted(([n, s] for n, s in ops.items()),
                                 key=lambda x: -x[1])[:10],
            "idle_gaps": [list(g) for g in tracereduce.host_activity(
                host, idlest.gaps, prefer=stagetrace.PROGRAM_SPANS)]}


def reader_ctx(cell, devices, rounds: int, window_s: float, traced, host):
    """What a per-layer reader (`layer_metrics/<metric>.py`) reads of a
    traced window: `traced` are its `tracereduce.Device`s, `host` its
    host events (start_ns, dur_ns, name)."""
    from benchmarks.chip import spec
    fl = cell.flops_module()
    return types.SimpleNamespace(
        cell=cell, chips=len(devices), rounds=rounds, window_s=window_s,
        devices=traced, host=host, peaks=spec.peaks(devices[0].device_kind),
        flops=fl.round_flops(cell.config, cell.traffic, len(devices)),
        wavg_bytes=fl.wavg_bytes(cell.config, cell.traffic))


def prepare(cell, seed: int, devices):
    """The cell's inputs from the seed, by the model family: data shards
    on the device (one worker per chip on a mesh), the weights' jitted
    maker and a host copy of them, and the trainer's key."""
    import jax

    from benchmarks.chip import reference, sut

    cfg, tr = cell.config, cell.traffic
    family = reference.family(cfg)
    key = seed_key(seed)
    k_data, k_weights, k_train = (jax.random.fold_in(key, i)
                                  for i in (1, 2, 3))
    mesh = sut.make_mesh(devices) if tr["layout"] == "mesh" else None
    with jax.default_device(devices[0]):
        shards = family.make_shards(k_data, tr["workers"], cfg, mesh)
        init = jax.jit(lambda k: family.init_params(k, cfg))
        params0 = jax.device_get(init(k_weights))
    return types.SimpleNamespace(devices=devices, mesh=mesh, shards=shards,
                                 params0=params0, key=k_train,
                                 weights=lambda: init(k_weights))


def program_steps(cell, prep):
    """The program's trainer, driven through its first CHECK_STEPS
    dispatches; (trainer, {rounds: host params after the first and the
    last of them})."""
    from benchmarks.chip import sut

    r = cell.traffic["rounds_per_dispatch"]
    trainer = sut.trainer(cell.config, cell.traffic, prep.weights,
                          prep.shards, prep.key, prep.mesh)
    after = {}
    for step in range(1, CHECK_STEPS + 1):
        sut.run_chunk(trainer, r)
        if step in (1, CHECK_STEPS):
            after[step * r] = sut.params(trainer)
    return trainer, after


def reference_steps(cell, prep, variant=None):
    """The reference over the same rounds: (objectives, after)."""
    from benchmarks.chip import reference

    r = cell.traffic["rounds_per_dispatch"]
    return reference.run(cell.config, cell.traffic, prep.params0,
                         prep.shards, prep.key, CHECK_STEPS * r,
                         record_after=(r, CHECK_STEPS * r), variant=variant,
                         device=prep.devices[0])


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             t0: float | None = None) -> dict:
    """One run of `cell` on `devices` (no platform check: `main` makes
    it). Returns the result object; its "checks" entry lists every
    compared number with its limit."""
    import jax
    import numpy as np

    from benchmarks.chip import check, spec, sut

    t0 = time.perf_counter() if t0 is None else t0
    r = cell.traffic["rounds_per_dispatch"]
    last = CHECK_STEPS * r
    t_import = time.perf_counter() - t0
    prep = prepare(cell, seed, devices)
    t_prep = time.perf_counter() - t0
    with jax.default_device(devices[0]):
        trainer, after = program_steps(cell, prep)
        setup_s = time.perf_counter() - t0
        print(f"setup: {t_import:.2f} s imports and devices, "
              f"{t_prep - t_import:.2f} s images and weights, "
              f"{setup_s - t_prep:.2f} s trainer and {CHECK_STEPS} "
              f"dispatches", file=sys.stderr)
        if trace:
            from benchmarks.chip import tracereduce
            with tempfile.TemporaryDirectory() as trace_dir:
                jax.profiler.start_trace(trace_dir)
                rounds, window_s = _window(trainer, r,
                                           min(seconds, TRACE_SECONDS))
                jax.profiler.stop_trace()
                traced, host = tracereduce.read_trace(trace_dir)
        else:
            rounds, window_s = _window(trainer, r, seconds)
        memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use",
                                                       0) for d in devices)
        objectives = sut.objectives(trainer)
        del trainer
        gc.collect()
        ref_obj, ref_after = reference_steps(cell, prep)
    values = check.readings(prep.params0, after, ref_after,
                            objectives[:last], ref_obj, r, last)
    window_obj = objectives[last:]
    failed = int(np.sum(~np.all(np.isfinite(window_obj), axis=1)))
    ok, table = check.verdict(values, cell.limits)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(memory_peak)}
    result = {"correct": bool(ok and rounds > 0 and failed == 0),
              "attempted": int(rounds), "failed": failed}
    if trace:
        ctx = reader_ctx(cell, devices, rounds, window_s, traced, host)
        metrics = {}
        for m in cell.per_layer:
            value = spec.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = sum(d.busy_s for d in traced) / len(traced)
        device["window_s"] = sum(d.window_s for d in traced) / len(traced)
        result.update(metrics=metrics, device=device,
                      breakdown=_breakdown(traced, host))
    else:
        known = {"rounds_per_s": rounds / window_s, "setup_s": setup_s}
        result.update(metrics={m["name"]: {"value": known[m["name"]],
                                           "unit": m["unit"]}
                               for m in cell.end_to_end}, device=device)
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, (v, lim) in table.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.chip import spec
    cell = spec.cell(args.workload)

    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: needs TPU chips, JAX found {devices[0].platform!r} "
              f"({devices[0].device_kind}); no result", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"run.py: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devices)}; no result", file=sys.stderr)
        return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices[:cell.chips], T0)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']:.6g} limit {c['limit']:.6g}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
