#!/usr/bin/env python3
"""Readings for the limits of `check.py`, on the chip at a cell's size.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 11,12,... [--control-seeds 21,22,23] [--out FILE]

In one process (the compiled programs are shared), for each of --seeds
it drives the program through the set-up's dispatches exactly as
run.py does and compares them with the reference: the lower readings.
For each of --control-seeds it puts the reference's own variants in the
program's place and compares them with the reference: the fp8 control,
half of each local batch left out, and (on a mesh) the Algorithm-2
exchange left out. A state left unchanged reads 1 by construction. The
reference runs on one chip in every cell, so --control-only makes these
readings for a mesh cell on one chip, with the cell's own shards made
there.
Writes one JSON line per reading. The benchmark's own runs never run
this; its tests run the same comparisons at a small size on the CPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2]),
                str(Path(__file__).resolve().parents[2] / "src")]

from benchmarks.chip import run  # noqa: E402


def readings(cell, prep, got, ref):
    from benchmarks.chip import check
    r = cell.traffic["rounds_per_dispatch"]
    last = run.CHECK_STEPS * r
    (obj, after), (ref_obj, ref_after) = got, ref
    return check.readings(prep.params0, after, ref_after, obj[:last],
                          ref_obj, r, last)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    ap.add_argument("--control-only", action="store_true",
                    help="only the variants, on one chip")
    args = ap.parse_args(argv)

    import jax
    from benchmarks.chip import spec, sut
    jax.config.update("jax_compilation_cache_dir",
                      str(run.ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = spec.cell(args.workload)
    variants = ["fp8", "half_batch"]
    if cell.traffic["layout"] == "mesh":
        variants.append("no_exchange")
    if args.control_only:
        cell = dataclasses.replace(
            cell, chips=1, traffic={**cell.traffic, "layout": "stacked"})
    devices = jax.devices()[:cell.chips]
    out = open(args.out, "a") if args.out else None

    def emit(**row):
        line = json.dumps({"cell": cell.name, **row})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    prep = None
    for seed in seeds:
        t = time.perf_counter()
        prep = None             # one seed's shards on the device at a time
        gc.collect()
        prep = run.prepare(cell, seed, devices)
        with jax.default_device(devices[0]):
            trainer, after = run.program_steps(cell, prep)
            obj = sut.objectives(trainer)
            del trainer
            gc.collect()
            ref = run.reference_steps(cell, prep)
        emit(seed=seed, kind="program", **readings(cell, prep, (obj, after),
                                                   ref),
             seconds=time.perf_counter() - t)
    for seed in control_seeds:
        prep = None
        gc.collect()
        prep = run.prepare(cell, seed, devices)
        with jax.default_device(devices[0]):
            ref = run.reference_steps(cell, prep)
            for variant in variants:
                t = time.perf_counter()
                got = run.reference_steps(cell, prep, variant)
                emit(seed=seed, kind=variant,
                     **readings(cell, prep, got, ref),
                     seconds=time.perf_counter() - t)
    return 0


if __name__ == "__main__":
    sys.exit(main())
