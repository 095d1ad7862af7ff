import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production mesh and extract roofline inputs.

MUST be run as its own process (`python -m repro.launch.dryrun ...`):
the XLA_FLAGS line above executes before any jax import so the host
platform exposes 512 placeholder devices.

Outputs one JSON per combination under results/dryrun/.
"""
import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from repro.configs import get_arch_config, list_archs, INPUT_SHAPES  # noqa: E402
from repro.configs.base import MeshConfig  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch import steps as steps_mod  # noqa: E402
from repro.launch import analysis  # noqa: E402

# long_500k needs sub-quadratic attention / bounded state (DESIGN.md §3):
LONG_OK = {"mamba2-130m", "zamba2-2.7b", "mixtral-8x22b", "gemma3-12b"}


def combos():
    for arch in list_archs():
        for shape in INPUT_SHAPES.values():
            if shape.name == "long_500k" and arch not in LONG_OK:
                continue
            yield arch, shape.name


def run_one(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
            schedule: str = "serial", tag: str = "",
            variant: str = "") -> dict:
    from repro.launch import variants as variants_mod

    cfg = get_arch_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh_cfg = MeshConfig(multi_pod=multi_pod)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = math.prod(mesh.shape.values())

    t0 = time.time()
    kw = {"schedule": schedule} if shape.kind == "train" else {}
    cfg, var_kw = variants_mod.apply(cfg, variant)
    if shape.kind == "train":
        kw.update(var_kw)
    step, args = steps_mod.build_step(cfg, shape, mesh, mesh_cfg, **kw)
    with jax.sharding.set_mesh(mesh):
        lowered = step.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        result = analysis.analyze_compiled(compiled, n_chips)
        if out_dir:
            # keep the optimized HLO so cost models can be re-run offline
            import gzip
            hlo_dir = os.path.join(os.path.dirname(out_dir) or ".", "hlo")
            os.makedirs(hlo_dir, exist_ok=True)
            hname = (f"{arch.replace('.', '_')}__{shape_name}__"
                     f"{'multi' if multi_pod else 'single'}"
                     f"{'_' + tag if tag else ''}.hlo.txt.gz")
            with gzip.open(os.path.join(hlo_dir, hname), "wt") as f:
                f.write(compiled.as_text())

    result.update({
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "n_chips": n_chips, "schedule": schedule if shape.kind == "train" else None,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
    })
    mem = result["memory"]
    peak = mem.get("peak_bytes")
    print(f"[dryrun] {arch} x {shape_name} x "
          f"{'multi' if multi_pod else 'single'}: "
          f"dominant={result['roofline']['dominant']} "
          f"compute={result['roofline']['compute_s']:.3e}s "
          f"memory={result['roofline']['memory_s']:.3e}s "
          f"collective={result['roofline']['collective_s']:.3e}s "
          f"peak/dev={peak/1e9 if peak else float('nan'):.2f}GB "
          f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)", flush=True)

    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"_{tag}" if tag else ""
        fname = (f"{arch.replace('.', '_')}__{shape_name}__"
                 f"{'multi' if multi_pod else 'single'}{suffix}.json")
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(result, f, indent=2)
    return result


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all",
                    help="input-shape name or 'all'")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--schedule", default="serial",
                    choices=["serial", "parallel"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--tag", default="", help="suffix for output files")
    ap.add_argument("--variant", default="",
                    help="perf variant (see repro.launch.variants)")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    pairs = [(a, s) for a, s in combos()
             if (args.arch in ("all", a)) and (args.shape in ("all", s))]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = []
    for arch, shape_name in pairs:
        for multi in meshes:
            suffix = f"_{args.tag}" if args.tag else ""
            fname = (f"{arch.replace('.', '_')}__{shape_name}__"
                     f"{'multi' if multi else 'single'}{suffix}.json")
            if args.skip_existing and os.path.exists(
                    os.path.join(args.out, fname)):
                print(f"[dryrun] skip existing {fname}", flush=True)
                continue
            try:
                run_one(arch, shape_name, multi, args.out,
                        schedule=args.schedule, tag=args.tag,
                        variant=args.variant)
            except Exception:
                print(f"[dryrun] FAILED {arch} x {shape_name} x "
                      f"{'multi' if multi else 'single'}", flush=True)
                traceback.print_exc()
                failures.append((arch, shape_name, multi))
    if failures:
        print(f"[dryrun] {len(failures)} FAILURES: {failures}", flush=True)
        sys.exit(1)
    print("[dryrun] all combinations lowered and compiled OK", flush=True)


if __name__ == "__main__":
    main()
