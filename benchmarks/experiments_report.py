"""Generate EXPERIMENTS.md sections from results/ artifacts.

  §Dry-run      from results/dryrun/*.json (memory / collective schedule)
  §Paper-validation  from results/bench/*.json curves
  §Perf         from results/perf/*.json hillclimb records
"""
from __future__ import annotations

import glob
import json
import os


def fmt_dryrun_section():
    out = ["## §Dry-run\n"]
    out.append("Every (architecture × input shape) lowered AND compiled on "
               "the single-pod 16×16 mesh and the 2×16×16 multi-pod mesh "
               "(512 host placeholder devices). Per-device memory and the "
               "collective schedule come from `compiled.memory_analysis()` "
               "and the loop-aware HLO parse (`repro.launch.hlo_costs`).\n")
    out.append("NOTE: the CPU backend upcasts bf16 buffers to f32, so "
               "peak-GB figures are ≈2× the real TPU bf16 footprint; "
               "relative comparisons are unaffected.\n")
    out.append("| arch | shape | mesh | peak GB/dev | collectives "
               "(AG/AR/RS/A2A/CP) |")
    out.append("|---|---|---|---|---|")
    for p in sorted(glob.glob("results/dryrun/*.json")):
        if os.path.basename(p).count("__") != 2:
            continue
        d = json.load(open(p))
        counts = d["collectives"]["counts"]
        cstr = "/".join(str(counts.get(k, 0)) for k in
                        ("all-gather", "all-reduce", "reduce-scatter",
                         "all-to-all", "collective-permute"))
        peak = (d["memory"].get("peak_bytes") or 0) / 1e9
        out.append(f"| {d['arch']} | {d['shape']} | {d['mesh']} | "
                   f"{peak:.2f} | {cstr} |")
    return "\n".join(out)


def fmt_bench_section():
    out = ["## §Paper-validation\n"]
    files = {
        "fig3_schedules": "Fig. 3 — serial vs parallel schedule, 3 datasets",
        "fig4_devices": "Fig. 4 — device count vs centralized",
        # fig5 writes one curves file per execution layout
        "fig5_fedgan_stacked": "Fig. 5 — proposed vs FedGAN (stacked)",
        "fig5_fedgan_mesh": "Fig. 5 — proposed vs FedGAN (mesh)",
        "fig6_scheduling": "Fig. 6 — scheduling ratio under stragglers",
    }
    for stem, title in files.items():
        path = f"results/bench/{stem}.json"
        if not os.path.exists(path):
            continue
        curves = json.load(open(path))
        out.append(f"### {title}\n")
        out.append("| setting | final FID | wall-clock (s) |")
        out.append("|---|---|---|")
        for c in curves:
            fids = [f for f in c["fid"] if f is not None]
            fid = fids[-1] if fids else float("nan")
            wall = c["wallclock"][-1] if c["wallclock"] else 0.0
            out.append(f"| {c['label']} | {fid:.2f} | {wall:.1f} |")
        out.append("")
    return "\n".join(out)


def fmt_perf_section():
    out = ["## §Perf\n"]
    files = sorted(glob.glob("results/perf/*.json"))
    if not files:
        out.append("(hillclimb records pending)")
    for p in files:
        d = json.load(open(p))
        out.append(f"### {d['pair']}\n")
        for it in d["iterations"]:
            out.append(f"- **{it['name']}** — hypothesis: {it['hypothesis']}")
            out.append(f"  - change: {it['change']}")
            out.append(f"  - before: {it['before']}  after: {it['after']}")
            out.append(f"  - verdict: {it['verdict']}")
        out.append("")
    return "\n".join(out)


def main():
    print(fmt_dryrun_section())
    print()
    print(fmt_bench_section())
    print()
    print(fmt_perf_section())


if __name__ == "__main__":
    main()
