"""Benchmark driver: the fused-driver bench and one benchmark per paper
figure, on the CPU. Prints ``name,us_per_call,derived`` CSV rows.

Scale via env: REPRO_BENCH_ROUNDS (default 12), REPRO_BENCH_FULL=1 for
the paper-faithful 64x64 DCGAN / n_d=n_g=5 / m_k=128 settings.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main() -> None:
    print("name,us_per_call,derived")
    from benchmarks import driver_bench
    driver_bench.main()

    from benchmarks import fig3_schedules, fig4_devices, fig5_fedgan, \
        fig6_scheduling
    fig3_schedules.main()
    fig4_devices.main()
    fig5_fedgan.main()
    fig6_scheduling.main()


if __name__ == "__main__":
    main()
