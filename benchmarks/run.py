"""Benchmark harness: one function per paper table/figure + roofline summary.

Prints ``name,us_per_call,derived`` CSV rows (harness contract), then the
roofline table if dry-run artifacts exist.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --quick    # smaller sizes
  PYTHONPATH=src python -m benchmarks.run --only fig5,fig7
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# figures 8-11 build their meshes in this process; off-accelerator the CPU
# backend needs host devices for them before JAX starts (ignored on a TPU)
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

from benchmarks import figures  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    benches = {
        "fig5": lambda: figures.fig5_sequential(
            sizes=(400_000, 1_000_000) if args.quick else (1_000_000, 4_000_000, 10_000_000)
        ),
        "fig6": lambda: figures.fig6_shared_threads(
            n=1_000_000 if args.quick else 4_000_000,
            threads=(1, 4, 16) if args.quick else (1, 2, 4, 8, 16, 32),
        ),
        "fig7": lambda: figures.fig7_vs_radix_baseline(
            sizes=(400_000,) if args.quick else (1_000_000, 4_000_000)
        ),
        "fig8": lambda: figures.fig8_distributed(n=400_000 if args.quick else 1_000_000),
        "fig9_11": lambda: figures.fig9_11_cluster_scaling(
            sizes=(400_000,) if args.quick else (400_000, 1_000_000, 4_000_000),
            Ps=(2, 8),
        ),
    }

    print("name,us_per_call,derived")
    for key, fn in benches.items():
        if only and key not in only:
            continue
        for name, us, derived in fn():
            print(f"{name},{us:.1f},{derived}")

    if (only is None or "roofline" in only) and os.path.isdir("artifacts/dryrun"):
        print("\n# Roofline (single pod) — see EXPERIMENTS.md §Roofline")
        from benchmarks import roofline

        cells = roofline.analyse()
        print(roofline.table(cells, "pod"))


if __name__ == "__main__":
    main()
