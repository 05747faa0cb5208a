"""Offer an open-loop cell several arrival rates in one process, to find the
highest rate its system sustains.

    python bench/sweep.py --workload topk.decode.steps --seeds 7,8 \\
        --seconds 10 --rates 18,22,26,30

One JSON line per rate and seed: the cell's end-to-end metrics and its
counters. A rate is sustained where no request is refused, the tail stays
flat and the backlog drains within about one batch's time after the window
closes (``drain_ms``). A cell of ``bench/candidates/`` runs too.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import harness  # noqa: E402
import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    try:
        with harness.PlanFile():
            for rate in [float(r) for r in args.rates.split(",")]:
                for seed in [int(s) for s in args.seeds.split(",")]:
                    result, _ = run.run_cell(args.workload, seed, args.seconds, fresh_plans=False,
                                             traffic_overrides={"rate_per_s": rate},
                                             candidates=True)
                    print(json.dumps({"rate_per_s": rate, "seed": seed, "correct": result["correct"],
                                      "failed": result["failed"], "metrics": result["metrics"],
                                      "counters": result["counters"]}), flush=True)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
