"""Read the numbers compared for ``correct`` over many seeds in one process:
the program's, and the control's (the plain reference at the precision
below the configuration's, put in the program's place).

    python bench/control.py --workload <name> --seconds <s> \\
        --seeds 11,12,13 --control-seeds 21,22,23

Each seed is one window of the cell at its own sizes and load, as
``run.py`` runs it; the runs share the process, so only the first compiles.
One JSON line per seed: which side, the seed, ``correct`` and the checks.
A cell of ``bench/candidates/`` runs too.
The benchmark's own runs never run the control.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import harness  # noqa: E402
import run  # noqa: E402


def control_system(workload: str):
    """The control of ``workload``'s configuration, in its loop's form."""
    bm = harness.benchmark(candidates=True)
    cell = harness.entry(bm["workloads"], workload, "workload")
    config = harness.data("configs", cell["config"])
    loop_mod = harness.module("traffic", harness.data("traffic", cell["traffic"])["kind"])
    return loop_mod.control_system(harness.module("reference", config["reference"]).control)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    sides = [("program", int(s), None) for s in args.seeds.split(",") if s]
    control = control_system(args.workload)
    sides += [("control", int(s), control) for s in args.control_seeds.split(",") if s]
    try:
        with harness.PlanFile():
            for side, seed, system in sides:
                result, _ = run.run_cell(args.workload, seed, args.seconds, system=system,
                                         fresh_plans=False, candidates=True)
                print(json.dumps({"side": side, "seed": seed, "correct": result["correct"],
                                  "attempted": result["attempted"], "failed": result["failed"],
                                  "metrics": result["metrics"], "checks": result["checks"]}),
                      flush=True)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
