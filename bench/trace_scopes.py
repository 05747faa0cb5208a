"""Run one cell once with its window traced, and attribute the trace to the
program's named scopes and host spans.

    python3 bench/trace_scopes.py --workload <name> --seed <n> --seconds <s> [--keep-trace <path>]

The cell may also be a candidate (``bench/candidates/``). The run is
``run.py``'s ``--trace 1`` run, with ``scopes.Recorder`` in place
of ``reduce_trace.Recorder``: each device operation keeps its scope from the
trace itself, and the program's ``repro.*`` host spans are kept beside the
benchmark's. The per-layer metrics then read exact scopes, and the result
line's ``breakdown`` gains ``scopes`` (device seconds by scope),
``clock_offset_ns`` and ``program_spans`` (the ``repro.*`` spans in the window,
by name). ``--keep-trace`` writes the extracted trace, scopes included.

``by_kind`` holds what the scope metrics read from the same window with the
scopes taken away, as ``run.py``'s own ``--trace 1`` run reads them: from
operation kinds and the live executables' HLO.
"""
import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import harness  # noqa: E402
import reduce_trace  # noqa: E402
import run  # noqa: E402
import scopes  # noqa: E402

SCOPE_METRICS = ("merge_share.sort", "partition_share.sort", "local_sort_share.sort",
                 "compact_share.sort")


def by_kind(doc: dict, cell: str, devices) -> dict:
    """The scope metrics of ``cell`` read from ``doc`` without its scopes."""
    bare = {"devices": doc["devices"], "host": doc["host"]}
    record = types.SimpleNamespace(trace=reduce_trace.Reduced(bare, devices=devices))
    out = {}
    for m in harness.per_layer(harness.benchmark(), cell):
        if m["name"] in SCOPE_METRICS:
            out[m["name"]] = harness.module("metrics", m["name"]).read(record)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args(argv)
    run.tracing = types.SimpleNamespace(Recorder=scopes.Recorder, Reduced=scopes.Scoped)
    keep = args.keep_trace or os.path.join(tempfile.mkdtemp(prefix="bench-scopes-"), "trace.json")
    try:
        result, checks = run.run_cell(args.workload, args.seed, args.seconds, True,
                                      t0=_T0, keep_trace=keep, candidates=True)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    with open(keep) as f:
        result["by_kind"] = by_kind(json.load(f), args.workload, range(result["device"]["count"]))
    if not args.keep_trace:
        os.remove(keep)
    for n, v, lim in checks:
        print(f"check {n}: {v} (limit {lim})", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
