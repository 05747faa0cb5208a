"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its configuration,
traffic mix, loop, reference and metric readers are found under ``bench/`` by
name (see ``harness.py``). Set-up runs from the start of this process to the
start of the window: imports, device start, inputs from ``--seed``, and a
warm-up that runs every program the window will. The window then lasts
``--seconds``. Afterwards the device's peak memory is read, the program's
state freed, and what the window produced compared with the plain reference.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window runs under JAX's profiler and the metrics are the
cell's per-layer metrics. The numbers compared for ``correct`` end standard
error, one per line, and the result line's ``checks``. The last line of
standard output is the result: one JSON object. Without a TPU, or with fewer
chips than the cell asks for, the run exits with code 2 and prints no result.
"""
import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import harness  # noqa: E402
import reduce_trace as tracing  # noqa: E402

sys.path.insert(0, harness.SRC)


def run_cell(name, seed, seconds, trace=False, *, t0=None, require_chip=True,
             system=None, config_overrides=None, traffic_overrides=None,
             fresh_plans=True, keep_trace=None, candidates=False):
    """One run of cell ``name``: returns ``(result, checks)``.

    ``require_chip=False`` skips the look for a TPU (the CPU tests use it),
    ``system`` replaces the program (see the loops), and the overrides replace
    keys of the configuration and the traffic mix (sizes a test can hold).
    ``fresh_plans=False`` keeps the process's plan file (several seeds in one
    process). ``keep_trace`` is a path to write the extracted trace to.
    ``candidates=True`` also finds the cells of ``bench/candidates/``.
    """
    t0 = time.perf_counter() if t0 is None else t0
    bm = harness.benchmark(candidates)
    cell = harness.entry(bm["workloads"], name, "workload")
    config = {**harness.data("configs", cell["config"]), **(config_overrides or {})}
    traffic = {**harness.data("traffic", cell["traffic"]), **(traffic_overrides or {})}
    loop_mod = harness.module("traffic", traffic["kind"])
    with harness.PlanFile() if fresh_plans else contextlib.nullcontext():
        import jax

        devices = harness.require_chips(cell["chips"]) if require_chip else jax.devices()[: cell["chips"]]
        kind = devices[0].device_kind
        if require_chip:
            harness.peaks(kind)
        harness.enable_compile_cache()
        marks = [time.perf_counter()]
        loop = loop_mod.Loop(config, traffic, seed, devices, system=system)
        marks.append(time.perf_counter())
        loop.warm()
        marks.append(time.perf_counter())
        setup_s = marks[-1] - t0
        # where set-up went: process and device start, inputs, warm-up
        setup_split = dict(zip(("start_s", "inputs_s", "warm_s"),
                               (b - a for a, b in zip([t0] + marks, marks))))
        recorder = tracing.Recorder() if trace else None
        if recorder:
            recorder.start()
        try:
            e2e = loop.run(seconds)
        finally:
            if recorder:
                recorder.stop()
        memory_peak = harness.memory_peak(devices)
        loop.release()
        reduced = None
        if recorder:
            doc = recorder.read()
            if keep_trace:
                with open(keep_trace, "w") as f:
                    json.dump(doc, f)
            reduced = tracing.Reduced(doc, devices=[d.id for d in devices])
        checks = loop.check()

    device = {
        "platform": devices[0].platform,
        "kind": kind,
        "count": len(devices),
        "memory_peak_bytes": memory_peak,
    }
    metrics = {}
    if trace:
        record = types.SimpleNamespace(
            trace=reduced, counters=loop.counters, config=config,
            peaks=lambda: harness.peaks(kind),
        )
        for m in harness.per_layer(bm, name):
            value = harness.module("metrics", m["name"]).read(record)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        device["busy_s"] = reduced.mean_busy_s()
        device["window_s"] = reduced.window_s
    else:
        e2e = {**e2e, "setup_s": setup_s}
        for m in harness.end_to_end(bm, name):
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": float(e2e[m["name"]]), "unit": m["unit"]}
    result = {
        "correct": all(value <= limit for _, value, limit in checks),
        "attempted": loop.attempted,
        "failed": loop.failed,
        "metrics": metrics,
        "device": device,
    }
    if trace:
        result["breakdown"] = reduced.breakdown()
    result["counters"] = {**loop.counters, "setup_split": setup_split}
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also write the extracted trace (JSON) to this path")
    args = ap.parse_args(argv)
    try:
        result, checks = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                                  t0=_T0, keep_trace=args.keep_trace)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for n, v, lim in checks:
        print(f"check {n}: {v} (limit {lim})", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
