"""The record-sort cell ``sortkv.zipf.1chip`` at a size a test can hold: it
runs correct and its traced run reads both ``engine/kv`` scopes; its control
and its faults read not correct; its roofline share counts 20 bytes a record;
and its readers read a trace recorded on a TPU v5e."""
import gzip
import json
import os
import subprocess
import sys
import textwrap
import types

import numpy as np
import pytest

import bench_testlib
from bench_testlib import BENCH, ROOT, clean_env, drive

import harness
import reduce_trace as rt
import scopes

CELL = "sortkv.zipf.1chip"
CONFIG = "sortkv-int32-pay4-2p24-1chip"
bench_testlib.SMALL.setdefault(CONFIG, {"keys": 4096})

FAULT_BODY = """
def ties_reversed(loop):
    # keys in order, but records with equal keys in reverse input order
    def call(x, cols):
        x = np.asarray(x)
        order = np.lexsort((-np.arange(x.size), x))
        return x[order], {k: np.asarray(v)[order] for k, v in cols.items()}
    return call


def column_altered(loop):
    def call(x, cols):
        k, c = loop.program(x, cols)
        c = {name: np.array(v) for name, v in c.items()}
        c["c2"][11] += 1
        return k, c
    return call
"""


def test_cell_runs_correct_and_its_trace_reads_both_scopes(tmp_path):
    """``run.py``'s ``--trace 1`` run of the cell on the CPU, with the
    scoped extraction (the CPU's operation events carry their module and
    instruction)."""
    code = textwrap.dedent(f"""
        import json, sys, types
        sys.path.insert(0, {BENCH!r})
        sys.path.insert(0, {os.path.join(ROOT, "src")!r})
        import harness, run, scopes
        v5e = harness.peaks("TPU v5 lite")
        harness.peaks = lambda kind: v5e  # the CPU has no entry in the peak table
        run.tracing = types.SimpleNamespace(Recorder=scopes.Recorder, Reduced=scopes.Scoped)
        result, _ = run.run_cell({CELL!r}, 2**40 + 7, 0.5, True, require_chip=False,
                                 config_overrides={bench_testlib.SMALL[CONFIG]!r})
        print(json.dumps(result))
    """)
    out = subprocess.run([sys.executable, "-c", code], env=clean_env(tmp_path),
                         capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert r["checks"] == {"mismatched_keys": {"value": 0, "limit": 0},
                           "mismatched_payload": {"value": 0, "limit": 0}}
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert set(m) == {"order_share.sortkv", "permute_share.sortkv", "hbm_floor_share.sortkv"}
    assert 0 < m["order_share.sortkv"] < 100 and 0 < m["permute_share.sortkv"] < 100
    assert m["order_share.sortkv"] + m["permute_share.sortkv"] <= 100 + 1e-6
    assert m["hbm_floor_share.sortkv"] > 0
    assert r["counters"]["record_bytes"] == 20 and r["counters"]["records_per_device"] == 4096
    assert r["breakdown"]["program_spans"] == {"repro.kv.dispatch": r["counters"]["calls"]}


def test_cell_runs_correct_untraced(tmp_path):
    r = drive(tmp_path, CELL, seed=2**33 + 1)
    assert r["correct"] and r["attempted"] > 0
    assert set(r["metrics"]) == {"sort_records_per_s", "setup_s"}
    assert list(r["checks"]) == ["mismatched_keys", "mismatched_payload"]


def test_control_is_not_correct(tmp_path):
    r = drive(tmp_path, CELL, system=f"control.control_system({CELL!r})")
    assert r["correct"] is False
    assert r["checks"]["mismatched_keys"]["value"] > 0


def test_tie_order_reversed_is_caught_by_the_payload_alone(tmp_path):
    r = drive(tmp_path, CELL, FAULT_BODY, system="ties_reversed")
    assert r["correct"] is False
    assert r["checks"]["mismatched_keys"]["value"] == 0
    assert r["checks"]["mismatched_payload"]["value"] > 0


def test_one_payload_column_altered_is_not_correct(tmp_path):
    r = drive(tmp_path, CELL, FAULT_BODY, system="column_altered")
    assert r["correct"] is False
    assert r["checks"]["mismatched_keys"]["value"] == 0
    assert r["checks"]["mismatched_payload"]["value"] == r["attempted"]


def test_hbm_floor_share_counts_twenty_bytes_a_record():
    reader = harness.module("metrics", "hbm_floor_share.sortkv")
    assert reader.floor_bytes(1 << 24, 20) == 2 * 20 * (1 << 24)
    doc = {"devices": {"0": [[0, 500_000_000, "sort s32[16777216]"]]},
           "host": [[0, 1_000_000_000, "bench.window"]]}
    run = types.SimpleNamespace(
        trace=rt.Reduced(doc, devices=[0]),
        counters={"calls": 1, "records_per_device": 1 << 24, "record_bytes": 20},
        peaks=lambda: harness.peaks("TPU v5 lite"),
    )
    # 671,088,640 bytes at 819 GB/s over 0.5 s of busy time
    assert reader.read(run) == pytest.approx(100 * 2 * 20 * (1 << 24) / 819e9 / 0.5)
    # a program whose loop reports no record counters: nothing to read
    run.counters = {"calls": 1, "keys_per_device": 1 << 24, "key_bytes": 4}
    assert reader.read(run) is None


def test_reference_is_a_stable_record_sort():
    ref = harness.module("reference", "sorted_records")
    k, c = ref.reference(np.array([3, 1, 3, 1, 2], np.int32), {"c0": np.arange(5, dtype=np.int32)})
    assert k.tolist() == [1, 1, 2, 3, 3] and c["c0"].tolist() == [1, 3, 4, 0, 2]
    # the control orders by the upper 16 bits alone: keys within one run stay unsorted
    k, _ = ref.control(np.array([5, 1, 1 << 16], np.int32), {})
    assert k.tolist() == [5, 1, 1 << 16]


def test_recorded_v5e_trace_reads_order_and_permute_shares():
    """Fourteen 2^24-record calls of the cell on a TPU v5e, extracted with
    scopes (``trace_scopes.py --keep-trace``); the kind-only reading that
    ``run.py``'s own trace gets agrees with the exact one."""
    with gzip.open(os.path.join(BENCH, "tests", "data", "trace_scopes_sortkv_v5e.json.gz"), "rt") as f:
        doc = json.load(f)
    kinds = {}
    for op, sc in zip(doc["devices"]["0"], doc["scopes"]["0"]):
        kinds.setdefault(op[2], set()).add(sc)
    counters = {"calls": 14, "records_per_device": 1 << 24, "record_bytes": 20}
    for t in (scopes.Scoped(doc, devices=[0]),
              scopes.Scoped({"devices": doc["devices"], "host": doc["host"]}, devices=[0], kinds=kinds)):
        run = types.SimpleNamespace(trace=t, counters=counters,
                                    peaks=lambda: harness.peaks("TPU v5 lite"))
        order = harness.module("metrics", "order_share.sortkv").read(run)
        permute = harness.module("metrics", "permute_share.sortkv").read(run)
        assert 4 < order < 6 and 94 < permute < 96
        assert order + permute > 99.9
        assert harness.module("metrics", "hbm_floor_share.sortkv").read(run) == pytest.approx(0.108, abs=0.001)
        assert t.breakdown()["program_spans"] == {"repro.kv.dispatch": 14}
