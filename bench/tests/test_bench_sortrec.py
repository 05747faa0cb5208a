"""The Sort Benchmark record cell ``sortrec.zipf.1chip`` at a size a test can
hold: it runs correct and its traced run reads both ``engine/kv`` scopes;
its control and its faults read not correct; its roofline share counts 100
bytes a record; and its window never holds more than two results."""
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

CELL = "sortrec.zipf.1chip"
CONFIG = "sortrec-k10-r100-2p24-1chip"
# 2^16 records: the hottest item's ~4,600 records share key bytes 0-7, so
# full 10-byte ties (and the order among them) are certain to be checked
bench_testlib.SMALL.setdefault(CONFIG, {"records": 1 << 16})

FAULT_BODY = """
def key_bytes_8_9_ignored(loop):
    from repro.engine import sort_kv

    return lambda t: sort_kv((t["w0"], t["w1"]), t)[1]


def ties_reversed(loop):
    # keys in order, but records with equal keys in reverse input order
    def call(t):
        t = {k: np.asarray(v) for k, v in t.items()}
        w0, w1, w2k = loop.reference.key_words(t)
        order = np.lexsort((-np.arange(w0.size), w2k, w1, w0))
        return {k: v[order] for k, v in t.items()}
    return call


def payload_word_altered(loop):
    def call(t):
        out = {k: np.array(v) for k, v in loop.program(t).items()}
        out["w17"][11] += 1
        return out
    return call


def record_dropped(loop):
    def call(t):
        return {k: np.delete(np.asarray(v), 5) for k, v in loop.program(t).items()}
    return call
"""


def _run(tmp_path, code: str) -> dict:
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)], env=clean_env(tmp_path),
                         capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_cell_runs_correct_and_its_trace_reads_order_and_permute_scopes(tmp_path):
    """``run.py``'s ``--trace 1`` run of the cell on the CPU, with the
    scoped extraction (the CPU's operation events carry their module and
    instruction)."""
    r = _run(tmp_path, f"""
        import json, sys, types
        sys.path.insert(0, {BENCH!r})
        sys.path.insert(0, {os.path.join(ROOT, "src")!r})
        import harness, run, scopes
        v5e = harness.peaks("TPU v5 lite")
        harness.peaks = lambda kind: v5e  # the CPU has no entry in the peak table
        run.tracing = types.SimpleNamespace(Recorder=scopes.Recorder, Reduced=scopes.Scoped)
        result, _ = run.run_cell({CELL!r}, 2**40 + 19, 0.5, True, require_chip=False,
                                 config_overrides={bench_testlib.SMALL[CONFIG]!r})
        print(json.dumps(result))
    """)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert r["checks"] == {"mismatched_keys": {"value": 0, "limit": 0},
                           "mismatched_payload": {"value": 0, "limit": 0}}
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert set(m) == {"order_share.sortrec", "permute_share.sortrec", "hbm_floor_share.sortrec",
                      "idle_share.sortrec"}
    assert 0 < m["order_share.sortrec"] < 100 and 0 < m["permute_share.sortrec"] < 100
    assert m["order_share.sortrec"] + m["permute_share.sortrec"] <= 100 + 1e-6
    assert m["hbm_floor_share.sortrec"] > 0 and 0 <= m["idle_share.sortrec"] < 100
    c = r["counters"]
    assert (c["records_per_device"], c["record_bytes"], c["key_bytes"], c["carried_bytes"]) == (
        1 << 16, 100, 10, 224)
    assert c["calls_checked"] == min(2, c["calls"])
    assert r["breakdown"]["program_spans"] == {"repro.kv.dispatch": c["calls"]}


def test_cell_runs_correct_untraced(tmp_path):
    r = drive(tmp_path, CELL, seed=2**33 + 5)
    assert r["correct"] and r["attempted"] > 0
    assert set(r["metrics"]) == {"sort_records_per_s", "setup_s"}
    assert list(r["checks"]) == ["mismatched_keys", "mismatched_payload"]


def test_control_is_not_correct(tmp_path):
    r = drive(tmp_path, CELL, system=f"control.control_system({CELL!r})")
    assert r["correct"] is False
    assert r["checks"]["mismatched_keys"]["value"] > 0


@pytest.mark.parametrize("fault,keys_wrong,payload_wrong", [
    ("key_bytes_8_9_ignored", True, True),
    ("ties_reversed", False, True),
    ("payload_word_altered", False, True),
    ("record_dropped", True, True),
])
def test_each_fault_is_not_correct(tmp_path, fault, keys_wrong, payload_wrong):
    r = drive(tmp_path, CELL, FAULT_BODY, system=fault)
    assert r["correct"] is False
    checked = r["counters"]["calls_checked"]
    assert (r["checks"]["mismatched_keys"]["value"] > 0) is keys_wrong
    assert (r["checks"]["mismatched_payload"]["value"] > 0) is payload_wrong
    if fault == "payload_word_altered":
        assert r["checks"]["mismatched_payload"]["value"] == checked
    if fault == "record_dropped":
        # a missing record shifts every position: all of them count
        assert r["checks"]["mismatched_keys"]["value"] == checked * (1 << 16)


def test_window_never_holds_more_than_two_results(tmp_path):
    """Each call counts the earlier results still alive; a result dropped by
    the loop is freed at once."""
    r = _run(tmp_path, f"""
        import json, sys, weakref
        sys.path.insert(0, {BENCH!r})
        sys.path.insert(0, {os.path.join(ROOT, "src")!r})
        import run

        class Result(dict):
            pass

        alive, refs = [], []

        def counting(loop):
            def call(t):
                alive.append(sum(ref() is not None for ref in refs))
                out = Result(loop.program(t))
                refs.append(weakref.ref(out))
                return out
            return call

        result, _ = run.run_cell({CELL!r}, 7, 1.0, require_chip=False, system=counting,
                                 config_overrides={{"records": 1 << 10}})
        print(json.dumps({{"alive": alive, "result": result}}))
    """)
    calls = r["result"]["counters"]["calls"]
    assert r["result"]["correct"] and calls >= 3
    # the warm-up call's result is gone before the window; in it, at most
    # one earlier result is alive while a call runs
    assert max(r["alive"]) == 1 and len(r["alive"]) == calls + 1
    assert r["result"]["counters"]["calls_checked"] == 2


def test_hbm_floor_share_counts_one_hundred_bytes_a_record():
    reader = harness.module("metrics", "hbm_floor_share.sortrec")
    assert reader.floor_bytes(1 << 24, 100) == 2 * 100 * (1 << 24)
    doc = {"devices": {"0": [[0, 400_000_000, "sort u32[16777216]"]]},
           "host": [[0, 1_000_000_000, "bench.window"]]}
    run = types.SimpleNamespace(
        trace=rt.Reduced(doc, devices=[0]),
        counters={"calls": 1, "records_per_device": 1 << 24, "record_bytes": 100,
                  "carried_bytes": 224},
        peaks=lambda: harness.peaks("TPU v5 lite"),
    )
    # 3,355,443,200 bytes at 819 GB/s over 0.4 s of busy time, not the 224 carried
    assert reader.read(run) == pytest.approx(100 * 2 * 100 * (1 << 24) / 819e9 / 0.4)
    assert harness.module("metrics", "idle_share.sortrec").read(run) == pytest.approx(60.0)
    # a loop that reports no record counters (a keys-only cell): nothing to read
    run.counters = {"calls": 1, "keys_per_device": 1 << 24, "key_bytes": 4}
    assert reader.read(run) is None


def test_reference_is_a_stable_memcmp_order_and_the_control_drops_bytes_8_9():
    ref = harness.module("reference", "sorted_record_table")
    u = lambda *v: np.array(v, np.uint32)  # noqa: E731
    table = {"w0": u(2, 1, 1, 1, 0xFFFFFFFF), "w1": u(0, 5, 5, 5, 0),
             "w2": u(0x00020001, 0x00030002, 0x00010003, 0x0003FFFF, 0),
             "w3": u(10, 11, 12, 13, 14)}
    out = ref.reference(table)
    # unsigned words; bytes 8-9 break the tie in bytes 0-7; equal keys stay in input order
    assert out["w3"].tolist() == [12, 11, 13, 10, 14]
    assert ref.control(table)["w3"].tolist() == [11, 12, 13, 10, 14]
