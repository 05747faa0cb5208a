"""The reduction from a profiler trace to busy time, idle gaps, operation
time and the breakdown: on a synthetic trace whose answers are known, and on
a small trace recorded on a TPU v5e."""
import gzip
import json
import os

import pytest

from bench_testlib import BENCH

import reduce_trace as rt

RECORDED = os.path.join(BENCH, "tests", "data", "trace_sort_v5e.json.gz")

# window 0..100 ns; device 0 busy 10..30 (two overlapping ops) and 50..60
SYNTH = {
    "devices": {
        "0": [[10, 25, "sort s32[2,8]"],
              [20, 30, "fusion s32[16]"],
              [50, 60, "all-to-all s32[4,4]"],
              [95, 120, "copy s32[16]"]],
        "1": [[0, 100, "sort s32[2,8]"]],
    },
    "host": [[0, 100, "bench.window"], [0, 12, "bench.sort_call"],
             [30, 55, "bench.block"], [60, 100, "bench.sort_call"]],
}


def test_union_and_clip():
    assert rt.union([[5, 9], [1, 3], [2, 4], [9, 10], [11, 11]]) == [[1, 4], [5, 10]]
    assert rt.clip([[0, 5], [8, 20], [30, 40]], 2, 25) == [[2, 5], [8, 20]]


def test_busy_idle_and_ops_on_a_known_trace():
    t = rt.Reduced(SYNTH, devices=[0])
    assert t.window_s == pytest.approx(100e-9)
    assert t.busy_s(0) == pytest.approx(35e-9)  # 10..30, 50..60, 95..100
    assert t.idle_gaps(0) == [[0, 10], [30, 50], [60, 95]]
    assert t.op_s(0, lambda kind: kind.startswith("all-to-all")) == pytest.approx(10e-9)
    assert t.op_s(0, lambda kind: kind.startswith("sort")) == pytest.approx(15e-9)


@pytest.mark.parametrize("kind", ["all-to-all s32[4,4]", "all_to_all s32[4,1,33554432]"])
def test_all_to_all_share_reads_either_spelling(kind):
    import types

    import harness

    doc = {"devices": {"0": [[0, 50, "sort s32[8]"], [50, 60, kind]]}, "host": [[0, 100, "bench.window"]]}
    run = types.SimpleNamespace(trace=rt.Reduced(doc, devices=[0]), counters={})
    assert harness.module("metrics", "all_to_all_share.sort").read(run) == pytest.approx(100 * 10 / 60)


def test_op_kind_from_hlo_text():
    text = ("%sort.12 = (s32[8,524288]{1,0:T(8,128)S(1)}, s32[8,524288]{1,0:T(8,128)}) "
            "sort(s32[8,524288]{1,0:T(8,128)S(1)} %reshape.14), dimensions={1}")
    assert rt.op_kind(text) == "sort s32[8,524288]"
    assert rt.op_kind("%iota = s32[8,524288]{1,0:T(8,128)S(1)} iota()") == "iota s32[8,524288]"
    assert rt.op_kind("%all-to-all.3 = s32[4,1024]{1,0} all-to-all(s32[4,1024] %p)") == "all-to-all s32[4,1024]"
    assert rt.op_kind("sort.0") == "sort"


def test_idle_gaps_take_the_host_span_that_covers_most():
    t = rt.Reduced(SYNTH, devices=[0])
    assert [t.label(g) for g in t.idle_gaps(0)] == ["bench.sort_call", "bench.block", "bench.sort_call"]
    b = t.breakdown()
    assert b["idle_gaps"][0] == ["bench.sort_call", pytest.approx(45e-9)]
    kinds = dict(b["device_ops"])
    assert kinds["sort s32[2,8]"] == pytest.approx(15e-9)
    assert kinds["copy s32[16]"] == pytest.approx(5e-9)


def test_busy_is_averaged_over_devices():
    t = rt.Reduced(SYNTH, devices=[0, 1])
    assert t.mean_busy_s() == pytest.approx((35e-9 + 100e-9) / 2)


def test_a_trace_without_the_window_or_a_device_is_refused():
    with pytest.raises(ValueError):
        rt.Reduced({"devices": {"0": []}, "host": []})
    with pytest.raises(ValueError):
        rt.Reduced(SYNTH, devices=[0, 3])


def _recorded():
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


def test_recorded_trace_reduces():
    t = rt.Reduced(_recorded(), devices=[0])
    assert 0 < t.busy_s(0) <= t.window_s
    gaps = sum(g[1] - g[0] for g in t.idle_gaps(0)) / 1e9
    assert gaps + t.busy_s(0) == pytest.approx(t.window_s)
    b = t.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert sum(v for _, v in b["device_ops"]) >= t.busy_s(0) * 0.999


@pytest.mark.parametrize("metric", ["idle_share.sort", "hbm_floor_share.sort"])
def test_recorded_trace_gives_shares_within_bounds(metric):
    import types

    import harness

    t = rt.Reduced(_recorded(), devices=[0])
    run = types.SimpleNamespace(
        trace=t, counters={"calls": 1, "keys_per_device": 1 << 22, "key_bytes": 4},
        peaks=lambda: harness.peaks("TPU v5 lite"),
    )
    v = harness.module("metrics", metric).read(run)
    assert v is None or 0 < v <= 100
