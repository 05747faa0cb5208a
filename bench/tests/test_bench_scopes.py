"""The reduction to the program's named scopes and host spans
(``bench/scopes.py``) and the five readers built on it: on synthetic traces
whose answers are known, on HLO text, and on a run of the four-device cell
on the CPU."""
import gzip
import json
import os
import subprocess
import sys
import textwrap
import types

import pytest

from bench_testlib import BENCH, ROOT, SMALL, clean_env

import harness
import scopes

# window 0..1000 ns; device 0: a merge round, a tile sort, an op with no
# scope; device 1 all merge. Host: one dispatch span per call, an overflow
# wait nested in an attempt nested in a sort call.
SCOPED = {
    "devices": {
        "0": [[100, 300, "sort s32[8,16]"], [300, 700, "sort s32[1,128]"],
              [700, 800, "copy s32[128]"], [900, 950, "sort s32[1,128]"]],
        "1": [[0, 1000, "sort s32[1,128]"]],
    },
    "scopes": {"0": ["repro.tile_sort", "repro.merge", "unscoped", "repro.merge"],
               "1": ["repro.merge"]},
    "host": [[0, 1000, "bench.window"], [0, 860, "bench.sort_call"],
             [90, 120, "repro.sort.dispatch"], [880, 900, "repro.sort.dispatch"],
             [800, 850, "repro.exchange.attempt"], [805, 845, "repro.exchange.overflow_wait"]],
}


def test_scope_of_takes_the_first_repro_component():
    assert scopes.scope_of("jit(f)/shard_map/repro.partition/jit(sort)/sort") == "repro.partition"
    assert scopes.scope_of("jit(f)/repro.merge/repro.inner/sort") == "repro.merge"
    assert scopes.scope_of("jit(f)/mul") == scopes.UNSCOPED


def test_scope_seconds_share_and_breakdown():
    t = scopes.Scoped(SCOPED, devices=[0, 1])
    assert t.scope_s(0, "repro.merge") == pytest.approx(450e-9)
    assert t.scope_s(0, "repro.tile_sort") == pytest.approx(200e-9)
    assert t.scope_s(1, "repro.merge") == pytest.approx(1000e-9)
    # device 0 busy 750 ns, device 1 busy 1000 ns
    assert t.share("repro.merge") == pytest.approx((100 * 450 / 750 + 100) / 2)
    b = t.breakdown()
    assert b["scopes"][0] == ["repro.merge", pytest.approx(725e-9)]
    assert dict(b["scopes"])["unscoped"] == pytest.approx(50e-9)
    assert b["program_spans"] == {"repro.exchange.attempt": 1, "repro.exchange.overflow_wait": 1,
                                  "repro.sort.dispatch": 2}
    assert "device_ops" in b and "idle_gaps" in b


def test_clock_offset_is_the_median_first_op_minus_dispatch():
    t = scopes.Scoped(SCOPED, devices=[0])
    # busy stretches of device 0 start at 100 and 900; dispatches at 90 and 880
    assert t.clock_offset_ns() == 15
    plain = {**SCOPED, "host": [h for h in SCOPED["host"] if h[2] != "repro.sort.dispatch"]}
    assert scopes.Scoped(plain, devices=[0]).clock_offset_ns() == 0


def test_idle_gaps_go_to_the_innermost_span_on_the_host_clock():
    doc = {
        "devices": {"0": [[0, 100, "a"], [300, 400, "b"], [700, 1000, "c"]]},
        "scopes": {"0": ["repro.merge"] * 3},
        "host": [[0, 1000, "bench.window"], [0, 1000, "bench.sort_call"],
                 [100, 300, "repro.exchange.attempt"], [110, 290, "repro.exchange.overflow_wait"],
                 [400, 700, "bench.block"]],
    }
    t = scopes.Scoped(doc, devices=[0])
    assert t.idle_gaps(0) == [[100, 300], [400, 700]]
    # 100..300: attempt covers 200, the wait 180, the call 200: the attempt
    # and the call tie, and the attempt is the shorter
    assert [t.label(g) for g in t.idle_gaps(0)] == ["repro.exchange.attempt", "bench.block"]
    # a device 50 ns behind the dispatch: the first gap is 50..250 on the host
    shifted = {**doc, "host": doc["host"] + [[650, 660, "repro.sort.dispatch"]]}
    t = scopes.Scoped(shifted, devices=[0])
    assert t.clock_offset_ns() == 50
    assert t.label([100, 300]) == "bench.sort_call"  # 50..250 lies in the call
    assert t.label([160, 340]) == "repro.exchange.overflow_wait"  # 110..290


def test_a_kind_shared_by_two_scopes_takes_the_nearest_certain_neighbour():
    ops = [[0, 10, "sort s32[64]"], [10, 20, "fusion s32[64]"], [20, 30, "all_to_all s32[4]"],
           [100, 110, "all-gather s32[256]"], [110, 120, "fusion s32[64]"], [200, 210, "mystery"]]
    kinds = {"sort s32[64]": {"repro.partition"}, "all_to_all s32[4]": {"repro.all_to_all"},
             "all-gather s32[256]": {"repro.compact"},
             "fusion s32[64]": {"repro.partition", "repro.compact"}}
    assert scopes.resolve(ops, kinds) == [
        "repro.partition", "repro.partition", "repro.all_to_all",
        "repro.compact", "repro.compact", "unscoped"]
    # where no certain neighbour shares a candidate, the candidates are named
    assert scopes.resolve([[0, 1, "fusion s32[64]"]], kinds) == ["repro.compact|repro.partition"]
    # a known scope wins over the kind's
    assert scopes.resolve(ops[:2], kinds, [None, "repro.x"]) == ["repro.partition", "repro.x"]


HLO = textwrap.dedent("""\
    HloModule jit_f, is_scheduled=true

    %fused_computation (param_0: s32[64]) -> s32[64] {
      %param_0 = s32[64]{0} parameter(0)
      ROOT %gather.1 = s32[64]{0} gather(%param_0), metadata={op_name="jit(f)/repro.compact/gather"}
    }

    %region_0 (a: s32[], b: s32[]) -> pred[] {
      ROOT %lt = pred[] compare(%a, %b), direction=LT, metadata={op_name="jit(f)/repro.merge/lt"}
    }

    ENTRY %main.9 (x.1: s32[64]) -> s32[64] {
      %x.1 = s32[64]{0} parameter(0), metadata={op_name="x"}
      %sort.3 = s32[64]{0} sort(%x.1), dimensions={0}, to_apply=%region_0, metadata={op_name="jit(f)/repro.merge/jit(_merge)/sort"}
      %copy.2 = s32[64]{0} copy(%sort.3)
      ROOT %fusion.4 = s32[64]{0} fusion(%copy.2), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/repro.compact/gather"}
    }
    """)


def test_hlo_table_keeps_top_level_instructions_with_their_scopes():
    module, by_name, by_kind = scopes.hlo_table(HLO)
    assert module == "jit_f"
    assert by_name == {"x.1": "unscoped", "sort.3": "repro.merge", "copy.2": "unscoped",
                       "fusion.4": "repro.compact"}
    assert by_kind["sort s32[64]"] == {"repro.merge"} and by_kind["sort"] == {"repro.merge"}
    assert by_kind["fusion s32[64]"] == {"repro.compact"}
    assert "gather s32[64]" not in by_kind and "compare pred[]" not in by_kind
    # a module with no repro scope adds no kind: its kinds would only blur
    plain = scopes.hlo_table(HLO.replace("repro.", "other."))
    assert scopes.kind_table([plain]) == {}
    assert scopes.kind_table([plain, (module, by_name, by_kind)])["copy s32[64]"] == {"unscoped"}


def _reader(name):
    return harness.module("metrics", name)


def _run(doc, devices, **kw):
    return types.SimpleNamespace(trace=scopes.Scoped(doc, devices=devices, **kw), counters={})


MODEL_D = {
    "devices": {d: [[0, 60, "fusion s32[64]"], [60, 70, "all_to_all s32[4,1,16]"],
                    [70, 85, "sort s32[128]"], [85, 90, "psum s32[4]"], [90, 100, "fusion s32[64]"]]
                for d in ("0", "1")},
    "scopes": {d: ["repro.partition", "repro.all_to_all", "repro.local_sort", "repro.counts",
                   "repro.compact"] for d in ("0", "1")},
    "host": [[0, 200, "bench.window"]],
}


@pytest.mark.parametrize("metric,doc,want", [
    ("merge_share.sort", SCOPED, (100 * 450 / 750 + 100) / 2),
    ("partition_share.sort", MODEL_D, 60.0),
    ("local_sort_share.sort", MODEL_D, 15.0),
    ("compact_share.sort", MODEL_D, 10.0),
])
def test_scope_readers_read_their_share(metric, doc, want):
    devices = sorted(doc["devices"], key=int)
    assert _reader(metric).read(_run(doc, devices)) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["merge_share.sort", "partition_share.sort",
                                    "local_sort_share.sort", "compact_share.sort"])
def test_scope_readers_read_nothing_from_a_program_without_scopes(metric):
    doc = {"devices": {"0": [[0, 50, "sort s32[8]"]]}, "host": [[0, 100, "bench.window"]]}
    assert _reader(metric).read(_run(doc, [0], kinds={"sort s32[8]": {"unscoped"}})) is None
    assert _reader(metric).read(types.SimpleNamespace(trace=None, counters={})) is None


def test_scope_readers_read_a_reduce_trace_doc_through_kinds(monkeypatch):
    import reduce_trace as rt

    doc = {"devices": {"0": [[0, 10, "sort s32[64]"], [10, 60, "fusion s32[64]"],
                             [60, 70, "all_to_all s32[4]"], [80, 90, "all-gather s32[256]"],
                             [90, 100, "fusion s32[64]"]]},
           "host": [[0, 100, "bench.window"]]}
    kinds = {"sort s32[64]": {"repro.partition"}, "all_to_all s32[4]": {"repro.all_to_all"},
             "all-gather s32[256]": {"repro.compact"},
             "fusion s32[64]": {"repro.partition", "repro.compact"}}
    monkeypatch.setattr(scopes, "live_tables", lambda: [("m", {"a": "repro.x"}, kinds)])
    run = types.SimpleNamespace(trace=rt.Reduced(doc, devices=[0]), counters={})
    assert _reader("partition_share.sort").read(run) == pytest.approx(100 * 60 / 90)
    assert _reader("compact_share.sort").read(run) == pytest.approx(100 * 20 / 90)


def test_window_compiles_reads_the_programs_traced_compile_count(monkeypatch):
    import repro.launch.compile_cache as cc

    run = types.SimpleNamespace(trace=scopes.Scoped(SCOPED, devices=[0]), counters={})
    monkeypatch.setitem(cc._counts, "traced", 0)
    assert _reader("window_compiles.sort").read(run) == 0
    monkeypatch.setitem(cc._counts, "traced", 2)
    assert _reader("window_compiles.sort").read(run) == 2
    assert _reader("window_compiles.sort").read(types.SimpleNamespace(trace=None)) is None


def test_scoped_run_of_the_four_device_cell_finds_every_model_d_scope(tmp_path):
    """``trace_scopes.py``'s run of ``sort.zipf.4chip`` at a small size on
    four CPU devices: the CPU backend's operation events carry their module
    and instruction, and the live executables' HLO names their scopes."""
    keep = tmp_path / "trace.json"
    code = textwrap.dedent(f"""
        import json, sys, types
        sys.path.insert(0, {BENCH!r})
        sys.path.insert(0, {os.path.join(ROOT, "src")!r})
        import harness, run, scopes
        v5e = harness.peaks("TPU v5 lite")
        harness.peaks = lambda kind: v5e  # the CPU has no entry in the peak table
        run.tracing = types.SimpleNamespace(Recorder=scopes.Recorder, Reduced=scopes.Scoped)
        result, _ = run.run_cell("sort.zipf.4chip", 2**40 + 5, 0.5, True, require_chip=False,
                                 config_overrides={SMALL["sort-int32-2p28-4chip"]!r},
                                 keep_trace={str(keep)!r})
        print(json.dumps(result))
    """)
    out = subprocess.run([sys.executable, "-c", code], env=clean_env(tmp_path, 4),
                         capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"]
    found = dict(result["breakdown"]["scopes"])
    for scope in ("repro.partition", "repro.all_to_all", "repro.local_sort", "repro.counts",
                  "repro.compact"):
        assert found.get(scope, 0) > 0, found
    for metric in ("partition_share.sort", "local_sort_share.sort", "compact_share.sort"):
        assert 0 < result["metrics"][metric]["value"] < 100
    assert result["metrics"]["window_compiles.sort"]["value"] == 0
    calls = result["counters"]["calls"]
    assert result["breakdown"]["program_spans"] == {
        "repro.compact.dispatch": calls, "repro.exchange.attempt": calls,
        "repro.exchange.overflow_wait": calls, "repro.sort.dispatch": calls}
    doc = json.loads(keep.read_text())
    assert set(doc["scopes"]) == {"0", "1", "2", "3"}
    assert all(len(doc["scopes"][d]) == len(doc["devices"][d]) for d in doc["scopes"])


def _recorded(name):
    with gzip.open(os.path.join(BENCH, "tests", "data", name), "rt") as f:
        return json.load(f)


def test_recorded_model_b_trace_reads_its_scopes():
    """Three 2^27-key calls of ``sort.uniform.1chip`` on a TPU v5e, extracted
    with scopes (``trace_scopes.py --keep-trace``)."""
    import reduce_trace as rt

    doc = _recorded("trace_scopes_uniform_v5e.json.gz")
    assert rt.Reduced(doc, devices=[0]).busy_s(0) > 13  # the old reduction still reads it
    t = scopes.Scoped(doc, devices=[0])
    b = t.breakdown()
    assert [k for k, _ in b["scopes"]] == ["repro.merge", "repro.tile_sort", "unscoped"]
    assert t.share("unscoped") < 5
    assert b["program_spans"] == {"repro.sort.dispatch": 3}
    run = types.SimpleNamespace(trace=t, counters={})
    assert 88 < _reader("merge_share.sort").read(run) < 92
    assert all(_reader(m).read(run) == 0 for m in ("partition_share.sort", "compact_share.sort"))


def test_recorded_model_d_trace_reads_its_scopes():
    """Two 2^28-key calls of ``sort.zipf.4chip`` on four TPU v5e chips,
    extracted with scopes: the s32[67108864] gathers of the partition and of
    the compaction share a kind, and each lands in its own scope."""
    doc = _recorded("trace_scopes_zipf_v5e.json.gz")
    t = scopes.Scoped(doc, devices=[0, 1, 2, 3])
    run = types.SimpleNamespace(trace=t, counters={})
    shares = {m: _reader(m).read(run) for m in
              ("partition_share.sort", "local_sort_share.sort", "compact_share.sort")}
    assert 65 < shares["partition_share.sort"] < 75
    assert 4 < shares["local_sort_share.sort"] < 8
    assert 18 < shares["compact_share.sort"] < 28
    assert t.share("unscoped") < 5
    gathers = {sc for d in t.devices for op, sc in zip(t.ops(d), t.scope[d])
               if op[2] == "fusion s32[67108864]"}
    assert gathers == {"repro.partition", "repro.compact"}
    assert t.breakdown()["program_spans"]["repro.exchange.overflow_wait"] == 2


def test_recorded_model_d_trace_reads_the_same_through_kinds():
    """The reading ``run.py``'s own trace gets (kinds only, scopes from the
    executables' HLO) agrees with the exact one: the nearest certain
    neighbour puts each shared-kind gather in its phase."""
    doc = _recorded("trace_scopes_zipf_v5e.json.gz")
    kinds = {}
    for d in doc["devices"]:
        for op, sc in zip(doc["devices"][d], doc["scopes"][d]):
            kinds.setdefault(op[2], set()).add(sc)
    exact = scopes.Scoped(doc, devices=[0, 1, 2, 3])
    bare = scopes.Scoped({"devices": doc["devices"], "host": doc["host"]},
                         devices=[0, 1, 2, 3], kinds=kinds)
    for scope in ("repro.partition", "repro.local_sort", "repro.compact"):
        assert bare.share(scope) == pytest.approx(exact.share(scope), abs=0.5)
