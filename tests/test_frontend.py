"""Multi-tenant SLO frontend: warmup, EDF scheduling, shed policy, load
simulation, and the queued front door's lifecycle under concurrent callers.

Everything timing-sensitive runs on ManualClock — dispatch order, deadline
sheds, latency percentiles, and goodput are deterministic functions of the
seed.  The lifecycle cases run twice: pump-driven (``poll()`` on the test's
thread) and on the background dispatcher thread (``start()``).
"""
import threading
import time

import numpy as np
import pytest

from repro.engine import (
    ManualClock,
    Planner,
    ShedError,
    SortFrontend,
    SortService,
    Tenant,
    make_trace,
    mesh_fingerprint,
    run_load,
    warmup,
)
from repro.engine.frontend import (
    batch_bucket_ladder,
    linear_service_time,
    payload_for,
    zipf_shares,
)


# ----------------------------------------------------------- trace streams ---
def test_trace_is_byte_for_byte_reproducible():
    kw = dict(duration_s=3.0, rates={"web": 40.0, "batch": 15.0},
              sizes=(64, 128, 256), zipf_a=1.2)
    a = make_trace(seed=42, **kw)
    b = make_trace(seed=42, **kw)
    assert a == b                       # dataclass equality: every field
    assert a != make_trace(seed=43, **kw)
    # payloads too: same (seed, seq) -> identical bytes
    for arr in a[:5]:
        assert payload_for(arr, seed=9).tobytes() == \
            payload_for(arr, seed=9).tobytes()
    assert all(arr.size in (64, 128, 256) for arr in a)
    assert all(0 <= arr.t <= 3.0 for arr in a)
    assert [arr.seq for arr in a] == list(range(len(a)))


def test_trace_tenant_streams_are_independent():
    """Adding a tenant to the mix must not perturb another tenant's stream."""
    solo = make_trace(duration_s=2.0, rates={"a": 20.0}, seed=7)
    mixed = make_trace(duration_s=2.0, rates={"a": 20.0, "b": 80.0}, seed=7)
    a_solo = [(x.t, x.size) for x in solo if x.tenant == "a"]
    a_mixed = [(x.t, x.size) for x in mixed if x.tenant == "a"]
    assert a_solo == a_mixed


def test_zipf_shares_and_size_skew():
    assert zipf_shares(4, 0.0) == (0.25, 0.25, 0.25, 0.25)
    shares = zipf_shares(3, 2.0)
    assert shares[0] > shares[1] > shares[2]
    assert abs(sum(shares) - 1.0) < 1e-12
    with pytest.raises(ValueError):
        zipf_shares(0, 1.0)
    # zipf_a > 0 makes the first (rank-1) size the most common
    tr = make_trace(duration_s=20.0, rates={"t": 50.0}, sizes=(64, 128, 256),
                    zipf_a=2.0, seed=1)
    counts = {s: sum(1 for a in tr if a.size == s) for s in (64, 128, 256)}
    assert counts[64] > counts[128] > counts[256]


def test_trace_rejects_bad_args():
    with pytest.raises(ValueError):
        make_trace(duration_s=0.0, rates={"a": 1.0})
    with pytest.raises(ValueError):
        make_trace(duration_s=1.0, rates={"a": -1.0})


# ------------------------------------------------------------- tenant model ---
def test_tenant_validation():
    with pytest.raises(ValueError):
        Tenant("t", weight=0.0)
    with pytest.raises(ValueError):
        Tenant("t", slo_ms=-5.0)
    with pytest.raises(ValueError):
        SortFrontend(tenants=[Tenant("a"), Tenant("a")])
    with pytest.raises(ValueError):
        SortFrontend(tenants=[])
    fe = SortFrontend(tenants=[Tenant("a")])
    with pytest.raises(KeyError):
        fe.submit("nobody", np.array([1], np.int32))


def test_weighted_backlog_slices():
    fe = SortFrontend(tenants=[Tenant("big", weight=3.0),
                               Tenant("small", weight=1.0),
                               Tenant("pinned", max_backlog=2)],
                      maxsize=40)
    assert fe.tenant_backlog_bound("big") == 24   # ceil(3/5 * 40)
    assert fe.tenant_backlog_bound("small") == 8  # ceil(1/5 * 40)
    assert fe.tenant_backlog_bound("pinned") == 2


# ------------------------------------------------------------ EDF dispatch ---
def test_edf_earlier_deadline_dispatches_first():
    clk = ManualClock()
    fe = SortFrontend(tenants=[Tenant("t")], clock=clk)
    # different sizes -> different signatures -> separate batches, so the
    # pump order exposes the scheduling decision
    relaxed = fe.submit("t", np.arange(256, dtype=np.int32)[::-1],
                        deadline=10.0)
    urgent = fe.submit("t", np.arange(1024, dtype=np.int32)[::-1],
                       deadline=1.0)
    first = fe.pump()
    assert first.bucket == 1024         # urgent (later-submitted) went first
    assert urgent.done() and not relaxed.done()
    fe.poll()
    assert (np.asarray(relaxed.result()) == np.arange(256)).all()


def test_priority_class_beats_deadline():
    clk = ManualClock()
    fe = SortFrontend(tenants=[Tenant("web", priority=0),
                               Tenant("batch", priority=1)], clock=clk)
    # batch has the tighter deadline, but priority classes are strict
    fe.submit("batch", np.arange(256, dtype=np.int32), deadline=0.5)
    fe.submit("web", np.arange(1024, dtype=np.int32), deadline=100.0)
    assert fe.pump().bucket == 1024
    fe.poll()


def test_compatible_requests_coalesce_across_tenants():
    clk = ManualClock()
    fe = SortFrontend(tenants=[Tenant("web", priority=0),
                               Tenant("batch", priority=1)],
                      max_batch=8, clock=clk)
    t1 = fe.submit("batch", np.array([5, 3, 4], np.int32))
    t2 = fe.submit("web", np.array([2, 9, 1], np.int32))
    info = fe.pump()                    # one batch, both tenants ride along
    assert info.n_requests == 2 and set(info.tenants) == {"web", "batch"}
    assert [int(v) for v in t1.result()] == [3, 4, 5]
    assert [int(v) for v in t2.result()] == [1, 2, 9]
    assert fe.stats.tenant_served == {"web": 1, "batch": 1}


# -------------------------------------------------------------- load shed ---
def test_shed_at_global_and_tenant_bounds():
    clk = ManualClock()
    fe = SortFrontend(tenants=[Tenant("a", weight=1.0),
                               Tenant("b", weight=1.0)],
                      maxsize=4, clock=clk)
    assert fe.tenant_backlog_bound("a") == 2
    req = np.array([1], np.int32)
    fe.submit("a", req), fe.submit("a", req)
    with pytest.raises(ShedError) as ei:
        fe.submit("a", req)             # a's weighted slice (2) is full
    assert ei.value.reason == "tenant_backlog" and ei.value.tenant == "a"
    fe.submit("b", req), fe.submit("b", req)
    with pytest.raises(ShedError) as ei:
        fe.submit("b", req)             # whole backlog (4) is full
    assert ei.value.reason == "global_backlog"
    # attribution: the right tenant, the right reason, the shared ledger
    assert fe.stats.shed == {"a": {"tenant_backlog": 1},
                             "b": {"global_backlog": 1}}
    assert fe.stats.shed_total() == 2
    assert fe.stats.shed_total("a") == 1
    fe.poll()


def test_expired_requests_shed_at_dispatch_with_reason():
    clk = ManualClock()
    fe = SortFrontend(tenants=[Tenant("t", slo_ms=50.0)], clock=clk)
    late = fe.submit("t", np.array([3, 1], np.int32))   # deadline = 0.05
    clk.advance(0.2)
    fresh = fe.submit("t", np.array([2, 4], np.int32))
    fe.poll()
    with pytest.raises(ShedError) as ei:
        late.result()
    assert ei.value.reason == "deadline"
    assert late.latency_s == pytest.approx(0.2)
    assert not late.slo_met
    assert [int(v) for v in fresh.result()] == [2, 4]
    assert fe.stats.shed == {"t": {"deadline": 1}}


def test_shed_expired_false_serves_late_and_counts_the_miss():
    clk = ManualClock()
    fe = SortFrontend(tenants=[Tenant("t", slo_ms=50.0)],
                      shed_expired=False, clock=clk)
    late = fe.submit("t", np.array([3, 1], np.int32))
    clk.advance(0.2)
    fe.poll()
    assert [int(v) for v in late.result()] == [1, 3]    # answered anyway
    assert not late.slo_met                             # ...but missed SLO
    assert fe.stats.shed_total() == 0


# ------------------------------------------------------------- AOT warmup ---
def test_batch_bucket_ladder():
    assert batch_bucket_ladder(1) == (1,)
    assert batch_bucket_ladder(8) == (1, 2, 4, 8)
    assert batch_bucket_ladder(5) == (1, 2, 4, 8)


def test_warm_cell_idempotent():
    svc = SortService()
    assert svc.warm_cell("sort", 1024, "int32") is True    # fresh compile
    assert svc.warm_cell("sort", 1024, "int32") is False   # already warm
    assert svc.stats.compiles == 1 and svc.stats.cache_hits == 1


def test_planner_warmup_cells_skips_moe_and_foreign_mesh():
    from repro.engine import SortPlan
    p = Planner()
    fp = mesh_fingerprint(None)
    p.plans[f"1024|int32|{fp}"] = SortPlan("shared")
    p.plans[f"moe/E8k2|256|float32|{fp}"] = SortPlan("shared")
    p.plans["4096|int32|mesh[x=4]"] = SortPlan("cluster")
    cells = p.warmup_cells()
    assert cells == [(1024, "int32")]   # moe + foreign-mesh keys skipped


def test_warmup_then_zero_lowerings_on_warmed_traffic():
    """Acceptance: after warmup(plan_table), serving any warmed cell performs
    zero fresh compiles — jax's own lowering counter, not just ours."""
    from jax._src import test_util as jtu

    from repro.engine import SortPlan
    planner = Planner()
    planner.plans[f"512|int32|{mesh_fingerprint(None)}"] = SortPlan("shared")
    svc = SortService(planner=planner)
    fe = SortFrontend(svc, tenants=[Tenant("t")], max_batch=4)
    report = fe.warmup(plan_table=planner, cells=[(1000, "int32")],
                       kinds=("sort", "argsort"))
    # (512 + 1024 buckets) x (sort, argsort) x bb ladder (1, 2, 4)
    assert report.compiled == 12 and report.cached == 0
    assert fe.warmup(plan_table=planner, cells=[(1000, "int32")],
                     kinds=("sort", "argsort")).compiled == 0

    rng = np.random.default_rng(0)
    with jtu.count_jit_and_pmap_lowerings() as count:
        tickets = [
            fe.submit("t", rng.integers(0, 1000, n).astype(np.int32),
                      kind=kind)
            for kind in ("sort", "argsort") for n in (400, 500, 900)
        ]
        fe.poll()
    assert count() == 0, "warmed cells must never re-trace"
    for t in tickets[:3]:
        assert np.asarray(t.result()).min() >= 0
    srt = np.asarray(tickets[0].result())
    assert (srt[:-1] <= srt[1:]).all()


# ----------------------------------------------------- overload simulation ---
def _overload_run():
    clk = ManualClock()
    fe = SortFrontend(
        SortService(),
        tenants=[Tenant("web", weight=2.0, priority=0, slo_ms=40.0),
                 Tenant("batch", weight=1.0, priority=1, slo_ms=200.0)],
        max_batch=4, maxsize=32, clock=clk,
    )
    tr = make_trace(duration_s=1.0, rates={"web": 700.0, "batch": 500.0},
                    sizes=(64, 128), seed=5)
    rep = run_load(fe, tr, clock=clk,
                   service_time=linear_service_time(base_ms=5.0,
                                                    us_per_key=0.02))
    return fe, rep


def test_overload_simulation_is_deterministic():
    fe1, rep1 = _overload_run()
    fe2, rep2 = _overload_run()
    assert rep1.derived() == rep2.derived()
    assert rep1.derived("web") == rep2.derived("web")
    assert len(rep1.tickets) == len(rep2.tickets)
    assert rep1.sheds == rep2.sheds
    assert fe1.stats.shed == fe2.stats.shed


def test_overload_priority_protects_the_interactive_tenant():
    fe, rep = _overload_run()
    # offered 1200/s vs ~800/s capacity: somebody lost — and the scheduler
    # must have made it the low-priority tenant, not the interactive one
    assert rep.offered == len(rep.tickets) + sum(
        1 for _ in rep.sheds) - sum(
        1 for t in rep.tickets
        if t.done() and isinstance(t.future.exception(), ShedError))
    assert 0.0 < rep.goodput() < 1.0
    assert rep.goodput("web") > rep.goodput("batch")
    assert rep.latency_percentiles(tenant="web")[95] <= 0.040 + 1e-9
    # every shed is attributed: report ledger totals == stats ledger totals
    assert len(rep.sheds) == fe.stats.shed_total()


# ------------------------------------------------------------- thread mode ---
def test_thread_mode_smoke():
    fe = SortFrontend(tenants=[Tenant("a"), Tenant("b")],
                      max_batch=8, start=True)
    results = {}

    def client(name, n_reqs):
        rng = np.random.default_rng(ord(name))
        got = []
        for _ in range(n_reqs):
            arr = rng.integers(0, 10_000, 200).astype(np.int32)
            got.append((arr, fe.submit(name, arr)))
        results[name] = got

    threads = [threading.Thread(target=client, args=(n, 8)) for n in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    with fe:                            # context manager drains + closes
        pass
    for name, got in results.items():
        for arr, ticket in got:
            assert (np.asarray(ticket.result()) == np.sort(arr)).all()
    assert fe.stats.tenant_served == {"a": 8, "b": 8}
    with pytest.raises(RuntimeError):
        fe.submit("a", np.array([1], np.int32))


def test_engine_level_warmup_entry_point():
    svc = SortService(planner=Planner())    # hermetic plan table
    rep = warmup(svc, cells=[(256, "int32")], kinds=("sort",), max_batch=2)
    assert rep.compiled == 2 and "warmup:" in rep.summary()
    assert rep.cells == [("sort", 256, "int32", bb, True) for bb in (1, 2)]


def test_pump_execution_failure_resolves_tickets_exceptionally():
    clk = ManualClock()
    svc = SortService()
    fe = SortFrontend(svc, tenants=[Tenant("t")], clock=clk)
    t1 = fe.submit("t", np.array([2, 1], np.int32))
    t2 = fe.submit("t", np.array([4, 3], np.int32))

    def boom(*a, **k):
        raise RuntimeError("executor died")

    svc._run_group = boom
    info = fe.pump()
    assert info.n_requests == 2
    for t in (t1, t2):
        with pytest.raises(RuntimeError, match="executor died"):
            t.result()
        assert t.latency_s is not None          # failure still stamps t_done


def test_warmup_sort_kv_cells_via_values_spec():
    svc = SortService(planner=Planner())
    rep = warmup(svc, cells=[(64, "int32")], kinds=("sort_kv",),
                 max_batch=1, values_spec=((), "float32"))
    assert rep.compiled == 1
    fe = SortFrontend(svc, tenants=[Tenant("t")], max_batch=1)
    keys = np.arange(40, 0, -1).astype(np.int32)        # len 40 -> 64 bucket
    t = fe.submit("t", keys, kind="sort_kv",
                  values=keys.astype(np.float32) / 10.0)
    compiles_before = svc.cache.misses
    fe.poll()
    sk, sv = t.result()
    assert [int(v) for v in sk[:3]] == [1, 2, 3]
    assert np.allclose(np.asarray(sv), np.asarray(sk) / 10.0)
    # warmed via values_spec: the serving submit was a pure cache hit
    assert svc.cache.misses == compiles_before


def test_backlog_views_and_double_close():
    clk = ManualClock()
    fe = SortFrontend(tenants=[Tenant("a"), Tenant("b")], clock=clk)
    fe.submit("a", np.array([1], np.int32))
    assert fe.backlog() == 1 and fe.backlog("a") == 1 and fe.backlog("b") == 0
    fe.close()
    fe.close()                                  # idempotent
    assert fe.backlog() == 0


# ---------------------------------------------- the queued front door ---
MODES = ("pump", "thread")


def _mk(rng, n):
    return rng.integers(0, 1_000_000, n).astype(np.int32)


def _dispatch(fe, mode):
    """Run what is pending: pump it here, or hand it to the dispatcher."""
    if mode == "pump":
        fe.poll()
    else:
        fe.start()


@pytest.mark.parametrize("mode", MODES)
def test_concurrent_producers_coalesce_into_one_executable_call(mode):
    """N producer threads of one bucket run as ONE batch with zero lowerings
    after warmup — jax's own counter, not just ours.  Dispatch starts only
    once every producer has submitted, so the batch boundary is the test's."""
    from jax._src import test_util as jtu

    N = 8
    rng = np.random.default_rng(0)
    fe = SortFrontend(tenants=[Tenant("t")], max_batch=N, clock=ManualClock())
    warm = [fe.submit("t", _mk(rng, 1000)) for _ in range(N)]
    fe.poll()                               # compiles the (N, 1024) cell
    assert all(t.done() for t in warm)
    batches0, coalesced0 = fe.stats.batches, fe.stats.coalesced_batches

    reqs = [_mk(rng, 900 + i) for i in range(N)]    # same 1024 bucket
    tickets = [None] * N

    def producer(i):
        tickets[i] = fe.submit("t", reqs[i])

    with jtu.count_jit_and_pmap_lowerings() as count:
        threads = [threading.Thread(target=producer, args=(i,))
                   for i in range(N)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        _dispatch(fe, mode)
        outs = [t.result(timeout=120) for t in tickets]
    fe.close()
    assert count() == 0, "steady-state frontend path must not re-trace"
    assert fe.stats.batches - batches0 == 1
    assert fe.stats.coalesced_batches - coalesced0 == 1
    assert fe.stats.batch_sizes[-1] == N and fe.stats.fill_ratio() == 1.0
    for r, o in zip(reqs, outs):
        assert (o == np.sort(r)).all()
    pct = fe.stats.latency_percentiles()
    assert 0 <= pct[50] <= pct[99]


@pytest.mark.parametrize("mode", MODES)
def test_many_threads_many_requests_correct_and_order_stable(mode):
    """Mixed kinds and buckets from many threads: every ticket resolves to
    its own request's answer, never a batchmate's.  In thread mode the
    dispatcher runs while the producers are still submitting."""
    n_threads, per_thread = 6, 6
    fe = SortFrontend(tenants=[Tenant("t")], max_batch=16,
                      clock=ManualClock(), start=mode == "thread")
    payloads = [
        [_mk(np.random.default_rng(100 * t + j), 50 + 37 * (j % 4))
         for j in range(per_thread)]
        for t in range(n_threads)
    ]
    got = [[] for _ in range(n_threads)]
    errors = []

    def producer(t):
        try:
            for j, r in enumerate(payloads[t]):
                if j % 3 == 0:
                    got[t].append(("argsort", r,
                                   fe.submit("t", r, kind="argsort")))
                elif j % 3 == 1:
                    v = np.arange(len(r), dtype=np.int32)
                    got[t].append(("sort_kv", r, fe.submit(
                        "t", r, kind="sort_kv", values=v)))
                else:
                    got[t].append(("sort", r, fe.submit("t", r)))
        except Exception as e:  # pragma: no cover - surfaced by the assert
            errors.append(e)

    threads = [threading.Thread(target=producer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    if mode == "pump":
        fe.poll()
    for per in got:
        for kind, r, ticket in per:
            ref = np.argsort(r, kind="stable")
            if kind == "sort":
                assert (ticket.result(timeout=120) == np.sort(r)).all()
            elif kind == "argsort":
                assert (ticket.result(timeout=120) == ref).all()
            else:
                sk, sv = ticket.result(timeout=120)
                assert (sk == r[ref]).all() and (sv == ref).all()
    fe.close()
    total = n_threads * per_thread
    assert fe.stats.requests == fe.stats.enqueued == total
    assert fe.stats.tenant_served == {"t": total}
    if mode == "pump":                  # everything was pending at once
        assert fe.stats.coalesced_batches < total


@pytest.mark.parametrize("mode", MODES)
def test_close_then_submit_raises(mode):
    rng = np.random.default_rng(4)
    fe = SortFrontend(tenants=[Tenant("t")], max_batch=4,
                      clock=ManualClock(), start=mode == "thread")
    tickets = [fe.submit("t", _mk(rng, 300)) for _ in range(8)]
    fe.close()                          # drains before it returns
    assert all(t.done() for t in tickets)
    fe.close()                          # idempotent
    with pytest.raises(RuntimeError, match="closed"):
        fe.submit("t", _mk(rng, 10))


def test_close_resolves_backlog_of_never_started_frontend():
    """close() on a frontend nobody pumped or started must not strand a
    ticket: it flushes the half-empty batch itself."""
    rng = np.random.default_rng(5)
    fe = SortFrontend(tenants=[Tenant("t")], max_batch=64,
                      clock=ManualClock())
    reqs = [_mk(rng, 64) for _ in range(3)]
    tickets = [fe.submit("t", r) for r in reqs]
    fe.close()
    for r, t in zip(reqs, tickets):
        assert (t.result(timeout=0) == np.sort(r)).all()
    assert fe.stats.batch_sizes[-1] == 3     # below max_batch, on close


@pytest.mark.parametrize("mode", MODES)
def test_context_manager_closes_and_resolves(mode):
    rng = np.random.default_rng(6)
    with SortFrontend(tenants=[Tenant("t")], max_batch=2,
                      clock=ManualClock(), start=mode == "thread") as fe:
        reqs = [_mk(rng, 50) for _ in range(3)]
        tickets = [fe.submit("t", r) for r in reqs]
    for r, t in zip(reqs, tickets):
        assert (t.result(timeout=0) == np.sort(r)).all()
    assert fe.backlog() == 0
    with pytest.raises(RuntimeError, match="closed"):
        fe.submit("t", _mk(rng, 10))    # the exit closed it


@pytest.mark.parametrize("request_kw", [
    dict(keys=np.array([1.0, np.nan], np.float32)),
    dict(keys=np.zeros((2, 2), np.int32)),
    dict(keys=np.arange(4), kind="sort_kv"),
    dict(keys=np.arange(4), kind="argsort", values=np.arange(4)),
    dict(keys=np.arange(4), kind="sort_kv", values=np.arange(3)),
    dict(keys=np.arange(4), kind="nope"),
], ids=["nan", "not_1d", "kv_without_values", "values_without_kv",
        "values_length", "unknown_kind"])
def test_validation_errors_raise_synchronously(request_kw):
    """Bad input raises on the caller's thread and is never admitted, so it
    cannot poison a batch."""
    kw = dict(request_kw)
    keys = kw.pop("keys")
    fe = SortFrontend(tenants=[Tenant("t")], clock=ManualClock())
    with pytest.raises(ValueError):
        fe.submit("t", keys, **kw)
    assert fe.stats.enqueued == 0 and fe.backlog() == 0
    assert fe.poll() == 0


@pytest.mark.parametrize("door", ["service", "frontend"])
def test_elapsed_accounting_stays_meaningful_under_concurrent_submitters(door):
    """Busy time is the union of execution spans, so with many threads
    submitting at once it stays <= real wall time and throughput stays a
    real keys/s figure.  (Deliberately on the real clock: the property is
    about wall time.)"""
    svc = SortService(planner=Planner())    # hermetic plan table
    rng = np.random.default_rng(7)
    reqs = [rng.integers(0, 1000, 2000).astype(np.int32) for _ in range(4)]
    svc.submit(reqs)                    # warm compile outside the window
    fe = None
    if door == "frontend":
        fe = SortFrontend(svc, tenants=[Tenant("t")], max_batch=4, start=True)
        fe.warmup(cells=[(2000, "int32")], kinds=("sort",))
    svc.stats.elapsed_s = 0.0

    def hammer():
        for _ in range(5):
            if fe is None:
                svc.submit(reqs)
            else:
                for t in [fe.submit("t", r) for r in reqs]:
                    t.result(timeout=120)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=hammer) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if fe is not None:
        fe.close()
    assert 0 < svc.stats.elapsed_s <= wall * 1.05, (svc.stats.elapsed_s, wall)
    assert svc.stats.throughput_keys_per_s() > 0


@pytest.mark.parametrize("mode", MODES)
def test_cancelled_ticket_is_skipped_without_killing_the_dispatcher(mode):
    """A caller cancels a queued request: it never runs, its batchmate is
    still answered, and the frontend keeps serving afterwards."""
    rng = np.random.default_rng(8)
    fe = SortFrontend(tenants=[Tenant("t")], max_batch=2,
                      clock=ManualClock())
    r1, r2 = _mk(rng, 40), _mk(rng, 40)
    t1, t2 = fe.submit("t", r1), fe.submit("t", r2)
    assert t1.future.cancel()
    _dispatch(fe, mode)
    assert (t2.result(timeout=120) == np.sort(r2)).all()
    assert t1.future.cancelled()
    r3 = _mk(rng, 40)
    t3 = fe.submit("t", r3)
    if mode == "pump":
        fe.poll()
    assert (t3.result(timeout=120) == np.sort(r3)).all()
    fe.close()
    assert fe.stats.tenant_served == {"t": 2}


@pytest.mark.parametrize("mode", MODES)
def test_caller_may_reuse_its_buffer_after_submit(mode):
    """submit snapshots the request: mutating the caller's arrays while the
    request waits for dispatch must not corrupt the answer."""
    rng = np.random.default_rng(9)
    fe = SortFrontend(tenants=[Tenant("t")], max_batch=8,
                      clock=ManualClock())
    buf = _mk(rng, 128)
    want = np.sort(buf)
    vbuf = np.arange(128, dtype=np.int32)
    ref = np.argsort(buf, kind="stable")
    t = fe.submit("t", buf)
    tkv = fe.submit("t", buf, kind="sort_kv", values=vbuf)
    buf[:] = -1                         # reused before the batch runs
    vbuf[:] = -1
    _dispatch(fe, mode)
    assert (t.result(timeout=120) == want).all()
    sk, sv = tkv.result(timeout=120)
    assert (sk == want).all() and (sv == ref).all()
    fe.close()


def test_dispatcher_thread_survives_an_execution_failure():
    """A failing batch resolves its own tickets exceptionally; the
    dispatcher thread goes on to serve the next batch."""
    svc = SortService()
    fe = SortFrontend(svc, tenants=[Tenant("t")], max_batch=2,
                      clock=ManualClock())
    real = svc._run_group
    calls = []

    def fail_once(*a, **k):
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("executor died")
        return real(*a, **k)

    svc._run_group = fail_once
    bad = [fe.submit("t", np.array([2, 1], np.int32)) for _ in range(2)]
    fe.start()
    for t in bad:
        with pytest.raises(RuntimeError, match="executor died"):
            t.result(timeout=120)
    good = fe.submit("t", np.array([5, 4, 3], np.int32))
    assert [int(v) for v in good.result(timeout=120)] == [3, 4, 5]
    fe.close()
