"""AsyncSortService: cross-caller coalescing, backpressure, lifecycle, stats.

Every timing-sensitive case runs on ``ManualClock`` — the injected monotonic
clock the queue reads for enqueue stamps, flush deadlines, latencies, and
delay adaptation.  Time moves only when a test calls ``advance``, so batch
boundaries are decided by the test, not by wall-clock races: a frozen clock
means groups flush *only* when full (or at close), and advancing past a
deadline flushes exactly the groups whose deadline passed.  No test in this
file sleeps or asserts on real elapsed time except the throughput-accounting
regression, which is explicitly about real wall time.
"""
import queue as stdqueue
import threading
import time

import numpy as np
import pytest

from repro.engine import (
    AsyncSortService,
    DelayController,
    ManualClock,
    QueueStats,
    SortService,
)


def _mk(rng, n):
    return rng.integers(0, 1_000_000, n).astype(np.int32)


# ------------------------------------------------------------- coalescing ---
def test_concurrent_producers_coalesce_into_one_executable_call():
    """Acceptance: N concurrent single-request producers of the same bucket
    execute as ONE batch (fewer than N), with zero recompiles after warmup —
    asserted with jax's lowering counter, not just our own stats.  The frozen
    ManualClock makes the coalescing deterministic: nothing can flush before
    the batch is full, no matter how the threads interleave."""
    from jax._src import test_util as jtu

    N = 8
    rng = np.random.default_rng(0)
    svc = AsyncSortService(max_batch=N, clock=ManualClock())
    # warmup: same bucket, same coalesced batch shape -> compiles (N, 1024)
    futs = [svc.submit_async(_mk(rng, 1000)) for _ in range(N)]
    for f in futs:
        f.result(timeout=120)
    batches_before = svc.stats.batches

    reqs = [_mk(rng, 900 + i) for i in range(N)]  # same 1024 bucket
    results = [None] * N

    def producer(i):
        results[i] = svc.submit_async(reqs[i]).result(timeout=120)

    with jtu.count_jit_and_pmap_lowerings() as count:
        threads = [threading.Thread(target=producer, args=(i,)) for i in range(N)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert count() == 0, "steady-state async path must not re-trace"
    executed = svc.stats.batches - batches_before
    assert executed < N, "cross-caller requests must coalesce"
    assert executed == 1  # frozen clock: only a full batch can flush
    for r, o in zip(reqs, results):
        assert (o == np.sort(r)).all()
    # QueueStats saw the coalesced batch
    st = svc.stats
    assert isinstance(st, QueueStats)
    assert st.coalesced_requests >= 2 * N and st.coalesced_batches >= 2
    assert st.batch_sizes[-1] == N and st.fill_ratio() > 0.9
    pct = st.latency_percentiles()
    assert 0 <= pct[50] <= pct[99]
    svc.close()


def test_many_threads_many_requests_correct_and_order_stable():
    """Stress: mixed kinds/buckets from many threads; every future resolves
    to its own request's oracle (no cross-request mixups under coalescing).
    Submission happens with the clock frozen, so partial groups pile up;
    one clock advance past the window then releases everything."""
    rng = np.random.default_rng(1)
    clock = ManualClock()
    svc = AsyncSortService(max_batch=16, max_delay_ms=5.0, clock=clock)
    per_thread = 6
    n_threads = 6
    payloads = [
        [_mk(np.random.default_rng(100 * t + j), 50 + 37 * (j % 4))
         for j in range(per_thread)]
        for t in range(n_threads)
    ]
    futs = [[] for _ in range(n_threads)]
    errors = []

    def producer(t):
        try:
            for j, r in enumerate(payloads[t]):
                if j % 3 == 0:
                    futs[t].append(("argsort", r, None,
                                    svc.submit_async(r, kind="argsort")))
                elif j % 3 == 1:
                    v = np.arange(len(r), dtype=np.int32)
                    futs[t].append(
                        ("sort_kv", r, v,
                         svc.submit_async(r, kind="sort_kv", values=v))
                    )
                else:
                    futs[t].append(("sort", r, None, svc.submit_async(r)))
        except Exception as e:  # pragma: no cover - surfaced via assert below
            errors.append(e)

    threads = [threading.Thread(target=producer, args=(t,)) for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    clock.advance(1.0)  # all deadlines pass; dispatcher flushes every group
    for t in range(n_threads):
        for kind, r, v, f in futs[t]:
            ref = np.argsort(r, kind="stable")
            if kind == "sort":
                assert (f.result(timeout=120) == np.sort(r)).all()
            elif kind == "argsort":
                assert (f.result(timeout=120) == ref).all()
            else:
                sk, sv = f.result(timeout=120)
                assert (sk == r[ref]).all() and (sv == ref).all()
    assert svc.stats.requests == n_threads * per_thread
    assert svc.stats.coalesced_batches < n_threads * per_thread  # some merging
    svc.close()


# ----------------------------------------------------------- backpressure ---
def test_backpressure_reject_policy_raises_queue_full():
    svc = AsyncSortService(maxsize=2, on_full="reject", start=False,
                           max_batch=2, clock=ManualClock())
    rng = np.random.default_rng(2)
    f1 = svc.submit_async(_mk(rng, 100))
    f2 = svc.submit_async(_mk(rng, 100))
    with pytest.raises(stdqueue.Full):
        svc.submit_async(_mk(rng, 100))
    assert svc.stats.rejected == 1 and svc.stats.enqueued == 2
    svc.start()  # dispatcher drains the two admitted requests (full batch)
    assert f1.result(timeout=120) is not None
    assert f2.result(timeout=120) is not None
    svc.close()


def test_backpressure_block_policy_completes_everything():
    """maxsize=1 + blocking producers: submits stall instead of failing, and
    every request still resolves correctly. The frozen clock pins the flush
    pattern: exactly three full max_batch=4 batches, nothing else."""
    rng = np.random.default_rng(3)
    svc = AsyncSortService(maxsize=1, on_full="block", max_batch=4,
                           clock=ManualClock())
    reqs = [_mk(rng, 200) for _ in range(12)]
    futs = [svc.submit_async(r) for r in reqs]
    for r, f in zip(reqs, futs):
        assert (f.result(timeout=120) == np.sort(r)).all()
    assert svc.stats.rejected == 0 and svc.stats.enqueued == 12
    assert list(svc.stats.batch_sizes)[-3:] == [4, 4, 4]
    svc.close()


# -------------------------------------------------------- drain and close ---
def test_drain_then_close_then_submit_raises():
    rng = np.random.default_rng(4)
    svc = AsyncSortService(max_batch=4, clock=ManualClock())
    futs = [svc.submit_async(_mk(rng, 300)) for _ in range(8)]  # 2 full batches
    assert svc.drain(timeout=120)
    assert all(f.done() for f in futs)
    svc.close()
    svc.close()  # idempotent
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit_async(_mk(rng, 10))


def test_close_resolves_backlog_of_never_started_service():
    """close() on a staged (start=False) service must not strand futures —
    even with a frozen clock whose deadlines can never fire."""
    rng = np.random.default_rng(5)
    svc = AsyncSortService(start=False, max_batch=64, clock=ManualClock())
    futs = [svc.submit_async(_mk(rng, 64)) for _ in range(3)]
    svc.close()  # starts, drains (flushing the half-empty batch), stops
    assert all(f.done() for f in futs)
    assert svc.stats.batch_sizes[-1] == 3  # flushed below max_batch on close


def test_context_manager_and_execution_error_propagates_to_futures():
    rng = np.random.default_rng(6)
    with AsyncSortService(max_batch=2, clock=ManualClock()) as svc:
        ok = [svc.submit_async(_mk(rng, 50)) for _ in range(2)]  # full batch
        assert all(len(f.result(timeout=120)) == 50 for f in ok)
        # inject an execution failure: every future in the batch must carry it
        boom = RuntimeError("injected")

        def exploding(*a, **k):
            raise boom

        svc.service._run_group = exploding
        bad = [svc.submit_async(_mk(rng, 50)) for _ in range(2)]
        for f in bad:
            assert f.exception(timeout=120) is boom
    with pytest.raises(RuntimeError):
        svc.submit_async(_mk(rng, 10))  # context exit closed it


def test_validation_errors_raise_synchronously():
    svc = AsyncSortService(start=False, clock=ManualClock())
    with pytest.raises(ValueError, match="NaN"):
        svc.submit_async(np.array([1.0, np.nan], np.float32))
    with pytest.raises(ValueError):
        svc.submit_async(np.zeros((2, 2), np.int32))  # not 1-D
    with pytest.raises(ValueError):
        svc.submit_async(np.arange(4), kind="sort_kv")  # missing values
    with pytest.raises(ValueError):
        svc.submit_async(np.arange(4), kind="nope")
    assert svc.stats.enqueued == 0
    svc.close()


# ------------------------------------------------------- stats accounting ---
def test_elapsed_accounting_stays_meaningful_under_concurrent_submitters():
    """Regression for summed-overlapping-spans accounting: N threads hammering
    one SortService must report busy time <= real wall time (interval union),
    so throughput_keys_per_s stays a real keys/sec figure.  (Deliberately on
    the real clock: the property under test is about wall time.)"""
    svc = SortService()
    rng = np.random.default_rng(7)
    reqs = [rng.integers(0, 1000, 2000).astype(np.int32) for _ in range(4)]
    svc.submit(reqs)  # warmup compile outside the timed window
    svc.stats.elapsed_s = 0.0

    N = 6
    t0 = time.perf_counter()

    def hammer():
        for _ in range(5):
            svc.submit(reqs)

    threads = [threading.Thread(target=hammer) for _ in range(N)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    assert 0 < svc.stats.elapsed_s <= wall * 1.05, (svc.stats.elapsed_s, wall)
    assert svc.stats.throughput_keys_per_s() > 0


def test_cancelled_future_is_skipped_without_killing_the_dispatcher():
    """Caller-side Future.cancel() on a queued request: the request is
    dropped, its batchmates still execute, and the dispatcher keeps serving."""
    rng = np.random.default_rng(8)
    clock = ManualClock()
    svc = AsyncSortService(start=False, max_batch=2, clock=clock)
    r1, r2 = _mk(rng, 40), _mk(rng, 40)
    f1 = svc.submit_async(r1)
    f2 = svc.submit_async(r2)
    assert f1.cancel()
    svc.start()  # the pair fills max_batch; the cancelled member is skipped
    assert (f2.result(timeout=120) == np.sort(r2)).all()
    assert f1.cancelled()
    r3 = _mk(rng, 40)
    f3 = svc.submit_async(r3)
    clock.advance(1.0)  # a lone request needs its deadline to pass
    assert (f3.result(timeout=120) == np.sort(r3)).all()
    svc.close()


def test_caller_may_reuse_its_buffer_after_submit_async():
    """submit_async snapshots the request: mutating the caller's array while
    the request waits in the coalescing window must not corrupt the result."""
    rng = np.random.default_rng(9)
    clock = ManualClock()
    svc = AsyncSortService(start=False, max_batch=8, clock=clock)
    buf = _mk(rng, 128)
    want = np.sort(buf)
    vbuf = np.arange(128, dtype=np.int32)
    ref = np.argsort(buf, kind="stable")
    f = svc.submit_async(buf)
    fkv = svc.submit_async(buf, kind="sort_kv", values=vbuf)
    buf[:] = -1  # caller reuses its buffer before the batch executes
    vbuf[:] = -1
    clock.advance(1.0)  # deadlines pass the moment the dispatcher looks
    svc.start()
    assert (f.result(timeout=120) == want).all()
    sk, sv = fkv.result(timeout=120)
    assert (sv == ref).all()
    svc.close()


# ------------------------------------------------------- adaptive window ---
def test_delay_controller_adapts_step_by_step():
    """Pure unit test of the policy on a manual clock: every decision is a
    deterministic function of the observed flushes, replayed step by step."""
    clock = ManualClock()
    ctl = DelayController(1.0, 8.0, clock=clock)
    assert ctl.delay_ms == 8.0  # starts patient (max_delay)

    # full batches before the deadline: shrink geometrically to the floor
    for want in (4.0, 2.0, 1.0, 1.0):
        ctl.observe_flush(n_requests=8, capacity=8, deadline_hit=False)
        assert ctl.delay_ms == pytest.approx(want)
    assert ctl.shrinks == 4

    # sparse deadline flushes: grow geometrically back to the ceiling
    for want in (1.5, 2.25, 3.375):
        ctl.observe_flush(n_requests=1, capacity=8, deadline_hit=True)
        assert ctl.delay_ms == pytest.approx(want)
    assert ctl.grows == 3

    # the middle regime holds: a decently-filled deadline flush, or a
    # below-capacity batch that didn't hit its deadline, changes nothing
    ctl.observe_flush(n_requests=5, capacity=8, deadline_hit=True)
    ctl.observe_flush(n_requests=5, capacity=8, deadline_hit=False)
    assert ctl.delay_ms == pytest.approx(3.375)

    # arrival rate comes straight off the injected clock
    for _ in range(5):
        ctl.note_arrival()
        clock.advance(0.1)
    assert ctl.arrival_rate() == pytest.approx(10.0)

    with pytest.raises(ValueError):
        DelayController(0.0, 8.0)
    with pytest.raises(ValueError):
        DelayController(9.0, 8.0)
    with pytest.raises(ValueError):
        DelayController(1.0, 8.0, shrink=1.5)


def test_adaptive_queue_shrinks_on_full_batches_and_grows_on_sparse():
    """Integration: the queue's effective window follows the traffic shape —
    full batches shrink it, sparse deadline flushes grow it, close-time
    flushes leave it alone. All on the fake clock, no sleeps."""
    rng = np.random.default_rng(10)
    clock = ManualClock()
    svc = AsyncSortService(max_batch=4, max_delay_ms=8.0, min_delay_ms=1.0,
                           clock=clock)
    assert svc.delay is not None and svc.delay_s == pytest.approx(8e-3)

    # a full batch flushes before its (frozen-clock) deadline -> shrink
    futs = [svc.submit_async(_mk(rng, 64)) for _ in range(4)]
    for f in futs:
        f.result(timeout=120)
    assert svc.delay.delay_ms == pytest.approx(4.0)
    assert svc.delay.shrinks == 1 and svc.delay.grows == 0

    # a lone request times out its (shrunken) window -> sparse flush, grow
    f = svc.submit_async(_mk(rng, 64))
    clock.advance(0.005)  # past the 4 ms window
    f.result(timeout=120)
    assert svc.delay.delay_ms == pytest.approx(6.0)
    assert svc.delay.grows == 1

    # arrival tracking rode along on the same clock
    assert svc.delay.arrival_rate() >= 0.0

    # a half-empty batch flushed by close() must not adapt the window
    svc.submit_async(_mk(rng, 64))
    svc.close()
    assert svc.delay.delay_ms == pytest.approx(6.0)
    assert svc.delay.shrinks == 1 and svc.delay.grows == 1


def test_fixed_window_service_has_no_controller():
    svc = AsyncSortService(start=False, max_delay_ms=3.0, clock=ManualClock())
    assert svc.delay is None
    assert svc.delay_s == pytest.approx(3e-3)
    svc.close()
