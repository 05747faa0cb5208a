"""The load the benchmark offers: the same for the same seed, and latency
counted from when a request was due."""
import numpy as np
import pytest

from bench_testlib import drive

import harness

open_loop = harness.module("traffic", "open_loop")


def test_arrivals_repeat_for_a_seed():
    a = open_loop.arrivals(300.0, 10.0, 2**31 + 17)
    assert np.array_equal(a, open_loop.arrivals(300.0, 10.0, 2**31 + 17))


def test_every_seed_offers_the_same_load_in_another_order():
    a, b = open_loop.arrivals(300.0, 10.0, 1), open_loop.arrivals(300.0, 10.0, 2)
    assert len(a) == len(b) == 3000
    assert not np.array_equal(a, b)
    assert np.allclose(np.sort(np.diff(a, prepend=0.0)), np.sort(np.diff(b, prepend=0.0)))
    assert a[-1] == pytest.approx(10.0) and np.all(np.diff(a) > 0)


def test_arrival_gaps_are_exponential():
    gaps = np.diff(open_loop.arrivals(200.0, 50.0, 5), prepend=0.0)
    assert gaps.mean() == pytest.approx(1 / 200.0, rel=1e-6)
    assert np.std(gaps) == pytest.approx(1 / 200.0, rel=0.05)


def test_periodic_arrivals_are_evenly_spaced_and_the_same_for_every_seed():
    a = open_loop.arrivals(21.0, 10.0, 1, "periodic")
    assert len(a) == 210 and a[-1] == pytest.approx(10.0)
    assert np.allclose(np.diff(a), 1 / 21.0)
    assert np.array_equal(a, open_loop.arrivals(21.0, 10.0, 2**40 + 3, "periodic"))
    with pytest.raises(ValueError):
        open_loop.arrivals(21.0, 10.0, 1, "bursty")


@pytest.mark.parametrize("dist", ["uniform", "zipf"])
def test_keys_repeat_for_a_seed(dist):
    keygen = __import__("keys")
    a = np.asarray(keygen.make_keys(2**33 + 1, 4096, dist))
    assert a.dtype == np.int32 and a.shape == (4096,)
    assert np.array_equal(a, np.asarray(keygen.make_keys(2**33 + 1, 4096, dist)))
    assert not np.array_equal(a, np.asarray(keygen.make_keys(2**33 + 2, 4096, dist)))


def test_zipf_keys_are_skewed():
    keygen = __import__("keys")
    x = np.asarray(keygen.make_keys(3, 1 << 16, "zipf"))
    _, counts = np.unique(x, return_counts=True)
    assert counts.max() / x.size > 0.04


def test_latency_counts_from_the_due_time(tmp_path):
    """The generator stalls 0.3 s on its first request; the requests due in
    that stall are sent late, and their latency includes the wait."""
    body = """
        import time
        from repro.engine import SortFrontend
        real = SortFrontend.submit
        state = {"n": 0}
        def stalled(self, *a, **k):
            state["n"] += 1
            if state["n"] == 16:  # the first after the warm-up ladder 1 + 2 + 4 + 8
                time.sleep(0.3)
            return real(self, *a, **k)
        SortFrontend.submit = stalled
    """
    r = drive(tmp_path, "topk.decode.steps", body, seconds=1.0)
    assert r["correct"]
    assert r["counters"]["gen_late_p99_ms"] > 200
    assert r["metrics"]["topk_p99_ms"]["value"] > 200
    assert r["counters"]["arrival_p99_ms"] > 200


def test_a_refused_request_fails_but_is_not_wrong(tmp_path):
    """The frontend refusing a request (a shed) is a failure with the drain's
    whole wait as its latency; it does not make the run incorrect."""
    body = """
        from repro.engine import SortFrontend, ShedError
        real = SortFrontend.submit
        state = {"n": 0}
        def shedding(self, tenant, *a, **k):
            state["n"] += 1
            if state["n"] > 15 and state["n"] % 2:  # every other one after warm-up
                raise ShedError(tenant, "global_backlog")
            return real(self, tenant, *a, **k)
        SortFrontend.submit = shedding
    """
    r = drive(tmp_path, "topk.decode.steps", body, seconds=1.0)
    assert r["correct"]
    assert r["failed"] == r["counters"]["refused"] == r["attempted"] // 2
    assert r["metrics"]["topk_p50_ms"]["value"] > 1_000  # half the requests wait out the drain


def test_a_decode_step_brings_its_rows_together(tmp_path):
    """Each arrival is one decode step: its rows are submitted together and
    are all requests of the window."""
    r = drive(tmp_path, "topk.decode.steps", seconds=1.0)
    assert r["correct"] and r["failed"] == 0
    assert r["attempted"] == 4 * r["counters"]["arrivals"] == r["counters"]["requests"]
    assert r["metrics"]["topk_p99_ms"]["value"] >= r["metrics"]["topk_p50_ms"]["value"]
