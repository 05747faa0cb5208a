"""Open-loop served top-k through ``SortFrontend``: decode steps that arrive
on a schedule, whether or not the server keeps up.

Traffic parameters (``bench/traffic/<mix>.json``): ``rate_per_s``, the mean
rate of arrivals; ``process``, how they are spaced; ``rows_per_arrival``, the
logits rows one arrival brings (a decode step of that many sequences; 1 for
independent rows); and ``drain_s``, how long past the window's close an
answer may still come. ``periodic`` arrivals come every ``1 / rate_per_s``
seconds, as the steps of one decode loop do. ``poisson`` arrivals have a
fixed set of gaps: the ``rate_per_s * seconds`` quantiles of the exponential
distribution, scaled to fill the window, in an order drawn from the seed.
Either way every seed offers the same load; the seed draws the rows.

Configuration (``bench/configs/<config>.json``): ``vocab_size`` (width of a
logits row), ``top_k`` (what the client keeps), ``max_batch``, ``tenant``,
``pool_rows`` (rows drawn from the seed, N(0, 1) float32) and ``sample``
(requests whose whole permutation is kept for the check).

An arrival submits its rows one after another, each as one request
``SortFrontend.submit(tenant, row, kind="argsort", ascending=False)``, the
way the serving driver's ``sample_next`` submits a decode step; the
frontend's own dispatcher thread answers them. A request's latency runs from
when its arrival was due to when the client holds its result. A request the
frontend refuses (``ShedError``), or that has no answer when the drain ends,
fails: it counts as waiting until the drain's end. Only a refusal is a
failure and not a wrong answer; an error, or no answer at all, makes the run
incorrect.
"""
from __future__ import annotations

import threading
import time
from functools import partial

import numpy as np

import harness


def _span(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


PROCESSES = ("periodic", "poisson")


def arrivals(rate: float, seconds: float, seed: int, process: str = "poisson") -> np.ndarray:
    """Due times (s from the window's start) of one run's arrivals."""
    if process not in PROCESSES:
        raise ValueError(f"arrival process must be one of {PROCESSES}, got {process!r}")
    n = max(1, int(round(rate * seconds)))
    if process == "periodic":
        return (np.arange(n) + 1) * (seconds / n)
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps *= seconds / gaps.sum()
    return np.cumsum(np.random.default_rng([seed, 1]).permutation(gaps))


def _counts(stats) -> dict:
    return {k: getattr(stats, k) for k in ("requests", "batches", "keys_in", "padded_keys", "compiles")}


class Loop:
    """One cell's inputs, warm-up, window and check.

    ``system``, if given, is called with this loop once its inputs exist and
    returns the ``SortService`` the frontend serves on in the program's
    default service's place: the control and the fault tests use it.
    """

    def __init__(self, config, traffic, seed, devices, system=None):
        from repro.engine import SortFrontend, Tenant

        self.seed = seed
        self.vocab = int(config["vocab_size"])
        self.k = int(config["top_k"])
        self.tenant = config["tenant"]
        self.sample_size = int(config["sample"])
        self.rate = float(traffic["rate_per_s"])
        self.process = traffic["process"]
        self.burst = int(traffic["rows_per_arrival"])
        self.drain_s = float(traffic["drain_s"])
        self.reference = harness.module("reference", config["reference"])
        rng = np.random.default_rng([seed, 0])
        self.pool = rng.standard_normal((int(config["pool_rows"]), self.vocab), dtype=np.float32)
        service = system(self) if system is not None else None
        # as the serving driver builds it: a late decode row is still served
        self.fe = SortFrontend(service, tenants=[Tenant(self.tenant)],
                               max_batch=int(config["max_batch"]), shed_expired=False)
        self.attempted = self.failed = 0
        self.counters = {}

    def warm(self):
        """Compile the frontend's batch ladder for this row width, then run
        one batch of every size in it before the dispatcher thread starts."""
        self.fe.warmup(cells=[(self.vocab, "float32")], kinds=("argsort",), ascending=(False,))
        b = 1
        while b <= self.fe.max_batch:
            tickets = [self.fe.submit(self.tenant, self.pool[i % len(self.pool)],
                                      kind="argsort", ascending=False) for i in range(b)]
            self.fe.poll()
            for t in tickets:
                t.result()
            b *= 2
        self.fe.start()

    def _done(self, i, fut):
        with _span("bench.client_result"):
            t = time.perf_counter()
            err = fut.exception()
            if err is None:
                res = fut.result()
                self.lengths[i] = len(res)
                self.top[i] = res[: self.k]
                if i in self.full:
                    self.full[i] = np.array(res)
                self.t_done[i] = t
            elif isinstance(err, self.shed_error):
                self.refused[i] = True
            else:
                self.errored[i] = True
            with self.settled:
                self.n_settled += 1
                self.settled.notify()

    def run(self, seconds: float) -> dict:
        from repro.engine import ShedError

        due = np.repeat(arrivals(self.rate, seconds, self.seed, self.process), self.burst)
        n = len(due)
        rng = np.random.default_rng([self.seed, 2])
        self.rows = rng.integers(0, len(self.pool), n)
        self.full = {int(i): None for i in rng.choice(n, min(n, self.sample_size), replace=False)}
        self.t_done = np.full(n, np.nan)
        self.sent = np.full(n, np.nan)
        self.refused = np.zeros(n, bool)
        self.errored = np.zeros(n, bool)
        self.shed_error = ShedError
        self.top = np.full((n, self.k), -1, np.int32)
        self.lengths = np.zeros(n, np.int64)
        self.settled, self.n_settled = threading.Condition(), 0
        submitted = 0
        before = _counts(self.fe.stats)
        t0 = time.perf_counter()
        with _span("bench.window"):
            for i in range(n):
                wait = t0 + due[i] - time.perf_counter()
                if wait > 0:
                    with _span("bench.await_arrival"):
                        time.sleep(wait)
                self.sent[i] = time.perf_counter()
                with _span("bench.submit"):
                    try:
                        ticket = self.fe.submit(self.tenant, self.pool[self.rows[i]],
                                                kind="argsort", ascending=False)
                    except ShedError:
                        self.refused[i] = True
                        continue
                ticket.future.add_done_callback(partial(self._done, i))
                submitted += 1
            close = t0 + seconds + self.drain_s
            with self.settled:
                self.settled.wait_for(lambda: self.n_settled == submitted,
                                      timeout=max(0.0, close - time.perf_counter()))
        t_end = time.perf_counter()
        after = _counts(self.fe.stats)
        d = {k: after[k] - before[k] for k in after}
        # a refused or unanswered request counts as waiting until the drain's end
        lat = np.where(np.isnan(self.t_done), close, self.t_done) - (t0 + due)
        step_lat = lat.reshape(-1, self.burst).max(axis=1)
        self.attempted = n
        self.failed = int(np.isnan(self.t_done).sum())
        self.counters = {
            "requests": n,
            "arrivals": len(step_lat),
            "refused": int(self.refused.sum()),
            "pad_ratio": (d["keys_in"] + d["padded_keys"]) / d["keys_in"] if d["keys_in"] else None,
            "batch_rows": d["requests"] / d["batches"] if d["batches"] else None,
            "gen_late_p99_ms": float(np.percentile((self.sent - (t0 + due)) * 1e3, 99)),
            "arrival_p99_ms": float(np.percentile(step_lat, 99) * 1e3),
            "compiles_in_window": d["compiles"],
            "drain_ms": (t_end - (t0 + seconds)) * 1e3,
        }
        return {"topk_p50_ms": float(np.percentile(lat, 50) * 1e3),
                "topk_p99_ms": float(np.percentile(lat, 99) * 1e3)}

    def release(self):
        self.fe.close()

    def check(self) -> list:
        refs = {int(r): self.reference.reference(self.pool[r]) for r in np.unique(self.rows)}
        answered = ~np.isnan(self.t_done)
        # a refusal is an answer (the request failed, it was not answered
        # wrong); an error in its place is a wrong answer
        unanswered = int((~answered & ~self.refused).sum())
        top_bad = 0
        for i in np.flatnonzero(answered):
            want = refs[int(self.rows[i])]
            top_bad += int(self.lengths[i] != self.vocab or not np.array_equal(self.top[i], want[: self.k]))
        perm_bad = sum(
            int(got is not None and not np.array_equal(got, refs[int(self.rows[i])]))
            for i, got in self.full.items()
        )
        self.failed += top_bad
        return [
            ("unanswered_requests", unanswered, 0),
            ("topk_mismatched_requests", top_bad, 0),
            ("permutation_mismatched_requests", perm_bad, 0),
        ]


def control_system(control):
    """Put ``control`` (a reference at lower precision) in the program's
    place: a ``SortService`` whose batches are answered with the control's
    permutation of each row, computed once per pool row at set-up."""
    from repro.engine import SortService

    class ControlService(SortService):
        def __init__(self, pool):
            super().__init__()
            self.answers = {row.tobytes(): control(row) for row in pool}

        def _run_group(self, kind, gk, reqs, vals=None, *, ascending=True):
            with self._lock:
                self.stats.requests += len(reqs)
                self.stats.batches += 1
                self.stats.keys_in += sum(len(r) for r in reqs)
            return [self.answers[r.tobytes()] for r in reqs]

    return lambda loop: ControlService(loop.pool)
