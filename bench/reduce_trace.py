"""From a profiler trace of one window to the numbers the per-layer metrics
read.

``Recorder`` traces the window with JAX's profiler, then ``extract`` keeps
what the reduction needs as plain data: each device's operations
(``[start_ns, end_ns, kind]``, from the device planes' "XLA Ops" line) and
the benchmark's own host spans (``[start_ns, end_ns, name]``, the
``TraceAnnotation`` spans named ``bench.*``). On a TPU v5e an operation's
event is named by its HLO text; its kind is the instruction's name without
XLA's numeric suffix, and the first shape of its result (``sort
s32[8,16777216]``). The device events there carry no JAX name stack.
``Reduced`` works on that data alone, so a small recorded trace in
``bench/tests/`` checks it without a chip.

Busy time is the union of a device's operation intervals inside the window,
which is the benchmark's ``bench.window`` host span. Idle gaps are the rest of
the window, each labelled by the ``bench.*`` span (other than the window) that
covers most of it.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import tempfile

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW = "bench.window"
NO_SPAN = "outside bench spans"
HLO = re.compile(r"^%?([^\s=]+) = \(?([a-z0-9]+\[[0-9,]*\])?")


def op_kind(text: str) -> str:
    """``%sort.12 = (s32[8,524288]{...}, ...) sort(...)`` -> ``sort s32[8,524288]``;
    a name that is not HLO text loses only its numeric suffixes."""
    m = HLO.match(text)
    if not m:
        return re.sub(r"\.\d+", "", text)
    name = re.sub(r"\.\d+$", "", m.group(1))
    return f"{name} {m.group(2)}" if m.group(2) else name


def extract(pb_path: str) -> dict:
    """Plain data from one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    devices, host = {}, []
    for plane in ProfileData.from_file(pb_path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = devices.setdefault(m.group(1), [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    ops.append([int(e.start_ns), int(e.start_ns + e.duration_ns), op_kind(e.name)])
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host.append([int(e.start_ns), int(e.start_ns + e.duration_ns), e.name])
    return {"devices": devices, "host": host}


class Recorder:
    """Traces the window into a temporary directory, removed once read."""

    def start(self):
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        import jax

        jax.profiler.stop_trace()

    def read(self) -> dict:
        try:
            [pb] = glob.glob(os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb"))
            return extract(pb)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def union(intervals) -> list:
    """Merged, sorted ``[start, end]`` intervals."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals if min(e, hi) > max(s, lo)]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


class Reduced:
    """The reductions of one extracted trace."""

    def __init__(self, doc: dict, devices=None):
        self.doc = doc
        windows = [s for s in doc["host"] if s[2] == WINDOW]
        if not windows:
            raise ValueError("the trace holds no bench.window span")
        self.lo, self.hi = windows[0][0], windows[0][1]
        present = sorted(doc["devices"], key=int)
        self.devices = [str(d) for d in devices] if devices is not None else present
        missing = set(self.devices) - set(present)
        if missing:
            raise ValueError(f"the trace holds no operations of device(s) {sorted(missing)}")

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def ops(self, dev) -> list:
        return self.doc["devices"][str(dev)]

    def _busy(self, dev, pick=None) -> list:
        return union(clip(
            [(s, e) for s, e, kind in self.ops(dev) if pick is None or pick(kind)],
            self.lo, self.hi,
        ))

    def busy_s(self, dev) -> float:
        return total(self._busy(dev)) / 1e9

    def mean_busy_s(self) -> float:
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    def op_s(self, dev, pick) -> float:
        """Seconds in the window in which an operation whose kind passes
        ``pick`` ran on ``dev``."""
        return total(self._busy(dev, pick)) / 1e9

    def idle_gaps(self, dev) -> list:
        gaps, t = [], self.lo
        for s, e in self._busy(dev):
            if s > t:
                gaps.append([t, s])
            t = max(t, e)
        if self.hi > t:
            gaps.append([t, self.hi])
        return gaps

    def label(self, gap) -> str:
        """The ``bench.*`` span (not the window) that covers most of ``gap``."""
        if not hasattr(self, "_spans"):
            self._spans = sorted((s, e, n) for s, e, n in self.doc["host"] if n != WINDOW)
            self._starts = [s for s, _, _ in self._spans]
            self._longest = max((e - s for s, e, _ in self._spans), default=0)
        best, best_ns = NO_SPAN, 0
        i = bisect.bisect_left(self._starts, gap[0] - self._longest)
        for s, e, name in self._spans[i: bisect.bisect_left(self._starts, gap[1])]:
            ns = min(e, gap[1]) - max(s, gap[0])
            if ns > best_ns:
                best, best_ns = name, ns
        return best

    def breakdown(self, top: int = 10) -> dict:
        """Device time by operation kind and idle time by what the host was
        doing, each in seconds averaged over the devices, largest first."""
        ops, idle = {}, {}
        n = len(self.devices)
        for d in self.devices:
            for s, e, kind in self.ops(d):
                s, e = max(s, self.lo), min(e, self.hi)
                if e > s:
                    ops[kind] = ops.get(kind, 0.0) + (e - s) / 1e9 / n
            for g in self.idle_gaps(d):
                k = self.label(g)
                idle[k] = idle.get(k, 0.0) + (g[1] - g[0]) / 1e9 / n

        def first(d):
            return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

        return {"device_ops": first(ops), "idle_gaps": first(idle)}
