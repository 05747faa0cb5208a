"""What every cell shares: its files found by name, the chip check, the run.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``. It names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); the mix names the loop that drives it
(``bench/traffic/<kind>.py``). Each per-layer metric is read by
``bench/metrics/<metric>.py``, and each configuration's plain reference is
``bench/reference/<reference>.py``. Nothing here names a cell, so a new cell,
configuration, traffic mix or metric is new files and entries, never an edit.

A candidate (``bench/candidates/<cell>.json``) holds the entries of a cell in
``BENCHMARK.json``'s form that the benchmark does not time yet: the CPU tests,
``control.py`` and ``sweep.py`` run it, and a later benchmark change copies
its entries into ``BENCHMARK.json``.
"""
from __future__ import annotations

import glob
import importlib.util
import json
import os
import shutil
import tempfile

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")  # the program under test


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def read_json(path: str):
    with open(path) as f:
        return json.load(f)


ENTRIES = ("configs", "workloads", "end_to_end", "per_layer")


def benchmark(candidates: bool = False) -> dict:
    """``BENCHMARK.json``, with every candidate's entries added if asked."""
    bm = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    if candidates:
        for path in sorted(glob.glob(os.path.join(BENCH, "candidates", "*.json"))):
            extra = read_json(path)
            for k in ENTRIES:
                bm[k] = bm[k] + extra.get(k, [])
    return bm


def entry(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def data(kind: str, name: str) -> dict:
    """``bench/<kind>/<name>.json``: a configuration or a traffic mix."""
    return read_json(os.path.join(BENCH, kind, f"{name}.json"))


def module(kind: str, name: str):
    """Load ``bench/<kind>/<name>.py`` (names may hold dots) as a module."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip; a kind missing from the table is an
    error, not a default."""
    table = read_json(os.path.join(BENCH, "peaks.json"))["chips"]
    if device_kind not in table:
        raise KeyError(f"bench/peaks.json has no entry for device kind {device_kind!r}")
    return table[device_kind]


def reports(metric: dict, cell: str) -> bool:
    """Whether ``cell`` reports an end-to-end metric (no ``workloads`` key
    means every cell does)."""
    return "workloads" not in metric or cell in metric["workloads"]


def end_to_end(bm: dict, cell: str) -> list:
    return [m for m in bm["end_to_end"] if reports(m, cell)]


def per_layer(bm: dict, cell: str) -> list:
    """Per-layer metrics this cell reports: those that list it, and those
    without a list whose ``moves`` the cell reports."""
    e2e = {m["name"] for m in end_to_end(bm, cell)}
    out = []
    for m in bm["per_layer"]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif m["moves"] in e2e:
            out.append(m)
    return out


def require_chips(chips: int):
    """The first ``chips`` TPU devices; raises ``NoChip`` otherwise. Never
    falls back to the CPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform!r} devices, nothing was run")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devices)}")
    return devices[:chips]


def seed_key(seed: int):
    """A JAX PRNG key for any non-negative whole-number seed, 64 bits wide."""
    import jax

    if seed < 0 or seed >= 1 << 64:
        raise ValueError("--seed must be a whole number in [0, 2**64)")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


class PlanFile:
    """A fresh plan-cache file for one run: ``REPRO_SORT_PLANS`` points at it
    before the program reads it, so learned capacity never carries over."""

    def __enter__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-plans-")
        self.prev = os.environ.get("REPRO_SORT_PLANS")
        os.environ["REPRO_SORT_PLANS"] = os.path.join(self.dir, "plans.json")
        return self

    def __exit__(self, *exc):
        if self.prev is None:
            os.environ.pop("REPRO_SORT_PLANS", None)
        else:
            os.environ["REPRO_SORT_PLANS"] = self.prev
        shutil.rmtree(self.dir, ignore_errors=True)


def enable_compile_cache() -> str:
    """The program's own compile-cache policy (``JAX_COMPILATION_CACHE_DIR``
    if set, else ``.jax_cache/`` in the checkout), with every program kept:
    a run after the first then compiles nothing."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable

    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the backend
    reports nothing)."""
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks_))

