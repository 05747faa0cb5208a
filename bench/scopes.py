"""Device time by the program's named scopes, and idle time by the program's
own host spans: ``reduce_trace`` carried one level into the program.

The program (``src/repro``) wraps each phase of model B and model D in a
``jax.named_scope`` named ``repro.<phase>`` and its host work on the hot path
in ``TraceAnnotation`` spans named ``repro.<module>.<action>``. A device
operation's scope is the first ``repro.*`` component of its name stack, or
``unscoped``.

``extract`` reads one ``.xplane.pb`` as ``reduce_trace.extract`` does, and
adds each operation's scope (``scopes``: per device, one scope per operation,
in the order of ``devices``) and the ``repro.*`` host spans. No event carries
a name stack (a TPU v5e's operation events hold only their device offset and
duration, and are named by HLO text without metadata), so the scope comes
from the compiled HLO text of the executables alive in this process, whose
``op_name`` metadata names each instruction's scope, keyed by module and
instruction name: the module from the event's ``hlo_module`` stat (CPU) or
from the device plane's "XLA Modules" line (TPU), the instruction from its
``hlo_op`` stat or from the HLO text the event is named by.

A trace that ``reduce_trace`` extracted (``run.py``'s own) names operations
by kind only. ``Scoped`` then gives each kind the scopes the live executables
give it; a kind that several scopes share (a gather of the same shape in the
partition and in the compaction) takes, among them, the scope of the nearest
operation in time whose scope is certain, since a phase's operations run
together.

``Scoped`` also labels each idle gap with the innermost span (bench or
program) that covers most of it, after moving device times onto the host
clock by ``clock_offset_ns``.
"""
from __future__ import annotations

import bisect
import re
import statistics
import weakref
from collections import defaultdict

import reduce_trace as rt

SCOPE_PREFIX = "repro."
UNSCOPED = "unscoped"
DISPATCH = "repro.sort.dispatch"
MODULES_LINE = "XLA Modules"
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
_MODULE = re.compile(r"^HloModule ([^\s,]+)")
_INSTR = re.compile(r"^\s*(?:ROOT )?(%?[^\s=]+ = .*)$")


def scope_of(name_stack: str) -> str:
    """``jit(f)/shard_map/repro.partition/gather`` -> ``repro.partition``."""
    for part in name_stack.split("/"):
        if part.startswith(SCOPE_PREFIX):
            return part
    return UNSCOPED


def instruction_name(text: str) -> str:
    """``%fusion.2 = s32[8]{0} fusion(...)`` -> ``fusion.2``; a bare name stays."""
    head = text.split(" = ", 1)[0] if " = " in text else text
    return head.lstrip("%").strip()


def hlo_table(text: str):
    """``(module, {instruction: scope}, {kind: {scopes}})`` of one HLO module's
    text. Instructions inside fusions, comparators and reducers run as part of
    their caller and are left out; the bodies of loops are kept."""
    module = _MODULE.match(text)
    module = module.group(1) if module else ""
    called = set(_CALLED.findall(text))
    by_name, by_kind = {}, defaultdict(set)
    skip = False
    for line in text.splitlines():
        if line and not line[0].isspace():
            head = line.split(" ", 1)[0]
            name = head.lstrip("%")
            skip = not line.startswith("ENTRY") and name in called
            continue
        if skip:
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        instr = m.group(1)
        op = _OP_NAME.search(instr)
        scope = scope_of(op.group(1)) if op else UNSCOPED
        name = instruction_name(instr)
        by_name[name] = scope
        # a TPU names an operation's event by its HLO text, the CPU by name
        by_kind[rt.op_kind(instr)].add(scope)
        by_kind[rt.op_kind(name)].add(scope)
    return module, by_name, by_kind


def live_tables():
    """``hlo_table`` of every executable alive in this process, or ``[]``
    where the backend lists none."""
    import jax

    try:
        exes = jax.devices()[0].client.live_executables()
    except AttributeError:  # a backend without the listing
        return []
    out = []
    for exe in exes:
        try:
            mods = exe.hlo_modules()
        except jax.errors.JaxRuntimeError:  # an executable deleted meanwhile
            continue
        out.extend(hlo_table(m.to_string()) for m in mods)
    return out


def kind_table(tables) -> dict:
    """``{kind: {scopes}}`` over every module that holds a ``repro`` scope."""
    out = defaultdict(set)
    for _, by_name, by_kind in tables:
        if any(s != UNSCOPED for s in by_name.values()):
            for kind, scopes in by_kind.items():
                out[kind] |= scopes
    return dict(out)


def extract(pb_path: str, tables=None) -> dict:
    """``reduce_trace.extract``'s data with each operation's scope and the
    program's ``repro.*`` host spans. ``tables`` defaults to the live
    executables' (``live_tables``)."""
    from jax.profiler import ProfileData

    tables = live_tables() if tables is None else tables
    names = defaultdict(dict)
    for module, by_name, _ in tables:
        for instr, scope in by_name.items():
            names[module].setdefault(instr, set()).add(scope)
    kinds = kind_table(tables)
    found = defaultdict(list)  # device -> [[start, end, kind], scope or None]

    def add(dev, e, stats, runs=(), starts=()):
        s = int(e.start_ns)
        module = stats.get("hlo_module")
        if module is None and runs:
            i = bisect.bisect_right(starts, s) - 1
            module = runs[i][2] if i >= 0 and s < runs[i][1] else None
        hit = names.get(module, {}).get(stats.get("hlo_op") or instruction_name(e.name))
        scope = next(iter(hit)) if hit and len(hit) == 1 else None
        found[dev].append([[s, int(e.start_ns + e.duration_ns), rt.op_kind(e.name)], scope])

    host = []
    for plane in ProfileData.from_file(pb_path).planes:
        m = rt.DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            runs = sorted(
                (int(e.start_ns), int(e.start_ns + e.duration_ns), re.sub(r"\(.*$", "", e.name))
                for e in (lines[MODULES_LINE].events if MODULES_LINE in lines else [])
            )
            starts = [r[0] for r in runs]
            found.setdefault(m.group(1), [])
            for e in (lines[rt.OPS_LINE].events if rt.OPS_LINE in lines else []):
                add(m.group(1), e, dict(e.stats), runs, starts)
        elif plane.name == rt.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith((rt.SPAN_PREFIX, SCOPE_PREFIX)):
                        host.append([int(e.start_ns), int(e.start_ns + e.duration_ns), e.name])
                        continue
                    # the CPU backend runs each device's operations on host
                    # threads: events with the operation's module and name
                    stats = dict(e.stats)
                    if "hlo_op" in stats and "hlo_module" in stats:
                        add(str(stats.get("device_ordinal", 0)), e, stats)
    devices, scopes = {}, {}
    for dev, ops in found.items():
        ops.sort(key=lambda o: o[0])
        devices[dev] = [op for op, _ in ops]
        scopes[dev] = resolve(devices[dev], kinds, [sc for _, sc in ops])
    return {"devices": devices, "scopes": scopes, "host": host}


class Recorder(rt.Recorder):
    """``reduce_trace.Recorder`` whose ``read`` keeps scopes and program spans."""

    def read(self) -> dict:
        import glob
        import os
        import shutil

        try:
            [pb] = glob.glob(os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb"))
            return extract(pb)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def resolve(ops, kinds: dict, known=None) -> list:
    """Each operation's scope: ``known``'s entry where it is not None, else
    its kind's one scope in ``kinds``, else, among its kind's scopes, that of
    the nearest operation in time whose scope is certain; where none is, the
    kind's scopes joined by ``|``."""
    known = known or [None] * len(ops)
    found = [{k} if k is not None else kinds.get(op[2], {UNSCOPED}) for op, k in zip(ops, known)]
    certain = sorted((op[0], next(iter(f))) for op, f in zip(ops, found) if len(f) == 1)
    starts = [c[0] for c in certain]
    out = []
    for op, f in zip(ops, found):
        if len(f) == 1:
            out.append(next(iter(f)))
            continue
        i = bisect.bisect_left(starts, op[0])
        near = sorted(
            (abs(certain[j][0] - op[0]), certain[j][1])
            for j in range(max(0, i - 64), min(len(certain), i + 64))
            if certain[j][1] in f
        )
        out.append(near[0][1] if near else "|".join(sorted(f)))
    return out


class Scoped(rt.Reduced):
    """``reduce_trace.Reduced`` with each operation's scope, the program's
    host spans in the idle labels, and the host-device clock offset.

    ``kinds`` maps an operation kind to its scopes where the trace carries
    none (defaults to the live executables', ``kind_table``)."""

    def __init__(self, doc: dict, devices=None, kinds=None):
        super().__init__(doc, devices=devices)
        known = doc.get("scopes", {})
        if kinds is None and any(d not in known for d in self.devices):
            kinds = kind_table(live_tables())
        self.scope = {d: known[d] if d in known else resolve(self.ops(d), kinds)
                      for d in self.devices}

    def has_scopes(self) -> bool:
        """Whether any operation in the window ran under a ``repro`` scope."""
        return any(
            sc != UNSCOPED and min(op[1], self.hi) > max(op[0], self.lo)
            for d in self.devices for op, sc in zip(self.ops(d), self.scope[d])
        )

    def scope_s(self, dev, scope: str) -> float:
        """Seconds in the window in which an operation of ``scope`` ran on ``dev``."""
        d = str(dev)
        return rt.total(rt.union(rt.clip(
            [(op[0], op[1]) for op, sc in zip(self.ops(d), self.scope[d]) if sc == scope],
            self.lo, self.hi,
        ))) / 1e9

    def share(self, scope: str) -> float:
        """Percent of busy time in ``scope``, averaged over the devices (a
        device that was never busy counts 0)."""
        return sum(
            100.0 * self.scope_s(d, scope) / busy if (busy := self.busy_s(d)) else 0.0
            for d in self.devices
        ) / len(self.devices)

    def clock_offset_ns(self) -> int:
        """Median over the program's sort calls of the device's first
        operation start minus the call's ``repro.sort.dispatch`` start; the
        first operation is the start of the busy stretch nearest the
        dispatch. 0 where the trace holds no dispatch span."""
        calls = [s for s, _, n in self.doc["host"] if n == DISPATCH and self.lo <= s <= self.hi]
        diffs = []
        for d in self.devices:
            starts = [s for s, _ in self._busy(d)]
            for c in calls:
                i = bisect.bisect_left(starts, c)
                near = [starts[j] for j in (i - 1, i) if 0 <= j < len(starts)]
                if near:
                    diffs.append(min(near, key=lambda s: abs(s - c)) - c)
        return int(statistics.median(diffs)) if diffs else 0

    def label(self, gap) -> str:
        """The span (not the window) that covers most of ``gap`` once moved
        onto the host clock; among equal covers the shortest, so the
        innermost span names the gap."""
        if not hasattr(self, "_spans"):
            self._spans = sorted((s, e, n) for s, e, n in self.doc["host"] if n != rt.WINDOW)
            self._starts = [s for s, _, _ in self._spans]
            self._longest = max((e - s for s, e, _ in self._spans), default=0)
            self._offset = self.clock_offset_ns()
        lo, hi = gap[0] - self._offset, gap[1] - self._offset
        best, best_key = rt.NO_SPAN, (0, 0)
        i = bisect.bisect_left(self._starts, lo - self._longest)
        for s, e, name in self._spans[i: bisect.bisect_left(self._starts, hi)]:
            key = (min(e, hi) - max(s, lo), -(e - s))
            if key[0] > 0 and key > best_key:
                best, best_key = name, key
        return best

    def breakdown(self, top: int = 10) -> dict:
        """``reduce_trace``'s breakdown plus ``scopes`` (device seconds by
        scope, averaged over the devices, largest first), the clock offset
        and ``program_spans`` (the program's host spans in the window, by
        name)."""
        out = super().breakdown(top)
        by = defaultdict(float)
        for d in self.devices:
            for scope in set(self.scope[d]):
                by[scope] += self.scope_s(d, scope) / len(self.devices)
        out["scopes"] = [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1]) if v > 0]
        out["clock_offset_ns"] = self.clock_offset_ns()
        spans = defaultdict(int)
        for s, _, name in self.doc["host"]:
            if name.startswith(SCOPE_PREFIX) and self.lo <= s <= self.hi:
                spans[name] += 1
        out["program_spans"] = dict(sorted(spans.items()))
        return out


_SCOPED = weakref.WeakKeyDictionary()


def of(run):
    """The scoped reduction of a run's trace, or None where there is no trace
    or no operation of the window ran under a ``repro`` scope (a program
    without named scopes)."""
    t = getattr(run, "trace", None)
    if t is None:
        return None
    if isinstance(t, Scoped):
        scoped = t
    elif t in _SCOPED:
        scoped = _SCOPED[t]
    else:
        scoped = _SCOPED[t] = Scoped(t.doc, devices=t.devices)
    return scoped if scoped.has_scopes() else None
