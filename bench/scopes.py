"""The program's own marks in a profiler trace: device time per scope and
device-idle time per host span.

``bench/trace.py`` reduces a trace to the benchmark's spans. This module
reads the same ``.xplane.pb`` for what the program marks itself with
``repro.trace``: the ``op_name`` path of every device op, which holds the
device scopes it was traced under (``ops.forest_sample``,
``forest.cell_trees``, ...), and the host spans named ``repro.*``.

A device op's path is the event's own ``tf_op`` stat where the event carries
one; otherwise it is looked up by (module, op) in the optimized HLO that the
trace keeps in its ``/host:metadata`` plane. A program loaded from the
persistent compilation cache has its HLO kept there under another program
id: its ops are then looked up in every module of the same name, and a path
is taken only where those modules agree on it. Scope time is the union of the
intervals of the ops whose path holds the scope (a ``while`` holds its
body's ops), per device; an idle gap goes to the innermost ``repro.`` span
that holds its midpoint.
"""
from __future__ import annotations

import bisect
import collections
import re
from dataclasses import dataclass, field
from pathlib import Path

from bench import trace as tracing

PROGRAM_PREFIX = "repro."
UNSCOPED = "(unscoped)"
OUTSIDE = "outside"
SCOPE = re.compile(r"[A-Za-z_]\w*\.\w+")   # a dotted scope name in a path
HLO_STAT = "Hlo Proto"


@dataclass
class Marks:
    """Intervals in seconds on the profiler's clock."""
    ops: dict = field(default_factory=dict)     # device -> [(s, e, op_name)]
    spans: list = field(default_factory=list)   # [(s, e, name)], repro.*


# -- a minimal protobuf reader: the HLO the trace keeps ---------------------
def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one protobuf message: the
    bytes of a length-delimited field, the integer of any other."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = bytes(buf[i:i + size]), i + size
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, value


def _get(buf, number: int):
    return next((v for n, v in _fields(buf) if n == number), b"")


def _hlo_op_names(hlo_proto) -> dict[str, str]:
    """Instruction name -> ``op_name`` over every computation of an
    ``HloProto`` (module 1; computations 3; instructions 2; name 1;
    metadata 7, its op_name 2)."""
    out = {}
    for n, comp in _fields(_get(hlo_proto, 1)):
        if n != 3:
            continue
        for m, ins in _fields(comp):
            if m == 2:
                op_name = bytes(_get(_get(ins, 7), 2)).decode()
                if op_name:
                    out[bytes(_get(ins, 1)).decode()] = op_name
    return out


def hlo_op_names(xspace: bytes) -> dict[str, dict[str, str]]:
    """Module name (``jit_f(3)``) -> instruction -> ``op_name``, from the
    ``Hlo Proto`` stats of the ``/host:metadata`` plane (XSpace planes 1;
    XPlane name 2, event_metadata 4, stat_metadata 5; XEventMetadata name 2,
    stats 5; XStat metadata_id 1, bytes_value 6)."""
    modules = {}
    for n, plane in _fields(memoryview(xspace)):
        if n != 1 or bytes(_get(plane, 2)) != b"/host:metadata":
            continue
        entries = collections.defaultdict(list)
        for k, entry in _fields(plane):
            if k in (4, 5):
                entries[k].append(_get(entry, 2))
        hlo_ids = {v for meta in entries[5] for f, v in _fields(meta)
                   if f == 1 and bytes(_get(meta, 2)).decode() == HLO_STAT}
        for meta in entries[4]:
            name = bytes(_get(meta, 2)).decode()
            for f, stat in _fields(meta):
                if f == 5 and _get(stat, 1) in hlo_ids:
                    modules[name] = _hlo_op_names(_get(stat, 6))
    return modules


# -- load and reduce ---------------------------------------------------------
def load(trace_dir: Path, platform: str) -> Marks:
    """Read the newest ``.xplane.pb`` under ``trace_dir``: the device ops of
    ``bench.trace.load``, each with its ``op_name`` path, and the ``repro.``
    host spans."""
    import jax

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    names = hlo_op_names(files[-1].read_bytes())
    data = jax.profiler.ProfileData.from_file(str(files[-1]))
    marks = Marks()
    for plane in data.planes:
        device = plane.name.startswith("/device:TPU:") and platform == "tpu"
        modules = []
        if device:
            modules = sorted(
                (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                for line in plane.lines
                if line.name in tracing.TPU_MODULE_LINES
                for ev in line.events)
        starts = [m[0] for m in modules]
        for line in plane.lines:
            cpu_op = (platform == "cpu" and plane.name.startswith("/host:")
                      and line.name.startswith("tf_XLA"))
            for ev in line.events:
                s = ev.start_ns * 1e-9
                iv = (s, s + ev.duration_ns * 1e-9)
                if device and line.name in tracing.TPU_OP_LINES:
                    k = bisect.bisect_right(starts, ev.start_ns) - 1
                    mod = modules[k][2] if k >= 0 else ""
                    path = _op_name(ev, names, mod)
                    marks.ops.setdefault(plane.name, []).append((*iv, path))
                elif plane.name.startswith("/host:"):
                    if ev.name.startswith(PROGRAM_PREFIX):
                        marks.spans.append((*iv, ev.name))
                    elif (cpu_op and not ev.name.startswith("end: ")
                          and "::" not in ev.name):
                        path = _op_name(ev, names, "")
                        marks.ops.setdefault("/host:CPU", []).append(
                            (*iv, path))
    return marks


def _op_name(ev, names: dict, module: str) -> str:
    stats = {k: str(v) for k, v in ev.stats}
    if stats.get("tf_op"):
        return stats["tf_op"]
    op = stats.get("hlo_op") or tracing._short(ev.name).lstrip("%")
    if "hlo_module" in stats:
        module = f"{stats['hlo_module']}({stats.get('program_id', '')})"
    return op_path(names, module, op)


def op_path(names: dict, module: str, op: str) -> str:
    """The ``op_name`` of ``op`` in ``module`` (``jit_f(3)``); where the
    trace keeps no HLO under that id, the one path that the modules of the
    same name agree on, else ``""``."""
    if module in names:
        return names[module].get(op, "")
    short = tracing._short(module)
    paths = {table[op] for name, table in names.items()
             if tracing._short(name) == short and op in table}
    return paths.pop() if len(paths) == 1 else ""


def scopes_of(path: str) -> set[str]:
    """The program's scopes in an ``op_name`` path: its dotted names."""
    return set(SCOPE.findall(path))


def reduce(tr: tracing.Trace, marks: Marks, top: int = 10) -> dict:
    """Device seconds per scope and idle seconds per ``repro.`` span inside
    the benchmark window of ``tr`` (as ``bench.trace.reduce`` finds it),
    averaged over the devices that ran operations, and their breakdowns."""
    windows = [(s, e) for s, e, n in tr.spans if n == tracing.WINDOW]
    if not windows or not marks.ops:
        raise ValueError("trace holds no benchmark window or no device "
                         "operation")
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    n_dev = len(marks.ops)
    scope_s: collections.Counter = collections.Counter()
    unscoped = 0.0
    idle: collections.Counter = collections.Counter()
    spans = collections.defaultdict(list)
    for iv in marks.spans:
        if lo <= iv[0] and iv[1] <= hi:
            spans[iv[2]].append(iv)
    by_name = {name: ([iv[0] for iv in sorted(ivs)], sorted(ivs))
               for name, ivs in spans.items()}
    for ops in marks.ops.values():
        by_scope = collections.defaultdict(list)
        for op in ops:
            for name in scopes_of(op[2]):
                by_scope[name].append(op)
        scoped = []
        for name, ivs in by_scope.items():
            cover = tracing.clip(tracing.union(ivs), lo, hi)
            scope_s[name] += tracing.measure(cover) / n_dev
            scoped += ivs
        busy = tracing.clip(tracing.union(ops), lo, hi)
        unscoped += (tracing.measure(busy) - tracing.overlap(
            busy, tracing.clip(tracing.union(scoped), lo, hi))) / n_dev
        for s, e in tracing.gaps(busy, lo, hi):
            where = tracing._innermost(by_name, 0.5 * (s + e))
            idle[where] += (e - s) / n_dev
    program_gap_s = {k: v for k, v in idle.items() if k != OUTSIDE}
    return {
        "scope_device_s": dict(scope_s),
        "program_gap_s": program_gap_s,
        "breakdown": {
            "device_scopes": [[n, t] for n, t in scope_s.most_common(top)]
            + [[UNSCOPED, unscoped]],
            "program_gaps": [[n, t] for n, t in idle.most_common(top)],
        },
    }
