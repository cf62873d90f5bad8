"""From a profiler trace to device busy time, device time inside the
benchmark's host spans, and the breakdown.

The device's busy time is the union of the intervals in which an operation
ran on it (the ``XLA Ops`` line of a TPU plane). The benchmark marks its own
host spans with ``jax.profiler.TraceAnnotation`` (``bench.window``,
``bench.step``, ``bench.build``, ``bench.drain``); device time inside a span
name is the part of the busy union that those spans cover. Idle gaps are
the complement of the busy union in the window, each named after the
innermost benchmark span that holds its midpoint.
"""
from __future__ import annotations

import bisect
import collections
import re
from dataclasses import dataclass, field
from pathlib import Path

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
TPU_OP_LINES = ("XLA Ops",)
TPU_MODULE_LINES = ("XLA Modules",)


@dataclass
class Trace:
    """Intervals in seconds on the profiler's clock."""
    device_ops: dict = field(default_factory=dict)   # device -> [(s, e, name)]
    modules: dict = field(default_factory=dict)      # device -> [(s, e, name)]
    spans: list = field(default_factory=list)        # [(s, e, name)]


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, disjoint cover of the given (start, end, ...) intervals."""
    out: list[list[float]] = []
    for s, e, *_ in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(merged) -> float:
    return sum(e - s for s, e in merged)


def clip(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in merged if e > lo and s < hi]


def overlap(a, b) -> float:
    """Measure of the intersection of two sorted disjoint covers."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    """The complement of a disjoint cover inside [lo, hi]."""
    out, t = [], lo
    for s, e in clip(merged, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def load(trace_dir: Path, platform: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``. On a TPU the
    device intervals are its ``XLA Ops`` events; on the CPU (used to test
    this reduction) they are the XLA thunks run by the CPU client's
    threads."""
    import jax

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(str(files[-1]))
    tr = Trace()
    for plane in data.planes:
        device = plane.name.startswith("/device:TPU:") and platform == "tpu"
        for line in plane.lines:
            for ev in line.events:
                s = ev.start_ns * 1e-9
                iv = (s, s + ev.duration_ns * 1e-9, ev.name)
                if device and line.name in TPU_OP_LINES:
                    tr.device_ops.setdefault(plane.name, []).append(iv)
                elif device and line.name in TPU_MODULE_LINES:
                    tr.modules.setdefault(plane.name, []).append(iv)
                elif plane.name.startswith("/host:"):
                    if ev.name.startswith(SPAN_PREFIX):
                        tr.spans.append(iv)
                    elif (platform == "cpu" and line.name.startswith("tf_XLA")
                          and not ev.name.startswith("end: ")
                          and "::" not in ev.name):
                        tr.device_ops.setdefault("/host:CPU", []).append(iv)
    return tr


def _short(name: str) -> str:
    """An op or module name without its HLO text and program hash."""
    return re.sub(r"\(\d+\)$", "", name.split(" = ")[0])


def _innermost(by_name: dict, t: float) -> str:
    """The narrowest span holding ``t``; spans of one name never overlap."""
    best, width = "outside", float("inf")
    for name, (starts, ivs) in by_name.items():
        k = bisect.bisect_right(starts, t) - 1
        if k >= 0 and ivs[k][1] >= t and ivs[k][1] - ivs[k][0] < width:
            best, width = name, ivs[k][1] - ivs[k][0]
    return best


def reduce(tr: Trace, top: int = 10) -> dict:
    """Window, busy time averaged over the devices that ran operations,
    device seconds and counts per span name, and the breakdown."""
    windows = [(s, e) for s, e, n in tr.spans if n == WINDOW]
    if not windows or not tr.device_ops:
        raise ValueError("trace holds no benchmark window or no device "
                         "operation")
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    spans = [iv for iv in tr.spans if lo <= iv[0] and iv[1] <= hi]
    by_name = collections.defaultdict(list)
    for iv in spans:
        by_name[iv[2]].append(iv)
    busy_per_device = {d: clip(union(ops), lo, hi)
                       for d, ops in tr.device_ops.items()}
    n_dev = len(busy_per_device)
    busy_s = sum(measure(b) for b in busy_per_device.values()) / n_dev
    span_device_s = {
        name: sum(overlap(b, union(ivs)) for b in busy_per_device.values())
        / n_dev for name, ivs in by_name.items()}

    op_time: collections.Counter = collections.Counter()
    for d, ops in tr.device_ops.items():
        mods = sorted(tr.modules.get(d, []))
        starts = [m[0] for m in mods]
        for s, e, name in ops:
            if not (lo <= s <= hi):
                continue
            k = bisect.bisect_right(starts, s) - 1
            mod = mods[k][2] if k >= 0 and mods[k][1] >= e else ""
            op = _short(name)
            op_time[f"{_short(mod)}/{op}" if mod else op] += (e - s) / n_dev
    sorted_spans = {name: ([iv[0] for iv in sorted(ivs)], sorted(ivs))
                    for name, ivs in by_name.items()}
    idle: collections.Counter = collections.Counter()
    for b in busy_per_device.values():
        for s, e in gaps(b, lo, hi):
            idle[_innermost(sorted_spans, 0.5 * (s + e))] += (e - s) / n_dev
    return {
        "window_s": hi - lo,
        "busy_s": busy_s,
        "span_device_s": span_device_s,
        "span_count": {name: len(ivs) for name, ivs in by_name.items()},
        "breakdown": {
            "device_ops": [[n, t] for n, t in op_time.most_common(top)],
            "idle_gaps": [[n, t] for n, t in idle.most_common(top)],
        },
    }
