"""Profiler trace -> device busy time, per-program device time, and the
device's idle gaps named by the benchmark's host spans.

`load` reads the ``.xplane.pb`` the JAX profiler writes into plain
event tuples; `reduce` works on those tuples alone, so a small recorded
trace (``tests/data``) checks the reduction without a chip.

An event is ``(plane, line, name, start_ns, dur_ns)``.  Device events
are on planes named ``/device:TPU:<n>``; their ``XLA Modules`` line
holds one event per program run, and busy time is the union of those
runs.  The ``XLA Ops`` line (one event per operation) is only counted:
a sequential scan emits millions of op events, and once the TPU
profiler's buffer is full (6,218,046 and 6,291,386 op events seen on a
v5e) it drops every later device event, programs included.  A trace
with `OPS_BUFFER_EVENTS` op events or more, or whose device events stop
more than `TAIL_SHARE` of the window before its end, is marked
truncated, and only the part its device events cover counts as traced;
so is a trace of the first seconds of the window.
Host spans are the benchmark's own ``jax.profiler.TraceAnnotation``
events, named ``bench/<span>``; the traced window is the
``bench/traced`` span (a run traces the first seconds of its window),
else, in a trace without it, the ``bench/window`` span.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, str, str, int, int]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
OPS_BUFFER_EVENTS = 6_000_000
TAIL_SHARE = 0.25
SPAN_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"
TRACED_SPAN = "bench/traced"


def load(trace_dir: str) -> Tuple[List[Event], Dict[str, int]]:
    """The program runs and benchmark spans of the newest ``.xplane.pb``
    under ``trace_dir``, and the op-event count of each device plane."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return [], {}
    data = ProfileData.from_file(files[-1])
    out, ops = [], {}
    for plane in data.planes:
        device = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if device and line.name == OPS_LINE:
                ops[plane.name] = sum(1 for _ in line.events)
            elif not device or line.name == MODULES_LINE:
                for ev in line.events:
                    if device or ev.name.startswith(SPAN_PREFIX):
                        out.append((plane.name, line.name, ev.name,
                                    int(ev.start_ns), int(ev.duration_ns)))
    return out, ops


def program_name(event_name: str) -> str:
    """Device module event name -> the jitted function's name:
    ``jit__msm_many_impl(123)`` -> ``_msm_many_impl``."""
    name = event_name.split("(", 1)[0].strip()
    return name[4:] if name.startswith("jit_") else name


def union_ns(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


@dataclasses.dataclass
class Summary:
    """What the per-layer readers take from one traced window."""
    window_s: float
    busy_s: float                       # averaged over the chips traced
    n_devices: int
    program_s: Dict[str, float]         # jitted fn name -> device seconds
    gaps: List[Tuple[str, float]]       # (host span open, idle seconds)
    truncated: bool                     # covers only part of the window

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(events: Sequence[Event],
           op_counts: Optional[Dict[str, int]] = None) -> Optional[Summary]:
    """Busy union, per-program time and named idle gaps inside the
    benchmark's ``bench/window`` span (cut to the part a truncated trace
    covers).  None when no device program ran in it."""
    dev = [(s, s + d, program_name(n), p) for p, l, n, s, d in events
           if DEVICE_PLANE.match(p) and l == MODULES_LINE]
    if not dev:
        return None
    windows = {n: (s, s + d) for p, l, n, s, d in events
               if n in (WINDOW_SPAN, TRACED_SPAN)}
    lo, hi = windows.get(TRACED_SPAN) or windows.get(WINDOW_SPAN) or (
        min(e[0] for e in dev), max(e[1] for e in dev))
    last = max(e[1] for e in dev)
    truncated = (TRACED_SPAN in windows
                 or any(n >= OPS_BUFFER_EVENTS
                        for n in (op_counts or {}).values())
                 or hi - last > TAIL_SHARE * (hi - lo))
    if truncated:
        hi = min(hi, last)
    spans = sorted((s, s + d, n[len(SPAN_PREFIX):])
                   for p, l, n, s, d in events
                   if n.startswith(SPAN_PREFIX)
                   and n not in (WINDOW_SPAN, TRACED_SPAN))
    ends = sorted((e, name) for s, e, name, _ in dev)
    ends_ns = [e for e, _ in ends]
    planes = sorted({e[3] for e in dev})
    busy_total, gaps = 0, []
    for plane in planes:
        busy = _clip(union_ns([(s, e) for s, e, _, p in dev if p == plane]),
                     lo, hi)
        busy_total += sum(e - s for s, e in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((_gap_name(s, e, spans, ends, ends_ns),
                             (e - s) * 1e-9))
    if busy_total == 0:
        return None
    prog: Dict[str, float] = {}
    for s, e, name, _ in dev:
        if e > lo and s < hi:
            prog[name] = prog.get(name, 0.0) + (min(e, hi) - max(s, lo)) * 1e-9
    return Summary(window_s=(hi - lo) * 1e-9,
                   busy_s=busy_total * 1e-9 / len(planes),
                   n_devices=len(planes), program_s=prog, gaps=gaps,
                   truncated=truncated)


def _gap_name(s: int, e: int, spans, ends, ends_ns) -> str:
    """The benchmark span open at the gap's midpoint (the innermost, if
    several), and the program that ended last before the gap."""
    mid = (s + e) // 2
    open_ = [(ss, name) for ss, se, name in spans if ss <= mid < se]
    span = max(open_)[1] if open_ else "unannotated"
    i = bisect.bisect_right(ends_ns, s) - 1
    return f"{span} after {ends[i][1]}" if i >= 0 else span


def top(pairs, n: int = 10) -> List[List]:
    """Sum seconds by name and keep the ``n`` largest, largest first."""
    acc: Dict[str, float] = {}
    for name, secs in pairs:
        acc[name] = acc.get(name, 0.0) + secs
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]
