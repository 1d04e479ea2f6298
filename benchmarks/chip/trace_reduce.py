"""From a profiler trace to the device numbers.

The reduction works on a plain structure so that a test can build one by hand:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops", "events": [(name, start_ns, duration_ns), ...]}]}]}

:func:`load_xplane` makes that structure from an ``.xplane.pb`` with nothing
but ``jax.profiler.ProfileData``.  Device planes are those named
``/device:TPU:<n>``; on each, the line ``XLA Ops`` holds one event per
operation that ran and ``XLA Modules`` one per executable.  Host planes carry
the program's own ``sheeprl/<phase>`` spans (``Diagnostics.span``), on the
same clock.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATION_PREFIX = "sheeprl/"
UNATTRIBUTED = "unattributed"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [(ev.name, int(ev.start_ns), int(ev.duration_ns)) for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def merge_intervals(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Union of half-open ``(start, end)`` intervals, sorted, non-overlapping."""
    merged: List[List[int]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def _overlap(merged: Sequence[Tuple[int, int]], start: int, end: int) -> int:
    return sum(max(0, min(e, end) - max(s, start)) for s, e in merged)


def _line(plane: Dict[str, Any], name: str) -> List[Event]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def device_planes(trace: Dict[str, Any]) -> List[Dict[str, Any]]:
    return [p for p in trace["planes"] if p["name"].startswith(DEVICE_PREFIX)]


def annotations(trace: Dict[str, Any]) -> List[Event]:
    """The program's host spans (``sheeprl/...``), from every host plane."""
    out: List[Event] = []
    for plane in trace["planes"]:
        if plane["name"].startswith(DEVICE_PREFIX):
            continue
        for line in plane["lines"]:
            out.extend(ev for ev in line["events"] if ev[0].startswith(ANNOTATION_PREFIX))
    return out


def owner_segments(spans: Iterable[Tuple[str, int, int]]) -> List[Tuple[int, int, str]]:
    """The host timeline cut at every edge of the ``(name, start, end)`` spans,
    each piece given to the innermost span over it: the one that started last
    (the shorter of two that start together).  Pieces under no span are left out."""
    spans = sorted(spans, key=lambda sp: sp[1])
    bounds = sorted({t for _, s, e in spans for t in (s, e)})
    segments: List[Tuple[int, int, str]] = []
    active: List[Tuple[str, int, int]] = []
    nxt = 0
    for a, b in zip(bounds, bounds[1:]):
        while nxt < len(spans) and spans[nxt][1] <= a:
            active.append(spans[nxt])
            nxt += 1
        active = [sp for sp in active if sp[2] > a]
        if active:
            owner = max(active, key=lambda sp: (sp[1], -sp[2]))
            segments.append((a, b, owner[0]))
    return segments


def idle_by_span(gaps: Iterable[Tuple[int, int]], segments: Sequence[Tuple[int, int, str]]) -> Dict[str, int]:
    """Nanoseconds of the gaps under each span's own pieces; the rest ``unattributed``."""
    starts = [seg[0] for seg in segments]
    out: Dict[str, int] = {}
    for gs, ge in gaps:
        left = ge - gs
        for a, b, name in segments[max(0, bisect.bisect_right(starts, gs) - 1):]:
            if a >= ge:
                break
            part = min(b, ge) - max(a, gs)
            if part > 0:
                out[name] = out.get(name, 0) + part
                left -= part
        if left > 0:
            out[UNATTRIBUTED] = out.get(UNATTRIBUTED, 0) + left
    return out


def short_name(name: str, limit: int = 96) -> str:
    """An operation's name as the trace gives it is its whole HLO line: keep the
    name and the start of what it computes."""
    head, _, rest = name.partition(" = ")
    text = f"{head} {rest}" if rest else head
    return text if len(text) <= limit else text[: limit - 3] + "..."


def reduce_trace(trace: Dict[str, Any], module_match: str = "train_step", top: int = 10) -> Dict[str, Any]:
    """Busy and idle time of the device and the time of one executable.

    The span is what the device's own events cover, first start to last end,
    averaged over the device planes.  ``module_match`` names the executable
    (the family's ``executables["train_step"]``); an execution of it at the
    trace's first or last operation is left out, since the trace may have cut
    it there.  The idle gaps go to the innermost of the program's spans the
    host was in.  Returns ``None`` values, never zeros, for what the trace
    does not hold.
    """
    planes = device_planes(trace)
    if not planes:
        raise ValueError("the trace has no /device:TPU plane: no operation ran on the device")
    busy_s, span_s, module_busy_s, module_runs = [], [], 0.0, 0
    op_seconds: Dict[str, float] = {}
    gaps: List[Tuple[int, int]] = []
    for plane in planes:
        ops = _line(plane, OPS_LINE)
        if not ops:
            continue
        merged = merge_intervals((s, s + d) for _, s, d in ops)
        first, last = merged[0][0], merged[-1][1]
        busy_s.append(sum(e - s for s, e in merged) / 1e9)
        span_s.append((last - first) / 1e9)
        for name, _, dur in ops:
            op_seconds[name] = op_seconds.get(name, 0.0) + dur / 1e9
        gaps.extend((merged[i][1], merged[i + 1][0]) for i in range(len(merged) - 1))
        for name, start, dur in _line(plane, MODULES_LINE):
            if module_match in name and start > first and start + dur < last:
                module_busy_s += _overlap(merged, start, start + dur) / 1e9
                module_runs += 1
    if not busy_s:
        raise ValueError("no device plane of the trace has an 'XLA Ops' line with events")
    n = len(busy_s)
    segments = owner_segments((name, start, start + dur) for name, start, dur in annotations(trace))
    idle = {name: ns / 1e9 for name, ns in idle_by_span(gaps, segments).items()}
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "busy_s": sum(busy_s) / n,
        "window_s": sum(span_s) / n,
        "idle_pct": 100.0 * (1.0 - sum(busy_s) / sum(span_s)) if sum(span_s) > 0 else None,
        "module_runs": module_runs,
        "module_device_ms": 1e3 * module_busy_s / module_runs if module_runs else None,
        "device_ops": [[short_name(k), v / n] for k, v in sorted(op_seconds.items(), key=lambda kv: -kv[1])[:top]],
        "idle_by_span": [[k, v / n] for k, v in sorted(idle.items(), key=lambda kv: -kv[1])[:top]],
        "longest_gap_ms": [(e - s) / 1e6 for s, e in longest],
    }

