"""From a profiler trace to the program's own names: executables, scopes, spans.

``trace_reduce`` gives the device's busy and idle time and the time of one
executable.  This reduction reads what the program itself names: its jitted
functions (``jit_<name>`` on the ``XLA Modules`` line), the ``jax.named_scope``
each operation was traced under, and the loop's ``sheeprl/<phase>`` spans,
which ``Diagnostics.span`` puts on the host plane of the same trace.  Which
executables and scopes an algorithm has, its family says (``executables``,
``train_step_scopes`` in ``families/<family>.py``).  It works
on a plain structure, ``trace_reduce``'s with one more field per event, so
that a test can build one by hand:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops", "events": [(name, start_ns, duration_ns, op_path), ...]},
                           {"name": "XLA Modules", "events": [(name, start_ns, duration_ns, ""), ...]}]},
                {"name": "/host:CPU",
                 "lines": [{"name": "python", "events": [("sheeprl/rollout", start_ns, duration_ns, ""), ...]}]}]}

``op_path`` is the operation's ``op_name`` as JAX wrote it into the HLO
(``jit(train_step)/jit(main)/transpose(jvp(rssm_scan))/while/body/...``): on a
v5e the ``XLA Ops`` events carry it as their ``tf_op`` stat (PERF.md §6).
Every reader of it returns ``None``, and none raises, where the trace lacks
what it looks for: the parent of the PR that added the names has none of them.
"""

from __future__ import annotations

import bisect
import functools
import os
import re
from typing import Any, Dict, List, Mapping, Optional, Pattern, Sequence, Tuple

from benchmarks.chip import run as command
from benchmarks.chip.trace_reduce import (
    ANNOTATION_PREFIX as SPAN_PREFIX, DEVICE_PREFIX, MODULES_LINE, OPS_LINE, UNATTRIBUTED, _line, device_planes,  # UNATTRIBUTED: for the readers
    find_xplane, idle_by_span, merge_intervals, owner_segments,
)

ITERATION_SPAN = SPAN_PREFIX + "rollout"
FETCH_SPAN = SPAN_PREFIX + "rollout/action-fetch"
UNSCOPED = "unscoped"
SCOPED_EXECUTABLE = "train_step"  # the executable whose operations are split by scope
# Nothing of the benchmark reads this: the scopes of a train step are its
# family's (``train_step_scopes``).  The name, and ``scope_of``'s one-argument
# form, stay for one test of the program outside the benchmark's directories
# (``tests/test_diagnostics/test_profiler_spans.py``), until it can be
# repointed (PERF.md section 7)
SCOPES = ("encoder", "rssm_scan", "decoder_heads", "imagination", "behaviour_losses", "optim")
# the counters of ``/metrics`` that the per-step and per-call readers divide by
ENV_STEPS = "sheeprl_env_steps_total"
TRAIN_CALLS = 'sheeprl_instrumented_calls_total{fn="train_step"}'
# the stats of an operation's metadata that may carry its op_name
PATH_STATS = ("tf_op",)

Event = Tuple[str, int, int, str]
Interval = Tuple[int, int]


# --------------------------------------------------------------------------
# loading
# --------------------------------------------------------------------------
def _xspace_class():
    """The message class of an ``.xplane.pb`` (``tensorflow.profiler.XSpace``),
    declared here with the fields this reduction reads and no others: the
    protobuf runtime skips the rest.  ``jax.profiler.ProfileData`` gives an
    event's own stats but not those of its metadata, and on the TPU the
    ``tf_op`` of an operation is a stat of its metadata."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    package = "bench.xplane"
    field = descriptor_pb2.FieldDescriptorProto
    file = descriptor_pb2.FileDescriptorProto(name="bench/xplane_subset.proto", package=package, syntax="proto3")
    messages = {
        "XSpace": [("planes", 1, "XPlane", True)],
        "XPlane": [("name", 2, field.TYPE_BYTES, False), ("lines", 3, "XLine", True),
                   ("event_metadata", 4, "EventMetadataEntry", True), ("stat_metadata", 5, "StatMetadataEntry", True)],
        "EventMetadataEntry": [("key", 1, field.TYPE_INT64, False), ("value", 2, "XEventMetadata", False)],
        "StatMetadataEntry": [("key", 1, field.TYPE_INT64, False), ("value", 2, "XStatMetadata", False)],
        "XLine": [("name", 2, field.TYPE_BYTES, False), ("timestamp_ns", 3, field.TYPE_INT64, False), ("events", 4, "XEvent", True)],
        "XEvent": [("metadata_id", 1, field.TYPE_INT64, False), ("offset_ps", 2, field.TYPE_INT64, False),
                   ("duration_ps", 3, field.TYPE_INT64, False)],
        "XEventMetadata": [("name", 2, field.TYPE_BYTES, False), ("stats", 5, "XStat", True)],
        "XStatMetadata": [("name", 2, field.TYPE_BYTES, False)],
        "XStat": [("metadata_id", 1, field.TYPE_INT64, False), ("str_value", 5, field.TYPE_BYTES, False),
                  ("ref_value", 7, field.TYPE_UINT64, False)],
    }
    for name, fields in messages.items():
        message = file.message_type.add(name=name)
        for fname, number, ftype, repeated in fields:
            entry = message.field.add(name=fname, number=number,
                                      label=field.LABEL_REPEATED if repeated else field.LABEL_OPTIONAL)
            if isinstance(ftype, str):
                entry.type, entry.type_name = field.TYPE_MESSAGE, f".{package}.{ftype}"
            else:
                entry.type = ftype
    pool = descriptor_pool.DescriptorPool()  # its own: the process may hold the full schema too
    pool.Add(file)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName(f"{package}.XSpace"))


def load_spans(path: str) -> Dict[str, Any]:
    """The plain structure from an ``.xplane.pb``: of the device planes the
    ``XLA Ops`` and ``XLA Modules`` lines, of the host planes the program's
    own spans and nothing else."""
    space = _xspace_class()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    planes = []
    for plane in space.planes:
        plane_name = plane.name.decode(errors="replace")
        device = plane_name.startswith(DEVICE_PREFIX)
        stat_names = {entry.key: entry.value.name.decode(errors="replace") for entry in plane.stat_metadata}
        path_keys = {key for key, name in stat_names.items() if name in PATH_STATS}
        names: Dict[int, Tuple[str, str]] = {}  # metadata id -> (event name, op path)
        for entry in plane.event_metadata:
            op_path = ""
            for stat in entry.value.stats if device else ():
                if stat.metadata_id in path_keys:
                    op_path = stat.str_value.decode(errors="replace") or stat_names.get(stat.ref_value, "")
                    break
            names[entry.key] = (entry.value.name.decode(errors="replace"), op_path)
        lines = []
        for line in plane.lines:
            line_name = line.name.decode(errors="replace")
            if device and line_name not in (OPS_LINE, MODULES_LINE):
                continue
            events: List[Event] = []
            for ev in line.events:
                name, op_path = names.get(ev.metadata_id, ("", ""))
                if device or name.startswith(SPAN_PREFIX):
                    events.append((name, line.timestamp_ns + ev.offset_ps // 1000, ev.duration_ps // 1000, op_path))
            if events:
                lines.append({"name": line_name, "events": events})
        planes.append({"name": plane_name, "lines": lines})
    return {"planes": planes}


def program_spans(trace: Dict[str, Any]) -> List[Tuple[str, int, int]]:
    """``(name, start, end)`` of every ``sheeprl/...`` span of every host plane."""
    out = []
    for plane in trace["planes"]:
        if plane["name"].startswith(DEVICE_PREFIX):
            continue
        for line in plane["lines"]:
            out.extend((ev[0], ev[1], ev[1] + ev[2]) for ev in line["events"] if ev[0].startswith(SPAN_PREFIX))
    return out


# --------------------------------------------------------------------------
# executables and scopes
# --------------------------------------------------------------------------
def _is_execution(event_name: str, executable: str) -> bool:
    """``jit_step(123)`` is an execution of ``jit_step``."""
    return event_name.split("(", 1)[0] == executable


def _overlap(merged: Sequence[Interval], starts: Sequence[int], start: int, end: int) -> int:
    """Length of ``[start, end)`` covered by the sorted, disjoint ``merged``."""
    total = 0
    for s, e in merged[max(0, bisect.bisect_right(starts, start) - 1):]:
        if s >= end:
            break
        total += max(0, min(e, end) - max(s, start))
    return total


@functools.lru_cache(maxsize=None)
def _scope_re(scope: str) -> Pattern[str]:
    return re.compile(r"(?:^|[/(])" + re.escape(scope) + r"(?:[)/]|$)")


def scope_of(op_path: str, scopes: Sequence[str] = SCOPES) -> str:
    """The first of ``scopes`` the path names as a component, bare or inside
    ``jvp(...)`` / ``transpose(jvp(...))``: forward and backward together."""
    for scope in scopes:
        if _scope_re(scope).search(op_path):
            return scope
    return UNSCOPED


def _outermost_ns_by_scope(ops: Sequence[Event], op_starts: Sequence[int], start: int, end: int,
                           scopes: Sequence[str]) -> Dict[str, int]:
    """Busy nanoseconds of ``[start, end)`` by scope, from ``ops`` sorted by
    start (the longer first of two that start together).  Each outermost event
    (one not inside another of the line: a ``while`` holds its body's
    operations) gives the time it adds to what the earlier ones covered, so the
    buckets sum to the union of the events, the executable's busy time.  An
    outermost event whose own path names no scope (a ``while`` has no path at
    all on the v5e) takes the first scope an event nested in it names."""
    outermost: List[List[Any]] = []  # [scope, nanoseconds]
    covered = start
    for _, s, d, path in ops[bisect.bisect_left(op_starts, start):bisect.bisect_left(op_starts, end)]:
        e = min(s + d, end)
        scope = scope_of(path, scopes)
        if s < covered and outermost and outermost[-1][0] == UNSCOPED:
            outermost[-1][0] = scope
        if e > covered:
            outermost.append([scope, e - max(s, covered)])
            covered = e
    by_scope: Dict[str, int] = {}
    for scope, ns in outermost:
        by_scope[scope] = by_scope.get(scope, 0) + ns
    return by_scope


# --------------------------------------------------------------------------
# the reduction
# --------------------------------------------------------------------------
def reduce_spans(trace: Dict[str, Any], executables: Mapping[str, str], scopes: Sequence[str]) -> Dict[str, Any]:
    """What the trace says under the program's names.

    ``module_ms``: device-busy milliseconds per execution of each of the
    family's ``executables`` (role -> ``jit_<name>``) that ran, by role, an
    execution at the trace's first or last operation left out (the trace may
    have cut it).  ``scope_ms``: inside the train step, milliseconds per run
    by scope (``scopes`` and ``unscoped``), summing to its ``module_ms``;
    ``None`` where no operation names a scope.
    ``idle_ms``: the device's idle gaps (between the merged ``XLA Ops``
    intervals, as ``reduce_trace`` finds them) per iteration of the loop by
    the span the host was in, the rest ``unattributed``; iterations run from
    one ``sheeprl/rollout`` start to the next, and the gaps outside the first
    and the last start are left out; ``None`` with fewer than two such
    starts."""
    planes = [p for p in device_planes(trace) if _line(p, OPS_LINE)]
    module_ns = {m: 0 for m in executables}
    module_runs = {m: 0 for m in executables}
    scope_ns: Dict[str, int] = {}
    spans = program_spans(trace)
    rollouts = sorted(s for name, s, _ in spans if name == ITERATION_SPAN)
    segments = owner_segments(spans)
    idle_ns: Dict[str, int] = {}
    for plane in planes:
        ops = sorted(_line(plane, OPS_LINE), key=lambda ev: (ev[1], -ev[2]))
        op_starts = [ev[1] for ev in ops]
        merged = merge_intervals((s, s + d) for _, s, d, _ in ops)
        if not merged:
            continue
        starts = [s for s, _ in merged]
        for name, start, dur, _ in _line(plane, MODULES_LINE):
            if start <= merged[0][0] or start + dur >= merged[-1][1]:
                continue  # at the trace's edge: it may have been cut there
            for module, executable in executables.items():
                if _is_execution(name, executable):
                    module_ns[module] += _overlap(merged, starts, start, start + dur)
                    module_runs[module] += 1
                    if module == SCOPED_EXECUTABLE:
                        for scope, ns in _outermost_ns_by_scope(ops, op_starts, start, start + dur, scopes).items():
                            scope_ns[scope] = scope_ns.get(scope, 0) + ns
        if len(rollouts) >= 2:
            first, last = rollouts[0], rollouts[-1]
            gaps = [(max(a, first), min(b, last)) for a, b in zip((e for _, e in merged), starts[1:])]
            for name, ns in idle_by_span([g for g in gaps if g[1] > g[0]], segments).items():
                idle_ns[name] = idle_ns.get(name, 0) + ns
    runs = module_runs.get(SCOPED_EXECUTABLE, 0)
    scoped = any(scope_ns.get(scope) for scope in scopes)  # a program without the scopes has nothing to split
    iterations = (len(rollouts) - 1) * len(planes)
    return {
        "module_ms": {m: module_ns[m] / module_runs[m] / 1e6 for m in executables if module_runs[m]},
        "module_runs": {m: n for m, n in module_runs.items() if n},
        "scope_ms": {s: scope_ns.get(s, 0) / runs / 1e6 for s in tuple(scopes) + (UNSCOPED,)} if runs and scoped else None,
        "idle_ms": {k: v / iterations / 1e6 for k, v in idle_ns.items()} if iterations > 0 else None,
        "iterations": max(iterations, 0),
    }


# --------------------------------------------------------------------------
# what the readers under metrics/ call
# --------------------------------------------------------------------------
@functools.lru_cache(maxsize=1)
def _reduced(xplane_path: str, executables: Tuple[Tuple[str, str], ...], scopes: Tuple[str, ...]) -> Dict[str, Any]:
    return reduce_spans(load_spans(xplane_path), dict(executables), scopes)


def for_run(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The reduction of this run's trace under its family's names, loaded once
    however many readers ask; ``None`` for a run that was not traced or left
    no trace behind."""
    family = run.get("family")
    if not run.get("trace") or family is None:
        return None
    try:
        path = find_xplane(os.path.join(command.WORK_DIR, run["cell"]["name"], "trace"))
        return _reduced(path, tuple(family.executables.items()), tuple(family.train_step_scopes))
    except (OSError, KeyError, ValueError):
        return None


def module_ms(run: Dict[str, Any], module: str) -> Optional[float]:
    reduced = for_run(run)
    return None if reduced is None else reduced["module_ms"].get(module)


def scope_ms(run: Dict[str, Any], scope: str) -> Optional[float]:
    reduced = for_run(run)
    return None if reduced is None or reduced["scope_ms"] is None else reduced["scope_ms"].get(scope)


def idle_ms(run: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Idle milliseconds an iteration by span; ``None`` where the trace has
    not two ``sheeprl/rollout`` spans to count iterations by."""
    reduced = for_run(run)
    return None if reduced is None else reduced["idle_ms"]


def counter_rate_ms(run: Dict[str, Any], phase: str, per: str) -> Optional[float]:
    """Growth of ``sheeprl_phase_seconds_total{phase}`` between the window's
    two scrapes over the growth of the counter ``per``, in milliseconds."""
    s0, s1 = run.get("scrapes") or ({}, {})
    seconds = f'sheeprl_phase_seconds_total{{phase="{phase}"}}'
    if seconds not in s1 or per not in s1:
        return None
    count = s1[per] - s0.get(per, 0.0)
    return 1e3 * (s1[seconds] - s0.get(seconds, 0.0)) / count if count > 0 else None
