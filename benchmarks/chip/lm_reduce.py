"""A train step that is scans within scans, split by scope.

``span_reduce`` gives an *outermost* operation's time to one scope, which
splits a step whose scans are its parts.  The recurrent on-policy update is
one scan over epochs around one over minibatches, with the delta rule's scan
over chunks and the triangular solve's loop inside: its one outermost
operation is the whole update.  Here every operation counts for *itself*: its
duration less that of the operations nested in it (a ``while`` keeps its own
overhead, its body's operations their own time), given to the first of the
family's scopes its path names (``span_reduce.scope_of``), or to ``unscoped``.
The buckets sum to the step's busy time.

Reads the trace through ``span_reduce.load_spans``; returns ``None``, and
never raises, where a run was not traced or its program names no scope.
"""

from __future__ import annotations

import functools
import json
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmarks.chip import run as command
from benchmarks.chip.span_reduce import (  # noqa: F401  (module_ms, counter_rate_ms, TRAIN_CALLS: for the readers of this family's cells)
    MODULES_LINE, OPS_LINE, TRAIN_CALLS, UNSCOPED, _is_execution, _line, counter_rate_ms, device_planes, load_spans, module_ms, scope_of,
)
from benchmarks.chip.trace_reduce import find_xplane

HERE = os.path.dirname(os.path.abspath(__file__))
Event = Tuple[str, int, int, str]


def self_ns_by_scope(ops: Sequence[Event], start: int, end: int, scopes: Sequence[str]) -> Dict[str, int]:
    """Self nanoseconds by scope of the operations that start in ``[start, end)``.
    ``ops`` are sorted by start, the longer first of two that start together."""
    out: Dict[str, int] = {}
    stack: List[List[Any]] = []  # [end, scope, self nanoseconds]

    def close(upto: int) -> None:
        while stack and stack[-1][0] <= upto:
            _, scope, ns = stack.pop()
            out[scope] = out.get(scope, 0) + max(ns, 0)

    for _, s, d, path in ops:
        if s < start or s >= end:
            continue
        close(s)
        if stack:
            stack[-1][2] -= d  # nested in the operation on top: not that one's own time
        stack.append([s + d, scope_of(path, scopes), d])
    close(1 << 62)
    return out


def reduce_scopes(trace: Dict[str, Any], executable: str, scopes: Sequence[str]) -> Optional[Dict[str, float]]:
    """Milliseconds of one execution of ``executable`` by scope (and
    ``unscoped``), over the executions that lie whole inside the trace."""
    total: Dict[str, int] = {}
    runs = 0
    for plane in device_planes(trace):
        ops = sorted(_line(plane, OPS_LINE), key=lambda ev: (ev[1], -ev[2]))
        if not ops:
            continue
        first, last = ops[0][1], max(ev[1] + ev[2] for ev in ops)
        for name, start, dur, _ in _line(plane, MODULES_LINE):
            if not _is_execution(name, executable) or start <= first or start + dur >= last:
                continue
            runs += 1
            for scope, ns in self_ns_by_scope(ops, start, start + dur, scopes).items():
                total[scope] = total.get(scope, 0) + ns
    if not runs or not any(total.get(scope) for scope in scopes):
        return None
    return {scope: total.get(scope, 0) / runs / 1e6 for scope in tuple(scopes) + (UNSCOPED,)}


@functools.lru_cache(maxsize=1)
def _reduced(xplane_path: str, executable: str, scopes: Tuple[str, ...]) -> Optional[Dict[str, float]]:
    return reduce_scopes(load_spans(xplane_path), executable, scopes)


def scope_ms(run: Dict[str, Any], scope: str) -> Optional[float]:
    family = run.get("family")
    if not run.get("trace") or family is None:
        return None
    try:
        path = find_xplane(os.path.join(command.WORK_DIR, run["cell"]["name"], "trace"))
        reduced = _reduced(path, family.executables["train_step"], tuple(family.train_step_scopes))
    except (OSError, KeyError, ValueError):
        return None
    return None if reduced is None else reduced.get(scope)


@functools.lru_cache(maxsize=1)
def _peaks() -> Dict[str, Any]:
    with open(os.path.join(HERE, "peaks.json")) as fh:
        return json.load(fh)


def peak(run: Dict[str, Any], key: str) -> float:
    """A peak of the run's device from ``peaks.json``; a device that is not in the table is an error."""
    return float(_peaks()[run["device"]["kind"]][key]) * run["device"]["count"]
