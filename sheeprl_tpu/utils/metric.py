"""Metric aggregation (torchmetrics replacement).

Mirrors the reference's `MetricAggregator` semantics
(/root/reference/sheeprl/utils/metric.py:17-195) on plain numpy: a named
registry of small stateful metrics with a global disable switch, NaN filtering
at compute time, and a rank-independent variant that keeps per-process values
separate.  Device arrays passed to ``update`` are converted to host scalars
lazily at compute() to avoid forcing a sync inside hot loops.
"""

from __future__ import annotations

from collections import deque
from typing import Any, Dict, List, Optional

import numpy as np


class MetricError(Exception):
    pass


class Metric:
    def update(self, value: Any) -> None:  # pragma: no cover - interface
        raise NotImplementedError

    def compute(self) -> Any:
        raise NotImplementedError

    def reset(self) -> None:
        raise NotImplementedError


class MeanMetric(Metric):
    def __init__(self, sync_on_compute: bool = False, **_: Any):
        self.sync_on_compute = sync_on_compute
        self._values: List[Any] = []

    def update(self, value: Any) -> None:
        self._values.append(value)

    def compute(self) -> float:
        if not self._values:
            return float("nan")
        vals = np.asarray([float(np.asarray(v)) for v in self._values], dtype=np.float64)
        return float(vals.mean())

    def reset(self) -> None:
        self._values = []


class SumMetric(Metric):
    def __init__(self, sync_on_compute: bool = False, **_: Any):
        self.sync_on_compute = sync_on_compute
        self._values: List[Any] = []

    def update(self, value: Any) -> None:
        self._values.append(value)

    def compute(self) -> float:
        vals = np.asarray([float(np.asarray(v)) for v in self._values], dtype=np.float64)
        return float(vals.sum()) if len(vals) else 0.0

    def reset(self) -> None:
        self._values = []


class MaxMetric(Metric):
    def __init__(self, **_: Any):
        self._values: List[Any] = []

    def update(self, value: Any) -> None:
        self._values.append(value)

    def compute(self) -> float:
        return float(max(float(np.asarray(v)) for v in self._values)) if self._values else float("nan")

    def reset(self) -> None:
        self._values = []


class LastValueMetric(Metric):
    def __init__(self, **_: Any):
        self._value: Optional[Any] = None

    def update(self, value: Any) -> None:
        self._value = value

    def compute(self) -> float:
        return float(np.asarray(self._value)) if self._value is not None else float("nan")

    def reset(self) -> None:
        self._value = None


class MetricAggregator:
    """Named metric registry with a global disable switch
    (reference utils/metric.py:17-146)."""

    disabled: bool = False

    def __init__(self, metrics: Optional[Dict[str, Metric]] = None, raise_on_missing: bool = False):
        self.metrics: Dict[str, Metric] = dict(metrics or {})
        self._raise_on_missing = raise_on_missing

    def add(self, name: str, metric: Metric) -> None:
        if name in self.metrics:
            raise MetricError(f"Metric '{name}' already exists")
        self.metrics[name] = metric

    def update(self, name: str, value: Any) -> None:
        if self.disabled:
            return
        if name not in self.metrics:
            if self._raise_on_missing:
                raise MetricError(f"Unknown metric '{name}'")
            return
        self.metrics[name].update(value)

    def pop(self, name: str) -> None:
        self.metrics.pop(name, None)

    def reset(self) -> None:
        if self.disabled:
            return
        for metric in self.metrics.values():
            metric.reset()

    def compute(self) -> Dict[str, float]:
        """Reduce all metrics, dropping NaNs (reference metric.py:117-146)."""
        if self.disabled:
            return {}
        out: Dict[str, float] = {}
        for name, metric in self.metrics.items():
            value = metric.compute()
            if value is None or (isinstance(value, float) and np.isnan(value)):
                continue
            out[name] = value
        return out

    def to(self, device: Any) -> "MetricAggregator":
        return self  # host-side by design

    def keys(self):
        return self.metrics.keys()

    def __contains__(self, name: str) -> bool:
        return name in self.metrics


class RankIndependentMetricAggregator:
    """Keeps per-process series un-reduced (reference metric.py:149-195).
    Single-controller JAX has one process per host, so values are already
    per-host; multi-host gathers via Runtime.all_gather at compute."""

    def __init__(self, runtime, metrics: Dict[str, Metric]):
        self._runtime = runtime
        self._aggregator = MetricAggregator(metrics)

    def update(self, name: str, value: Any) -> None:
        self._aggregator.update(name, value)

    def compute(self) -> Dict[str, List[float]]:
        local = self._aggregator.compute()
        gathered = self._runtime.all_gather(local)
        if isinstance(gathered, dict):
            return {k: [v] if not isinstance(v, list) else v for k, v in gathered.items()}
        return gathered

    def reset(self) -> None:
        self._aggregator.reset()


class DeviceMetricsDrain:
    """Batches train-step metric fetches: a blocking value fetch waits for
    every step dispatched before it, so fetching per iteration would drain
    the dispatch queue the loop keeps ahead of the device.  The Dreamer hot
    loops therefore never fetch per-iteration — device rows accumulate and
    are pulled in one transfer every ``threshold`` steps or at the log
    boundary (``flush_into``).  Shared by the dreamer_v1/v2/v3 loops."""

    def __init__(self, threshold: int = 256):
        self._threshold = threshold
        self._pending: list = []
        self._pending_extra: list = []
        self._rows: list = []
        self._extra_rows: list = []

    def append(self, metrics, extra=None) -> None:
        """Queue one device metric vector (plus, optionally, a small device
        pytree — the learn-health stats dict — fetched in the SAME transfer
        as the metric rows, so carrying it costs zero extra syncs)."""
        self._pending.append(metrics)
        self._pending_extra.append(extra)
        if len(self._pending) >= self._threshold:
            self._drain()

    def _drain(self) -> None:
        if self._pending:
            import jax
            import jax.numpy as jnp

            stacked = jnp.stack(self._pending)
            extras = [e if e else {} for e in self._pending_extra]
            # ONE device_get for rows + extras together (an empty extras list
            # degenerates to the plain row fetch)
            rows, fetched = jax.device_get((stacked, extras))
            self._rows.extend(rows)
            self._extra_rows.extend(e for e in fetched if e)
            self._pending.clear()
            self._pending_extra.clear()

    def flush_into(
        self, aggregator: "MetricAggregator", metric_order, observer=None, extra_observer=None
    ) -> None:
        """Fetch everything pending and feed the named aggregator.

        ``observer(rows)``, when given, sees the raw per-gradient-step metric
        rows *before* NaN filtering — the diagnostics sentinel uses this to
        detect non-finite train steps that the aggregator would silently drop
        at compute time.  ``extra_observer(extras)`` sees the fetched extra
        pytrees of the interval (the Dreamer loops route their learn-health
        stats dicts to ``diag.on_health`` through it)."""
        self._drain()
        if observer is not None and self._rows:
            observer(list(self._rows))
        for row in self._rows:
            for name, value in zip(metric_order, row):
                aggregator.update(name, float(value))
        self._rows.clear()
        if extra_observer is not None and self._extra_rows:
            extra_observer(list(self._extra_rows))
        self._extra_rows.clear()
