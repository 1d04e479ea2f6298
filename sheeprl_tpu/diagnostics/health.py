"""Learning-dynamics observability: in-graph train-health statistics +
host-side anomaly detectors (ISSUE 9).

The journal/telemetry/memory/goodput pillars say how fast and whether-alive a
run is; this module says whether it is *learning*.  Three layers:

* **In-graph stats, zero extra syncs** — :func:`health_stats` is a
  jit-compatible pure function of ``(grads, updates, params)`` computing
  per-top-level-module gradient/update/parameter norms, the update-to-weight
  ratio and the dead-unit fraction *inside* the already-guarded train steps
  (ppo / a2c / sac family / ``_dreamer_main``, the same sites the NaN
  sentinel instruments).  The returned stats pytree of scalars rides the
  step's existing output fetch — the dispatch count and the ``device_get``
  count are unchanged (the ppo CLI e2e pins both).  The global grad norm it
  computes is *shared* with the sentinel's finiteness check, so enabling
  health removes one whole-tree reduction instead of adding one.

* **Host-side anomaly detectors** — :class:`HealthMonitor` keeps rolling
  windows over the per-step stats (fed by ``diag.on_health``) and the
  aggregated metric stream (fed at every log boundary, like the divergence
  detector): policy-entropy collapse, value explained-variance floor,
  update/weight-ratio band, loss plateau and per-module dead-gradient.  A
  breach must hold for ``diagnostics.health.confirm`` consecutive
  observations before ONE flood-controlled, fsync'd ``anomaly`` event fires
  (carrying the offending window); recovery journals ``anomaly_end``.  The
  live ``Telemetry/health/*`` gauges merge into every metric interval and
  the ``/metrics`` endpoint.

* **Cross-run regression diff** — ``tools/health_report.py`` (per-run
  post-mortem with per-module trajectory tables) and ``tools/health_diff.py``
  (two journals' watched trajectories under tolerance bands, non-zero exit
  on regression — the "did this PR change learning?" CI primitive) consume
  the journal records this module writes; the journal-side helpers they
  share (:func:`metric_series`, :func:`active_anomalies`) live here.

Like :class:`~sheeprl_tpu.diagnostics.sentinel.SentinelSpec`, the in-graph
configuration is a hashable trace-time constant (:class:`HealthSpec`), so the
``make_train_step`` builders read it straight from ``cfg`` without threading
new arguments through ``shard_map``/``jit`` signatures.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Any, Callable, Dict, List, Mapping, NamedTuple, Optional, Sequence, Tuple


class HealthSpec(NamedTuple):
    """Trace-time health-stats configuration for the jitted train steps."""

    enabled: bool = False
    per_module: bool = False
    dead_eps: float = 1e-8


def health_spec(cfg: Mapping[str, Any]) -> HealthSpec:
    """Extract the :class:`HealthSpec` from a composed run config.

    Tolerates configs without a ``diagnostics`` section (the HLO
    tests compose partial configs and call ``make_train_step`` directly):
    missing means disabled, which keeps those compiled graphs byte-identical.
    """
    diag = cfg.get("diagnostics") or {}
    health = diag.get("health") or {}
    enabled = bool(diag.get("enabled", False)) and bool(health.get("enabled", True))
    return HealthSpec(
        enabled=enabled,
        per_module=bool(health.get("per_module", False)),
        dead_eps=float(health.get("dead_eps", 1e-8)),
    )


# ---------------------------------------------------------------------------
# jit-compatible in-graph statistics
# ---------------------------------------------------------------------------


def top_level_modules(tree: Any) -> Dict[str, Any]:
    """Group a parameter-like pytree by its top-level module names.

    Descends through single-key mappings first (flax's ``{"params": {...}}``
    wrapper must not collapse everything into one "params" module) and groups
    by the keys of the first multi-key mapping.  A non-mapping tree (or a
    mapping of leaves) grouped as a single ``all`` module keeps the helper
    total on exotic structures.
    """
    node = tree
    while isinstance(node, Mapping) and len(node) == 1:
        (only,) = node.values()
        if not isinstance(only, Mapping):
            break
        node = only
    if isinstance(node, Mapping) and len(node) > 1:
        return {str(k): node[k] for k in node}
    return {"all": node}


def _unit_counts(tree: Any, dead_eps: float):
    """(dead units, total units) over a gradient tree (jit-compatible).

    A *unit* is a slice along a leaf's LAST axis (the output-feature axis of
    dense/conv kernels; each element of a bias/scalar).  A unit is dead when
    the max |grad| over its slice is <= ``dead_eps`` — the in-graph
    formulation of "this neuron received no learning signal this step".
    """
    import jax
    import jax.numpy as jnp

    dead = jnp.asarray(0.0, jnp.float32)
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        arr = jnp.asarray(leaf)
        if not jnp.issubdtype(arr.dtype, jnp.inexact):
            continue
        if arr.ndim == 0:
            unit_mag = jnp.abs(arr)[None]
            n_units = 1
        else:
            axes = tuple(range(arr.ndim - 1))
            unit_mag = jnp.max(jnp.abs(arr), axis=axes) if axes else jnp.abs(arr)
            n_units = int(arr.shape[-1])
        dead = dead + jnp.sum((unit_mag <= dead_eps).astype(jnp.float32))
        total += n_units
    return dead, total


def _tree_norm(tree: Any):
    import jax
    import jax.numpy as jnp

    leaves = [
        jnp.asarray(l)
        for l in jax.tree_util.tree_leaves(tree)
        if jnp.issubdtype(jnp.asarray(l).dtype, jnp.inexact)
    ]
    if not leaves:
        return jnp.asarray(0.0, jnp.float32)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves))


def health_stats(
    grads: Any,
    updates: Any,
    params: Any,
    *,
    per_module: bool = False,
    dead_eps: float = 1e-8,
) -> Dict[str, Any]:
    """Per-top-level-module train-health statistics (jit-compatible).

    Returns a flat ``{name: scalar}`` dict that can ride a train step's
    existing output fetch:

    * ``grad_norm`` / ``update_norm`` / ``param_norm`` — global L2 norms
      (``grad_norm`` is exactly ``optax.global_norm(grads)``, so the sentinel
      finiteness check shares it instead of reducing the tree twice);
    * ``update_ratio`` — ``update_norm / param_norm`` (the "how fast are the
      weights moving" number; ~1e-3 is healthy, ~0 is frozen, ~1 is blowing
      up);
    * ``dead_frac`` — fraction of units (last-axis slices) whose max |grad|
      is <= ``dead_eps``;
    * ``module/<name>/<stat>`` — the same five per top-level module when
      ``per_module`` (``diagnostics=full``).

    ``grads``/``updates``/``params`` must share their top-level module
    structure (they do at every call site: the gradient tree mirrors the
    parameter tree, and ``optimizer.update`` returns updates in it too).
    """
    import jax.numpy as jnp

    eps = jnp.asarray(1e-12, jnp.float32)

    def stats_of(g, u, p) -> Dict[str, Any]:
        grad_norm = _tree_norm(g)
        update_norm = _tree_norm(u)
        param_norm = _tree_norm(p)
        dead, total = _unit_counts(g, dead_eps)
        return {
            "grad_norm": grad_norm,
            "update_norm": update_norm,
            "param_norm": param_norm,
            "update_ratio": update_norm / (param_norm + eps),
            "dead_frac": dead / jnp.asarray(max(1, total), jnp.float32),
        }

    out = dict(stats_of(grads, updates, params))
    if per_module:
        grad_modules = top_level_modules(grads)
        update_modules = top_level_modules(updates)
        param_modules = top_level_modules(params)
        for name in grad_modules:
            module = stats_of(
                grad_modules[name],
                update_modules.get(name, grad_modules[name]),
                param_modules.get(name, grad_modules[name]),
            )
            for stat, value in module.items():
                out[f"module/{name}/{stat}"] = value
    return out


def explained_variance(values: Any, returns: Any):
    """Value-function explained variance ``1 - Var(returns - values) /
    Var(returns)`` (jit-compatible; 0 when the return variance vanishes).

    1.0 = the critic predicts returns perfectly; 0 = no better than the
    mean; < 0 = actively worse.  A saturated/diverged value head shows up as
    this sliding toward (or below) zero long before the loss curve says so.
    """
    import jax.numpy as jnp

    values = jnp.asarray(values, jnp.float32).reshape(-1)
    returns = jnp.asarray(returns, jnp.float32).reshape(-1)
    var_returns = jnp.var(returns)
    ev = 1.0 - jnp.var(returns - values) / jnp.where(var_returns > 1e-12, var_returns, 1.0)
    return jnp.where(var_returns > 1e-12, ev, 0.0)


def mean_stats(stats_list: Sequence[Optional[Mapping[str, Any]]]) -> Dict[str, float]:
    """Key-wise mean over a sequence of fetched stats dicts (Dreamer's drain
    hands the per-gradient-step dicts of one log interval here).  ``None`` /
    empty entries are skipped; values coerce through ``float``."""
    sums: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    for stats in stats_list:
        if not stats:
            continue
        for key, value in stats.items():
            try:
                v = float(value)
            except (TypeError, ValueError):
                continue
            sums[key] = sums.get(key, 0.0) + v
            counts[key] = counts.get(key, 0) + 1
    return {k: sums[k] / counts[k] for k in sums}


# ---------------------------------------------------------------------------
# host-side anomaly detection
# ---------------------------------------------------------------------------

#: Gauge-key prefix for everything this module merges into the metric stream.
HEALTH_PREFIX = "Telemetry/health/"
#: Scalar-subset gauge keys (registered in schema.METRICS; per-module detail
#: keys are built dynamically and stay journal/TB-only).
_SCALAR_GAUGES = ("grad_norm", "update_norm", "param_norm", "update_ratio", "dead_frac", "value_ev")


class HealthMonitor:
    """Rolling-window learning-health anomaly detection behind the facade.

    Opened on rank 0 only (its outputs are the journal and the gauges); every
    hook is a cheap no-op until then.  Two feeds:

    * :meth:`on_stats` — per-train-dispatch stats fetched by the loops
      (update/weight ratio, dead fractions, value EV);
    * :meth:`observe_metrics` — the aggregated metric stream at each log
      boundary (entropy collapse, loss plateau).

    A detector must breach for ``confirm`` consecutive observations before
    its single fsync'd ``anomaly`` event (flood control: one per detector
    while the condition holds); the first clean observation journals
    ``anomaly_end``.  Thread-safe: the metrics server snapshots from its own
    thread.
    """

    #: how many recent observations each journaled anomaly window carries
    WINDOW_KEEP = 12

    def __init__(self, cfg: Optional[Mapping[str, Any]]):
        cfg = cfg or {}
        diag_cfg = cfg.get("diagnostics") or {}
        health_cfg = diag_cfg.get("health") or {}
        self.enabled = bool(health_cfg.get("enabled", True))
        self.per_module = bool(health_cfg.get("per_module", False))
        self.confirm = int(health_cfg.get("confirm", 3))
        if self.confirm < 1:
            raise ValueError(
                f"diagnostics.health.confirm must be >= 1, got {health_cfg.get('confirm')!r}"
            )
        det = health_cfg.get("detectors") or {}
        self.entropy_key = det.get("entropy_key", "Loss/entropy_loss")
        floor = det.get("entropy_floor")
        self.entropy_floor = None if floor is None else float(floor)
        ev_floor = det.get("value_ev_floor")
        self.value_ev_floor = None if ev_floor is None else float(ev_floor)
        low = det.get("update_ratio_low", 1e-8)
        high = det.get("update_ratio_high", 1.0)
        self.update_ratio_low = None if low is None else float(low)
        self.update_ratio_high = None if high is None else float(high)
        if (
            self.update_ratio_low is not None
            and self.update_ratio_high is not None
            and self.update_ratio_low >= self.update_ratio_high
        ):
            raise ValueError(
                "diagnostics.health.detectors.update_ratio_low must be < update_ratio_high, "
                f"got {low!r} >= {high!r}"
            )
        dead_max = det.get("dead_frac_max", 0.95)
        self.dead_frac_max = None if dead_max is None else float(dead_max)
        self.plateau_key = det.get("plateau_key")
        self.plateau_window = int(det.get("plateau_window", 20))
        if self.plateau_window < 2:
            raise ValueError(
                f"diagnostics.health.detectors.plateau_window must be >= 2, "
                f"got {det.get('plateau_window')!r}"
            )
        rtol = det.get("plateau_rtol", 1e-3)
        self.plateau_rtol = None if rtol is None else float(rtol)
        inject = health_cfg.get("inject_entropy_collapse_iter")
        self.inject_entropy_collapse_iter = None if inject is None else int(inject)
        if self.enabled and self.inject_entropy_collapse_iter is not None and self.entropy_floor is None:
            # the drill forces the watched metric to 0, but the detector only
            # observes it when a floor is armed — an injection that cannot
            # fire must fail loudly, not journal a fault_injection event that
            # falsely validates the alerting chain
            raise ValueError(
                "diagnostics.health.inject_entropy_collapse_iter is set but "
                "diagnostics.health.detectors.entropy_floor is null — the entropy-collapse "
                "detector is disarmed and the drill could never fire; set a floor "
                "(e.g. detectors.entropy_floor=0.05)"
            )

        self._lock = threading.Lock()
        self._journal_fn: Optional[Callable[..., None]] = None
        self._sync_fn: Optional[Callable[[], None]] = None
        self._opened = False
        self._latest: Dict[str, float] = {}
        # per-detector state, keyed (kind, subject)
        self._windows: Dict[Tuple[str, str], deque] = {}
        self._breaches: Dict[Tuple[str, str], int] = {}
        self._active: Dict[Tuple[str, str], Dict[str, Any]] = {}
        self._anomalies_total = 0
        self._observe_calls = 0
        self._injecting = False

    # -- lifecycle ---------------------------------------------------------
    def open(
        self,
        journal_fn: Optional[Callable[..., None]] = None,
        sync_fn: Optional[Callable[[], None]] = None,
    ) -> None:
        if self._opened:
            return
        self._journal_fn = journal_fn
        self._sync_fn = sync_fn
        self._opened = True

    def _journal(self, event: str, **fields: Any) -> None:
        if self._journal_fn is not None:
            self._journal_fn(event, **fields)

    # -- detector core ------------------------------------------------------
    def _observe_value(
        self,
        kind: str,
        subject: str,
        value: float,
        breach: bool,
        step: Optional[int],
        required: Optional[int] = None,
        window: Optional[deque] = None,
        **payload: Any,
    ) -> None:
        """One observation of one watched series (caller holds the lock).

        Journals the flood-controlled ``anomaly`` (fsync'd, with the
        offending window) after ``required`` consecutive breaches (default:
        the configured ``confirm``), and ``anomaly_end`` on the first clean
        observation while active.  A caller that maintains its own window
        (the plateau detector, whose window IS the confirmation) passes it
        in; otherwise a per-key recent-values deque is kept here.
        """
        key = (kind, subject)
        if window is None:
            window = self._windows.setdefault(key, deque(maxlen=self.WINDOW_KEEP))
            window.append(round(float(value), 6))
        required = self.confirm if required is None else required
        if breach:
            self._breaches[key] = self._breaches.get(key, 0) + 1
            if key not in self._active and self._breaches[key] >= required:
                self._active[key] = {"since_step": step}
                self._anomalies_total += 1
                self._journal(
                    "anomaly",
                    kind=kind,
                    subject=subject,
                    step=step,
                    value=round(float(value), 6),
                    window=list(window),
                    confirm=required,
                    **payload,
                )
                if self._sync_fn is not None:
                    # the whole point is catching a run that dies wastefully:
                    # the record must survive a SIGKILL right after it fires
                    self._sync_fn()
        else:
            self._breaches[key] = 0
            if key in self._active:
                since = self._active.pop(key).get("since_step")
                self._journal(
                    "anomaly_end",
                    kind=kind,
                    subject=subject,
                    step=step,
                    since_step=since,
                    value=round(float(value), 6),
                )

    # -- feeds --------------------------------------------------------------
    def on_stats(self, step: Optional[int], stats: Mapping[str, Any]) -> None:
        """Digest one fetched train-step stats dict (from ``health_stats``)."""
        if not self._opened or not stats:
            return
        clean: Dict[str, float] = {}
        for key, value in stats.items():
            try:
                clean[str(key)] = float(value)
            except (TypeError, ValueError):
                continue
        if not clean:
            return
        with self._lock:
            self._latest.update(clean)
            ratio = clean.get("update_ratio")
            if ratio is not None and (
                self.update_ratio_low is not None or self.update_ratio_high is not None
            ):
                low_breach = self.update_ratio_low is not None and ratio < self.update_ratio_low
                high_breach = self.update_ratio_high is not None and ratio > self.update_ratio_high
                self._observe_value(
                    "update_ratio_band",
                    "update_ratio",
                    ratio,
                    low_breach or high_breach,
                    step,
                    low=self.update_ratio_low,
                    high=self.update_ratio_high,
                )
            if self.dead_frac_max is not None:
                for key, value in clean.items():
                    if key == "dead_frac":
                        subject = "dead_frac"
                    elif key.startswith("module/") and key.endswith("/dead_frac"):
                        subject = key
                    else:
                        continue
                    self._observe_value(
                        "dead_gradient",
                        subject,
                        value,
                        value >= self.dead_frac_max,
                        step,
                        max=self.dead_frac_max,
                    )
            ev = clean.get("value_ev")
            if ev is not None and self.value_ev_floor is not None:
                self._observe_value(
                    "value_ev_floor",
                    "value_ev",
                    ev,
                    ev < self.value_ev_floor,
                    step,
                    floor=self.value_ev_floor,
                )

    def observe_metrics(self, step: Optional[int], metrics: Mapping[str, Any]) -> None:
        """Digest one aggregated-metrics interval (called at every log
        boundary, after the gauges were merged)."""
        if not self._opened:
            return
        import numpy as np

        with self._lock:
            self._observe_calls += 1
            call = self._observe_calls
            inject = (
                self.inject_entropy_collapse_iter is not None
                and self.inject_entropy_collapse_iter <= call
                < self.inject_entropy_collapse_iter + self.confirm
            )
            if inject and not self._injecting:
                self._injecting = True
                self._journal(
                    "fault_injection",
                    iter_num=call,
                    kind="entropy_collapse",
                    intervals=self.confirm,
                )
            if self.entropy_key and self.entropy_floor is not None:
                value = metrics.get(self.entropy_key)
                if inject:
                    value = 0.0
                if isinstance(value, (int, float)) and np.isfinite(float(value)):
                    # magnitude floor: collapse drives both true-entropy and
                    # negative-entropy (Loss/entropy_loss) metrics toward 0
                    self._observe_value(
                        "entropy_collapse",
                        self.entropy_key,
                        float(value),
                        abs(float(value)) < abs(self.entropy_floor),
                        step,
                        floor=self.entropy_floor,
                    )
            if self.plateau_key and self.plateau_rtol is not None:
                value = metrics.get(self.plateau_key)
                if isinstance(value, (int, float)) and np.isfinite(float(value)):
                    key = ("loss_plateau", str(self.plateau_key))
                    window = self._windows.setdefault(key, deque(maxlen=self.plateau_window))
                    window.append(round(float(value), 6))
                    full = len(window) == self.plateau_window
                    scale = max(float(np.median(np.abs(np.asarray(window)))), 1e-12)
                    spread = (max(window) - min(window)) / scale if full else float("inf")
                    # the plateau window IS the confirmation window (breach =
                    # "the last plateau_window values moved < rtol"), so one
                    # breaching observation fires: required=1
                    self._observe_value(
                        "loss_plateau",
                        str(self.plateau_key),
                        float(value),
                        full and spread < self.plateau_rtol,
                        step,
                        required=1,
                        window=window,
                        rtol=self.plateau_rtol,
                        spread=round(spread, 8) if full else None,
                    )

    def open_anomaly_kinds(self) -> List[str]:
        """Sorted kinds of the currently-active anomalies (the decoupled
        promotion gate's "open sentinel anomaly" veto signal — cheap enough
        to consult once per trainer iteration)."""
        if not self._opened:
            return []
        with self._lock:
            return sorted({kind for kind, _subject in self._active})

    # -- gauges / snapshots --------------------------------------------------
    def interval_metrics(self) -> Dict[str, float]:
        """The ``Telemetry/health/*`` gauges merged into every metric
        interval: the latest stats (per-module detail included when the spec
        collects it) plus the live active-anomaly count."""
        if not self._opened:
            return {}
        with self._lock:
            if not self._latest and not self._anomalies_total:
                return {}
            out = {HEALTH_PREFIX + k: v for k, v in self._latest.items()}
            out[HEALTH_PREFIX + "anomalies"] = float(len(self._active))
        return out

    def snapshot(self) -> Dict[str, Any]:
        """The fixed scalar subset for ``/metrics`` (per-module detail stays
        journal/TB-only: Prometheus series must come from the registered
        vocabulary — see ``schema.METRICS``)."""
        with self._lock:
            gauges: Dict[str, float] = {}
            for stat in _SCALAR_GAUGES:
                if stat in self._latest:
                    gauges[HEALTH_PREFIX + stat] = self._latest[stat]
            gauges[HEALTH_PREFIX + "anomalies"] = float(len(self._active))
            counters = {"health_anomalies_total": self._anomalies_total}
            active = ",".join(sorted(f"{kind}:{subject}" for kind, subject in self._active))
            info = {"health_active_anomalies": active or None}
        return {"gauges": gauges, "counters": counters, "info": info}

    def summary(self) -> Dict[str, Any]:
        """Run totals folded into the closing ``telemetry_summary`` event."""
        with self._lock:
            return {
                "health_anomalies": self._anomalies_total,
                "health_anomalies_open": len(self._active),
            }


# ---------------------------------------------------------------------------
# journal-side helpers (shared by report.py, tools/health_report.py and
# tools/health_diff.py — do NOT re-inline this logic)
# ---------------------------------------------------------------------------


def metric_series(
    events: List[Dict[str, Any]], name: str
) -> List[Tuple[Optional[float], float]]:
    """``[(step, value)]`` trajectory of one metric over a journal's
    ``metrics`` events (non-numeric values — the journal's "nan"/"inf"
    strings included — are skipped)."""
    out: List[Tuple[Optional[float], float]] = []
    for event in events:
        if event.get("event") != "metrics":
            continue
        value = (event.get("metrics") or {}).get(name)
        if isinstance(value, (int, float)):
            step = event.get("step")
            out.append((float(step) if isinstance(step, (int, float)) else None, float(value)))
    return out


def watched_metric_names(events: List[Dict[str, Any]], prefixes: Sequence[str]) -> List[str]:
    """Sorted union of metric names matching any watch prefix (an exact name
    is its own prefix) over a journal's metrics events."""
    names: set = set()
    for event in events:
        if event.get("event") != "metrics":
            continue
        for name, value in (event.get("metrics") or {}).items():
            if isinstance(value, (int, float)) and any(name.startswith(p) for p in prefixes):
                names.add(name)
    return sorted(names)


def active_anomalies(events: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Anomaly events without a matching ``anomaly_end`` (keyed kind+subject),
    in firing order — what the ``!! ANOMALY`` banner reports."""
    open_by_key: Dict[Tuple[str, str], Dict[str, Any]] = {}
    for event in events:
        kind = event.get("event")
        if kind not in ("anomaly", "anomaly_end"):
            continue
        key = (str(event.get("kind")), str(event.get("subject")))
        if kind == "anomaly":
            open_by_key[key] = event
        else:
            open_by_key.pop(key, None)
    return sorted(open_by_key.values(), key=lambda e: e.get("t") or 0.0)
