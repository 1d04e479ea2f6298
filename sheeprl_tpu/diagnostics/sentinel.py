"""NaN/divergence sentinel: in-graph finiteness guards + host-side detector.

Two layers, addressing the two documented failure modes:

* **In-graph** (jit-compatible, runs inside the algorithms' train steps): the
  losses and the global gradient norm are reduced to a single finiteness flag
  per optimizer step.  Under ``policy=skip_update`` the already-computed
  parameter/optimizer-state update is discarded via ``jnp.where`` selection —
  a poisoned batch then costs one wasted step instead of a corrupted run.
  The flag and the grad norm ride the step's metric vector back to the host,
  so ``warn``/``halt`` need no extra device fetch.
* **Host-side** (:class:`DivergenceDetector`): rolling-window checks on the
  aggregated metric stream at each log boundary — policy-entropy floor (the
  pixel-CartPole ent_coef=3e-4 collapse mode) and loss-explosion ratio versus
  the window median.  Findings are returned as structured ``divergence``
  events for the run journal; the detector never stops a run by itself.

The in-graph pieces are pure functions of :class:`SentinelSpec`, a hashable
trace-time constant, so ``make_train_step`` builders can read it from ``cfg``
without threading new arguments through ``shard_map``/``jit`` signatures.
"""

from __future__ import annotations

from collections import deque
from typing import Any, Dict, List, Mapping, NamedTuple, Optional, Sequence

VALID_POLICIES = ("warn", "skip_update", "halt")


class SentinelHalt(RuntimeError):
    """Raised (host-side) when a non-finite update lands under ``policy=halt``."""


class SentinelSpec(NamedTuple):
    """Trace-time sentinel configuration for the jitted train steps."""

    enabled: bool = False
    policy: str = "warn"
    inject_nan_iter: Optional[int] = None

    @property
    def skip_update(self) -> bool:
        return self.enabled and self.policy == "skip_update"


def sentinel_spec(cfg: Mapping[str, Any]) -> SentinelSpec:
    """Extract the :class:`SentinelSpec` from a composed run config.

    Tolerates configs without a ``diagnostics`` section (the HLO
    tests compose partial configs and call ``make_train_step`` directly):
    missing means disabled, which keeps those compiled graphs byte-identical.
    """
    diag = cfg.get("diagnostics") or {}
    sent = diag.get("sentinel") or {}
    enabled = bool(diag.get("enabled", False)) and bool(sent.get("enabled", False))
    policy = str(sent.get("policy", "warn"))
    if policy not in VALID_POLICIES:
        raise ValueError(f"diagnostics.sentinel.policy must be one of {VALID_POLICIES}, got {policy!r}")
    inject = sent.get("inject_nan_iter")
    return SentinelSpec(enabled=enabled, policy=policy, inject_nan_iter=None if inject is None else int(inject))


# --------------------------------------------------------------------------
# jit-compatible helpers (imported lazily-by-caller inside train steps)
# --------------------------------------------------------------------------


def finite_flag(*scalars):
    """``True`` iff every scalar in ``scalars`` is finite (jit-compatible).

    Checking the *global grad norm* instead of every gradient leaf is both
    cheaper and equivalent for this purpose: any NaN/Inf leaf makes the norm
    NaN/Inf.
    """
    import jax.numpy as jnp

    return jnp.all(jnp.isfinite(jnp.stack([jnp.asarray(s, jnp.float32).reshape(()) for s in scalars])))


def tree_all_finite(tree):
    """Finiteness flag over every floating leaf of a pytree (jit-compatible)."""
    import jax
    import jax.numpy as jnp

    leaves = [l for l in jax.tree_util.tree_leaves(tree) if jnp.issubdtype(jnp.asarray(l).dtype, jnp.inexact)]
    if not leaves:
        return jnp.asarray(True)
    return jnp.all(jnp.stack([jnp.all(jnp.isfinite(l)) for l in leaves]))


def select_finite(finite, new_tree, old_tree):
    """Per-leaf ``where(finite, new, old)`` — the skip_update selection.

    ``finite`` is a scalar bool; broadcasting keeps this one fused select per
    leaf, and NaNs in the rejected branch are inert under ``where``.
    """
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(lambda n, o: jnp.where(finite, n, o), new_tree, old_tree)


def poison_tree(tree):
    """Replace every floating leaf with NaNs (fault injection for tests).

    Shapes/dtypes (and therefore compiled graphs) are unchanged; integer and
    bool leaves pass through so index/one-hot inputs stay valid.
    """
    import jax
    import jax.numpy as jnp

    def _poison(leaf):
        arr = jnp.asarray(leaf)
        if jnp.issubdtype(arr.dtype, jnp.inexact):
            return jnp.full(arr.shape, jnp.nan, arr.dtype)
        return leaf

    return jax.tree_util.tree_map(_poison, tree)


# --------------------------------------------------------------------------
# Host-side rolling divergence detector
# --------------------------------------------------------------------------


class DivergenceDetector:
    """Rolling-window divergence checks over the aggregated metric stream.

    Fed once per log boundary (so windows are cheap and host-side only);
    returns structured event dicts for the journal:

    * ``entropy_collapse`` — ``entropy_key``'s *magnitude* falls below
      ``entropy_floor``.  Collapse drives the policy entropy toward 0, which
      is a shrinking magnitude both for true-entropy metrics and for
      PPO-style ``Loss/entropy_loss`` (negative entropy), so one floor works
      for either sign convention.
    * ``loss_explosion`` — a watched ``Loss/*`` metric jumps above
      ``loss_explosion_ratio`` x its rolling median magnitude.
    * ``nonfinite_metric`` — a watched metric arrives as NaN/Inf (aggregators
      normally drop NaNs before logging, so this mostly fires via the raw
      journal path).
    """

    def __init__(
        self,
        window: int = 20,
        min_points: int = 5,
        loss_explosion_ratio: float = 10.0,
        entropy_key: Optional[str] = None,
        entropy_floor: Optional[float] = None,
        watch_prefixes: Sequence[str] = ("Loss/",),
    ):
        if window < 2:
            raise ValueError(f"divergence window must be >= 2, got {window}")
        self._window = int(window)
        self._min_points = max(2, int(min_points))
        self._ratio = float(loss_explosion_ratio) if loss_explosion_ratio else 0.0
        self._entropy_key = entropy_key
        self._entropy_floor = None if entropy_floor is None else float(entropy_floor)
        self._watch_prefixes = tuple(watch_prefixes)
        self._history: Dict[str, deque] = {}

    def _watched(self, name: str) -> bool:
        return any(name.startswith(p) for p in self._watch_prefixes)

    def observe(self, step: int, metrics: Mapping[str, Any]) -> List[Dict[str, Any]]:
        import numpy as np

        events: List[Dict[str, Any]] = []
        for name, value in metrics.items():
            try:
                v = float(value)
            except (TypeError, ValueError):
                continue
            if name == self._entropy_key and self._entropy_floor is not None and np.isfinite(v):
                if abs(v) < abs(self._entropy_floor):
                    events.append(
                        {
                            "kind": "entropy_collapse",
                            "metric": name,
                            "value": v,
                            "floor": self._entropy_floor,
                            "step": step,
                        }
                    )
            if not self._watched(name):
                continue
            if not np.isfinite(v):
                events.append({"kind": "nonfinite_metric", "metric": name, "value": v, "step": step})
                continue
            hist = self._history.setdefault(name, deque(maxlen=self._window))
            if self._ratio and len(hist) >= self._min_points:
                baseline = float(np.median(np.abs(np.asarray(hist))))
                if baseline > 1e-8 and abs(v) > self._ratio * baseline:
                    events.append(
                        {
                            "kind": "loss_explosion",
                            "metric": name,
                            "value": v,
                            "baseline_median": baseline,
                            "ratio": abs(v) / baseline,
                            "step": step,
                        }
                    )
            hist.append(v)
        return events
