"""Core host-side utilities.

TPU-native re-design of the reference's ``sheeprl/utils/utils.py`` (see
/root/reference/sheeprl/utils/utils.py:34-316).  Device-side numerics (symlog,
two-hot, GAE, lambda-values) live in :mod:`sheeprl_tpu.ops` as pure JAX
functions; this module keeps only what genuinely belongs on the host:
config containers, schedules and the `Ratio` replay-ratio scheduler.
"""

from __future__ import annotations

import os
import warnings
from typing import Any, Dict, Mapping, Sequence

import numpy as np


class dotdict(dict):
    """A dictionary supporting dot notation (reference: utils/utils.py:34-60)."""

    __getattr__ = dict.get
    __setattr__ = dict.__setitem__
    __delattr__ = dict.__delitem__

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        for k, v in self.items():
            if isinstance(v, dict) and not isinstance(v, dotdict):
                self[k] = dotdict(v)

    def __getstate__(self):
        return dict(self)

    def __setstate__(self, state):
        self.update(state)

    def as_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        for k, v in self.items():
            out[k] = v.as_dict() if isinstance(v, dotdict) else v
        return out


def polynomial_decay(
    current_step: int,
    *,
    initial: float = 1.0,
    final: float = 0.0,
    max_decay_steps: int = 100,
    power: float = 1.0,
) -> float:
    """Polynomially decay a coefficient (reference: utils/utils.py:128-145)."""
    if current_step > max_decay_steps or initial == final:
        return final
    return (initial - final) * ((1 - current_step / max_decay_steps) ** power) + final


class Ratio:
    """Gradient-step budgeter: decides how many optimizer steps the trainer
    owes the policy-step counter at a given replay ratio (behavioural parity
    with reference utils/utils.py:262-300; re-derived as a credit accumulator).

    Every call banks ``(step - last_step) * ratio`` of fractional gradient-step
    credit and pays out its integer part, carrying the remainder — so over a
    run exactly ``ratio`` gradient steps happen per policy step, regardless of
    call granularity.  The first call pays a pretrain burst of
    ``pretrain_steps * ratio`` instead (clamped to the steps actually taken).

    Lives on the host next to the training loop; checkpointed via
    ``state_dict``.
    """

    def __init__(self, ratio: float, pretrain_steps: int = 0):
        if pretrain_steps < 0:
            raise ValueError(f"'pretrain_steps' must be non-negative, got {pretrain_steps}")
        if ratio < 0:
            raise ValueError(f"'ratio' must be non-negative, got {ratio}")
        self._ratio = float(ratio)
        self._pretrain_steps = int(pretrain_steps)
        self._last_step: float | None = None
        self._credit = 0.0

    def __call__(self, step: int) -> int:
        if self._ratio == 0:
            return 0
        if self._last_step is None:
            self._last_step = step
            burst = self._pretrain_steps
            if burst > 0 and step < burst:
                warnings.warn(
                    f"pretrain_steps ({burst}) exceeds the policy steps taken so far ({step}); "
                    f"clamping the pretrain burst to {step} steps to keep the effective "
                    f"replay ratio at {self._ratio}."
                )
                self._pretrain_steps = burst = step
            return int((burst if burst > 0 else step) * self._ratio)
        self._credit += (step - self._last_step) * self._ratio
        self._last_step = step
        repeats = int(self._credit)
        self._credit -= repeats
        return repeats

    def state_dict(self) -> Dict[str, Any]:
        return {
            "ratio": self._ratio,
            "last_step": self._last_step,
            "credit": self._credit,
            "pretrain_steps": self._pretrain_steps,
        }

    def load_state_dict(self, state_dict: Mapping[str, Any]) -> "Ratio":
        # also accept the pre-rewrite key names so old checkpoints resume
        self._ratio = state_dict.get("ratio", state_dict.get("_ratio"))
        self._last_step = state_dict.get("last_step", state_dict.get("_prev"))
        self._credit = state_dict.get("credit", 0.0)
        self._pretrain_steps = state_dict.get("pretrain_steps", state_dict.get("_pretrain_steps", 0))
        if self._ratio is None:
            raise KeyError(f"Unrecognized Ratio state: {sorted(state_dict)}")
        return self


def print_config(
    cfg: Mapping[str, Any],
    fields: Sequence[str] = ("algo", "buffer", "checkpoint", "env", "fabric", "metric"),
) -> None:
    """Pretty-print the composed config tree (reference: utils/utils.py:210-246)."""
    try:
        import rich.syntax
        import rich.tree
        import yaml

        tree = rich.tree.Tree("CONFIG", style="dim", guide_style="dim")
        for field in fields:
            section = cfg.get(field)
            if section is None:
                continue
            branch = tree.add(field, style="dim", guide_style="dim")
            if isinstance(section, dict):
                content = yaml.safe_dump(section.as_dict() if isinstance(section, dotdict) else dict(section))
            else:
                content = str(section)
            branch.add(rich.syntax.Syntax(content, "yaml"))
        rich.print(tree)
    except Exception:  # pragma: no cover - cosmetic only
        pass


def save_configs(cfg: "dotdict", log_dir: str) -> None:
    """Archive the run config as YAML (reference: utils/utils.py:249-251)."""
    import yaml

    os.makedirs(log_dir, exist_ok=True)
    with open(os.path.join(log_dir, "config.yaml"), "w") as fp:
        yaml.safe_dump(cfg.as_dict() if isinstance(cfg, dotdict) else dict(cfg), fp, sort_keys=False)


def nest_dotted(flat: Mapping[str, Any]) -> Dict[str, Any]:
    """Turn ``{"a.b": 1}`` into ``{"a": {"b": 1}}``."""
    out: Dict[str, Any] = {}
    for key, value in flat.items():
        node = out
        parts = key.split(".")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = value
    return out


def get_diagnostics(runtime, cfg: Mapping[str, Any], log_dir: str):
    """Return the run's opened :class:`~sheeprl_tpu.diagnostics.Diagnostics`.

    The CLI attaches a facade to the runtime before launch; entrypoints
    invoked directly (search harness, benchmarks, tests) get one built here
    from their own ``cfg``.  Opening is idempotent and rank-0 gated, so every
    training loop can call this right after ``get_log_dir`` and use the hooks
    unconditionally.
    """
    from sheeprl_tpu.diagnostics import build_diagnostics

    diag = getattr(runtime, "diagnostics", None)
    if diag is None:
        diag = build_diagnostics(cfg)
        runtime.diagnostics = diag
    diag.open(log_dir, rank_zero=runtime.is_global_zero, device=runtime.device_info)
    return diag


def subprocess_cli_env(device_count: int | None = None) -> Dict[str, str]:
    """Environment for spawning ``python -m sheeprl_tpu`` children from an
    arbitrary cwd (chaos drills): force the CPU
    platform, pin the virtual host-device count — REPLACING any inherited
    pin, so the caller gets the mesh it asked for even under a test
    harness's own ``XLA_FLAGS`` — and prepend this checkout to PYTHONPATH
    (same discipline as the supervisor's ``_child_env``, which deliberately
    does NOT force CPU: its children may own the real chip)."""
    import re

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if device_count is not None:
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+", "", env.get("XLA_FLAGS", "")
        ).strip()
        env["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={int(device_count)}"
        ).strip()
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    existing = env.get("PYTHONPATH", "")
    if pkg_root not in existing.split(os.pathsep):
        env["PYTHONPATH"] = pkg_root + (os.pathsep + existing if existing else "")
    return env


def unbind_parameters(tree):
    """No-op placeholder mirroring the reference's ``unwrap_fabric``: parameters
    in JAX are plain pytrees of arrays, there is nothing to unwrap."""
    return tree


def npify(tree):
    """Convert a pytree of (possibly device) arrays to host numpy arrays."""
    import jax

    return jax.tree_util.tree_map(lambda x: np.asarray(x), tree)
