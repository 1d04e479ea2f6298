"""Window arithmetic: the two end-to-end metrics from the env's own clock.

``times`` are ``time.time()`` at every ``step`` call the envs received, in
order (``steplog.py``).  Both numbers are taken over the whole window
``[t0, t0 + seconds]``: a stall anywhere in it takes steps away from the rate
and puts a long gap into the tail.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def window_metrics(times: np.ndarray, t0: float, seconds: float, env: np.ndarray) -> Dict[str, float]:
    """``env_steps_per_s``: step calls inside the window over its seconds,
    whichever env received them.  ``action_gap_p95_ms``: 95th percentile of
    the gaps between successive step calls of one env (``env`` says which env
    each stamp is from), over every gap that ends
    inside the window (so the gap that is open when the window starts counts
    whole), the envs' gaps pooled.  ``steps`` and ``gaps`` are counts;
    ``steps_by_10s`` counts the steps of each ten seconds of the window, which
    is where a person sees when a stall fell."""
    times = np.asarray(times, np.float64)
    if seconds <= 0:
        raise ValueError(f"the window needs a positive length, got {seconds}")
    t1 = t0 + seconds
    inside = (times >= t0) & (times <= t1)
    steps = int(inside.sum())
    env = np.asarray(env)
    gaps = np.concatenate([np.diff(times[env == e])[inside[env == e][1:]] for e in np.unique(env)] or [np.zeros(0)])
    if steps < 2 or gaps.size < 1:
        raise ValueError(f"only {steps} env steps fell inside the window: nothing to measure")
    return {
        "env_steps_per_s": steps / seconds,
        "action_gap_p95_ms": float(np.percentile(gaps, 95)) * 1e3,
        "action_gap_p50_ms": float(np.percentile(gaps, 50)) * 1e3,
        "steps": steps,
        "gaps": int(gaps.size),
        "steps_by_10s": np.bincount(np.minimum((times[inside] - t0) // 10.0, (seconds - 1e-9) // 10.0).astype(int)).tolist(),
    }
