"""The arithmetic of what decides ``correct``: norms by leaf, gaps beside limits.

Which tensors of a run are compared with which reference is a family's own
business (``families/<family>.py``: ``compare``); what every family's
comparison is made of sits here, and names no algorithm:

- norms are compared by the worst leaf: the gap between the program's norm
  and the reference's, over the reference's norm of that leaf or of the
  median leaf, whichever is larger (:func:`worst_leaf_gap`);
- a train step's numbers (:func:`step_gaps`): each step's losses, the first
  gradient of every leaf as the optimizer got it (from Adam's first moment
  after one step, :func:`find_adam_mu`), and every leaf's change after the
  recorded steps.  Leaves whose first gradient in the reference is under a
  thousandth of the median leaf's are left out of the change (Adam moves them
  by round-off alone);
- a number is held to its limit where the cell's file has one (``limits``,
  with the readings they were set from in ``PERF.md``) and printed as a
  reading where it has none (:func:`hold`).
"""

from __future__ import annotations

import math
import sys
from typing import Any, Dict, List, Mapping, Optional, Sequence

import numpy as np

ADAM_B1 = 0.9


def _norms(tree: Any) -> List[float]:
    import jax

    return [float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64))))) for x in jax.tree_util.tree_leaves(tree)]


def leaf_gaps(program: List[float], reference: List[float], keep: Optional[List[bool]] = None) -> List[float]:
    """``|a - b| / max(b, median b)`` of every leaf; ``nan`` where ``keep`` drops it."""
    median = float(np.median(reference))
    return [
        abs(a - b) / max(b, median) if keep is None or keep[i] else math.nan
        for i, (a, b) in enumerate(zip(program, reference))
    ]


def worst_leaf_gap(program: List[float], reference: List[float], keep: Optional[List[bool]] = None) -> float:
    """Worst ``|a - b| / max(b, median b)`` over the leaves that ``keep`` keeps."""
    gaps = [g for g in leaf_gaps(program, reference, keep) if not math.isnan(g)]
    return max(gaps) if gaps else math.nan


def leaf_names(tree: Any) -> List[str]:
    import jax

    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    return ["/".join(str(getattr(k, "key", k)) for k in path) for path, _ in paths]


def worst_leaves(program: Dict[str, Any], reference: Dict[str, Any], module: str, top: int = 3) -> List[str]:
    """For a person: the leaves of ``module`` whose first gradient is farthest off, with both norms."""
    names = leaf_names(reference["first_grads"][module])
    a, b = _norms(program["first_grads"][module]), _norms(reference["first_grads"][module])
    gaps = leaf_gaps(a, b)
    order = sorted(range(len(gaps)), key=lambda i: -gaps[i])[:top]
    return [f"{names[i]} gap {gaps[i]:.4f} program {a[i]:.5g} reference {b[i]:.5g}" for i in order]


def _change_norms(after: Any, before: Any) -> List[float]:
    """Norm of every leaf's change, a leaf at a time (the trees are never copied whole)."""
    import jax

    pairs = zip(jax.tree_util.tree_leaves(after), jax.tree_util.tree_leaves(before))
    return [float(np.linalg.norm((np.asarray(a, np.float64) - np.asarray(b, np.float64)).ravel())) for a, b in pairs]


def step_gaps(program: Dict[str, Any], reference: Dict[str, Any], params_before: Any,
              modules: Sequence[str]) -> Dict[str, float]:
    """The numbers compared for the train step.  ``program`` and ``reference``
    hold ``losses`` [steps][one a module, in the order of ``modules``],
    ``first_grads`` and ``params_after`` by module."""
    out: Dict[str, float] = {}
    for i, name in enumerate(modules):
        gaps = [abs(p[i] - r[i]) / max(abs(r[i]), 1e-6) for p, r in zip(program["losses"], reference["losses"])]
        out[f"loss_gap.{name}"] = float(max(gaps))
    for module in modules:
        ref_grad = _norms(reference["first_grads"][module])
        out[f"grad_gap.{module}"] = worst_leaf_gap(_norms(program["first_grads"][module]), ref_grad)
        moved = [g >= 1e-3 * float(np.median(ref_grad)) for g in ref_grad]
        out[f"change_gap.{module}"] = worst_leaf_gap(
            _change_norms(program["params_after"][module], params_before[module]),
            _change_norms(reference["params_after"][module], params_before[module]),
            keep=moved,
        )
    return out


def find_adam_mu(state: Any) -> Any:
    """The first-moment tree inside an optax chain's state."""
    if hasattr(state, "mu"):
        return state.mu
    if isinstance(state, (tuple, list)):
        for sub in state:
            found = find_adam_mu(sub)
            if found is not None:
                return found
    return None


def hold(checks: Dict[str, Dict[str, Any]], limits: Mapping[str, float], name: str, value: float) -> None:
    """``value`` beside its limit among ``checks``; a number the cell's file
    gives no limit is a reading on stderr and decides nothing."""
    if name in limits:
        checks[name] = {"value": value, "limit": limits[name], "ok": bool(value <= limits[name])}
    else:
        print(f"bench: reading {name}: {value!r} (not compared)", file=sys.stderr)
