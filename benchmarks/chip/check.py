"""What decides ``correct``: the timed path's first steps against the reference.

Two comparisons, both on what the loop's own compiled step and replay path
produced at the timed sizes (the :class:`~benchmarks.chip.harness.Recorder`
copied it on the way through):

- **the replay path**: every row of the three recorded batches is held, byte
  for byte, against what the env emitted from the seed: the frame the row
  claims to be (its stamped index), the order of the rows of a sequence, and
  the reward, first/terminal flags and action that belong to that frame.
  Exact: the limit is 0 mismatches.
- **the train step**: the reference (``reference.py``, float32) follows the
  same three steps from the same weights, batches and keys.  Compared: each
  step's three losses, the first gradient of every leaf as the optimizer got
  it (from Adam's first moment after one step), and every leaf's change after
  the three steps.  Norms are compared by the worst leaf: the gap between the
  program's norm and the reference's, over the reference's norm of that leaf
  or of the median leaf, whichever is larger.  Leaves whose first gradient in
  the reference is under a thousandth of the median leaf's are left out of the
  change (Adam moves them by round-off alone).

The limits are data: the cell's file holds them (``limits``), with the
readings they were set from in ``PERF.md``.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

import numpy as np

from benchmarks.chip.envs import EpisodeSchedule, frame_bank, frame_index, frame_of, reward_table, REWARD_TABLE

MODULES = ("world_model", "actor", "critic")  # also the order of the three losses
PROGRAM_LOSS_INDEX = (0, 6, 7)  # world-model, policy and value loss in the step's metric vector
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


def step_gaps(program: Dict[str, Any], reference: Dict[str, Any], params_before: Any) -> Dict[str, float]:
    """The numbers compared for the train step.  ``program`` and ``reference``
    hold ``losses`` [steps][3], ``first_grads`` and ``params_after`` by module."""
    out: Dict[str, float] = {}
    for i, name in enumerate(MODULES):
        gaps = [abs(p[i] - r[i]) / max(abs(r[i]), 1e-6) for p, r in zip(program["losses"], reference["losses"])]
        out[f"loss_gap.{name}"] = float(max(gaps))
    for module in MODULES:
        ref_grad = _norms(reference["first_grads"][module])
        out[f"grad_gap.{module}"] = worst_leaf_gap(_norms(program["first_grads"][module]), ref_grad)
        moved = [g >= 1e-3 * float(np.median(ref_grad)) for g in ref_grad]
        out[f"change_gap.{module}"] = worst_leaf_gap(
            _change_norms(program["params_after"][module], params_before[module]),
            _change_norms(reference["params_after"][module], params_before[module]),
            keep=moved,
        )
    return out


def program_readings(recorder: Any) -> Dict[str, Any]:
    """What the timed path produced, in the reference's layout."""
    import jax

    return {
        "losses": [[float(m[i]) for i in PROGRAM_LOSS_INDEX] for m in recorder.metrics],
        "first_grads": {
            k: jax.tree_util.tree_map(lambda mu: np.asarray(mu) / (1 - ADAM_B1), recorder.mu_after_first[k])
            for k in MODULES
        },
        "params_after": recorder.params_after,
    }


# -- the replay path -----------------------------------------------------------
def replay_mismatches(inputs: List[Dict[str, Any]], step_log: Dict[str, np.ndarray], env: Dict[str, Any],
                      seed: int) -> Dict[str, int]:
    """Rows of the recorded batches that are not what the env emitted."""
    bank = frame_bank(seed)
    rewards = reward_table(seed, float(env.get("reward_pct", 5.0)))
    schedule = EpisodeSchedule(seed, int(env["episode_min"]), int(env["episode_max"]), env.get("first_episodes", ()))
    n_actions = int(env["n_actions"])
    frames_bad = order_bad = labels_bad = 0
    for item in inputs:
        batch = item["batch"]
        frames = np.rint((np.asarray(batch["rgb"], np.float64) + 0.5) * 255.0).astype(np.uint8)
        index = frame_index(frames)  # [T, B]
        schedule.extend_to(int(index.max()) + 1)
        resets, finals = set(schedule.resets), set(schedule.finals)
        acted_at = np.full(int(index.max()) + 2, -1, np.int64)
        known = step_log["frames_at"] <= int(index.max())
        acted_at[step_log["frames_at"][known]] = step_log["actions"][known]
        order_bad += int(np.sum(index[1:] != index[:-1] + 1))
        actions = np.asarray(batch["actions"])
        for t in range(index.shape[0]):
            for b in range(index.shape[1]):
                k = int(index[t, b])
                if not np.array_equal(frames[t, b], frame_of(bank, k)):
                    frames_bad += 1
                want_action = np.zeros(n_actions, np.float32)
                if k not in finals:
                    if acted_at[k] < 0:
                        labels_bad += 1
                    else:
                        want_action[acted_at[k]] = 1.0
                ok = (
                    float(batch["is_first"][t, b, 0]) == float(k in resets)
                    and float(batch["terminated"][t, b, 0]) == float(k in finals)
                    and float(batch["rewards"][t, b, 0]) == (0.0 if k in resets else float(rewards[k % REWARD_TABLE]))
                    # a sampled action is hard + probs - probs: one-hot to rounding
                    and float(np.abs(actions[t, b] - want_action).max()) <= 1e-5
                )
                labels_bad += int(not ok)
    return {"replay_frame_mismatches": frames_bad, "replay_order_breaks": order_bad, "replay_label_mismatches": labels_bad}


# -- the player forward --------------------------------------------------------
def player_gap(program: Dict[str, Any], reference: Dict[str, Any]) -> float:
    """Norm of the difference of the recurrent states the forward pass left, over the reference's norm."""
    a, b = np.asarray(program["recurrent"], np.float64), np.asarray(reference["recurrent"], np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# -- all of it -----------------------------------------------------------------
def _noise_dtype(config: Dict[str, Any]) -> Any:
    import jax.numpy as jnp

    return jnp.bfloat16 if str(config.get("precision", "")).startswith("bf16") else jnp.float32


def compare_run(recorder: Any, step_log: Dict[str, np.ndarray], config: Dict[str, Any], cell: Dict[str, Any],
                seed: int, controls: Optional[List[str]] = None, player: Optional[Dict[str, Any]] = None) -> Dict[str, Dict[str, Any]]:
    """Every number compared, beside its limit.  ``controls`` also reads the
    reference in the named lower precisions (``bfloat16``) against itself
    and prints that on stderr; it decides nothing."""
    import sys

    from benchmarks.chip.reference import first_steps, player_step

    limits = cell["limits"]
    checks: Dict[str, Dict[str, Any]] = {}
    if len(recorder.inputs) < 3 or recorder.params_after is None:
        return {"recorded_steps": {"value": float(len(recorder.inputs)), "limit": 3.0, "ok": False}}
    for name, value in replay_mismatches(recorder.inputs, step_log, cell["env"], seed).items():
        checks[name] = {"value": float(value), "limit": 0.0, "ok": value == 0}

    shapes, hyper = config["shapes"], config["hyper"]
    noise = _noise_dtype(config)
    reference = first_steps(shapes, hyper, recorder.params_before, recorder.moments_before, recorder.inputs, noise_dtype=noise)
    gaps = step_gaps(program_readings(recorder), reference, recorder.params_before)
    for name, value in gaps.items():
        if name in limits:
            checks[name] = {"value": value, "limit": limits[name], "ok": bool(value <= limits[name])}
        else:
            print(f"bench: reading {name}: {value!r} (not compared)", file=sys.stderr)
    if controls:
        program = program_readings(recorder)
        for module in MODULES:
            for line in worst_leaves(program, reference, module):
                print(f"bench: worst first gradient, {module}: {line}", file=sys.stderr)
        print(f"bench: losses program {program['losses']} reference {[list(map(float, l)) for l in reference['losses']]}", file=sys.stderr)
    # the forward pass that chose the action after the third step, with the weights that step left
    if player is None:
        checks["player_recorded"] = {"value": 0.0, "limit": 1.0, "ok": False}
    else:
        forward = lambda quant: player_step(  # noqa: E731
            shapes, recorder.params_after, player["before"], player["obs"]["rgb"], player["key"], quant
        )
        sound = forward("float32")
        gaps["player_gap"] = player_gap(player["after"], sound)
        same_action = bool(np.array_equal(np.argmax(player["actions"], -1), np.argmax(sound["actions"], -1)))
        print(f"bench: reading player action equals the reference's: {same_action}", file=sys.stderr)
        for control in controls or []:
            print(f"bench: control {control} player_gap: {player_gap(forward(control), sound)!r}", file=sys.stderr)
        name = "player_gap"
        if name in limits:
            checks[name] = {"value": gaps[name], "limit": limits[name], "ok": bool(gaps[name] <= limits[name])}
        else:
            print(f"bench: reading {name}: {gaps[name]!r} (not compared)", file=sys.stderr)
    for control in controls or []:
        lower = first_steps(shapes, hyper, recorder.params_before, recorder.moments_before, recorder.inputs,
                            quant=control, noise_dtype=noise)
        for name, value in step_gaps(lower, reference, recorder.params_before).items():
            print(f"bench: control {control} {name}: {value!r}", file=sys.stderr)
    return checks
