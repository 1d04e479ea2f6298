"""The DreamerV3 family: all that the benchmark knows of this algorithm.

A configuration's file names its family (``"family": "dreamer_v3"``) and the
harness finds this file by that name.  What it answers is the whole of what a
family brings (``manifest.FAMILY_ANSWERS``; PERF.md section 3):

- :func:`install`: the benchmark's weights go in where the program builds its
  agent, and the player's first forward pass after the last recorded step is
  copied on the way through;
- :func:`split_step`: how to read the arguments and the result of the loop's
  instrumented ``train_step``;
- :func:`compare`: what decides ``correct``.  Three comparisons, all on what
  the loop's own compiled step, ring and player produced at the timed sizes:
  **the replay path** (every row of the recorded batches is held, byte for
  byte, against what the env emitted from the seed: the frame the row claims
  to be by its stamped index, the order of the rows of a sequence, and the
  reward, first/terminal flags and action that belong to that frame; exact,
  the limit is 0 mismatches), **the train step** (the configuration's plain
  reference follows the same steps from the same weights, batches and keys;
  ``check.step_gaps``) and **the player forward**;
- :func:`train_step_flops` and :data:`executables`, :data:`train_step_scopes`
  for the per-layer readers;
- :data:`faults`: the faults this family's step can have, each planted under
  the loop's compiled step to show that :func:`compare` catches it
  (``run.py --fault <name>``; the tests plant the same ones at a tiny size);
- :data:`env_group` and :func:`env_overrides`: the env the cells of this
  family run against (``envs.py`` through ``hydra/env/chipbench.yaml``).
"""

from __future__ import annotations

import sys
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from benchmarks.chip import flops
from benchmarks.chip.check import ADAM_B1, find_adam_mu, hold, step_gaps, worst_leaves
from benchmarks.chip.harness import to_host
from benchmarks.chip.envs import EpisodeSchedule, frame_bank, frame_index, frame_of, reward_table, REWARD_TABLE
from benchmarks.chip.manifest import load_file

MODULES = ("world_model", "actor", "critic")  # also the order of the three losses
PROGRAM_LOSS_INDEX = (0, 6, 7)  # world-model, policy and value loss in the step's metric vector

# the executables of an iteration, by the jitted function's name, and the
# ``jax.named_scope``s inside the train step (an operation goes to the first its path names)
executables = {"train_step": "jit_train_step", "player": "jit_player_step",
               "replay_gather": "jit_replay_gather", "replay_add": "jit_replay_add"}
train_step_scopes = ("encoder", "rssm_scan", "decoder_heads", "imagination", "behaviour_losses", "optim")

env_group = "chipbench"


def env_overrides(cell: Dict[str, Any], log_path: str) -> List[str]:
    return [f"env.wrapper.{k}={v}" for k, v in cell["env"].items()] + [f"env.wrapper.log_path={log_path}"]


def train_step_flops(config: Dict[str, Any]) -> Dict[str, float]:
    return flops.train_step_flops(config["shapes"])


# -- the program, patched where it builds its agent and where its player acts ---
def install(seed: int, recorder: Any) -> Callable[[], None]:
    from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as dv3
    from sheeprl_tpu.algos.dreamer_v3.agent import PlayerDV3

    from benchmarks.chip.weights import make_weights

    original_build = dv3._build_agent_from_state
    original_get_actions = PlayerDV3.get_actions

    def get_actions(self, wm_params, actor_params, obs, key, greedy=False, mask=None):
        """The player's forward pass, the first call after the last recorded
        step copied on the way through: it acts with the weights that step left."""
        if recorder.player is not None or recorder.params_after is None:
            return original_get_actions(self, wm_params, actor_params, obs, key, greedy, mask)
        record = {"before": to_host(self.state), "obs": to_host(obs), "key": np.asarray(key)}
        actions = original_get_actions(self, wm_params, actor_params, obs, key, greedy, mask)
        record.update(after=to_host(self.state), actions=np.asarray(actions))
        recorder.player = record
        return actions

    def build_agent(runtime, actions_dim, is_continuous, cfg, obs_space, state):
        wm_def, actor_def, critic_def, params = original_build(
            runtime, actions_dim, is_continuous, cfg, obs_space, state
        )
        return wm_def, actor_def, critic_def, make_weights(params, seed)

    dv3._build_agent_from_state = build_agent
    PlayerDV3.get_actions = get_actions

    def restore() -> None:
        dv3._build_agent_from_state = original_build
        PlayerDV3.get_actions = original_get_actions

    return restore


def split_step(args: tuple, out: Optional[tuple]) -> Dict[str, Any]:
    """``train_step(params, opt_states, moments_state, batch, key, tau)`` returns
    the new three, then the metric vector.  Of the optimizers' states the
    comparison reads Adam's first moments, by module."""
    params, opt_states, moments_state, batch, key, tau = args
    if out is None:
        return {"params": params, "batch": batch, "key": key, "aux": {"moments": moments_state, "tau": float(tau)}}
    return {"params": out[0], "opt_state": {k: find_adam_mu(v) for k, v in out[1].items()}, "metrics": out[3]}


# -- the faults this step can have ---------------------------------------------
# Each takes the loop's compiled step and returns one that the loop calls in
# its place, with the same shapes, so nothing compiles anew.
def unchanged(step: Callable) -> Callable:
    """A step that does its work and returns its state as it got it."""
    import jax

    def broken(params, opt_states, moments_state, *rest):
        copy = lambda tree: jax.tree_util.tree_map(lambda x: x + 0, tree)  # noqa: E731  (the step donates its arguments)
        out = step(copy(params), copy(opt_states), copy(moments_state), *rest)
        return (params, opt_states, moments_state) + tuple(out[3:])

    return broken


def half_batch(step: Callable) -> Callable:
    """Half of the batch left out, the mean taken over the rest: the second
    half of the rows is overwritten with the first before the step sees them."""
    import jax.numpy as jnp

    def broken(params, opt_states, moments_state, batch, key, tau):
        def first_half_twice(v):
            half = v[:, : v.shape[1] // 2]
            return jnp.concatenate([half, half], axis=1)

        return step(params, opt_states, moments_state, {k: first_half_twice(v) for k, v in batch.items()}, key, tau)

    return broken


faults: Dict[str, Callable[[Callable], Callable]] = {"unchanged": unchanged, "half_batch": half_batch}


# -- what the timed path produced, in the reference's layout -------------------
def program_readings(recorded: Any) -> Dict[str, Any]:
    import jax

    return {
        "losses": [[float(step["metrics"][i]) for i in PROGRAM_LOSS_INDEX] for step in recorded.steps],
        "first_grads": {
            k: jax.tree_util.tree_map(lambda mu: np.asarray(mu) / (1 - ADAM_B1), recorded.opt_state_after_first[k])
            for k in MODULES
        },
        "params_after": recorded.params_after,
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
        known = step_log["marks"] <= int(index.max())
        acted_at[step_log["marks"][known]] = step_log["actions"][known]
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


def compare(recorded: Any, player: Optional[Dict[str, Any]], step_log: Dict[str, np.ndarray], config: Dict[str, Any],
            cell: Dict[str, Any], seed: int, controls: Optional[List[str]] = None) -> Dict[str, Dict[str, Any]]:
    """Every number compared, beside its limit.  ``controls`` also reads the
    reference in the named lower precisions (``bfloat16``) against itself
    and prints that on stderr; it decides nothing."""
    reference_file = load_file(config["reference"], "bench_reference_" + config["name"])
    first_steps, player_step = reference_file.first_steps, reference_file.player_step

    limits = cell["limits"]
    checks: Dict[str, Dict[str, Any]] = {}
    if len(recorded.steps) < 3 or recorded.params_after is None:
        return {"recorded_steps": {"value": float(len(recorded.steps)), "limit": 3.0, "ok": False}}
    inputs = [{"batch": s["batch"], "key": s["key"], "tau": s["aux"]["tau"]} for s in recorded.steps]
    moments_before = recorded.steps[0]["aux"]["moments"]
    for name, value in replay_mismatches(inputs, step_log, cell["env"], seed).items():
        checks[name] = {"value": float(value), "limit": 0.0, "ok": value == 0}

    shapes, hyper = config["shapes"], config["hyper"]
    noise = _noise_dtype(config)
    reference = first_steps(shapes, hyper, recorded.params_before, moments_before, inputs, noise_dtype=noise)
    program = program_readings(recorded)
    gaps = step_gaps(program, reference, recorded.params_before, MODULES)
    for name, value in gaps.items():
        hold(checks, limits, name, value)
    if controls:
        for module in MODULES:
            for line in worst_leaves(program, reference, module):
                print(f"bench: worst first gradient, {module}: {line}", file=sys.stderr)
        print(f"bench: losses program {program['losses']} reference {[list(map(float, l)) for l in reference['losses']]}", file=sys.stderr)
    # the forward pass that chose the action after the third step, with the weights that step left
    if player is None:
        checks["player_recorded"] = {"value": 0.0, "limit": 1.0, "ok": False}
    else:
        forward = lambda quant: player_step(  # noqa: E731
            shapes, recorded.params_after, player["before"], player["obs"]["rgb"], player["key"], quant
        )
        sound = forward("float32")
        same_action = bool(np.array_equal(np.argmax(player["actions"], -1), np.argmax(sound["actions"], -1)))
        print(f"bench: reading player action equals the reference's: {same_action}", file=sys.stderr)
        for control in controls or []:
            print(f"bench: control {control} player_gap: {player_gap(forward(control), sound)!r}", file=sys.stderr)
        hold(checks, limits, "player_gap", player_gap(player["after"], sound))
    for control in controls or []:
        lower = first_steps(shapes, hyper, recorded.params_before, moments_before, inputs, quant=control, noise_dtype=noise)
        for name, value in step_gaps(lower, reference, recorded.params_before, MODULES).items():
            print(f"bench: control {control} {name}: {value!r}", file=sys.stderr)
    return checks
