"""A second family, as new files only: ``exp=ppo_recurrent``, the on-policy
recurrent loop a token-action sequence policy will train under.

It answers what ``manifest.FAMILY_ANSWERS`` lists and nothing else, and is
the proof that the harness needs no edit for a family it has not met
(``tests/test_benchmark/test_bench_second_family.py`` on the CPU; PERF.md
section 6 has the runs on the chip).  Its comparison holds the replay path
only: every row of the first three rollouts' training data is held against
what the envs emitted from the seed.  Of the faults a step can have it brings
the one that comparison can see, a state returned unchanged.  A cell of this
family that is to stand in ``BENCHMARK.json`` brings a plain reference for the
step as well, and with it the half batch.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import numpy as np

from benchmarks.chip.check import find_adam_mu
from benchmarks.chip.seqenv import STATE_DIM, episode_length, reward_of, state_of

executables = {"train_step": "jit_update", "player": "jit_policy_step"}
train_step_scopes = ()  # the loop names no scope inside its update

env_group = "seqprobe"


def env_overrides(cell: Dict[str, Any], log_path: str) -> List[str]:
    return [f"env.wrapper.{k}={v}" for k, v in cell["env"].items()] + [f"env.wrapper.log_path={log_path}"]


def train_step_flops(config: Dict[str, Any]) -> Dict[str, float]:
    """Matrix multiplications of one update from shapes: every row of the
    rollout goes forward and backward (3x forward) once an epoch."""
    s = config["shapes"]
    units, hidden = s["dense_units"], s["lstm_hidden_size"]
    encoder = s["state_dim"] * units + max(s["mlp_layers"] - 1, 0) * units * units + units * s["mlp_features_dim"]
    lstm = 4 * hidden * (s["mlp_features_dim"] + s["n_actions"] + hidden)
    heads = 2 * (hidden * units + max(s["mlp_layers"] - 1, 0) * units * units) + units * (s["n_actions"] + 1)
    rows = s["rollout_steps"] * s["num_envs"]
    total = 2.0 * 3 * rows * s["update_epochs"] * (encoder + lstm + heads)
    return {"total": total}


def install(seed: int, recorder: Any) -> Callable[[], None]:
    """The benchmark's weights go in where the loop builds its agent.  This
    family's comparison reads no player forward, so ``recorder.player`` stays empty."""
    from sheeprl_tpu.algos.ppo_recurrent import ppo_recurrent as loop

    from benchmarks.chip.weights import make_weights

    original_build = loop.build_agent

    def build_agent(*args, **kwargs):
        agent, params, sample_obs = original_build(*args, **kwargs)
        return agent, make_weights(params, seed), sample_obs

    loop.build_agent = build_agent

    def restore() -> None:
        loop.build_agent = original_build

    return restore


def split_step(args: tuple, out: Optional[tuple]) -> Dict[str, Any]:
    """``train_step(params, opt_state, data, key, coefs)`` returns the new two and the mean losses."""
    params, opt_state, data, key, coefs = args
    if out is None:
        return {"params": params, "batch": data, "key": key, "aux": {"coefs": coefs}}
    return {"params": out[0], "opt_state": find_adam_mu(out[1]), "metrics": out[2]}


def unchanged(step: Callable) -> Callable:
    """A step that does its work and returns its parameters and optimizer state as it got them."""
    import jax

    def broken(params, opt_state, *rest):
        copy = lambda tree: jax.tree_util.tree_map(lambda x: x + 0, tree)  # noqa: E731  (the step donates its arguments)
        out = step(copy(params), copy(opt_state), *rest)
        return (params, opt_state) + tuple(out[2:])

    return broken


faults = {"unchanged": unchanged}


def replay_mismatches(batches: List[Dict[str, np.ndarray]], step_log: Dict[str, np.ndarray], env: Dict[str, Any],
                      seed: int, num_envs: int) -> Dict[str, int]:
    """Rows of the recorded training data (``[L, S, ...]``, sequence ``s`` from
    env ``s % num_envs``) that are not what the envs emitted."""
    acted = {(int(e), int(k)): int(a) for e, k, a in zip(step_log["env"], step_log["marks"], step_log["actions"])}
    rows_bad = order_bad = labels_bad = 0
    for batch in batches:
        state = np.asarray(batch["state"], np.float32)
        L, S = state.shape[:2]
        index, who = state[..., 0].astype(np.int64), state[..., 1].astype(np.int64)
        done = np.asarray(batch["dones"])[..., 0] > 0
        # a step from the last observation of an episode returns the reset's: one index is skipped
        order_bad += int(np.sum(index[1:] != index[:-1] + 1 + done[:-1]))
        order_bad += int(np.sum(who != (np.arange(S) % num_envs)[None, :]))
        for l in range(L):
            for s in range(S):
                e, k = int(who[l, s]), int(index[l, s])
                if state.shape[-1] != STATE_DIM or not np.array_equal(state[l, s], state_of(seed, e, k)):
                    rows_bad += 1
                length = episode_length(int(env["episode_len"]), e)
                last = (k + 1) % (length + 1) == length  # the observation this step produced ends the episode
                ok = (
                    acted.get((e, k), -1) == int(np.asarray(batch["actions"])[l, s, 0])
                    and float(batch["rewards"][l, s, 0]) == reward_of(seed, e, k + 1)
                    and bool(done[l, s]) == last
                )
                labels_bad += int(not ok)
    return {"replay_row_mismatches": rows_bad, "replay_order_breaks": order_bad, "replay_label_mismatches": labels_bad}


def compare(recorded: Any, player: Optional[Dict[str, Any]], step_log: Dict[str, np.ndarray], config: Dict[str, Any],
            cell: Dict[str, Any], seed: int, controls: Optional[List[str]] = None) -> Dict[str, Dict[str, Any]]:
    if len(recorded.steps) < 3 or recorded.params_after is None:
        return {"recorded_steps": {"value": float(len(recorded.steps)), "limit": 3.0, "ok": False}}
    found = replay_mismatches([s["batch"] for s in recorded.steps], step_log, cell["env"], seed, int(config["shapes"]["num_envs"]))
    checks = {name: {"value": float(value), "limit": 0.0, "ok": value == 0} for name, value in found.items()}
    # the step did something: every recorded loss is a number, and the parameters moved
    moved = any(not np.array_equal(a, b) for a, b in zip(_leaves(recorded.params_after), _leaves(recorded.params_before)))
    finite = all(np.all(np.isfinite(s["metrics"])) for s in recorded.steps)
    checks["step_moved_and_finite"] = {"value": float(moved and finite), "limit": 1.0, "ok": bool(moved and finite)}
    return checks


def _leaves(tree: Any) -> List[np.ndarray]:
    import jax

    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]
