"""The Olmo-Hybrid-under-PPO family: a hybrid language model as a token-action
policy under ``exp=ppo_recurrent_olmo_hybrid``, the recurrent on-policy loop.

What it answers is the whole of what a family brings (``manifest.FAMILY_ANSWERS``;
PERF.md section 3).  One call of the loop's ``train_step`` is one *update*:
every sequence of a rollout, ``update_epochs`` times over, in minibatches (8
gradient steps at the cell's sizes).  :func:`compare` holds, at the published
widths, what the timed path produced:

- **the replay path**, exact: every row of the three recorded rollouts (token,
  action, reward, done, reset) is what the env of that column emitted when
  given the actions its own log holds (``tokenenv.emitted``);
- **the player against the full-sequence forward** (``logprob_gap``: the
  mean absolute gap, ``logprob_gap.worst``: the worst of a rollout's 8,192,
  ``value_gap``): the log-probabilities and values the player stored while
  decoding token by token through its state and cache, against the plain
  reference's forward of the same tokens, whole, from the same snapshot and
  with the parameters the player had.  Rollout 1 (from empty state, episode
  ends inside) and rollout 2 (from a carried state and cache, with the
  parameters the first update left): the check that sees a wrong reset, a
  stale cache or a dropped carry;
- **the update** (``loss_gap.*``, ``grad_gap``, ``change_gap``): the plain
  reference *follows* the first update from ``params_before``: on each of its
  8 minibatches in turn, drawn from the program's own random stream, the three
  losses and the whole gradient (a layer and one sequence at a time, so that
  it fits beside Adam's moments), then one step of AdamW behind the
  global-norm clip, written out (``follow_update``).  Held: the first gradient
  step's losses and every leaf's gradient norm as the optimizer got it (the
  step reports both for every gradient step; the later steps are read and not
  held, each side standing by then where its own rounding put it), and over
  the whole update the norm of every leaf's change by the worst leaf
  (``change_gap``: a state returned unchanged reads 1; an epoch too many or
  too few, a wrong learning rate, clip or moment reads what it is off by).
  Of the second update, whose parameters are the first's (held by
  ``change_gap``) and whose moments the Recorder does not keep, the first
  gradient step is held the same way;
- **the step moved** (``params_moved``): every leaf of the parameters differs
  after the first update (a leaf too small for ``change_gap``'s median rule to
  see still has to move: ``bf16-true`` leaves 27% of them where they were).

The Recorder's host copies are a stated subset: ``params_before`` whole, the
parameters after the first update whole (under ``opt_state``), no parameters
after the third, no Adam moment.
"""

from __future__ import annotations

import sys
from typing import Any, Callable, Dict, List, Mapping, Optional

import numpy as np

from benchmarks.chip.check import hold, leaf_gaps, leaf_names, worst_leaf_gap
from benchmarks.chip.manifest import load_file

executables = {"train_step": "jit_update", "player": "jit_policy_step"}
# the jax.named_scopes of the update (models/hybrid_lm.py, algos/ppo_recurrent); an operation goes to the first its path names
train_step_scopes = ("embed", "delta_rule", "delta_rule_proj", "full_attention", "swiglu", "vocab_head", "ppo_loss", "optim")

env_group = "tokenbench"
LINEAR = "linear_attention"
F32_BYTES = 4


def env_overrides(cell: Dict[str, Any], log_path: str) -> List[str]:
    return [f"env.wrapper.{k}={v}" for k, v in cell["env"].items()] + [f"env.wrapper.log_path={log_path}"]


# -- work from shapes ------------------------------------------------------------
def parameter_counts(shapes: Mapping[str, Any]) -> Dict[str, int]:
    """Parameters this chip holds, by what they are."""
    D, I, V = shapes["hidden_size"], shapes["intermediate_size"], shapes["vocab_held"]
    H, dk, dv = shapes["heads_held"], shapes["linear_key_head_dim"], shapes["linear_value_head_dim"]
    dh, taps = D // shapes["heads_total"], shapes["linear_conv_kernel_dim"]
    n_linear = sum(kind == LINEAR for kind in shapes["layer_types"])
    n_full = len(shapes["layer_types"]) - n_linear
    linear_matmul = D * H * (2 * dk + 2 * dv + 2) + H * dv * D
    linear_other = taps * H * (2 * dk + dv) + 2 * H + dv
    return {
        "linear_matmul": n_linear * linear_matmul,
        "full_matmul": n_full * 4 * D * H * dh,
        "mlp_matmul": len(shapes["layer_types"]) * 3 * D * I,
        "head_matmul": D * V + D,
        "embedding": V * D,
        "other": n_linear * linear_other + len(shapes["layer_types"]) * 2 * D + D,
    }


def forward_flops_per_token(shapes: Mapping[str, Any]) -> Dict[str, float]:
    """FLOPs one token needs going forward (2 a multiply-add): the matrix
    multiplications, the delta rule in its *recurrent* form (``6 dk dv`` a
    head: decay, read, write, read-out) and causal attention over half the
    sequence's own keys (the carried keys are data-dependent and left out: a floor)."""
    counts = parameter_counts(shapes)
    H, dk, dv = shapes["heads_held"], shapes["linear_key_head_dim"], shapes["linear_value_head_dim"]
    dh = shapes["hidden_size"] // shapes["heads_total"]
    n_linear = sum(kind == LINEAR for kind in shapes["layer_types"])
    n_full = len(shapes["layer_types"]) - n_linear
    return {
        "matmul": 2.0 * sum(v for k, v in counts.items() if k.endswith("_matmul")),
        "delta_rule": n_linear * H * 6.0 * dk * dv,
        "attention": n_full * H * 2.0 * 2.0 * dh * shapes["sequence_length"] / 2,
    }


def update_tokens(shapes: Mapping[str, Any]) -> int:
    return int(shapes["rollout_steps"]) * int(shapes["num_envs"]) * int(shapes["update_epochs"])


def train_step_flops(config: Dict[str, Any]) -> Dict[str, float]:
    """One update: forward and backward (3x forward) of every token of the
    rollout, ``update_epochs`` times; recomputation is not counted."""
    per_token = forward_flops_per_token(config["shapes"])
    tokens = update_tokens(config["shapes"])
    out = {k: 3.0 * tokens * v for k, v in per_token.items()}
    out["total"] = sum(out.values())
    return out


def delta_rule_work(config: Dict[str, Any]) -> Dict[str, float]:
    """What the ``delta_rule`` scope of one update has to do whatever
    implements it: the recurrent form's FLOPs (forward ``6 dk dv`` a token a
    head, three times that with the backward) and the bytes of q, k, v, a, b
    and o once each way (read or written forward, their gradients backward)."""
    s = config["shapes"]
    dk, dv = s["linear_key_head_dim"], s["linear_value_head_dim"]
    token_heads = update_tokens(s) * s["heads_held"] * sum(kind == LINEAR for kind in s["layer_types"])
    return {"flops": token_heads * 3.0 * 6.0 * dk * dv, "bytes": token_heads * 2.0 * (2 * dk + 2 * dv + 2) * F32_BYTES}


def decode_bytes(config: Dict[str, Any]) -> float:
    """Bytes one decode step of the whole vector must move: every weight held
    except the embedding's untouched rows, the linear layers' state read and
    written.  The cache's read is left out (it grows with the episode): a floor."""
    s = config["shapes"]
    counts = parameter_counts(s)
    weights = sum(v for k, v in counts.items() if k != "embedding") + s["num_envs"] * s["hidden_size"]
    n_linear = sum(kind == LINEAR for kind in s["layer_types"])
    state = n_linear * s["num_envs"] * s["heads_held"] * s["linear_key_head_dim"] * s["linear_value_head_dim"]
    return float(F32_BYTES * (weights + 2 * state))


# -- the program, patched where it builds its agent --------------------------------
def make_policy_weights(template: Any, seed: int) -> Any:
    """The benchmark's weights (``weights.py``: kernels normal with variance
    1/fan_in, norm scales one) and, for the leaves that rule leaves at nought,
    the family's own convention: ``A`` log-uniform in [1, 16] and ``dt``
    log-uniform in [1e-3, 1e-1] a head, so that the decay is a deployment's
    (0.85-0.999 a token) and not ``exp(-softplus(.))``."""
    import jax
    import jax.numpy as jnp

    from benchmarks.chip.weights import make_weights

    params = make_weights(template, seed)
    key = jax.random.PRNGKey(int(seed) ^ 0xA106)
    paths, treedef = jax.tree_util.tree_flatten_with_path(params)
    leaves = []
    for i, (path, leaf) in enumerate(paths):
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "A_log":
            leaf = jnp.log(jax.random.uniform(jax.random.fold_in(key, i), leaf.shape, jnp.float32, 1.0, 16.0)).astype(leaf.dtype)
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(jax.random.fold_in(key, i), leaf.shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
            leaf = (dt + jnp.log(-jnp.expm1(-dt))).astype(leaf.dtype)
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, leaves)


_player_faults: List[Callable] = []  # what a planted fault of the player puts under the loop's three programs (``resets_ignored``)


def install(seed: int, recorder: Any) -> Callable[[], None]:
    """The benchmark's weights go in where the loop builds its agent.  What
    the comparison reads of the player is in the recorded batches (the
    log-probabilities and values it stored), so ``recorder.player`` stays
    empty.  The loop's player is built after its train step: a fault of the
    player that was planted by then is put under it here."""
    from sheeprl_tpu.algos.ppo_recurrent import ppo_recurrent as loop

    original_build, original_player = loop.build_agent, loop.make_token_player

    def build_agent(*args, **kwargs):
        agent, params, sample_obs = original_build(*args, **kwargs)
        return agent, make_policy_weights(params, seed), sample_obs

    def make_token_player(*args, **kwargs):
        programs = original_player(*args, **kwargs)
        for under_the_player in _player_faults:
            programs = under_the_player(*programs)
        return programs

    loop.build_agent, loop.make_token_player = build_agent, make_token_player

    def restore() -> None:
        loop.build_agent, loop.make_token_player = original_build, original_player
        _player_faults.clear()

    return restore


def split_step(args: tuple, out: Optional[tuple]) -> Dict[str, Any]:
    """``train_step(params, opt_state, data, key, coefs)`` returns the new
    two, the mean losses, and every gradient step's losses and gradient norms.
    Of its state the comparison reads the parameters after the first update
    (``opt_state`` is copied once) and none after the last."""
    params, opt_state, data, key, coefs = args
    if out is None:
        return {"params": params, "batch": data, "key": key, "aux": {"coefs": coefs}}
    return {"params": (), "opt_state": {"params": out[0]}, "metrics": {"mean": out[2], **out[3]}}


# -- the faults this step can have ---------------------------------------------------
def unchanged(step: Callable) -> Callable:
    """A step that does its work and returns its parameters as it got them.
    They wait on the host meanwhile: the step donates its arguments, and a
    second copy of them does not fit on the chip beside the update."""
    import jax

    def broken(params, opt_state, *rest):
        kept = jax.tree_util.tree_map(np.asarray, params)
        out = step(params, opt_state, *rest)
        jax.block_until_ready(out[2])
        del params
        return (jax.tree_util.tree_map(jax.numpy.asarray, kept),) + tuple(out[1:])

    return broken


def half_batch(step: Callable) -> Callable:
    """Half of the sequences left out: the second half is overwritten with the first before the step sees them."""
    import jax
    import jax.numpy as jnp

    def broken(params, opt_state, data, key, coefs):
        def first_half_twice(v):
            half = v[:, : v.shape[1] // 2]
            return jnp.concatenate([half, half], axis=1)

        return step(params, opt_state, jax.tree_util.tree_map(first_half_twice, data), key, coefs)

    return broken


def carry_dropped(step: Callable) -> Callable:
    """Training sequences start from zero state and an empty cache."""
    import jax
    import jax.numpy as jnp

    def broken(params, opt_state, data, key, coefs):
        return step(params, opt_state, {**data, "state0": jax.tree_util.tree_map(jnp.zeros_like, data["state0"])}, key, coefs)

    return broken


def epochs_twice(step: Callable) -> Callable:
    """The update's epochs run twice over: the step is one compiled program
    of 8 gradient steps, which a wrapper cannot end early (minibatches
    skipped) but can run again.  The losses and gradient norms it reports are
    the first pass's, so every gradient step it reports is sound: only the
    parameters it returns are 16 steps on."""
    import jax

    def broken(params, opt_state, data, key, coefs):
        first = step(params, opt_state, data, key, coefs)
        again = step(first[0], first[1], data, jax.random.fold_in(key, 1), coefs)
        return tuple(again[:2]) + tuple(first[2:])

    return broken


def resets_ignored(step: Callable) -> Callable:
    """A fault of the *player*: it never starts an episode anew.  The resets
    staged beside the tokens are nought by the time it decodes, so state and
    cache run on through an episode's end; the env, the rows and the learner's
    sequences are sound.  Planted as the harness plants every fault, under the
    train step, which it leaves as it is: the loop builds its player after
    that, and ``install`` puts ``under_the_player`` there."""
    import jax

    def without_resets(program: Callable) -> Callable:
        def sound_but_for_resets(params, carry, staged):
            return program(params, carry, staged.at[1].set(0))

        return sound_but_for_resets

    def under_the_player(policy_step: Callable, value_step: Callable, snapshot_of: Callable) -> tuple:
        return jax.jit(without_resets(policy_step), donate_argnums=(1,)), jax.jit(without_resets(value_step)), snapshot_of

    _player_faults[:] = [under_the_player]
    return step


faults: Dict[str, Callable[[Callable], Callable]] = {
    "unchanged": unchanged, "half_batch": half_batch, "carry_dropped": carry_dropped, "epochs_twice": epochs_twice,
    "resets_ignored": resets_ignored,
}


# -- the replay path -------------------------------------------------------------------
def replay_mismatches(batches: List[Dict[str, np.ndarray]], step_log: Dict[str, np.ndarray], env: Dict[str, Any],
                      seed: int, num_envs: int, vocab: int) -> Dict[str, int]:
    """Rows of the recorded rollouts (``[L, S, 1]``, sequence ``s`` from env
    ``s % num_envs``, rollout ``r`` the env's steps ``r L ...``) that are not
    what the envs emitted when given the actions their logs hold."""
    from benchmarks.chip.tokenenv import emitted

    L, S = batches[0]["actions"].shape[:2]
    chunks = S // num_envs
    steps = len(batches) * L * chunks
    rows_bad = labels_bad = actions_bad = 0
    for e in range(num_envs):
        logged = step_log["actions"][step_log["env"] == e][:steps]
        if len(logged) < steps:
            return {"replay_row_mismatches": steps * num_envs, "replay_action_mismatches": 0, "replay_label_mismatches": 0}
        want = emitted(seed, e, logged, {**env, "vocab": vocab})
        for r, batch in enumerate(batches):
            for c in range(chunks):
                s, lo = c * num_envs + e, (r * chunks + c) * L
                col = lambda k: np.asarray(batch[k])[:, s, 0]  # noqa: E731
                rows_bad += int(np.sum(col("token") != want["token"][lo:lo + L]))
                actions_bad += int(np.sum(col("actions").astype(np.int64) != logged[lo:lo + L]))
                labels_bad += int(np.sum(
                    (col("rewards") != want["rewards"][lo:lo + L]) | (col("dones") != want["dones"][lo:lo + L])
                    | (col("resets") != want["resets"][lo:lo + L])))
    return {"replay_row_mismatches": rows_bad, "replay_action_mismatches": actions_bad, "replay_label_mismatches": labels_bad}


# -- all of it ---------------------------------------------------------------------------
def _sequences(batch: Mapping[str, Any], token: str = "token") -> Dict[str, Any]:
    """A recorded rollout in the reference's layout, on the host: ``[S, T]``
    leaves and each sequence's snapshot (a gigabyte at the cell's size, of
    which the reference puts a few sequences on the device at a time)."""
    import jax

    seq = lambda k, dtype: np.ascontiguousarray(np.asarray(batch[k])[..., 0].T.astype(dtype))  # noqa: E731
    return {
        "tokens": seq(token, np.int32), "resets": seq("resets", np.int32), "actions": seq("actions", np.int32),
        "logprobs": seq("logprobs", np.float32), "values": seq("values", np.float32),
        "advantages": seq("advantages", np.float32), "returns": seq("returns", np.float32),
        "snapshot": jax.tree_util.tree_map(lambda x: np.asarray(x)[0], batch["state0"]),
    }


def minibatches(key: np.ndarray, shapes: Mapping[str, Any]) -> np.ndarray:
    """The sequences of each of an update's gradient steps in turn, from the program's own random stream."""
    import jax

    sequences = shapes["num_envs"] * (shapes["rollout_steps"] // shapes["sequence_length"])
    epochs = jax.random.split(jax.numpy.asarray(key), int(shapes["update_epochs"]))
    drawn = [jax.random.permutation(k, sequences).reshape(int(shapes["num_minibatches"]), -1) for k in epochs]
    return np.concatenate([np.asarray(d) for d in drawn])


def reference_reading(reference_file: Any, config: Dict[str, Any], params_host: Any, step: Dict[str, Any], steps: int,
                      quant: str = "float32", after_host: Any = None) -> Dict[str, Any]:
    """What the reference makes of one recorded update from the parameters it
    began with: the log-probability of every stored action and every value of
    the rollout, whole sequences from their snapshots; then the update
    followed for its first ``steps`` gradient steps: their losses and gradient
    norms.  With ``after_host``, the program's parameters after the whole
    update, also the norm of every leaf's change in the reference
    (``change``), in the program (``program_change``) and between the two
    (``apart``), a leaf at a time on the device."""
    import jax
    import jax.numpy as jnp

    shapes, hyper = config["shapes"], config["hyper"]
    seqs = _sequences(step["batch"])
    snapshot = seqs.pop("snapshot")
    params = jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), params_host)
    read = reference_file.player_readings(shapes, params, seqs, snapshot, quant=quant)
    picked = minibatches(step["key"], shapes)[:steps]
    followed = reference_file.follow_update(shapes, hyper, params, seqs, snapshot, picked, quant=quant,
                                            clip_coef=float(np.asarray(step["aux"]["coefs"][0])))
    del params
    reading = {
        "logprobs": np.asarray(read["logprobs"], np.float64), "values": np.asarray(read["values"], np.float64),
        "losses": np.asarray(followed["losses"], np.float64), "grad_norms": followed["grad_norms"],
        "advantage_scale": [float(np.mean(np.abs(seqs["advantages"][rows]))) for rows in picked],
    }
    if after_host is not None:
        length = lambda x: jnp.sqrt(jnp.sum(jnp.square(x)))  # noqa: E731
        norms = jax.jit(lambda r, a, b: jnp.stack([length(r - b), length(a - b), length(r - a)]))
        leaves = zip(*(jax.tree_util.tree_leaves(t) for t in (followed.pop("params"), after_host, params_host)))
        found = np.asarray([np.asarray(norms(r, jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)), np.float64) for r, a, b in leaves])
        reading.update(change=list(found[:, 0]), program_change=list(found[:, 1]), apart=list(found[:, 2]))
    return reading


def program_reading(step: Dict[str, Any], steps: int, reference: Dict[str, Any]) -> Dict[str, Any]:
    """The same numbers as the timed path left them: what the player stored, what the step reported."""
    seq = lambda k: np.asarray(step["batch"][k], np.float64)[..., 0].T  # noqa: E731
    return {
        "logprobs": seq("logprobs"), "values": seq("values"),
        "losses": np.asarray(step["metrics"]["losses"], np.float64)[:steps],
        "grad_norms": [[float(x) for x in row] for row in np.asarray(step["metrics"]["grad_norms"])[:steps]],
        "change": reference.get("program_change"),
    }


def gaps_between(program: Dict[str, Any], reference: Dict[str, Any]) -> Dict[str, float]:
    """The numbers compared.  Of the gradient steps read, the first is taken
    at parameters both sides have exactly; from the second on each side stands
    where its own steps put it, and the same names with ``.later`` give the
    worst of those steps: readings, which one bfloat16 pass a product moves by
    tenths (PERF.md section 6), and which nothing holds."""
    loss_scale = np.asarray([[max(scale, 1e-6), max(abs(loss[1]), 1e-6), max(abs(loss[2]), 1e-6)]
                             for scale, loss in zip(reference["advantage_scale"], reference["losses"])])
    loss_gaps = np.abs(program["losses"] - reference["losses"]) / loss_scale
    grad_gaps = [float(worst_leaf_gap(p, r)) for p, r in zip(program["grad_norms"], reference["grad_norms"])]
    off = np.abs(program["logprobs"] - reference["logprobs"])
    out = {
        "logprob_gap": float(np.mean(off)), "logprob_gap.worst": float(np.max(off)),
        "value_gap": float(np.linalg.norm(program["values"] - reference["values"]) / max(np.linalg.norm(reference["values"]), 1e-12)),
        "loss_gap.policy": float(loss_gaps[0, 0]), "loss_gap.value": float(loss_gaps[0, 1]), "loss_gap.entropy": float(loss_gaps[0, 2]),
        "grad_gap": grad_gaps[0],
    }
    if len(grad_gaps) > 1:
        later = np.max(loss_gaps[1:], axis=0)
        out.update({"loss_gap.policy.later": float(later[0]), "loss_gap.value.later": float(later[1]), "grad_gap.later": max(grad_gaps[1:])})
    if reference.get("change") is not None:
        out["change_gap"] = float(worst_leaf_gap(program["change"], reference["change"]))
    return out


def compare(recorded: Any, player: Optional[Dict[str, Any]], step_log: Dict[str, np.ndarray], config: Dict[str, Any],
            cell: Dict[str, Any], seed: int, controls: Optional[List[str]] = None) -> Dict[str, Dict[str, Any]]:
    """Every number compared, beside its limit.  ``controls`` also reads the
    reference in the named lower precisions (``bfloat16``) against itself and
    prints that on stderr; it decides nothing."""
    import jax

    if len(recorded.steps) < 3 or recorded.opt_state_after_first is None:
        return {"recorded_steps": {"value": float(len(recorded.steps)), "limit": 3.0, "ok": False}}
    reference_file = load_file(config["reference"], "bench_reference_" + config["name"])
    limits, shapes = cell["limits"], config["shapes"]
    stats = jax.devices()[0].memory_stats() or {}
    print(f"bench: device bytes in use before the reference: {stats.get('bytes_in_use')!r}", file=sys.stderr)

    checks: Dict[str, Dict[str, Any]] = {}
    found = replay_mismatches([s["batch"] for s in recorded.steps], step_log, cell["env"], seed, int(shapes["num_envs"]),
                              int(shapes["vocab_held"]))
    for name, value in found.items():
        checks[name] = {"value": float(value), "limit": 0.0, "ok": value == 0}

    before, after_first = recorded.params_before, recorded.opt_state_after_first["params"]
    pairs = zip(jax.tree_util.tree_leaves(after_first), jax.tree_util.tree_leaves(before))
    moved = float(np.mean([not np.array_equal(a, b) for a, b in pairs]))
    checks["params_moved"] = {"value": moved, "limit": 1.0, "ok": moved == 1.0}
    finite = all(bool(np.all(np.isfinite(np.asarray(v)))) for s in recorded.steps for v in s["metrics"].values())
    checks["losses_finite"] = {"value": float(finite), "limit": 1.0, "ok": finite}

    worst: Dict[str, float] = {}
    names = leaf_names(before)
    every = int(shapes["update_epochs"]) * int(shapes["num_minibatches"])
    # update 1 followed whole from the parameters it began with; of update 2, whose moments are not kept, the first gradient step
    for n, (params_host, steps, after) in enumerate([(before, every, after_first), (after_first, 1, None)]):
        step = recorded.steps[n]
        reference = reference_reading(reference_file, config, params_host, step, steps, after_host=after)
        program = program_reading(step, steps, reference)
        for name, value in gaps_between(program, reference).items():
            print(f"bench: update {n + 1} {name}: {value!r}", file=sys.stderr)
            worst[name] = max(worst.get(name, 0.0), value)
        if after is not None:  # how far apart the two changes lie, where change_gap compares their lengths
            median = float(np.median(reference["change"]))
            apart = float(max(a / max(r, median) for a, r in zip(reference["apart"], reference["change"])))
            print(f"bench: reading update {n + 1} change_apart: {apart!r} (not compared)", file=sys.stderr)
        if controls:
            by_leaf = leaf_gaps(program["grad_norms"][0], reference["grad_norms"][0])
            for i in np.argsort(-np.nan_to_num(np.asarray(by_leaf)))[:3]:
                print(f"bench: update {n + 1} worst gradient norm: {names[i]} gap {by_leaf[i]:.5f}", file=sys.stderr)
            if after is not None:
                by_leaf = leaf_gaps(program["change"], reference["change"])
                for i in np.argsort(-np.nan_to_num(np.asarray(by_leaf)))[:3]:
                    print(f"bench: update {n + 1} worst change: {names[i]} gap {by_leaf[i]:.5f}", file=sys.stderr)
        for control in controls or []:
            lower = reference_reading(reference_file, config, params_host, step, steps, quant=control, after_host=after)
            for name, value in gaps_between(lower, reference).items():
                print(f"bench: control {control} update {n + 1} {name}: {value!r}", file=sys.stderr)
    for name, value in worst.items():
        hold(checks, limits, name, value)
    return checks
