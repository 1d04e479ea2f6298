"""The sparse-attention-expert-policy-under-PPO family: ``exp=ppo_recurrent_sparse_moe``,
the recurrent on-policy loop with ``models/sparse_moe_lm.py`` as its backbone.

What it answers is the whole of what a family brings (``manifest.FAMILY_ANSWERS``;
PERF.md section 3).  One call of the loop's ``train_step`` is one *update*.
:func:`compare` holds what the timed path produced, as
``families/olmo_hybrid_ppo.py`` does for its backbone and with its pieces (the
replay path, the player against the full-sequence forward, the first update
followed through AdamW, the first gradient step of a later one), and beside
them what is new here:

- the later update read is the *third*: its rollout is the first in which an
  episode outgrows ``topk``, so that the selection bites in what is compared
  (``split_step`` keeps the parameters that update began with);

- ``loss_gap.index``: the indexers' loss of the first gradient step;
- ``grad_gap.indexer`` and ``grad_gap.experts``: the worst leaf of each kind
  apart (``grad_gap`` is over every leaf);
- ``attended_share_gap``: the update's own count of attended over visible
  positions against the reference's, which is exact (``jax.lax.top_k`` of every row).

The *work* functions count the published algorithm's work from shapes and
from the positions the run recorded (``sheeprl_policy_visible_positions_total``
and ``..._attended_positions_total`` on ``/metrics``, the host's mirror summed
over the window's vector steps: a decoded token and the same token as a query
of the update see the same positions), the same whatever implements a scope.
Each is a floor: the least any implementation must do.
"""

from __future__ import annotations

import dataclasses
import sys
from typing import Any, Callable, Dict, List, Mapping, Optional

import numpy as np

from benchmarks.chip.check import hold, leaf_gaps, leaf_names, worst_leaf_gap
from benchmarks.chip.families import olmo_hybrid_ppo as standing
from benchmarks.chip.manifest import load_file

executables = {"train_step": "jit_update", "player": "jit_policy_step"}
# the jax.named_scopes of the update (models/sparse_moe_lm.py, algos/ppo_recurrent); an operation goes to the first its path names
train_step_scopes = ("embed", "attn_proj", "index_score", "select", "sparse_attention", "index_loss", "moe_route", "moe_experts",
                     "vocab_head", "ppo_loss", "optim")

env_group = "tokenbench"
env_overrides = standing.env_overrides  # the cell's ``env`` holds ``vocab`` too: the group's file reads another backbone's
F32_BYTES, BF16_BYTES = 4, 2
VISIBLE, ATTENDED, ENV_STEPS = "sheeprl_policy_visible_positions_total", "sheeprl_policy_attended_positions_total", "sheeprl_env_steps_total"


# -- work from shapes ------------------------------------------------------------
def parameter_counts(s: Mapping[str, Any]) -> Dict[str, int]:
    """Parameters this chip holds, by what they are."""
    D, n = s["hidden_size"], s["num_layers"]
    q, kv = s["num_heads"] * s["head_dim"], s["num_kv_heads"] * s["head_dim"]
    return {
        "attention_matmul": n * (2 * D * q + 2 * D * kv),
        "indexer_matmul": n * D * (s["indexer_heads"] * s["indexer_head_dim"] + s["indexer_head_dim"] + s["indexer_heads"]),
        "router_matmul": n * D * s["experts_total"],
        "experts_matmul": n * s["experts_held"] * 3 * D * s["expert_width"],
        "head_matmul": D * s["vocab_held"] + D,
        "embedding": s["vocab_held"] * D,
        "other": n * (2 * D + 2 * s["head_dim"] + 2 * s["indexer_head_dim"]) + D,
    }


def forward_flops_per_token(s: Mapping[str, Any], visible: float, attended: float, picks_held: float) -> Dict[str, float]:
    """FLOPs one token needs going forward, a layer's parts summed over the
    layers (2 a multiply-add): the projections at their widths, the index score
    over the ``visible`` positions, attention over the ``attended`` ones, and the
    ``picks_held`` picks (of ``experts_per_token``) that fell on held experts."""
    counts, n = parameter_counts(s), s["num_layers"]
    return {
        "matmul": 2.0 * (counts["attention_matmul"] + counts["indexer_matmul"] + counts["router_matmul"] + counts["head_matmul"]),
        "index_score": n * 2.0 * s["indexer_heads"] * s["indexer_head_dim"] * visible,
        "sparse_attention": n * 4.0 * s["num_heads"] * s["head_dim"] * attended,
        "experts": n * picks_held * 3 * 2.0 * s["hidden_size"] * s["expert_width"],
    }


def update_tokens(s: Mapping[str, Any]) -> int:
    return int(s["rollout_steps"]) * int(s["num_envs"]) * int(s["update_epochs"])


def train_step_flops(config: Dict[str, Any]) -> Dict[str, float]:
    """One update: forward and backward (3x forward) of every token of the
    rollout, ``update_epochs`` times; recomputation is not counted.  The work
    that depends on positions and picks is taken at the traffic's expectation
    (``shapes.expected``: the configuration's file says how each was reckoned);
    the roofline readers count it from the run's own positions."""
    s = config["shapes"]
    expected = s["expected"]
    per_token = forward_flops_per_token(s, expected["visible"], expected["attended"], s["experts_per_token"] * s["experts_held"] / s["experts_total"])
    out = {k: 3.0 * update_tokens(s) * v for k, v in per_token.items()}
    out["total"] = sum(out.values())
    return out


def window_positions(run: Mapping[str, Any]) -> Optional[Dict[str, float]]:
    """Mean visible and attended positions a query of a layer over the window, from the program's counters."""
    s0, s1 = run["scrapes"]
    queries = s1.get(ENV_STEPS, 0.0) - s0.get(ENV_STEPS, 0.0)
    if VISIBLE not in s1 or queries <= 0:
        return None
    return {"visible": (s1[VISIBLE] - s0.get(VISIBLE, 0.0)) / queries, "attended": (s1[ATTENDED] - s0.get(ATTENDED, 0.0)) / queries}


def picks_held_share(run: Mapping[str, Any]) -> Optional[float]:
    """The share of the router's picks that fell on held experts over the window's updates."""
    return _mean_over_updates(run, "sheeprl_policy_picks_held_share_sum")


def attended_share(run: Mapping[str, Any]) -> Optional[float]:
    """Attended over visible positions, over the window's updates (the update's own count)."""
    return _mean_over_updates(run, "sheeprl_policy_attended_share_sum")


def _mean_over_updates(run: Mapping[str, Any], name: str) -> Optional[float]:
    s0, s1 = run["scrapes"]
    updates = s1.get("sheeprl_policy_updates_total", 0.0) - s0.get("sheeprl_policy_updates_total", 0.0)
    return None if name not in s1 or updates <= 0 else (s1[name] - s0.get(name, 0.0)) / updates


def update_work(run: Mapping[str, Any]) -> Optional[Dict[str, Dict[str, float]]]:
    """FLOPs and bytes of one update's new kernels, each a floor:

    - ``index_score``: ``2 Hi di`` a visible position a query, three times with the backward (its loss differentiates it);
    - ``select``: every visible score read once (4 B) and the selection written as positions (4 B an attended one);
      not differentiated; its sort or search is not counted;
    - ``sparse_attention``: ``4 Hq dh`` an attended position a query, three times with the backward;
    - ``experts``: the three products of every pick on a held expert, three times with the backward."""
    s, found, held = run["config"]["shapes"], window_positions(run), picks_held_share(run)
    if found is None or held is None:
        return None
    per_token = forward_flops_per_token(s, found["visible"], found["attended"], s["experts_per_token"] * held)
    tokens, n = update_tokens(s), s["num_layers"]
    return {
        "index_score": {"flops": 3.0 * tokens * per_token["index_score"], "bytes": 0.0},
        "select": {"flops": 0.0, "bytes": tokens * n * F32_BYTES * (found["visible"] + found["attended"])},
        "sparse_attention": {"flops": 3.0 * tokens * per_token["sparse_attention"], "bytes": 0.0},
        "moe_experts": {"flops": 3.0 * tokens * per_token["experts"], "bytes": 0.0},
    }


def decode_bytes(run: Mapping[str, Any]) -> Optional[float]:
    """Bytes one decode step of the whole vector must move: the view's
    kernels at the width the view holds them (the operands of MXU products as
    bfloat16, the indexer's and the router's float32), the embedding's rows
    read, the index keys of the positions held, the keys and values of the
    selected rows, the three rows written.  A floor."""
    s, found = run["config"]["shapes"], window_positions(run)
    if found is None:
        return None
    counts = parameter_counts(s)
    kernels = BF16_BYTES * (counts["attention_matmul"] + counts["experts_matmul"] + counts["head_matmul"] - s["hidden_size"])
    kernels += F32_BYTES * (counts["indexer_matmul"] + counts["router_matmul"] + counts["other"] + s["hidden_size"] + s["num_envs"] * s["hidden_size"])
    row = 2 * s["num_kv_heads"] * s["head_dim"]
    cache = s["num_envs"] * s["num_layers"] * F32_BYTES * (found["visible"] * s["indexer_head_dim"] + found["attended"] * row + row + s["indexer_head_dim"])
    return float(kernels + cache)


# -- the program, patched where it builds its agent --------------------------------
NORM_SCALES = {"attn/q_norm/scale": (1.3, 2.1), "attn/k_norm/scale": (1.3, 2.1), "indexer/k_norm/scale": (2.0, 4.0)}


def make_policy_weights(template: Any, seed: int) -> Any:
    """The benchmark's weights (``weights.py``: kernels normal with variance
    1/fan_in, norm scales one) and the family's own for what that rule does not
    fit: a stack of experts' kernels with the variance of one expert's fan-in,
    and the scales of the head norms of ``q`` and ``k`` and of the indexer's
    LayerNorm drawn uniformly from :data:`NORM_SCALES`, so that a head's
    attention weights and the index scores are peaked (with unit scales
    attention over thousands of random keys is all but uniform, and a program
    that attended every position would read the same numbers; the
    configuration's file states the entropy reached)."""
    import jax
    import jax.numpy as jnp

    from benchmarks.chip.weights import make_weights

    params = make_weights(template, seed)
    key = jax.random.PRNGKey(int(seed) ^ 0x5A0E)
    paths, treedef = jax.tree_util.tree_flatten_with_path(params)
    leaves = []
    for i, (path, leaf) in enumerate(paths):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if name.rsplit("/", 1)[-1] in ("w1", "w3", "w2"):
            leaf = (jax.random.normal(jax.random.fold_in(key, i), leaf.shape, jnp.float32) / np.sqrt(leaf.shape[-2])).astype(leaf.dtype)
        for norm, (low, high) in NORM_SCALES.items():
            if name.endswith(norm):
                leaf = jax.random.uniform(jax.random.fold_in(key, i), leaf.shape, jnp.float32, low, high).astype(leaf.dtype)
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def install(seed: int, recorder: Any) -> Callable[[], None]:
    """The benchmark's weights go in where the loop builds its agent, as the
    standing family's do; a fault of the player goes under its programs as
    there.  A fault of the *model* (the four new ones) is a wrong
    configuration: the loop's programs are traced at their first call, after
    the harness has planted the fault, so the agent built here is kept and
    the fault re-states its configuration before anything is traced."""
    from sheeprl_tpu.algos.ppo_recurrent import ppo_recurrent as loop

    original_build, original_player = loop.build_agent, loop.make_token_player

    def build_agent(*args, **kwargs):
        agent, params, sample_obs = original_build(*args, **kwargs)
        _built[:] = [agent]
        return agent, make_policy_weights(params, seed), sample_obs

    def make_token_player(*args, **kwargs):
        programs = original_player(*args, **kwargs)
        for under_the_player in standing._player_faults:
            programs = under_the_player(*programs)
        return programs

    loop.build_agent, loop.make_token_player = build_agent, make_token_player

    def restore() -> None:
        loop.build_agent, loop.make_token_player = original_build, original_player
        standing._player_faults.clear()
        _built.clear()
        _updates_seen.clear()

    return restore


_built: List[Any] = []  # the agent the loop built in this run
_updates_seen: List[int] = []  # one entry for every update whose arguments ``split_step`` has been shown in this run
HELD_UPDATES = (0, 2)  # the recorded updates the comparison reads: the first, followed whole, and the third's first gradient step


def split_step(args: tuple, out: Optional[tuple]) -> Dict[str, Any]:
    """The standing family's reading of the step, and the parameters the
    *third* update began with beside its coefficients: its rollout is the first
    in which an episode outgrows ``topk``, and the Recorder keeps the
    parameters of the first update only."""
    found = standing.split_step(args, out)
    if out is None:
        _updates_seen.append(len(_updates_seen))
        if _updates_seen[-1] == HELD_UPDATES[-1]:
            found["aux"] = {**found["aux"], "params": args[0]}
    return found


# -- the faults ------------------------------------------------------------------------
def _model_fault(**wrong: Any) -> Callable[[Callable], Callable]:
    """A model that runs under a configuration other than the one it states:
    every program of the loop (the player's and the update's) is traced from it."""

    def plant(step: Callable) -> Callable:
        agent = _built[0]
        changed = {k: v(agent.config) if callable(v) else v for k, v in wrong.items()}
        object.__setattr__(agent, "config", dataclasses.replace(agent.config, **changed))  # a flax module is frozen: the fault is not
        return step

    return plant


def half_batch(step: Callable) -> Callable:
    """The standing family's fault, made in place: the second half of the
    sequences overwritten with the first in a program that is donated the
    batch (a second copy of a 2.28 GB snapshot does not fit beside this
    update; the loop deletes its own reference after the step)."""
    import jax

    def first_half_twice(v):
        half = v.shape[1] // 2
        return v.at[:, half:].set(v[:, :half])

    overwrite = jax.jit(lambda data: jax.tree_util.tree_map(first_half_twice, data), donate_argnums=0)
    return lambda params, opt_state, data, key, coefs: step(params, opt_state, overwrite(data), key, coefs)


def carry_dropped(step: Callable) -> Callable:
    """Training sequences start from an empty cache: the snapshot's count of
    held positions is nought (what the rows hold is then seen by no query), and
    nothing of its 2.28 GB is copied."""
    import jax.numpy as jnp

    def broken(params, opt_state, data, key, coefs):
        return step(params, opt_state, {**data, "state0": {**data["state0"], "pos": jnp.zeros_like(data["state0"]["pos"])}}, key, coefs)

    return broken


faults: Dict[str, Callable[[Callable], Callable]] = {
    **standing.faults,
    "half_batch": half_batch,
    "carry_dropped": carry_dropped,
    # attends every visible position: the selection never bites
    "selection_skipped": _model_fault(topk=lambda c: c.cache_len),
    "topk_halved": _model_fault(topk=lambda c: c.topk // 2),
    # the indexer never learns: its loss is reported and not added
    "index_loss_dropped": _model_fault(index_loss_coef=0.0),
    "gates_unnormalised": _model_fault(norm_topk_prob=False),
}


# -- all of it ---------------------------------------------------------------------------
LOSSES = ("policy", "value", "entropy", "index")


def gaps_between(program: Dict[str, Any], reference: Dict[str, Any], names: List[str]) -> Dict[str, float]:
    """The numbers compared: the standing family's, of the first gradient step
    read (the later ones are readings), and the new ones.  A loss's gap is over
    the reference's own size (the policy loss's over the advantages'); the two
    shares' gaps are absolute."""
    reported = np.asarray(reference["losses"])
    scale = np.concatenate([np.maximum(np.asarray(reference["advantage_scale"])[:, None], 1e-6), np.maximum(np.abs(reported[:, 1:4]), 1e-6)], axis=1)
    loss_gaps = np.abs(program["losses"][:, :4] - reported[:, :4]) / scale
    off = np.abs(program["logprobs"] - reference["logprobs"])
    out = {
        "logprob_gap": float(np.mean(off)), "logprob_gap.worst": float(np.max(off)),
        "value_gap": float(np.linalg.norm(program["values"] - reference["values"]) / max(np.linalg.norm(reference["values"]), 1e-12)),
        **{f"loss_gap.{name}": float(loss_gaps[0, i]) for i, name in enumerate(LOSSES)},
        "attended_share_gap": float(abs(program["losses"][0, 4] - reported[0, 4])),
        "picks_held_share_gap": float(abs(program["losses"][0, 5] - reported[0, 5])),
    }
    indexer = ["/indexer/" in name for name in names]
    experts = [name.split("/")[-1] in ("w1", "w3", "w2") or "/router/" in name for name in names]
    grad_gaps = [{
        "grad_gap": float(worst_leaf_gap(p, r)), "grad_gap.indexer": float(worst_leaf_gap(p, r, keep=indexer)),
        "grad_gap.experts": float(worst_leaf_gap(p, r, keep=experts)),
    } for p, r in zip(program["grad_norms"], reference["grad_norms"])]
    out.update(grad_gaps[0])
    if len(grad_gaps) > 1:
        later = np.max(loss_gaps[1:], axis=0)
        out.update({f"loss_gap.{name}.later": float(later[i]) for i, name in enumerate(LOSSES) if name != "entropy"})
        out["grad_gap.later"] = max(g["grad_gap"] for g in grad_gaps[1:])
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
    found = standing.replay_mismatches([s["batch"] for s in recorded.steps], step_log, cell["env"], seed, int(shapes["num_envs"]),
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
    # update 1 followed whole from the parameters it began with; of update 3, whose rollout is the first in which the selection
    # bites (every env passes ``topk`` positions, ends its first episode and restarts), the first gradient step: its moments are not kept
    third = recorded.steps[HELD_UPDATES[-1]]["aux"]["params"]
    for n, (params_host, steps, after) in zip(HELD_UPDATES, [(before, every, after_first), (third, 1, None)]):
        step = recorded.steps[n]
        reference = standing.reference_reading(reference_file, config, params_host, step, steps, after_host=after)
        program = standing.program_reading(step, steps, reference)
        for name, value in gaps_between(program, reference, names).items():
            print(f"bench: update {n + 1} {name}: {value!r}", file=sys.stderr)
            worst[name] = max(worst.get(name, 0.0), value)
        print(f"bench: reading update {n + 1} attended_share: {float(reference['losses'][0, 4])!r} "
              f"picks_held_share: {float(reference['losses'][0, 5])!r} index_loss: {float(reference['losses'][0, 3])!r} (not compared)", file=sys.stderr)
        if controls:
            for what, key in (("gradient norm", "grad_norms"), ("change", "change")):
                if key == "change" and after is None:
                    continue
                pair = (program[key][0], reference[key][0]) if key == "grad_norms" else (program[key], reference[key])
                by_leaf = leaf_gaps(*pair)
                for i in np.argsort(-np.nan_to_num(np.asarray(by_leaf)))[:3]:
                    print(f"bench: update {n + 1} worst {what}: {names[i]} gap {by_leaf[i]:.5f}", file=sys.stderr)
        for control in controls or []:
            lower = standing.reference_reading(reference_file, config, params_host, step, steps, quant=control, after_host=after)
            for name, value in gaps_between(lower, reference, names).items():
                print(f"bench: control {control} update {n + 1} {name}: {value!r}", file=sys.stderr)
    for name, value in worst.items():
        hold(checks, limits, name, value)
    return checks
