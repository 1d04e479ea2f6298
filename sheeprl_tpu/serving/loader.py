"""Checkpoint discovery, per-algo policy adapters and the promotion health
gate for the serving tier.

A :class:`PolicyHandle` is everything the server needs to turn a checkpoint
into a servable policy, with the algo-specific parts closed over once at build
time: how a request's observation row is validated, how a group of rows is
assembled into one padded device slab, the pure ``(params, obs, key) ->
actions`` step (greedy or stochastic) the service AOT-compiles per batch
bucket, and how a *new* checkpoint's params are converted for a hot swap.

Adapters exist for the feed-forward actor families — ``ppo`` / ``a2c`` (the
shared PPO-style agent) and ``sac`` (the tanh-Gaussian actor) — and, since
the session layer (:mod:`sheeprl_tpu.serving.sessions`), for the stateful
families too: ``ppo_recurrent`` (LSTM carry + previous actions) and
``dreamer_v3`` (RSSM recurrent/stochastic state).  A stateful handle sets
``stateful=True`` and exposes ``make_state_step`` — a pure
``(params, state, obs, is_first, key) -> (actions, new_state)`` step whose
``is_first`` reset handling is bit-identical to the training player; the
service keeps the per-session state resident in a fixed-capacity device slab
and gathers/scatters it around every dispatch (howto/serving.md "Sessions").

The health gate mirrors ``tools/health_diff.py``'s machine check: a candidate
checkpoint is promotable when the training run's journal (the ``version_N``
dir the checkpoint lives under) has no open learning-health anomalies
(:func:`~sheeprl_tpu.diagnostics.health.active_anomalies`).  Standalone
checkpoints without a journal are governed by
``serving.reload.allow_unjournaled``.
"""

from __future__ import annotations

import os
import re
from dataclasses import dataclass, field
from math import prod
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

#: algo name -> handle builder; the public surface for registering new
#: servable families (signature: (cfg, obs_space, action_space, agent_state))
SERVABLE_BUILDERS: Dict[str, Callable] = {}

_CKPT_RE = re.compile(r"ckpt_(\d+)_\d+\.ckpt$")


def checkpoint_step(path: str) -> Optional[int]:
    """Policy step encoded in a checkpoint filename (``ckpt_{step}_{rank}``),
    or None for foreign spellings (those sort by mtime instead)."""
    match = _CKPT_RE.search(os.path.basename(str(path)))
    return int(match.group(1)) if match else None


def latest_checkpoint(ckpt_dir: str) -> Optional[str]:
    """Newest checkpoint in a directory: highest encoded step, falling back
    to mtime for filenames the step pattern does not match."""
    try:
        names = [n for n in os.listdir(str(ckpt_dir)) if n.endswith(".ckpt")]
    except OSError:
        return None
    if not names:
        return None

    def sort_key(name: str) -> Tuple[int, float]:
        step = checkpoint_step(name)
        path = os.path.join(str(ckpt_dir), name)
        try:
            mtime = os.path.getmtime(path)
        except OSError:
            mtime = 0.0
        return (step if step is not None else -1, mtime)

    return os.path.join(str(ckpt_dir), max(names, key=sort_key))


def journal_for_checkpoint(ckpt_path: str) -> Optional[str]:
    """The training run's journal that governs this checkpoint: checkpoints
    land in ``<version_N>/checkpoint/``, the journal in ``<version_N>/``."""
    version_dir = os.path.dirname(os.path.dirname(os.path.abspath(str(ckpt_path))))
    path = os.path.join(version_dir, "journal.jsonl")
    return path if os.path.isfile(path) else None


def checkpoint_health(
    ckpt_path: str,
    health_gate: bool = True,
    allow_unjournaled: bool = True,
) -> Tuple[bool, str, List[Dict[str, Any]]]:
    """Is this checkpoint promotable?  Returns ``(ok, reason, open_anomalies)``.

    The machine check from ISSUE 9's down-payment: read the training run's
    journal next to the checkpoint and refuse promotion while any
    learning-health ``anomaly`` event has no matching ``anomaly_end``.
    """
    if not health_gate:
        return True, "health gate disabled", []
    journal_path = journal_for_checkpoint(ckpt_path)
    if journal_path is None:
        if allow_unjournaled:
            return True, "no training journal (allow_unjournaled)", []
        return False, "no training journal next to the checkpoint", []
    from sheeprl_tpu.diagnostics.health import active_anomalies
    from sheeprl_tpu.diagnostics.journal import read_journal

    open_anomalies = active_anomalies(read_journal(journal_path))
    if open_anomalies:
        kinds = sorted({f"{e.get('kind')}:{e.get('subject')}" for e in open_anomalies})
        return False, f"open learning-health anomalies: {', '.join(kinds)}", open_anomalies
    return True, "journal clean", []


# ---------------------------------------------------------------------------
# the handle
# ---------------------------------------------------------------------------


@dataclass
class PolicyHandle:
    """One servable policy: the algo-specific closures the service drives.

    ``make_step(greedy)`` returns a PURE function of ``(params, obs, key)``
    (jit/AOT-compilable; the key is traced-but-unused on the greedy path so
    both modes share one signature).  ``assemble(rows, width)`` pads a request
    group to the bucket width — the padded rows are zeros and are sliced off
    before any response sees them.  ``load_params`` converts a *new*
    checkpoint's agent state (:func:`agent_state_from_checkpoint`) for an
    atomic hot swap.

    Stateful families (``stateful=True``) additionally carry ``state_spec``
    (per-row recurrent-state arrays, same ``{key: (shape, dtype)}`` layout as
    ``obs_spec``) and ``make_state_step(greedy)`` — a pure
    ``(params, state, obs, is_first, key) -> (actions, new_state)`` where
    ``state`` is a dict of ``[B, ...]`` arrays and ``is_first`` is ``[B, 1]``
    float (1 resets that row to its initial state IN-GRAPH, so reset handling
    compiles into the AOT executable and matches the training player exactly).
    ``make_step`` is None for stateful handles — the service drives the
    session slab path instead.

    ``log_row`` (optional) maps a validated obs row to the per-key arrays the
    request log stores — the seam that lets ``sac`` log the FLAT concatenated
    ``observations`` key offline training expects.
    """

    algo: str
    obs_spec: Dict[str, Tuple[Tuple[int, ...], str]]
    action_shape: Tuple[int, ...]
    params: Any
    make_step: Optional[Callable[[bool], Callable]]
    assemble: Callable[[List[Dict[str, np.ndarray]], int], Any]
    validate: Callable[[Any], Dict[str, np.ndarray]]
    load_params: Callable[[Dict[str, Any]], Any]
    ckpt_path: str = ""
    ckpt_step: int = 0
    meta: Dict[str, Any] = field(default_factory=dict)
    stateful: bool = False
    state_spec: Dict[str, Tuple[Tuple[int, ...], str]] = field(default_factory=dict)
    make_state_step: Optional[Callable[[bool], Callable]] = None
    log_row: Optional[Callable[[Dict[str, np.ndarray]], Dict[str, np.ndarray]]] = None

    def zero_obs(self, width: int) -> Any:
        """A zeros slab at ``width`` (warmup compiles trace against this)."""
        return self.assemble([], width)


def _row_validator(
    obs_spec: Dict[str, Tuple[Tuple[int, ...], str]],
) -> Callable[[Any], Dict[str, np.ndarray]]:
    def validate(obs: Any) -> Dict[str, np.ndarray]:
        if not isinstance(obs, dict):
            raise ValueError(f"obs must be a dict of observation keys, got {type(obs).__name__}")
        row: Dict[str, np.ndarray] = {}
        for key, (shape, dtype) in obs_spec.items():
            if key not in obs:
                raise ValueError(f"obs is missing key {key!r} (expected {sorted(obs_spec)})")
            arr = np.asarray(obs[key], dtype=dtype)
            if int(arr.size) != int(prod(shape) if shape else 1):
                raise ValueError(
                    f"obs[{key!r}] has {arr.size} elements, expected shape {tuple(shape)}"
                )
            row[key] = arr.reshape(shape)
        return row

    return validate


def _dict_assembler(
    obs_spec: Dict[str, Tuple[Tuple[int, ...], str]],
) -> Callable[[List[Dict[str, np.ndarray]], int], Dict[str, np.ndarray]]:
    def assemble(rows: List[Dict[str, np.ndarray]], width: int) -> Dict[str, np.ndarray]:
        slab: Dict[str, np.ndarray] = {}
        for key, (shape, dtype) in obs_spec.items():
            buf = np.zeros((int(width),) + tuple(shape), dtype=dtype)
            for i, row in enumerate(rows):
                buf[i] = row[key]
            slab[key] = buf
        return slab

    return assemble


def _actions_dim(action_space) -> Tuple[Tuple[int, ...], bool, bool]:
    import gymnasium as gym

    is_continuous = isinstance(action_space, gym.spaces.Box)
    is_multidiscrete = isinstance(action_space, gym.spaces.MultiDiscrete)
    actions_dim = tuple(
        action_space.shape
        if is_continuous
        else (action_space.nvec.tolist() if is_multidiscrete else [action_space.n])
    )
    return tuple(int(a) for a in actions_dim), is_continuous, is_multidiscrete


def _jnp_tree(state: Any) -> Any:
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(jnp.asarray, state)


def _ppo_like_handle(cfg, obs_space, action_space, agent_state) -> PolicyHandle:
    """ppo / a2c: the shared feed-forward PPO-style agent — one apply returns
    ``(actions, log_prob, entropy, value)``; serving keeps the actions."""
    import importlib

    agent_module = importlib.import_module(f"sheeprl_tpu.algos.{cfg.algo.name}.agent")
    actions_dim, is_continuous, _ = _actions_dim(action_space)
    agent, params, _ = agent_module.build_agent(
        None, actions_dim, is_continuous, cfg, obs_space, agent_state
    )
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    obs_spec: Dict[str, Tuple[Tuple[int, ...], str]] = {}
    for k in cnn_keys:
        obs_spec[k] = (tuple(obs_space[k].shape), "float32")
    for k in mlp_keys:
        obs_spec[k] = ((int(prod(obs_space[k].shape)),), "float32")

    def make_step(greedy: bool) -> Callable:
        def step(p, obs, key):
            actions, _, _, _ = agent.apply(p, obs, key=key, greedy=greedy)
            return actions

        return step

    action_shape = (sum(actions_dim),) if is_continuous else (len(actions_dim),)
    return PolicyHandle(
        algo=str(cfg.algo.name),
        obs_spec=obs_spec,
        action_shape=action_shape,
        params=params,
        make_step=make_step,
        assemble=_dict_assembler(obs_spec),
        validate=_row_validator(obs_spec),
        load_params=_jnp_tree,
        meta={"is_continuous": is_continuous, "actions_dim": list(actions_dim)},
    )


def _sac_handle(cfg, obs_space, action_space, agent_state) -> PolicyHandle:
    """sac: the tanh-Gaussian actor — greedy is the squashed mean, stochastic
    is ``sample_and_log_prob``.  Vector keys concatenate into the flat obs the
    nets consume (same layout as ``algos/sac/utils.py::prepare_obs``)."""
    from sheeprl_tpu.algos.sac.agent import build_agent

    actor_def, _, params, *_rest = build_agent(None, cfg, obs_space, action_space, agent_state)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    obs_spec = {k: ((int(prod(obs_space[k].shape)),), "float32") for k in mlp_keys}

    def assemble(rows: List[Dict[str, np.ndarray]], width: int) -> np.ndarray:
        dim = sum(shape[0] for shape, _ in obs_spec.values())
        buf = np.zeros((int(width), dim), dtype=np.float32)
        for i, row in enumerate(rows):
            buf[i] = np.concatenate([row[k] for k in mlp_keys], axis=-1)
        return buf

    def make_step(greedy: bool) -> Callable:
        if greedy:

            def step(p, obs, key):
                return actor_def.apply(p["actor"], obs, method="greedy_action")

        else:

            def step(p, obs, key):
                action, _ = actor_def.apply(p["actor"], obs, key, method="sample_and_log_prob")
                return action

        return step

    def log_row(row: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        # the request log stores the FLAT concat the nets consumed — the
        # 'observations' key offline sac/droq training requires
        return {"observations": np.concatenate([row[k] for k in mlp_keys], axis=-1)}

    return PolicyHandle(
        algo="sac",
        obs_spec=obs_spec,
        action_shape=tuple(action_space.shape),
        params=params,
        make_step=make_step,
        assemble=assemble,
        validate=_row_validator(obs_spec),
        load_params=_jnp_tree,
        meta={"is_continuous": True},
        log_row=log_row,
    )


def _ppo_recurrent_handle(cfg, obs_space, action_space, agent_state) -> PolicyHandle:
    """ppo_recurrent: the LSTM agent served statefully.  Per-session state is
    ``{hx, cx, prev_actions}``; the step masks all three by ``1 - is_first``
    BEFORE the apply — exactly the host-side reset the training player does
    (``players.py::LSTMPlayer.begin_step``: ``hx *= (1 - dones)`` etc.) — then advances one
    sequence step and rebuilds ``prev_actions`` (one-hot per discrete head,
    raw actions when continuous) for the next request."""
    from sheeprl_tpu.algos.ppo_recurrent.agent import build_agent

    actions_dim, is_continuous, _ = _actions_dim(action_space)
    agent, params, _ = build_agent(None, actions_dim, is_continuous, cfg, obs_space, agent_state)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    obs_spec: Dict[str, Tuple[Tuple[int, ...], str]] = {}
    for k in cnn_keys:
        obs_spec[k] = (tuple(obs_space[k].shape), "float32")
    for k in mlp_keys:
        obs_spec[k] = ((int(prod(obs_space[k].shape)),), "float32")
    hidden = int(cfg.algo.rnn.lstm.hidden_size)
    act_sum = int(sum(actions_dim))
    state_spec = {
        "hx": ((hidden,), "float32"),
        "cx": ((hidden,), "float32"),
        "prev_actions": ((act_sum,), "float32"),
    }

    def make_state_step(greedy: bool) -> Callable:
        import jax
        import jax.numpy as jnp

        def step(p, state, obs, is_first, key):
            keep = 1.0 - is_first  # [B, 1]; 1 -> fresh episode, zero the carry
            hx = state["hx"] * keep
            cx = state["cx"] * keep
            prev_actions = state["prev_actions"] * keep
            seq_obs = {k: v[None] for k, v in obs.items()}  # [1, B, ...]
            actions, _, _, _, (new_hx, new_cx) = agent.apply(
                p, seq_obs, prev_actions[None], hx, cx, key=key, greedy=greedy
            )
            actions_row = actions[0]  # [B, out]
            if is_continuous:
                next_prev = actions_row
            else:
                next_prev = jnp.concatenate(
                    [
                        jax.nn.one_hot(actions_row[:, j].astype(jnp.int32), d)
                        for j, d in enumerate(actions_dim)
                    ],
                    axis=-1,
                )
            return actions_row, {"hx": new_hx, "cx": new_cx, "prev_actions": next_prev}

        return step

    action_shape = (sum(actions_dim),) if is_continuous else (len(actions_dim),)
    return PolicyHandle(
        algo="ppo_recurrent",
        obs_spec=obs_spec,
        action_shape=action_shape,
        params=params,
        make_step=None,
        assemble=_dict_assembler(obs_spec),
        validate=_row_validator(obs_spec),
        load_params=_jnp_tree,
        meta={"is_continuous": is_continuous, "actions_dim": list(actions_dim)},
        stateful=True,
        state_spec=state_spec,
        make_state_step=make_state_step,
    )


def _dreamer_v3_handle(cfg, obs_space, action_space, agent_state) -> PolicyHandle:
    """dreamer_v3: the world-model policy served statefully.  Per-session
    state is the RSSM triplet ``{recurrent, stochastic, actions}``; resets
    blend the (learnable, params-dependent) initial state in by the
    ``is_first`` mask — the same masked blend as ``PlayerDV3._reset_masked``
    — and the step mirrors ``PlayerDV3._step`` op for op (encode ->
    recurrent_step -> representation -> actor.act).  Image keys travel as
    raw uint8 and are scaled in-graph exactly like ``prepare_obs``."""
    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent

    actions_dim, is_continuous, _ = _actions_dim(action_space)
    state_dict = dict(agent_state or {})
    wm_def, actor_def, _, params = build_agent(
        None,
        actions_dim,
        is_continuous,
        cfg,
        obs_space,
        state_dict.get("world_model"),
        state_dict.get("actor"),
        state_dict.get("critic"),
        state_dict.get("target_critic"),
    )
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    obs_spec: Dict[str, Tuple[Tuple[int, ...], str]] = {}
    for k in cnn_keys:
        obs_spec[k] = (tuple(obs_space[k].shape), "uint8")
    for k in mlp_keys:
        obs_spec[k] = ((int(prod(obs_space[k].shape)),), "float32")
    wm_cfg = cfg.algo.world_model
    recurrent_size = int(wm_cfg.recurrent_model.recurrent_state_size)
    stochastic_size = int(wm_cfg.stochastic_size) * int(wm_cfg.discrete_size)
    act_sum = int(sum(actions_dim))
    state_spec = {
        "recurrent": ((recurrent_size,), "float32"),
        "stochastic": ((stochastic_size,), "float32"),
        "actions": ((act_sum,), "float32"),
    }

    def make_state_step(greedy: bool) -> Callable:
        import jax
        import jax.numpy as jnp

        def step(p, state, obs, is_first, key):
            wm_params, actor_params = p["world_model"], p["actor"]
            n = is_first.shape[0]
            h0, z0 = wm_def.apply(wm_params, (n,), method="initial_states")
            init = {
                "recurrent": h0,
                "stochastic": z0,
                "actions": jnp.zeros((n, act_sum), jnp.float32),
            }
            st = jax.tree_util.tree_map(
                lambda i, s: is_first * i + (1.0 - is_first) * s, init, state
            )
            prepared = {}
            for k in cnn_keys:
                prepared[k] = obs[k].astype(jnp.float32) / 255.0 - 0.5
            for k in mlp_keys:
                prepared[k] = obs[k]
            k1, k2 = jax.random.split(key)
            embedded = wm_def.apply(wm_params, prepared, method="encode")
            recurrent = wm_def.apply(
                wm_params, st["stochastic"], st["actions"], st["recurrent"], method="recurrent_step"
            )
            if wm_def.decoupled_rssm:
                _, stochastic = wm_def.apply(wm_params, None, embedded, k1, method="representation")
            else:
                _, stochastic = wm_def.apply(wm_params, recurrent, embedded, k1, method="representation")
            latent = jnp.concatenate([stochastic, recurrent], axis=-1)
            actions = actor_def.apply(actor_params, latent, k2, greedy, None, method="act")
            return actions, {"recurrent": recurrent, "stochastic": stochastic, "actions": actions}

        return step

    # dreamer actions are the actor's raw output: the one-hot concat for
    # discrete heads (clients argmax per head, like algos/dreamer_v3/utils.py
    # ``test()``), the squashed continuous vector otherwise
    return PolicyHandle(
        algo="dreamer_v3",
        obs_spec=obs_spec,
        action_shape=(act_sum,),
        params=params,
        make_step=None,
        assemble=_dict_assembler(obs_spec),
        validate=_row_validator(obs_spec),
        load_params=_jnp_tree,
        meta={"is_continuous": is_continuous, "actions_dim": list(actions_dim)},
        stateful=True,
        state_spec=state_spec,
        make_state_step=make_state_step,
    )


SERVABLE_BUILDERS.update(
    {
        "ppo": _ppo_like_handle,
        "a2c": _ppo_like_handle,
        "sac": _sac_handle,
        "ppo_recurrent": _ppo_recurrent_handle,
        "dreamer_v3": _dreamer_v3_handle,
    }
)

#: checkpoint keys that make up a Dreamer-family agent state (those runs
#: checkpoint each module separately instead of one "agent" tree)
DREAMER_STATE_KEYS = ("world_model", "actor", "critic", "target_critic")


def agent_state_from_checkpoint(state: Mapping[str, Any]) -> Dict[str, Any]:
    """The servable agent state inside a loaded checkpoint: ``state["agent"]``
    for the single-tree families, the per-module dict for the Dreamer family
    (``world_model``/``actor``/...)."""
    if "agent" in state:
        return state["agent"]
    if "world_model" in state:
        return {k: state[k] for k in DREAMER_STATE_KEYS if k in state}
    raise ValueError(
        f"checkpoint has no servable agent state (keys: {sorted(state)}); expected "
        f"'agent' or the Dreamer module keys {list(DREAMER_STATE_KEYS)}"
    )


def build_policy(cfg, obs_space, action_space, agent_state: Optional[Dict[str, Any]] = None) -> PolicyHandle:
    """Adapter dispatch: ``cfg.algo.name`` -> :class:`PolicyHandle` (random
    init params when ``agent_state`` is None)."""
    algo = str(cfg.algo.name)
    builder = SERVABLE_BUILDERS.get(algo)
    if builder is None:
        raise ValueError(
            f"Algorithm {algo!r} has no servable adapter; registered builders: "
            f"{sorted(SERVABLE_BUILDERS)}.  Stateless actors register a plain "
            "make_step handle; recurrent/model-based families register a stateful "
            "handle served through the session layer (howto/serving.md 'Sessions')"
        )
    return builder(cfg, obs_space, action_space, agent_state)


def load_policy(cfg, ckpt_path: str) -> PolicyHandle:
    """Checkpoint -> :class:`PolicyHandle`: read the state, rebuild the obs /
    action spaces the way the evaluation entrypoints do (one throwaway env —
    the spaces are not archived anywhere else), then adapter-dispatch."""
    import gymnasium as gym

    from sheeprl_tpu.envs.env import make_env
    from sheeprl_tpu.utils.checkpoint import load_state

    state = load_state(str(ckpt_path))
    try:
        agent_state = agent_state_from_checkpoint(state)
    except ValueError as err:
        raise ValueError(f"Checkpoint '{ckpt_path}': {err}") from None
    cfg.env.capture_video = False
    env = make_env(cfg, cfg.seed, 0, None, "serve")()
    try:
        obs_space = env.observation_space
        action_space = env.action_space
    finally:
        env.close()
    if not isinstance(obs_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation space (need a Dict): {obs_space}")
    handle = build_policy(cfg, obs_space, action_space, agent_state)
    handle.ckpt_path = str(ckpt_path)
    handle.ckpt_step = checkpoint_step(ckpt_path) or 0
    return handle
