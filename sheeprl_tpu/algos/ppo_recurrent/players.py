"""The players of the recurrent on-policy loop: what a policy carries through a
rollout, and where.  Two classes of one surface; the loop names neither:

- ``evaluate(params, batch)``: the forward ``loss_fn`` differentiates, from each training
  sequence's initial state: log-probabilities, entropy, values, ``[L, S, 1]``;
- ``start(diag, keys, num_envs, rollout_steps, seq_len)``: the rollout's programs and the carried state;
- a vector step: ``begin_step(prev_dones)`` (an ended episode and a training sequence's
  start reach the carried state), ``stage(obs, prev_dones)``, ``act(params, staged)``,
  ``fetch(out)`` (the actions on the host, ``[N, A]``, and what the step adds to the
  replay row beside the common keys);
- a rollout's end: ``end_rollout(params, obs, prev_dones)`` (the bootstrap value, and what
  the player kept of the rollout beside the row), ``initial_state(local)`` (what each
  training sequence starts from, ``[1, S, ...]``, sequence ``s = chunk * N + env``);
- ``test(params, log_dir)``: the greedy test episode's return, ``None`` where there is none.

:class:`LSTMPlayer` is the reference's: ``hx``, ``cx`` a row per env, stored with every
step.  :class:`TokenPlayer` drives any model of ``models/hybrid_lm.py``'s contract
(``apply(params, tokens, resets, state, decode=, write=)``, ``init_state(n)``): the carried
state is a pytree that stays on the device through the rollout, is donated to
``policy_step``, is copied once where a training sequence starts (the learner's constant,
as ``hx0``/``cx0`` are) and never reaches the host; the rollout's log-probabilities and
values are kept beside it and fetched once a rollout; resets are in-graph.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.algos.ppo_recurrent.agent import token_key
from sheeprl_tpu.algos.ppo_recurrent.utils import prepare_obs, test
from sheeprl_tpu.envs.env import make_env
from sheeprl_tpu.models.hybrid_lm import carry_bytes
from sheeprl_tpu.parallel.precision import cast_floating, compute_dtype_of


def make_token_player(agent, cfg, rollout_steps: int):
    """The token policy's three programs of the rollout.  ``policy_step``
    decodes one token an env through the carried state, which it is donated
    (a cache of a gigabyte is written in place, not copied a token), samples
    the next token and stores its log-probability and the value at the
    rollout's step ``t``; ``value_step`` reads the value of the next
    observation and writes nothing; ``snapshot_of`` copies the carried state
    where a training sequence starts.  ``staged`` is ``[2, N]`` int32: the
    observed tokens and the resets."""
    cdt = compute_dtype_of(cfg)

    def policy_step(params, carry, staged):
        tokens, resets = staged[0][:, None], staged[1][:, None]
        logits, values, state = agent.apply(cast_floating(params, cdt), tokens, resets, carry["state"], decode=True)
        key, sample_key = jax.random.split(carry["key"])
        logp_all = jax.nn.log_softmax(logits[:, 0], axis=-1)
        actions = jax.random.categorical(sample_key, logp_all, axis=-1)
        logprobs = jnp.take_along_axis(logp_all, actions[:, None], axis=-1)[:, 0]
        t = carry["t"] % rollout_steps
        carry = {
            "state": state,
            "key": key,
            "t": carry["t"] + 1,
            "logprobs": carry["logprobs"].at[t].set(logprobs),
            "values": carry["values"].at[t].set(values[:, 0]),
        }
        return actions.astype(jnp.int32), carry

    def value_step(params, carry, staged):
        tokens, resets = staged[0][:, None], staged[1][:, None]
        return agent.apply(cast_floating(params, cdt), tokens, resets, carry["state"], decode=True, write=False)[1][:, 0]

    def snapshot_of(state):
        return jax.tree_util.tree_map(jnp.copy, state)

    return jax.jit(policy_step, donate_argnums=(1,)), jax.jit(value_step), jax.jit(snapshot_of)


class TokenPlayer:
    def __init__(self, agent, cfg):
        self.agent, self.cfg, self.key = agent, cfg, token_key(cfg)

    def evaluate(self, params, batch):
        """Leaves are time-major ``[L, S, 1]``, the model's batch-major."""
        tokens = batch[self.key][..., 0].T.astype(jnp.int32)
        resets = batch["resets"][..., 0].T.astype(jnp.int32)
        state0 = jax.tree_util.tree_map(lambda x: x[0], batch["state0"])
        logits, values, _ = self.agent.apply(cast_floating(params, compute_dtype_of(self.cfg)), tokens, resets, state0)
        with jax.named_scope("ppo_loss"):
            logp_all = jax.nn.log_softmax(logits, axis=-1)
            actions = batch["actions"][..., 0].T.astype(jnp.int32)
            logprobs = jnp.take_along_axis(logp_all, actions[..., None], axis=-1)
            entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1, keepdims=True)
        return logprobs.swapaxes(0, 1), entropy.swapaxes(0, 1), values.T[..., None]

    def start(self, diag, keys, num_envs, rollout_steps, seq_len):
        # through the loop's module, now: the benchmark's families replace the name there, and a planted fault of
        # the player goes under these programs only once the train step is instrumented
        from sheeprl_tpu.algos.ppo_recurrent import ppo_recurrent as loop

        self.policy_step, self.value_step, self.snapshot_of = loop.make_token_player(self.agent, self.cfg, rollout_steps)
        self.carry = {
            "state": self.agent.init_state(num_envs),
            "key": keys.next(),
            "t": jnp.zeros((), jnp.int32),
            "logprobs": jnp.zeros((rollout_steps, num_envs), jnp.float32),
            "values": jnp.zeros((rollout_steps, num_envs), jnp.float32),
        }
        self.carry_nbytes = carry_bytes(self.carry["state"])
        diag.register_footprint("policy_carry", self.carry_nbytes)
        self.positions = np.zeros(num_envs, np.int64)  # the host's mirror of the caches' lengths
        self.diag, self.num_envs, self.seq_len = diag, num_envs, seq_len
        self.steps, self.snapshots = 0, []  # vector steps taken; the copies where this rollout's sequences start

    def begin_step(self, prev_dones):
        if self.steps % self.seq_len == 0:
            # where a training sequence starts: the learner's constant, one copy on the device
            self.snapshots.append(self.snapshot_of(self.carry["state"]))
        self.steps += 1
        self.positions = np.where(prev_dones[:, 0] > 0, 0, self.positions) + 1
        self.diag.note_policy_state(int(prev_dones.sum()), int(self.positions.sum()), self.carry_nbytes)

    def stage(self, obs, prev_dones):
        """The observed tokens and the resets, staged together: ``[2, N]`` int32, which the
        call into the program puts on the device (a ``device_put`` of its own ahead of the
        call costs the vector step 0.3 ms more: PERF.md section 6, PR 31)."""
        tokens = np.asarray(obs[self.key]).reshape(self.num_envs)
        return np.stack([tokens, prev_dones[:, 0]]).astype(np.int32)

    def act(self, params, staged):
        actions, self.carry = self.policy_step(params, self.carry, staged)  # the step's one put rides the call
        return actions

    def fetch(self, out):
        # the step's one fetch; the observed token is the next input, and the rest of a row stays on the device
        return np.asarray(out).reshape(self.num_envs, 1), {}

    def end_rollout(self, params, obs, prev_dones):
        next_values = np.asarray(self.value_step(params, self.carry, self.stage(obs, prev_dones))).reshape(self.num_envs, 1)
        # the rollout's one fetch of what the player stored while decoding
        return next_values, {k: np.asarray(self.carry[k])[..., None] for k in ("logprobs", "values")}

    def initial_state(self, local):
        snapshots, self.snapshots = self.snapshots, []
        return {"state0": jax.tree_util.tree_map(lambda *xs: jnp.concatenate(xs, axis=0)[None], *snapshots)}

    def test(self, params, log_dir):
        return None  # sampling is this policy's decoding: it has no greedy episode


class LSTMPlayer:
    def __init__(self, agent, cfg, greedy=False):
        self.agent, self.cfg, self.greedy = agent, cfg, greedy
        self.obs_keys = dict(cnn_keys=list(agent.cnn_keys), mlp_keys=list(agent.mlp_keys))

    def evaluate(self, params, batch):
        cdt = compute_dtype_of(self.cfg)
        _, logprobs, entropy, values, _ = self.agent.apply(
            cast_floating(params, cdt),
            cast_floating({k: batch[k] for k in self.obs_keys["cnn_keys"] + self.obs_keys["mlp_keys"]}, cdt),
            cast_floating(batch["prev_actions"], cdt),
            cast_floating(batch["hx0"][0], cdt),
            cast_floating(batch["cx0"][0], cdt),
            resets=batch["resets"],
            actions=batch["actions"],
        )
        return logprobs, entropy, values

    def start(self, diag, keys, num_envs, rollout_steps, seq_len):
        agent, greedy = self.agent, self.greedy

        @jax.jit
        def policy_step(params, obs, prev_actions, hx, cx, key):
            actions, logprobs, _, values, (hx, cx) = agent.apply(params, obs, prev_actions, hx, cx, key=key, greedy=greedy)
            return actions, logprobs, values, hx, cx

        @jax.jit
        def value_step(params, obs, prev_actions, hx, cx):
            return agent.apply(params, obs, prev_actions, hx, cx, method="get_values")

        self.policy_step, self.value_step = policy_step, value_step
        self.keys, self.num_envs, self.seq_len = keys, num_envs, seq_len
        self.hx = self.cx = jnp.zeros((num_envs, self.cfg.algo.rnn.lstm.hidden_size), jnp.float32)
        self.prev_actions = np.zeros((num_envs, int(sum(self.agent.actions_dim))), np.float32)

    def begin_step(self, prev_dones):
        # reset state on done BEFORE stepping (reference resets at episode starts)
        if self.cfg.algo.reset_recurrent_state_on_done and prev_dones.any():
            mask = jnp.asarray(1.0 - prev_dones, jnp.float32)
            self.hx, self.cx = self.hx * mask, self.cx * mask
            self.prev_actions = self.prev_actions * (1.0 - prev_dones)

    def stage(self, obs, prev_dones):
        key = self.keys.next()
        obs = prepare_obs(obs, num_envs=self.num_envs, **self.obs_keys)
        self.row = {"prev_actions": self.prev_actions, "hx": np.asarray(self.hx), "cx": np.asarray(self.cx)}
        return obs, key

    def act(self, params, staged):
        obs, key = staged
        *out, self.hx, self.cx = self.policy_step(params, obs, jnp.asarray(self.prev_actions)[None], self.hx, self.cx, key)
        return out

    def fetch(self, out):
        actions, logprobs, values = (np.asarray(x)[0] for x in out)
        self.row.update(logprobs=logprobs.reshape(self.num_envs, -1), values=values.reshape(self.num_envs, -1))
        # prev-action input to the RNN is one-hot for discrete heads
        # (reference ppo_recurrent.py:284,356: dim = sum(actions_dim))
        if self.agent.is_continuous:
            self.prev_actions = actions.reshape(self.num_envs, -1).astype(np.float32)
        else:
            onehots = [np.eye(d, dtype=np.float32)[actions[:, j].astype(np.int64)] for j, d in enumerate(self.agent.actions_dim)]
            self.prev_actions = np.concatenate(onehots, axis=-1)
        return actions, self.row

    def end_rollout(self, params, obs, prev_dones):
        obs = prepare_obs(obs, num_envs=self.num_envs, **self.obs_keys)
        return np.asarray(self.value_step(params, obs, jnp.asarray(self.prev_actions)[None], self.hx, self.cx))[0], {}

    def initial_state(self, local):
        # the stored state at each sequence's first step; the rest of the two columns is nobody's
        return {k + "0": local.pop(k)[:: self.seq_len].reshape(1, -1, self.hx.shape[-1]) for k in ("hx", "cx")}

    def test(self, params, log_dir):
        env = make_env(self.cfg, self.cfg.seed, 0, log_dir, "test", vector_env_idx=0)()
        return test(LSTMPlayer(self.agent, self.cfg, greedy=True), params, env, self.cfg)


def make_player(agent, cfg, greedy: bool = False):
    """The player of the agent's kind: a model that makes its own carried state (``init_state``) is a token policy."""
    return TokenPlayer(agent, cfg) if hasattr(agent, "init_state") else LSTMPlayer(agent, cfg, greedy)
