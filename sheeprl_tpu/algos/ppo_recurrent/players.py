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
- an update's end: ``after_update(losses)`` (what the update reported beside the three PPO losses, for the page);
- ``test(params, log_dir)``: the greedy test episode's return, ``None`` where there is none.

:class:`LSTMPlayer` is the reference's: ``hx``, ``cx`` a row per env, stored with every
step.  :class:`TokenPlayer` drives any model of ``models/hybrid_lm.py``'s contract
(``apply(params, tokens, resets, state, decode=, write=)``, ``init_state(n)``; :class:`SparseTokenPlayer`
one of ``models/sparse_moe_lm.py``'s, whose update has a loss of its own): the carried
state is a pytree that stays on the device through the rollout, is donated to
``policy_step``, is copied once where a training sequence starts (the learner's constant,
as ``hx0``/``cx0`` are) and never reaches the host; the rollout's log-probabilities and
values are kept beside it and fetched once a rollout; resets are in-graph.  Its programs
read a *view* of the parameters (:func:`make_policy_view`), made once an update and
dropped before it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from sheeprl_tpu.algos.ppo_recurrent.agent import token_key
from sheeprl_tpu.algos.ppo_recurrent.utils import prepare_obs, test
from sheeprl_tpu.envs.env import make_env
from sheeprl_tpu.models.hybrid_lm import carry_bytes
from sheeprl_tpu.models.sparse_moe_lm import AUX, SparseMoELM
from sheeprl_tpu.ops.sparse_attention import decode_cache_rows
from sheeprl_tpu.parallel.precision import cast_floating, compute_dtype_of, resolve_precision


def make_token_player(agent, cfg, rollout_steps: int):
    """The token policy's three programs of the rollout.  ``policy_step``
    decodes one token an env through the carried state, which it is donated
    (a cache of a gigabyte is written in place, not copied a token), samples
    the next token and stores its log-probability and the value at the
    rollout's step ``t``; ``value_step`` reads the value of the next
    observation and writes nothing; ``snapshot_of`` copies the carried state
    where a training sequence starts.  ``params`` is the player's view of the
    parameters (:func:`make_policy_view`: the two steps cast nothing);
    ``staged`` is ``[2, N]`` int32: the observed tokens and the resets."""

    def policy_step(params, carry, staged):
        tokens, resets = staged[0][:, None], staged[1][:, None]
        logits, values, state = agent.apply(params, tokens, resets, carry["state"], decode=True)
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
        return agent.apply(params, tokens, resets, carry["state"], decode=True, write=False)[1][:, 0]

    def snapshot_of(state):
        return jax.tree_util.tree_map(jnp.copy, state)

    return jax.jit(policy_step, donate_argnums=(1,)), jax.jit(value_step), jax.jit(snapshot_of)


def products_round_to_bfloat16(cfg) -> bool:
    """Whether a float32 product at the default precision takes its operands
    rounded to bfloat16: the TPU's MXU in one pass, unless ``matmul_precision``
    asks for more passes.  A CPU's float32 product is exact."""
    return jax.default_backend() == "tpu" and str(cfg.get("matmul_precision", "default")) in ("default", "bfloat16")


def policy_view_dtypes(agent, cfg, num_envs: int):
    """The abstract parameters, and in their leaves' order the dtype the
    player's view holds each in (``None``: the parameter itself).

    - ``bf16-mixed`` (parameters wider than the compute dtype): every floating
      leaf in the compute dtype, the cast ``policy_step`` used to make anew
      every token;
    - ``32-true`` where :func:`products_round_to_bfloat16`: as bfloat16 the
      leaves that are operands of such a product, and nothing else: the
      kernels of the ``nn.Dense`` modules a decoded token goes through, found
      by what they are and not by their name (a convolution's taps and the
      embedding's rows are leaves named ``kernel`` too: multiplied
      elementwise and gathered, rounding them would change the numbers), and
      the leaves a module of another kind lists as such (``mxu_operands``: a
      stack of experts).  A ``nn.Dense`` that asks for a precision of its own
      is none of them (an indexer's and a router's, float32 at ``highest``: a
      rounded score or logit picks other positions and experts).
      A product with one row or one column is none of them: XLA:TPU rewrites
      it as a multiply and a reduction in float32, which reads all of its
      operands (the value head's one column; every product of a single env).
      Rounding once an update what the MXU rounds every token changes no number;
    - otherwise (``bf16-true``, more passes asked for, off the TPU): none."""
    through_the_mxu = set()

    def note(next_fun, args, kwargs, context):
        module = context.module
        if context.method_name == "__call__" and args and math.prod(args[0].shape[:-1]) > 1:
            if isinstance(module, nn.Dense):
                operands = ("kernel",) if module.precision is None and module.features > 1 else ()
            else:
                operands = getattr(module, "mxu_operands", ())
            through_the_mxu.update(("params",) + tuple(module.path) + (name,) for name in operands)
        return next_fun(*args, **kwargs)

    with nn.intercept_methods(note):  # abstract all through: nothing is put on the device to find this out
        shapes = jax.eval_shape(
            lambda key, tokens: agent.init(key, tokens, tokens, agent.init_state(num_envs), decode=True),
            jax.ShapeDtypeStruct((2,), jnp.uint32), jax.ShapeDtypeStruct((num_envs, 1), jnp.int32))
    param_dtype, compute_dtype = (jnp.dtype(d) for d in resolve_precision(cfg.fabric.precision))
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    if compute_dtype != param_dtype:
        dtypes = [compute_dtype if jnp.issubdtype(x.dtype, jnp.floating) else None for _, x in leaves]
    elif param_dtype == jnp.float32 and products_round_to_bfloat16(cfg):
        dtypes = [jnp.dtype(jnp.bfloat16) if tuple(k.key for k in path) in through_the_mxu else None for path, _ in leaves]
    else:
        dtypes = [None] * len(leaves)
    return shapes, dtypes


def make_policy_view(agent, cfg, num_envs: int):
    """``(view_of, nbytes)``: ``view_of(params)`` is the tree ``policy_step``
    and ``value_step`` read, each leaf in the dtype :func:`policy_view_dtypes`
    gives it, and ``nbytes`` what it holds on the device beside the parameters.
    One program (``jit_policy_view``) casts the leaves that change; the others
    are the parameters' own arrays, not copies.  Where none changes the view
    *is* the parameters."""
    shapes, dtypes = policy_view_dtypes(agent, cfg, num_envs)
    cast_at = [i for i, d in enumerate(dtypes) if d is not None]
    if not cast_at:
        return (lambda params: params), 0

    @jax.jit
    def policy_view(kernels):
        return [x.astype(dtypes[i]) for i, x in zip(cast_at, kernels)]

    def view_of(params):
        leaves, treedef = jax.tree_util.tree_flatten(params)
        for i, x in zip(cast_at, policy_view([leaves[i] for i in cast_at])):
            leaves[i] = x
        return treedef.unflatten(leaves)

    sizes = [x.size for x in jax.tree_util.tree_leaves(shapes)]
    return view_of, int(sum(sizes[i] * dtypes[i].itemsize for i in cast_at))


def _view(player, params):
    """The player's view of ``params``, made when it first meets them: once an update, once more after a resume."""
    if player.viewed is not params:
        player.viewed, player.view = params, player.view_of(params)
    return player.view


def _sequences_of(batch, key):
    """A token policy's training sequences: leaves are time-major ``[L, S, 1]``, the model's batch-major."""
    tokens = batch[key][..., 0].T.astype(jnp.int32)
    resets = batch["resets"][..., 0].T.astype(jnp.int32)
    return tokens, resets, jax.tree_util.tree_map(lambda x: x[0], batch["state0"])


def _token_terms(logits, values, batch):
    """What the loss reads of a token policy's forward pass: the stored actions' log-probabilities, the entropy, the values."""
    with jax.named_scope("ppo_loss"):
        logp_all = jax.nn.log_softmax(logits, axis=-1)
        actions = batch["actions"][..., 0].T.astype(jnp.int32)
        logprobs = jnp.take_along_axis(logp_all, actions[..., None], axis=-1)
        entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1, keepdims=True)
    return logprobs.swapaxes(0, 1), entropy.swapaxes(0, 1), values.T[..., None]


class TokenPlayer:
    def __init__(self, agent, cfg):
        self.agent, self.cfg, self.key = agent, cfg, token_key(cfg)

    def evaluate(self, params, batch):
        logits, values, _ = self.agent.apply(cast_floating(params, compute_dtype_of(self.cfg)), *_sequences_of(batch, self.key))
        return _token_terms(logits, values, batch)

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
        self.view_of, self.view_nbytes = make_policy_view(self.agent, self.cfg, num_envs)
        diag.register_footprint("policy_view", self.view_nbytes)
        self.viewed = self.view = None  # the parameters last seen and the view of them; both dropped at a rollout's end
        self.positions = np.zeros(num_envs, np.int64)  # the host's mirror of the caches' lengths
        self.diag, self.num_envs, self.seq_len = diag, num_envs, seq_len
        self.steps, self.snapshots = 0, []  # vector steps taken; the copies where this rollout's sequences start

    def begin_step(self, prev_dones):
        if self.steps % self.seq_len == 0:
            # where a training sequence starts: the learner's constant, one copy on the device
            self.snapshots.append(self.snapshot_of(self.carry["state"]))
        self.steps += 1
        self.positions = np.where(prev_dones[:, 0] > 0, 0, self.positions) + 1
        self.diag.note_policy_state(int(prev_dones.sum()), int(self.positions.sum()), self.carry_nbytes, self.view_nbytes)

    def stage(self, obs, prev_dones):
        """The observed tokens and the resets, staged together: ``[2, N]`` int32, which the
        call into the program puts on the device (a ``device_put`` of its own ahead of the
        call costs the vector step 0.3 ms more: PERF.md section 6, PR 31)."""
        tokens = np.asarray(obs[self.key]).reshape(self.num_envs)
        return np.stack([tokens, prev_dones[:, 0]]).astype(np.int32)

    def act(self, params, staged):
        actions, self.carry = self.policy_step(_view(self, params), self.carry, staged)  # the step's one put rides the call
        return actions

    def fetch(self, out):
        # the step's one fetch; the observed token is the next input, and the rest of a row stays on the device
        return np.asarray(out).reshape(self.num_envs, 1), {}

    def end_rollout(self, params, obs, prev_dones):
        next_values = np.asarray(self.value_step(_view(self, params), self.carry, self.stage(obs, prev_dones))).reshape(self.num_envs, 1)
        # the value is on the host, so nothing reads the view any more: it must not be alive beside the update's temporaries
        self.viewed = self.view = None
        # the rollout's one fetch of what the player stored while decoding
        return next_values, {k: np.asarray(self.carry[k])[..., None] for k in ("logprobs", "values")}

    def initial_state(self, local):
        snapshots, self.snapshots = self.snapshots, []
        return {"state0": jax.tree_util.tree_map(lambda *xs: jnp.concatenate(xs, axis=0)[None], *snapshots)}

    def after_update(self, losses):
        pass

    def test(self, params, log_dir):
        return None  # sampling is this policy's decoding: it has no greedy episode


class SparseTokenPlayer(TokenPlayer):
    """A policy of ``models/sparse_moe_lm.py``: the update differentiates the
    indexers' loss beside PPO's and reports it with the shares of positions
    attended and of picks held; the page says how many positions the next
    decode step attends, how many its kernel may stream, and what each kind of
    cache holds."""

    def evaluate(self, params, batch):
        logits, values, _, report = self.agent.apply(
            cast_floating(params, compute_dtype_of(self.cfg)), *_sequences_of(batch, self.key), aux=True)
        own = (self.agent.config.index_loss_coef * report["index_loss"], tuple(report[name] for name in AUX))
        return _token_terms(logits, values, batch) + (own,)

    def start(self, diag, keys, num_envs, rollout_steps, seq_len):
        super().start(diag, keys, num_envs, rollout_steps, seq_len)
        layers = self.carry["state"]["layers"]
        diag.note_policy_gauges(carry_bytes_by_kind={
            "kv": carry_bytes([(layer["k"], layer["v"]) for layer in layers]), "index": carry_bytes([layer["ki"] for layer in layers])})

    def begin_step(self, prev_dones):
        super().begin_step(prev_dones)
        # what the decode step about to run attends and may stream of its caches (the positions before its token), from the
        # host's mirror: no fetch
        c = self.agent.config
        self.diag.note_policy_selection(int(self.positions.sum()), int(np.minimum(self.positions, c.topk).sum()),
                                        decode_cache_rows(self.positions - 1, c.cache_len))

    def after_update(self, losses):
        self.diag.note_policy_update(**{name: float(value) for name, value in zip(AUX, losses[3:])})


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

    def after_update(self, losses):
        pass

    def test(self, params, log_dir):
        env = make_env(self.cfg, self.cfg.seed, 0, log_dir, "test", vector_env_idx=0)()
        return test(LSTMPlayer(self.agent, self.cfg, greedy=True), params, env, self.cfg)


def make_player(agent, cfg, greedy: bool = False):
    """The player of the agent's kind: a model that makes its own carried state (``init_state``) is a token policy."""
    if isinstance(agent, SparseMoELM):
        return SparseTokenPlayer(agent, cfg)
    return TokenPlayer(agent, cfg) if hasattr(agent, "init_state") else LSTMPlayer(agent, cfg, greedy)
