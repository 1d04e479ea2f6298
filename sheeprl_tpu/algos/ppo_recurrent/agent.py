"""Recurrent-PPO agent (reference /root/reference/sheeprl/algos/ppo_recurrent/agent.py:18-470).

Encoder → [pre-MLP] → LSTM → [post-MLP] → actor heads + critic.  The LSTM is
an `nn.OptimizedLSTMCell` stepped by `lax.scan` over the sequence axis — the
reference's cuDNN `nn.LSTM` + pack_padded_sequence machinery (agent.py:68-82)
is replaced by fixed-length sequences with in-graph state resets on done
(`reset_recurrent_state_on_done`), which keeps every shape static for XLA.
"""

from __future__ import annotations

from math import prod
from typing import Any, Dict, List, Optional, Sequence, Tuple

import gymnasium
import jax
import jax.numpy as jnp
from flax import linen as nn

from sheeprl_tpu.algos.ppo.agent import _CNNEncoder, _MLPEncoder
from sheeprl_tpu.models.blocks import MLP
from sheeprl_tpu.ops.distributions import Categorical, Normal


class _ResetLSTMCell(nn.Module):
    """LSTM cell that zeroes its carry where ``reset`` is 1 before stepping
    (the `reset_recurrent_state_on_done` semantics, in-graph)."""

    hidden_size: int

    @nn.compact
    def __call__(self, carry, inp):
        h, c = carry
        x_t, reset_t = inp
        h = h * (1 - reset_t)
        c = c * (1 - reset_t)
        (c, h), out = nn.OptimizedLSTMCell(features=self.hidden_size)((c, h), x_t)
        return (h, c), out


class RecurrentPPOAgent(nn.Module):
    """Sequence-level forward: obs leaves are ``[L, B, ...]``."""

    actions_dim: Sequence[int]
    is_continuous: bool
    cnn_keys: Sequence[str] = ()
    mlp_keys: Sequence[str] = ()
    encoder_cfg: Any = None
    rnn_cfg: Any = None
    actor_cfg: Any = None
    critic_cfg: Any = None

    def setup(self) -> None:
        enc = self.encoder_cfg
        self._cnn_enc = (
            _CNNEncoder(features_dim=enc["cnn_features_dim"], keys=tuple(self.cnn_keys)) if self.cnn_keys else None
        )
        self._mlp_enc = (
            _MLPEncoder(
                keys=tuple(self.mlp_keys),
                features_dim=enc["mlp_features_dim"],
                dense_units=enc["dense_units"],
                mlp_layers=enc.get("mlp_layers", 1) or 1,
                dense_act=enc.get("dense_act", "relu"),
                layer_norm=enc.get("layer_norm", True),
            )
            if self.mlp_keys
            else None
        )
        rnn = self.rnn_cfg
        self.lstm_hidden_size = rnn["lstm"]["hidden_size"]
        pre = rnn["pre_rnn_mlp"]
        self._pre_mlp = (
            MLP(
                hidden_sizes=[pre["dense_units"]],
                activation=pre.get("activation", "relu"),
                layer_norm=pre.get("layer_norm", False),
            )
            if pre["apply"]
            else None
        )
        post = rnn["post_rnn_mlp"]
        self._post_mlp = (
            MLP(
                hidden_sizes=[post["dense_units"]],
                activation=post.get("activation", "relu"),
                layer_norm=post.get("layer_norm", False),
            )
            if post["apply"]
            else None
        )
        self._cell = nn.scan(
            _ResetLSTMCell,
            variable_broadcast="params",
            split_rngs={"params": False},
            in_axes=0,
            out_axes=0,
        )(hidden_size=self.lstm_hidden_size)
        a = self.actor_cfg
        self.actor_backbone = MLP(
            hidden_sizes=[a["dense_units"]] * a["mlp_layers"],
            activation=a["dense_act"],
            layer_norm=a["layer_norm"],
        )
        if self.is_continuous:
            self.actor_heads = [nn.Dense(int(sum(self.actions_dim)) * 2)]
        else:
            self.actor_heads = [nn.Dense(d) for d in self.actions_dim]
        c = self.critic_cfg
        self.critic = MLP(
            hidden_sizes=[c["dense_units"]] * c["mlp_layers"],
            output_dim=1,
            activation=c["dense_act"],
            layer_norm=c["layer_norm"],
        )

    def _features(self, obs: Dict[str, jax.Array]) -> jax.Array:
        feats = []
        if self._cnn_enc is not None:
            feats.append(self._cnn_enc(obs))
        if self._mlp_enc is not None:
            feats.append(self._mlp_enc(obs))
        return jnp.concatenate(feats, axis=-1) if len(feats) > 1 else feats[0]

    def rnn_scan(
        self,
        features: jax.Array,  # [L, B, F]
        prev_actions: jax.Array,  # [L, B, A]
        hx: jax.Array,  # [B, H]
        cx: jax.Array,  # [B, H]
        resets: Optional[jax.Array] = None,  # [L, B, 1] — 1 resets BEFORE step t
    ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
        x = jnp.concatenate([features, prev_actions], axis=-1)
        if self._pre_mlp is not None:
            x = self._pre_mlp(x)
        resets_seq = resets if resets is not None else jnp.zeros(x.shape[:2] + (1,))
        (hx, cx), outs = self._cell((hx, cx), (x, resets_seq))
        if self._post_mlp is not None:
            outs = self._post_mlp(outs)
        return outs, (hx, cx)

    def __call__(
        self,
        obs: Dict[str, jax.Array],
        prev_actions: jax.Array,
        hx: jax.Array,
        cx: jax.Array,
        resets: Optional[jax.Array] = None,
        key: Optional[jax.Array] = None,
        actions: Optional[jax.Array] = None,
        greedy: bool = False,
    ):
        """Return (actions, logprobs, entropies, values, (hx, cx)); everything
        ``[L, B, ...]``."""
        features = self._features(obs)
        out, (hx, cx) = self.rnn_scan(features, prev_actions, hx, cx, resets)
        values = self.critic(out)
        pre = self.actor_backbone(out)
        outs = [head(pre) for head in self.actor_heads]
        if self.is_continuous:
            mean, log_std = jnp.split(outs[0], 2, axis=-1)
            std = jnp.exp(log_std)
            dist = Normal(mean, std, event_dims=1)
            if actions is None:
                actions = dist.mode if greedy else dist.rsample(key)
            log_prob = dist.log_prob(actions)[..., None]
            entropy = dist.entropy()[..., None]
            return actions, log_prob, entropy, values, (hx, cx)
        sampled: List[jax.Array] = []
        log_probs: List[jax.Array] = []
        entropies: List[jax.Array] = []
        split_actions = (
            jnp.split(actions, len(self.actions_dim), axis=-1) if actions is not None else [None] * len(outs)
        )
        for i, logits in enumerate(outs):
            dist = Categorical(logits=logits)
            if split_actions[i] is None:
                if greedy:
                    act_idx = jnp.argmax(logits, axis=-1)
                else:
                    act_idx = dist.sample(jax.random.fold_in(key, i))
                act = act_idx[..., None].astype(jnp.float32)
            else:
                act = split_actions[i]
                act_idx = act[..., 0].astype(jnp.int32)
            sampled.append(act)
            log_probs.append(dist.log_prob(act_idx)[..., None])
            entropies.append(dist.entropy()[..., None])
        return (
            jnp.concatenate(sampled, axis=-1),
            jnp.sum(jnp.concatenate(log_probs, axis=-1), axis=-1, keepdims=True),
            jnp.sum(jnp.concatenate(entropies, axis=-1), axis=-1, keepdims=True),
            values,
            (hx, cx),
        )

    def get_values(self, obs, prev_actions, hx, cx, resets=None) -> jax.Array:
        features = self._features(obs)
        out, _ = self.rnn_scan(features, prev_actions, hx, cx, resets)
        return self.critic(out)


def token_key(cfg) -> str:
    """The one observation key a token policy reads."""
    return list(cfg.algo.mlp_keys.encoder)[0]


def token_backbone(cfg):
    """``(model class, its configuration)`` of a token backbone (``algo.backbone``), each under the config group of its name."""
    backbone = str(cfg.algo.backbone)
    if backbone == "sparse_moe":
        from sheeprl_tpu.models.sparse_moe_lm import SparseMoEConfig, SparseMoELM

        return SparseMoELM, SparseMoEConfig.from_cfg(cfg.algo.sparse_moe)
    from sheeprl_tpu.models.hybrid_lm import HybridConfig, HybridLM

    return HybridLM, HybridConfig.from_cfg(cfg.algo.olmo_hybrid)


def build_token_agent(cfg, actions_dim, is_continuous, obs_space, agent_state=None):
    """A language model as the policy: the observation is a token id, the
    action the next one, both over the ids this chip holds."""
    from sheeprl_tpu.parallel.precision import compute_dtype_of

    model, config = token_backbone(cfg)
    backbone = str(cfg.algo.backbone)
    key = token_key(cfg)
    space = obs_space[key]
    if is_continuous or len(actions_dim) != 1 or not isinstance(space, gymnasium.spaces.Discrete):
        raise ValueError(
            f"algo.backbone={backbone} needs one Discrete observation ({key!r}) and one Discrete action, "
            f"got observation {space} and actions {tuple(actions_dim)}"
        )
    if int(space.n) != config.vocab_held or int(actions_dim[0]) != config.vocab_held:
        raise ValueError(
            f"the env speaks {int(space.n)} ids and takes {int(actions_dim[0])}; "
            f"algo.{backbone}.vocab_held is {config.vocab_held}"
        )
    agent = model(config, dtype=compute_dtype_of(cfg))
    sample = jnp.zeros((1, 1), jnp.int32)
    if agent_state is not None:
        params = jax.tree_util.tree_map(jnp.asarray, agent_state)
    else:
        params = jax.jit(lambda k: agent.init(k, sample, sample, agent.init_state(1)))(jax.random.PRNGKey(int(cfg.seed or 0)))
    return agent, params, {key: sample}


TOKEN_BACKBONES = ("olmo_hybrid", "sparse_moe")


def build_agent(
    runtime,
    actions_dim: Sequence[int],
    is_continuous: bool,
    cfg,
    obs_space: gymnasium.spaces.Dict,
    agent_state: Optional[Dict[str, Any]] = None,
):
    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    # ``algo.backbone``: ``lstm`` (the preset's ``algo.rnn``) or a token backbone (``algo.<its name>``); the one place
    # that compares the name: the loop takes its player from the kind of agent built here (``players.make_player``)
    if str(cfg.algo.get("backbone", "lstm") or "lstm") in TOKEN_BACKBONES:
        return build_token_agent(cfg, actions_dim, is_continuous, obs_space, agent_state)
    agent = RecurrentPPOAgent(
        actions_dim=tuple(int(a) for a in actions_dim),
        is_continuous=is_continuous,
        cnn_keys=tuple(cnn_keys),
        mlp_keys=tuple(mlp_keys),
        encoder_cfg=cfg.algo.encoder,
        rnn_cfg=cfg.algo.rnn,
        actor_cfg=cfg.algo.actor,
        critic_cfg=cfg.algo.critic,
    )
    sample_obs = {}
    for k in cnn_keys:
        sample_obs[k] = jnp.zeros((1, 1) + tuple(obs_space[k].shape), jnp.float32)
    for k in mlp_keys:
        sample_obs[k] = jnp.zeros((1, 1, prod(obs_space[k].shape)), jnp.float32)
    act_sum = int(sum(actions_dim))
    hx = jnp.zeros((1, cfg.algo.rnn.lstm.hidden_size), jnp.float32)
    params = agent.init(
        jax.random.PRNGKey(int(cfg.seed or 0)),
        sample_obs,
        jnp.zeros((1, 1, act_sum), jnp.float32),
        hx,
        hx,
        key=jax.random.PRNGKey(0),
    )
    if agent_state is not None:
        params = jax.tree_util.tree_map(jnp.asarray, agent_state)
    return agent, params, sample_obs
