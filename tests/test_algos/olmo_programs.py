"""The three compiled programs of ``exp=ppo_recurrent_olmo_hybrid`` at the
small size the CPU tests use, as text: ``jit_policy_step`` and ``jit_update``
lowered (StableHLO without locations), ``jit_policy_view`` as the jaxpr of the
view under the TPU's rule.  ``python tests/test_algos/olmo_programs.py <out.json>``
writes their hashes; ``tests/golden/olmo_hybrid_programs.json`` is that file
written from the commit before the second token backbone came (ISSUE 38), and
``test_ppo_recurrent_sparse_moe.py`` holds every later tree to it."""

import hashlib
import json
import sys
from unittest import mock

TINY = [
    "exp=ppo_recurrent_olmo_hybrid", "fabric.accelerator=cpu", "env.num_envs=2", "algo.rollout_steps=16",
    "algo.per_rank_sequence_length=8", "algo.per_rank_num_batches=2", "env.wrapper.episode_min=5", "env.wrapper.episode_max=20",
    "metric.log_level=0", "buffer.memmap=False",
    "algo.olmo_hybrid.hidden_size=32", "algo.olmo_hybrid.intermediate_size=48", "algo.olmo_hybrid.heads_total=4",
    "algo.olmo_hybrid.heads_held=2", "algo.olmo_hybrid.linear_key_head_dim=6", "algo.olmo_hybrid.linear_value_head_dim=12",
    "algo.olmo_hybrid.vocab_total=64", "algo.olmo_hybrid.vocab_held=16", "algo.olmo_hybrid.cache_len=24",
    "algo.olmo_hybrid.chunk_size=4",
]


def program_texts():
    import gymnasium as gym
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh

    from sheeprl_tpu.algos.ppo_recurrent import players
    from sheeprl_tpu.algos.ppo_recurrent.agent import build_agent
    from sheeprl_tpu.algos.ppo_recurrent.ppo_recurrent import make_train_step
    from sheeprl_tpu.config import compose, instantiate

    cfg = compose(overrides=TINY)
    n, steps, seq = 2, 16, 8
    space = gym.spaces.Dict({"token": gym.spaces.Discrete(16)})
    agent, params, _ = build_agent(None, (16,), False, cfg, space)
    policy_step, _, _ = players.make_token_player(agent, cfg, steps)
    carry = {"state": agent.init_state(n), "key": jax.random.PRNGKey(0), "t": jnp.zeros((), jnp.int32),
             "logprobs": jnp.zeros((steps, n), jnp.float32), "values": jnp.zeros((steps, n), jnp.float32)}
    texts = {"jit_policy_step": policy_step.lower(params, carry, jnp.zeros((2, n), jnp.int32)).as_text()}

    optimizer = optax.chain(optax.clip_by_global_norm(cfg.algo.max_grad_norm), instantiate(cfg.algo.optimizer))
    mesh = Mesh(jax.devices()[:1], ("data",))
    update = make_train_step(players.make_player(agent, cfg), optimizer, cfg, mesh, 2, 2)
    column = lambda dtype=jnp.float32: jnp.zeros((seq, 4, 1), dtype)  # noqa: E731
    data = {k: column() for k in ("token", "actions", "rewards", "dones", "resets", "logprobs", "values", "returns", "advantages")}
    data["state0"] = jax.tree_util.tree_map(lambda x: jnp.concatenate([x, x])[None], agent.init_state(n))
    coefs = tuple(jnp.asarray(c, jnp.float32) for c in (0.2, 0.001, 0.2))
    texts["jit_update"] = update.lower(params, optimizer.init(params), data, jax.random.PRNGKey(1), coefs).as_text()

    with mock.patch.object(players, "products_round_to_bfloat16", lambda cfg: True):
        view_of, _ = players.make_policy_view(agent, cfg, n)
    texts["jit_policy_view"] = str(jax.make_jaxpr(view_of)(params))
    return texts


def program_hashes():
    return {name: hashlib.sha256(text.encode()).hexdigest() for name, text in program_texts().items()}


if __name__ == "__main__":
    with open(sys.argv[1], "w") as fh:
        json.dump(program_hashes(), fh, indent=1)
