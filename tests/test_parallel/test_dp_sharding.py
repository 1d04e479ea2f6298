"""Prove multi-device data-parallelism is real, not replicated compute.

Round-1 verdict: Dreamer/DroQ/SAC-AE "DP" compiled with batch sharding
``PartitionSpec()`` (fully replicated) and no all-reduce in the HLO — N
devices computing the identical batch.  These tests pin the fix: on a real
8-device mesh the compiled train step must (a) take the batch sharded over
the ``data`` axis and (b) contain a cross-device collective (the gradient
pmean / Moments all-gather), and the step must run and keep params replicated.
"""

from __future__ import annotations

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from sheeprl_tpu.config import compose, instantiate
from sheeprl_tpu.parallel.dp import stage
from sheeprl_tpu.parallel.mesh import make_mesh

N_DEV = 8


def _dv3_step_and_args(mesh):
    """Shared tiny-DV3 fixture lives in ``__graft_entry__._tiny_dv3`` (also
    exercised by the driver's multichip dryrun)."""
    from __graft_entry__ import _tiny_dv3

    _, step, args, _ = _tiny_dv3(mesh=mesh, world_size=N_DEV)
    return step, args


def _assert_batch_sharded(sharding, mesh, batch_axis):
    """The compiled argument sharding must split the batch axis over the mesh."""
    assert isinstance(sharding, NamedSharding)
    spec = sharding.spec
    assert len(spec) > batch_axis and spec[batch_axis] == "data", f"batch not sharded: {spec}"


def test_dv3_step_is_sharded_with_collectives():
    mesh = make_mesh(n_devices=N_DEV)
    step, args = _dv3_step_and_args(mesh)

    lowered = step.lower(*args)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    assert "all-reduce" in hlo or "all-gather" in hlo, "no cross-device collective in compiled HLO"

    # batch is argument index 3; every leaf must enter sharded over "data"
    for leaf in jax.tree_util.tree_leaves(args[3]):
        _assert_batch_sharded(leaf.sharding, mesh, batch_axis=1)

    # trailing output is the learn-health stats dict ({} unless
    # diagnostics.health collects it — ISSUE 9)
    params, opt_states, moments, metrics = compiled(*args)[:4]
    jax.block_until_ready(metrics)
    assert np.isfinite(np.asarray(metrics)).all()
    # params must come back replicated (spec ()) so the player can use them
    for leaf in jax.tree_util.tree_leaves(params):
        assert leaf.sharding.is_fully_replicated


def test_dv3_moments_quantile_is_global():
    """The Moments EMA must see the all-gathered lambda values: feeding
    device-disjoint value ranges must produce the global quantile, not a
    per-device one (reference utils.py:56-64 all_gathers before quantile)."""
    from sheeprl_tpu.algos.dreamer_v3.utils import update_moments

    mesh = make_mesh(n_devices=N_DEV)
    from jax import shard_map

    def body(state, x):
        _, _, new_state = update_moments(state, x, decay=0.0, axis_name="data")
        return new_state

    mapped = jax.jit(
        shard_map(
            body,
            mesh=mesh,
            in_specs=(P(), P("data")),
            out_specs=P(),
            check_vma=False,
        )
    )
    # shard d holds values 1000*d .. 1000*d+99: the global 5%/95% quantiles
    # span shards; a per-device quantile would return identical low/high EMA
    # only if gathered globally
    x = np.concatenate([1000.0 * d + np.arange(100.0) for d in range(N_DEV)]).astype(np.float32)
    state = {"low": jnp.zeros(()), "high": jnp.zeros(())}
    out = mapped(state, jnp.asarray(x))
    expected_low = np.quantile(x, 0.05)
    expected_high = np.quantile(x, 0.95)
    np.testing.assert_allclose(float(out["low"]), expected_low, rtol=1e-5)
    np.testing.assert_allclose(float(out["high"]), expected_high, rtol=1e-5)


@pytest.mark.parametrize("algo", ["droq", "sac_ae"])
def test_offpolicy_step_is_sharded_with_collectives(algo):
    mesh = make_mesh(n_devices=N_DEV)
    G, B = 2, 2 * N_DEV
    rng = np.random.default_rng(0)

    if algo == "droq":
        from sheeprl_tpu.algos.droq.agent import build_agent
        from sheeprl_tpu.algos.droq.droq import make_train_step

        cfg = compose(
            [
                "exp=droq",
                "env=dummy",
                "env.id=continuous_dummy",
                "algo.mlp_keys.encoder=[state]",
                "algo.per_rank_batch_size=2",
            ]
        )
        obs_space = gym.spaces.Dict({"state": gym.spaces.Box(-1, 1, (4,), np.float32)})
        act_space = gym.spaces.Box(-1, 1, (2,), np.float32)
        actor_def, critic_def, params, target_entropy = build_agent(None, cfg, obs_space, act_space)
        optimizers = {k: instantiate(getattr(cfg.algo, k).optimizer) for k in ("actor", "critic")}
        optimizers["alpha"] = instantiate(cfg.algo.alpha.optimizer)
        opt_states = {
            "actor": optimizers["actor"].init(params["actor"]),
            "critic": optimizers["critic"].init(params["critic"]),
            "alpha": optimizers["alpha"].init(params["log_alpha"]),
        }
        step = make_train_step(actor_def, critic_def, optimizers, cfg, target_entropy, mesh=mesh)
        data = stage(
            {
                "observations": rng.normal(size=(G, B, 4)).astype(np.float32),
                "next_observations": rng.normal(size=(G, B, 4)).astype(np.float32),
                "actions": rng.normal(size=(G, B, 2)).astype(np.float32),
                "rewards": rng.normal(size=(G, B, 1)).astype(np.float32),
                "terminated": np.zeros((G, B, 1), np.float32),
            },
            mesh,
            batch_axis=1,
        )
        actor_data = stage(
            {"observations": rng.normal(size=(G, B, 4)).astype(np.float32)}, mesh, batch_axis=1
        )
        keys = jax.random.split(jax.random.PRNGKey(0), G)
        args = (params, opt_states, data, actor_data, keys)
        batch_argnum = 2
    else:
        from sheeprl_tpu.algos.sac_ae.agent import build_agent
        from sheeprl_tpu.algos.sac_ae.sac_ae import make_train_step

        cfg = compose(
            [
                "exp=sac_ae",
                "env=dummy",
                "env.id=continuous_dummy",
                "algo.mlp_keys.encoder=[state]",
                "algo.mlp_keys.decoder=[state]",
                "algo.cnn_keys.encoder=[rgb]",
                "algo.cnn_keys.decoder=[rgb]",
                "algo.per_rank_batch_size=2",
                "algo.dense_units=8",
                "algo.mlp_layers=1",
            ]
        )
        obs_space = gym.spaces.Dict(
            {
                "rgb": gym.spaces.Box(0, 255, (3, 64, 64), np.uint8),
                "state": gym.spaces.Box(-1, 1, (4,), np.float32),
            }
        )
        act_space = gym.spaces.Box(-1, 1, (2,), np.float32)
        encoder_def, decoder_def, actor_def, critic_def, params, target_entropy = build_agent(
            None, cfg, obs_space, act_space
        )
        optimizers = {
            "critic": instantiate(cfg.algo.critic.optimizer),
            "actor": instantiate(cfg.algo.actor.optimizer),
            "alpha": instantiate(cfg.algo.alpha.optimizer),
            "encoder": instantiate(cfg.algo.encoder.optimizer),
            "decoder": instantiate(cfg.algo.decoder.optimizer),
        }
        opt_states = {
            "critic": optimizers["critic"].init((params["encoder"], params["critic"])),
            "actor": optimizers["actor"].init(params["actor"]),
            "alpha": optimizers["alpha"].init(params["log_alpha"]),
            "encoder": optimizers["encoder"].init(params["encoder"]),
            "decoder": optimizers["decoder"].init(params["decoder"]),
        }
        step = make_train_step(
            encoder_def, decoder_def, actor_def, critic_def, optimizers, cfg, target_entropy, mesh=mesh
        )
        data = stage(
            {
                "rgb": rng.integers(0, 255, (G, B, 3, 64, 64)).astype(np.float32),
                "next_rgb": rng.integers(0, 255, (G, B, 3, 64, 64)).astype(np.float32),
                "state": rng.normal(size=(G, B, 4)).astype(np.float32),
                "next_state": rng.normal(size=(G, B, 4)).astype(np.float32),
                "actions": rng.normal(size=(G, B, 2)).astype(np.float32),
                "rewards": rng.normal(size=(G, B, 1)).astype(np.float32),
                "terminated": np.zeros((G, B, 1), np.float32),
            },
            mesh,
            batch_axis=1,
        )
        keys = jax.random.split(jax.random.PRNGKey(0), G)
        args = (params, opt_states, jnp.int32(0), data, keys)
        batch_argnum = 3

    compiled = step.lower(*args).compile()
    hlo = compiled.as_text()
    assert "all-reduce" in hlo, f"no gradient all-reduce in compiled {algo} HLO"
    for leaf in jax.tree_util.tree_leaves(args[batch_argnum]):
        _assert_batch_sharded(leaf.sharding, mesh, batch_axis=1)
    out = compiled(*args)
    jax.block_until_ready(out)
    losses = np.asarray(out[-1])
    assert np.isfinite(losses).all()


def test_prefetch_staged_yields_all_slices_in_order():
    """prefetch_staged must reproduce exactly the per-step slices (staged one
    ahead) — content parity with the eager loop it replaced."""
    import numpy as np

    from sheeprl_tpu.parallel.dp import prefetch_staged

    samples = {"x": np.arange(5 * 3, dtype=np.float32).reshape(5, 3)}
    out = list(prefetch_staged(samples, 5, None, transform=lambda t: {"x": t["x"] * 2}))
    assert len(out) == 5
    for i, batch in enumerate(out):
        np.testing.assert_allclose(np.asarray(batch["x"]), samples["x"][i] * 2)
    assert list(prefetch_staged(samples, 0, None)) == []
