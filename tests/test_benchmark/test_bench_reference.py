"""Pieces of the plain reference against values worked out by hand, and the
control: the reference computed one precision lower, or on half of the batch,
reads far from the reference itself."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.chip import reference as ref
from benchmarks.chip.check import step_gaps, worst_leaf_gap
from benchmarks.chip.families.dreamer_v3 import MODULES, faults as FAULTS, replay_mismatches
from benchmarks.chip.envs import BenchEnv
from benchmarks.chip.weights import make_weights

SHAPES = dict(image_channels=3, image_size=64, cnn_channels_multiplier=2, cnn_stages=4, dense_units=16, mlp_layers=2,
              recurrent_state_size=16, hidden_size=16, stochastic_size=4, discrete_size=4, bins=255, n_actions=5,
              sequence_length=8, batch_size=4, horizon=3)
HYPER = dict(gamma=0.997, lmbda=0.95, ent_coef=3e-4, kl_dynamic=0.5, kl_representation=0.1, kl_free_nats=1.0,
             kl_regularizer=1.0, continue_scale_factor=1.0,
             moments=dict(decay=0.99, max=1.0, percentile_low=0.05, percentile_high=0.95),
             world_model=dict(lr=1e-4, eps=1e-8, clip=1000.0), actor=dict(lr=8e-5, eps=1e-5, clip=100.0),
             critic=dict(lr=8e-5, eps=1e-5, clip=100.0))


def test_twohot_head_by_hand():
    logits = jnp.zeros((1, 255))
    assert float(ref.twohot_mean(logits)[0, 0]) == pytest.approx(0.0, abs=1e-5)
    # symlog(0) sits on the middle bin: the log-probability is that bin's alone
    assert float(ref.twohot_log_prob(logits, jnp.zeros((1, 1)))[0]) == pytest.approx(-np.log(255), rel=1e-5)
    # a value between two bins splits its weight by distance: still -log(255) on uniform logits
    assert float(ref.twohot_log_prob(logits, jnp.full((1, 1), 0.3))[0]) == pytest.approx(-np.log(255), rel=1e-5)
    # all mass on the two bins around symlog(1.0): the two weights sum to one
    bins = np.linspace(-20.0, 20.0, 255)
    below = int(np.sum(bins <= np.log(2.0))) - 1
    peaked = jnp.full((1, 255), -1e9).at[0, below].set(0.0).at[0, below + 1].set(0.0)
    assert float(ref.twohot_log_prob(peaked, jnp.ones((1, 1)))[0]) == pytest.approx(-np.log(2.0), rel=1e-4)


def test_symlog_kl_and_layer_norm():
    x = jnp.array([-3.0, 0.0, 3.0])
    assert np.allclose(ref.symexp(ref.symlog(x)), x, atol=1e-5)
    same = jnp.log(jnp.array([[[0.25, 0.75], [0.5, 0.5]]]))
    assert float(ref.categorical_kl(same, same)[0]) == pytest.approx(0.0, abs=1e-6)
    other = jnp.log(jnp.array([[[0.5, 0.5], [0.5, 0.5]]]))
    want = 0.25 * np.log(0.25 / 0.5) + 0.75 * np.log(0.75 / 0.5)
    assert float(ref.categorical_kl(same, other)[0]) == pytest.approx(want, rel=1e-5)
    y = ref.layer_norm({"scale": jnp.ones(4), "bias": jnp.zeros(4)}, jnp.array([[1.0, 2.0, 3.0, 4.0]]), 0.0)
    assert np.allclose(y.mean(), 0.0, atol=1e-6) and np.allclose(y.var(), 1.0, atol=1e-5)


def test_adam_and_the_clip_by_hand():
    params, grads = {"w": jnp.array([1.0, 2.0])}, {"w": jnp.array([3.0, -4.0])}
    clipped, norm = ref.clip_by_global_norm(grads, 2.5)
    assert float(norm) == pytest.approx(5.0) and np.allclose(clipped["w"], [1.5, -2.0])
    same, _ = ref.clip_by_global_norm(grads, 10.0)
    assert np.allclose(same["w"], grads["w"])
    new, state = ref.adam_update(params, grads, ref.adam_init(params), lr=0.1, eps=0.0)
    assert np.allclose(new["w"], [0.9, 2.1], atol=1e-6)  # the first Adam step is lr * sign(g)
    assert np.allclose(state["mu"]["w"], [0.3, -0.4]) and int(state["count"]) == 1


@pytest.mark.parametrize("name,digits", [("float32", 7), ("bfloat16", 2)])
def test_quantizers_round_as_their_type(name, digits):
    x = jnp.array([1.2345678, -0.0123456, 300.0, 1000.0])
    y = np.asarray(ref.quantizer(name)(x))
    assert np.allclose(y, np.asarray(x), rtol=10.0 ** -digits * 5)
    assert (name == "float32") == bool(np.array_equal(y, np.asarray(x)))
    with pytest.raises(ValueError):
        ref.quantizer("float8")


def test_worst_leaf_gap_measures_against_the_median_leaf():
    assert worst_leaf_gap([1.0, 2.0, 1e-9], [1.0, 1.0, 0.0]) == pytest.approx(1.0)
    assert worst_leaf_gap([1.0, 1.0, 1e-9], [1.0, 1.0, 0.0]) == pytest.approx(1e-9)
    assert worst_leaf_gap([1.0, 2.0], [1.0, 1.0], keep=[True, False]) == 0.0


def _program_like_tree(key):
    """A parameter tree with the program's names, at the tiny shapes."""
    from sheeprl_tpu.algos.dreamer_v3.agent import Actor, Critic, WorldModel

    s = SHAPES
    wm = WorldModel(cnn_keys=("rgb",), mlp_keys=(), cnn_decoder_keys=("rgb",), mlp_decoder_keys=(), mlp_output_dims=(),
                    cnn_input_channels=(3,), image_size=(64, 64), channels_multiplier=2, cnn_stages=4,
                    encoder_dense_units=16, encoder_mlp_layers=2, decoder_dense_units=16, decoder_mlp_layers=2,
                    recurrent_state_size=16, stochastic_size=4, discrete_size=4, rssm_dense_units=16, rssm_hidden_size=16,
                    reward_dense_units=16, reward_mlp_layers=2, reward_bins=255, continue_dense_units=16, continue_mlp_layers=2)
    latent = 16 + 16
    shapes = {
        "world_model": jax.eval_shape(lambda: wm.init(key, {"rgb": jnp.zeros((1, 3, 64, 64))}, jnp.zeros((1, 5)), jnp.ones((1, 1)), key)),
        "actor": jax.eval_shape(lambda: Actor(latent_state_size=latent, actions_dim=(5,), is_continuous=False, dense_units=16, mlp_layers=2).init(key, jnp.zeros((1, latent)))),
        "critic": jax.eval_shape(lambda: Critic(dense_units=16, mlp_layers=2).init(key, jnp.zeros((1, latent)))),
    }
    shapes["target_critic"] = shapes["critic"]
    return shapes


@pytest.fixture(scope="module")
def traffic():
    """Weights and three batches made as a run makes them, with no program in the loop."""
    seed, T, B = 11, 8, 4
    params = jax.device_get(make_weights(_program_like_tree(jax.random.PRNGKey(0)), seed))
    env = BenchEnv(seed=seed, n_actions=5, episode_min=20, episode_max=40, step_ms=0.0, reward_pct=20.0)
    obs, _ = env.reset()
    rows = []
    rng = np.random.default_rng(seed)
    first = 1.0
    for _ in range(3 * T * B):
        action = int(rng.integers(5))
        rows.append({"rgb": obs["rgb"].astype(np.float32) / 255.0 - 0.5, "actions": np.eye(5, dtype=np.float32)[action],
                     "rewards": np.zeros(1, np.float32), "is_first": np.array([first], np.float32), "terminated": np.zeros(1, np.float32)})
        obs, reward, done, _, _ = env.step(action)
        first = 0.0
        if done:
            obs, _ = env.reset()
            first = 1.0
    inputs = []
    for i in range(3):
        chunk = rows[i * T * B:(i + 1) * T * B]
        batch = {k: np.stack([np.stack([chunk[b * T + t][k] for b in range(B)]) for t in range(T)]) for k in chunk[0]}
        inputs.append({"batch": batch, "key": np.asarray(jax.random.PRNGKey(100 + i)), "tau": 1.0 if i == 0 else 0.02})
    moments = {"low": np.zeros((), np.float32), "high": np.zeros((), np.float32)}
    return params, moments, inputs


def _steps(traffic, **kwargs):
    params, moments, inputs = traffic
    return ref.first_steps(SHAPES, HYPER, params, moments, inputs, noise_dtype=jnp.float32, **kwargs)


@pytest.fixture(scope="module")
def sound(traffic):
    return _steps(traffic)


def test_weights_come_from_the_seed(traffic):
    tree = _program_like_tree(jax.random.PRNGKey(0))
    again = jax.device_get(make_weights(tree, 11))
    other = jax.device_get(make_weights(tree, 12))
    leaves = lambda t: jax.tree_util.tree_leaves(t)  # noqa: E731
    assert all(np.array_equal(a, b) for a, b in zip(leaves(traffic[0]), leaves(again)))
    kernels = [(a, b) for a, b in zip(leaves(again), leaves(other)) if a.ndim >= 2]
    assert kernels and all(not np.array_equal(a, b) for a, b in kernels)
    assert all(np.any(a != 0) for a, _ in kernels)  # no head starts at zero
    assert all(np.array_equal(a, b) for a, b in zip(leaves(again["critic"]), leaves(again["target_critic"])))


def test_the_reference_repeats_itself_and_moves_every_leaf(traffic, sound):
    again = _steps(traffic)
    gaps = step_gaps(again, sound, traffic[0], MODULES)
    assert max(gaps.values()) == 0.0
    assert all(np.isfinite(loss).all() for loss in sound["losses"])
    for module in ("world_model", "actor", "critic"):
        before, after = jax.tree_util.tree_leaves(traffic[0][module]), jax.tree_util.tree_leaves(sound["params_after"][module])
        moved = [not np.array_equal(a, b) for a, b in zip(before, after)]
        assert sum(moved) >= len(moved) - 1  # a key's bias under softmax may stay


def test_the_reference_in_a_lower_precision_reads_far_off(traffic, sound):
    gaps = step_gaps(_steps(traffic, quant="bfloat16"), sound, traffic[0], MODULES)
    # the reference against itself reads 0.0 on every number (the test above)
    assert max(gaps[f"{kind}_gap.{module}"] for kind in ("grad", "change") for module in ("world_model", "actor", "critic")) > 0.05, gaps


def _toy_step(params, opt_states, moments_state, batch, key, tau):
    """The program's step signature on a toy state: the parameter moves by the batch's mean."""
    moved = {"w": params["w"] - batch["x"].mean()}
    return moved, {"count": opt_states["count"] + 1}, moments_state, jnp.stack([batch["x"].mean()])


def test_the_half_batch_fault_keeps_the_shapes_and_drops_the_second_half():
    x = jnp.arange(24, dtype=jnp.float32).reshape(3, 4, 2)  # [T, B, ...]: rows 2 and 3 of every step are left out
    state = ({"w": jnp.zeros(())}, {"count": jnp.zeros((), jnp.int32)}, {"low": jnp.zeros(())})
    seen = {}

    def spy(params, opt_states, moments_state, batch, key, tau):
        seen.update(batch)
        return _toy_step(params, opt_states, moments_state, batch, key, tau)

    out = FAULTS["half_batch"](spy)(*state, {"x": x}, None, 1.0)
    assert seen["x"].shape == x.shape and np.array_equal(seen["x"][:, 2:], x[:, :2]) and np.array_equal(seen["x"][:, :2], x[:, :2])
    assert float(out[3][0]) == pytest.approx(float(x[:, :2].mean())) != pytest.approx(float(x.mean()))


def test_the_unchanged_fault_does_the_work_and_returns_the_state_it_got():
    state = ({"w": jnp.ones(())}, {"count": jnp.zeros((), jnp.int32)}, {"low": jnp.zeros(())})
    out = FAULTS["unchanged"](_toy_step)(*state, {"x": jnp.full((2, 2, 1), 3.0)}, None, 1.0)
    assert out[0] is state[0] and out[1] is state[1] and out[2] is state[2]
    assert float(out[3][0]) == 3.0  # the step's own metrics still come back


def test_the_replay_check_catches_a_row_that_is_not_the_envs():
    seed, env_params = 3, {"n_actions": 5, "episode_min": 6, "episode_max": 9, "first_episodes": [4], "reward_pct": 50.0}
    env = BenchEnv(seed=seed, step_ms=0.0, **env_params)
    obs, _ = env.reset()
    rows, first = [], 1.0
    last_reward, final = 0.0, 0.0
    for i in range(24):
        action = i % 5
        row = {"rgb": obs["rgb"].astype(np.float32) / 255.0 - 0.5, "actions": np.eye(5, dtype=np.float32)[action],
               "rewards": np.array([last_reward], np.float32), "is_first": np.array([first], np.float32),
               "terminated": np.array([final], np.float32)}
        if final:  # the loop's bookkeeping row of an episode's last frame: no action, then the reset's frame
            row["actions"] = np.zeros(5, np.float32)
            rows.append(row)
            obs, _ = env.reset()
            first, last_reward, final = 1.0, 0.0, 0.0
            continue
        rows.append(row)
        obs, last_reward, done, _, _ = env.step(action)
        first, final = 0.0, float(done)
    log = {"times": env.log.times[: env.log.n], "actions": env.log.actions[: env.log.n], "marks": env.log.marks[: env.log.n]}
    batch = {k: np.stack([r[k] for r in rows[:16]]).reshape((8, 2) + rows[0][k].shape, order="F") for k in rows[0]}
    clean = replay_mismatches([{"batch": batch}], log, env_params, seed)
    assert clean == {"replay_frame_mismatches": 0, "replay_order_breaks": 0, "replay_label_mismatches": 0}
    swapped = {k: v.copy() for k, v in batch.items()}
    swapped["rgb"][[2, 3], 0] = swapped["rgb"][[3, 2], 0]
    assert replay_mismatches([{"batch": swapped}], log, env_params, seed)["replay_order_breaks"] > 0
    smudged = {k: v.copy() for k, v in batch.items()}
    smudged["rgb"][1, 1, 0, 5, 5] += 1.0 / 255.0
    assert replay_mismatches([{"batch": smudged}], log, env_params, seed)["replay_frame_mismatches"] == 1
    relabeled = {k: v.copy() for k, v in batch.items()}
    relabeled["rewards"][4, 0, 0] = 1.0 - relabeled["rewards"][4, 0, 0]
    assert replay_mismatches([{"batch": relabeled}], log, env_params, seed)["replay_label_mismatches"] == 1
