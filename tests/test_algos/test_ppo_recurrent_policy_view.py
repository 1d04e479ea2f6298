"""The token player's view of the parameters (ISSUE 35).

``policy_step`` and ``value_step`` read a view: where the backend's default
product rounds float32 operands to bfloat16 (the TPU under ``32-true``), the
kernels that are operands of such products as bfloat16 and every other leaf
as it is; under ``bf16-mixed`` the cast the step used to make every token;
elsewhere the parameters themselves.  Made once an update, dropped before it.
The CPU is not a backend that rounds, so the tests that need the rule say so
themselves (``products_round_to_bfloat16`` patched): no option of the program."""

import gc
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.algos.ppo_recurrent import players
from sheeprl_tpu.models.hybrid_lm import FULL, LINEAR, HybridConfig, HybridLM

from test_ppo_recurrent_olmo import TINY, _run_cli, compiles  # noqa: F401  (``compiles`` is a fixture)

D, I, V, H, DK, DV, DH, TAPS = 32, 48, 16, 2, 6, 12, 8, 4
CONFIG = HybridConfig(
    hidden_size=D, intermediate_size=I, layer_types=(LINEAR, LINEAR, LINEAR, FULL), heads_total=4, heads_held=H, head_share=0,
    linear_key_head_dim=DK, linear_value_head_dim=DV, linear_conv_kernel_dim=TAPS, linear_allow_neg_eigval=True, rms_norm_eps=1e-6,
    vocab_total=64, vocab_held=V, vocab_share=0, cache_len=24, chunk_size=4,
)
# the operands of a product that goes through the MXU, by name: the MLPs', the projections of both layer kinds, the gates, the head
MLP = {"gate_proj": D * I, "up_proj": D * I, "down_proj": I * D}
LINEAR_PRODUCTS = {"q_proj": D * H * DK, "k_proj": D * H * DK, "v_proj": D * H * DV, "a_proj": D * H, "b_proj": D * H,
                   "g_proj": D * H * DV, "o_proj": H * DV * D}
FULL_PRODUCTS = {"q_proj": D * H * DH, "k_proj": D * H * DH, "v_proj": D * H * DH, "o_proj": H * DH * D}
PRODUCTS = {
    **{f"layers_{i}/mlp/{n}/kernel": size for i in range(4) for n, size in MLP.items()},
    **{f"layers_{i}/mixer/{n}/kernel": size for i in range(3) for n, size in LINEAR_PRODUCTS.items()},
    **{f"layers_3/mixer/{n}/kernel": size for n, size in FULL_PRODUCTS.items()},
    "lm_head/kernel": D * V,
}
# and what no such product takes: gathered, multiplied elementwise, a scalar a head, or the one column of the value head
# (a product XLA:TPU rewrites as a multiply and a reduction in float32: on the chip its rounding moved the values by 3e-3)
KEPT = (
    ["embed_tokens/kernel", "final_norm/scale", "value_head/kernel"]
    + [f"layers_{i}/{n}/scale" for i in range(4) for n in ("mixer_norm", "mlp_norm")]
    + [f"layers_{i}/mixer/{n}" for i in range(3) for n in ("q_conv/kernel", "k_conv/kernel", "v_conv/kernel", "A_log", "dt_bias", "o_norm/scale")]
)


class _Cfg(dict):
    __getattr__ = dict.get


def _cfg(precision="32-true", matmul_precision="default"):
    return _Cfg(fabric=_Cfg(precision=precision), matmul_precision=matmul_precision)


def _names(tree):
    return {"/".join(str(k.key) for k in path[1:]): x for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _params(agent, seed=0):
    token = jnp.zeros((1, 1), jnp.int32)
    return agent.init(jax.random.PRNGKey(seed), token, token, agent.init_state(1))


@pytest.fixture
def rounding():
    with mock.patch.object(players, "products_round_to_bfloat16", lambda cfg: True):
        yield


def test_the_view_casts_the_product_operands_and_nothing_else(rounding):
    agent = HybridLM(CONFIG)
    params = _params(agent)
    view_of, nbytes = players.make_policy_view(agent, _cfg(), 2)
    view, before = _names(view_of(params)), _names(params)
    assert set(view) == set(before) == set(PRODUCTS) | set(KEPT)  # every leaf of the model is on one list or the other
    for name, size in PRODUCTS.items():
        assert view[name].dtype == jnp.bfloat16 and view[name].size == size, name
        np.testing.assert_array_equal(np.asarray(view[name]), np.asarray(before[name].astype(jnp.bfloat16)))
    for name in KEPT:
        assert view[name] is before[name], name  # the parameter's own array: float32, and no copy of it
    assert nbytes == 2 * sum(PRODUCTS.values())  # what is reckoned: two bytes a matrix weight, and nothing for the rest
    assert nbytes == sum(x.nbytes for x in view.values() if x.dtype == jnp.bfloat16)


@pytest.mark.parametrize("precision, matmul_precision, backend, want", [
    ("32-true", "default", "tpu", "products"), ("32-true", "bfloat16", "tpu", "products"),
    ("32-true", "high", "tpu", "params"), ("32-true", "highest", "tpu", "params"), ("32-true", "float32", "tpu", "params"),
    ("32-true", "default", "cpu", "params"), ("bf16-true", "default", "tpu", "params"),
    ("bf16-mixed", "default", "tpu", "all"), ("bf16-mixed", "default", "cpu", "all"),
])
def test_which_view_holds_is_read_from_the_facts(precision, matmul_precision, backend, want):
    agent = HybridLM(CONFIG, dtype=players.compute_dtype_of(_cfg(precision)))
    params = players.cast_floating(_params(agent), players.resolve_precision(precision)[0])
    with mock.patch.object(jax, "default_backend", lambda: backend):
        view_of, nbytes = players.make_policy_view(agent, _cfg(precision, matmul_precision), 2)
    view = view_of(params)
    cast = {name for name, x in _names(view).items() if x is not _names(params)[name]}
    if want == "params":
        assert view is params and nbytes == 0
    elif want == "products":
        assert cast == set(PRODUCTS) and nbytes == 2 * sum(PRODUCTS.values())
    else:  # what ``policy_step`` made anew every token until PR 35: ``cast_floating(params, bfloat16)``
        assert cast == set(_names(params)) and nbytes == 2 * sum(x.size for x in jax.tree_util.tree_leaves(params))
        for got, ref in zip(jax.tree_util.tree_leaves(view), jax.tree_util.tree_leaves(players.cast_floating(params, jnp.bfloat16))):
            assert got.dtype == ref.dtype == jnp.bfloat16 and np.array_equal(np.asarray(got), np.asarray(ref))


def test_three_vector_steps_through_the_view_are_those_of_the_rounded_parameters(rounding):
    """On the CPU a product of float32 operands is exact, so kernels held as
    bfloat16 and float32 kernels rounded through bfloat16 are one arithmetic:
    everything a step leaves is equal, not close."""
    agent, cfg, steps, envs = HybridLM(CONFIG), _cfg(), 3, 2
    params = _params(agent, seed=3)
    view = players.make_policy_view(agent, cfg, envs)[0](params)
    rounded = jax.tree_util.tree_map(lambda x, v: v.astype(jnp.float32) if v.dtype == jnp.bfloat16 else x, params, view)
    policy_step, value_step, _ = players.make_token_player(agent, cfg, steps)
    staged = np.array([[[3, 7], [1, 0]], [[5, 2], [0, 0]], [[9, 4], [0, 1]]], np.int32)  # env 0 starts anew, then env 1 does
    left = []
    for tree in (view, rounded, params):
        carry = {"state": agent.init_state(envs), "key": jax.random.PRNGKey(5), "t": jnp.zeros((), jnp.int32),
                 "logprobs": jnp.zeros((steps, envs), jnp.float32), "values": jnp.zeros((steps, envs), jnp.float32)}
        actions, logits = [], []
        for row in staged:
            logits.append(agent.apply(tree, row[0][:, None], row[1][:, None], carry["state"], decode=True)[0])
            taken, carry = policy_step(tree, carry, row)
            actions.append(taken)
        left.append(jax.tree_util.tree_map(np.asarray, (actions, logits, carry, value_step(tree, carry, staged[0]))))
    for got, want in zip(jax.tree_util.tree_leaves(left[0]), jax.tree_util.tree_leaves(left[1])):
        np.testing.assert_array_equal(got, want)
    # and the test can fail: the parameters as they are give other logits (a CPU does not round them)
    assert not np.array_equal(left[0][1][0], left[2][1][0])


def test_a_single_env_has_no_product_through_the_mxu(rounding):
    """One row a decoded step: every product is a multiply and a reduction in float32, so nothing may be rounded."""
    agent = HybridLM(CONFIG)
    params = _params(agent)
    view_of, nbytes = players.make_policy_view(agent, _cfg(), 1)
    assert view_of(params) is params and nbytes == 0


def test_on_the_cpu_the_view_is_the_parameters():
    """Under ``32-true`` off the TPU nothing is cast and no program is made: ``view_of`` hands back what it got."""
    agent = HybridLM(CONFIG)
    params = _params(agent)
    view_of, nbytes = players.make_policy_view(agent, _cfg(), 2)
    assert view_of(params) is params and nbytes == 0
    assert not players.products_round_to_bfloat16(_cfg())


def _bfloat16_alive():
    return [x for x in jax.live_arrays() if x.dtype == jnp.bfloat16]


def test_the_view_is_made_once_an_update_and_is_not_alive_beside_it(rounding):
    """Two iterations through the CLI: two views; none alive where
    ``train_step`` is called (on the chip it would stand beside the update's
    4 GB of temporaries) nor after ``main`` returns, with the rest of the player."""
    from sheeprl_tpu.diagnostics import Diagnostics

    made, at_the_update, footprints = [], [], {}
    make, instrument, register = players.make_policy_view, Diagnostics.instrument, Diagnostics.register_footprint

    def counting(agent, cfg, num_envs):
        view_of, nbytes = make(agent, cfg, num_envs)
        return (lambda params: (made.append(nbytes), view_of(params))[1]), nbytes

    def watching(self, name, fn, **kwargs):
        step = instrument(self, name, fn, **kwargs)

        def watched(*args):
            gc.collect()
            at_the_update.append((len(made), len(_bfloat16_alive())))
            return step(*args)

        return watched

    with mock.patch.object(players, "make_policy_view", counting), mock.patch.object(Diagnostics, "instrument", watching), \
            mock.patch.object(Diagnostics, "register_footprint", lambda self, name, x: (footprints.update({name: x}), register(self, name, x))[1]):
        _run_cli(*TINY, "algo.total_steps=64", "checkpoint.save_last=False")
    assert made == [2 * sum(PRODUCTS.values())] * 2  # 2 envs x 16 steps an iteration: two iterations, a view each
    assert at_the_update == [(1, 0), (2, 0)]
    assert footprints["policy_view"] == made[0] and footprints["policy_carry"] > 0
    gc.collect()
    assert not _bfloat16_alive()
    assert not [p for p in gc.get_objects() if isinstance(p, players.TokenPlayer) and hasattr(p, "num_envs")]


def test_the_views_program_compiles_once(rounding, compiles):  # noqa: F811
    """Three iterations make three views with one program: nothing compiles from the second iteration on."""
    _run_cli(*TINY, "algo.total_steps=96", "checkpoint.save_last=False")
    assert [n for n in compiles.names if "policy_view" in n] == ["jit(policy_view)"]
    assert len([n for n in compiles.names if n == "jit(policy_step)"]) == 1


def test_the_views_bytes_are_on_the_metrics_page():
    from sheeprl_tpu.diagnostics.metrics_server import render_prometheus
    from sheeprl_tpu.diagnostics.telemetry import Telemetry

    page = render_prometheus({"policy_state": {"state_resets_total": 1, "cache_positions": 2, "carry_bytes": 3, "view_bytes": 1435937280}})
    assert "# TYPE sheeprl_policy_view_bytes gauge\nsheeprl_policy_view_bytes 1.43594e+09" in page
    telemetry = Telemetry.__new__(Telemetry)  # the counter's own arithmetic, without a run around it
    telemetry._lock, telemetry._policy_state = mock.MagicMock(), {}
    telemetry.note_policy_state(2, 40, 1000, 500)
    telemetry.note_policy_state(1, 41, 1000, 500)
    assert telemetry._policy_state == {"state_resets_total": 3, "cache_positions": 41, "carry_bytes": 1000, "view_bytes": 500}
