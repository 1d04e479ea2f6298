"""The DV3 family's dynamic-learning scan keeps only what depends on its carry
(`utils.py::dynamic_learning_scan` + `RSSM.scan_*`).

* against the one-step `RSSM.dynamic` driven a step at a time (the form the
  three train-step builders held before): identical posterior draws, and
  logits and recurrent states equal to float32 re-association — at
  ``chunks=1``, ``chunks>1`` with and without burn-in, ``decoupled_rssm=True``,
  a stack with biases in place of LayerNorm, and once each through the JEPA
  and P2E train-step builders;
* gradients: the scan's own backward (the kernels' gradients taken once after
  the loop) against plain autodiff through `RSSM.dynamic` a step, for every
  leaf of the world model's parameters, the embedded observations and the
  actions, in each of those forms and through both builders' train steps; the
  burn-in loop passes no gradient;
* structure: in `make_train_step`'s jaxpr the T-step loops hold no product
  with the embedded observation, none of the transition head's and no random
  bits, the transposed loop neither computes nor carries anything of a
  kernel's shape, and the four kernels' gradients are products over all T x B
  rows outside it; and the parameter tree is the one checked in beside this file.
"""

from __future__ import annotations

import functools
import json
from pathlib import Path

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from sheeprl_tpu.algos.dreamer_v3.utils import chunked_dynamic_scan, dynamic_learning_scan, init_moments_state
from sheeprl_tpu.config import compose

T, B = 8, 2
STOCH, DISCRETE, REC = 4, 4, 8
# widths that no two layers of the tiny model share, so that a product's shapes name its layer
EMBED, DENSE, HIDDEN = 20, 10, 12
TINY = [
    "env=dummy",
    "env.id=discrete_dummy",
    f"algo.per_rank_batch_size={B}",
    f"algo.per_rank_sequence_length={T}",
    "algo.horizon=3",
    "algo.dense_units=8",
    "algo.mlp_layers=1",
    f"algo.world_model.encoder.dense_units={EMBED}",
    "algo.world_model.encoder.cnn_channels_multiplier=2",
    f"algo.world_model.recurrent_model.recurrent_state_size={REC}",
    f"algo.world_model.recurrent_model.dense_units={DENSE}",
    f"algo.world_model.representation_model.hidden_size={HIDDEN}",  # the transition head's width too
    f"algo.world_model.discrete_size={DISCRETE}",
    f"algo.world_model.stochastic_size={STOCH}",
    "algo.cnn_keys.encoder=[]",
    "algo.cnn_keys.decoder=[]",
    "algo.mlp_keys.encoder=[state]",
    "algo.mlp_keys.decoder=[state]",
    "metric.log_level=0",
]
OBS_SPACE = gym.spaces.Dict({"state": gym.spaces.Box(-np.inf, np.inf, (10,), np.float32)})
ACTIONS_DIM = (3,)


def _batch(seed: int = 3):
    rngs = jax.random.split(jax.random.PRNGKey(seed), 6)
    return {
        "state": jax.random.normal(rngs[0], (T, B, 10)),
        "actions": jax.nn.one_hot(jax.random.randint(rngs[1], (T, B), 0, 3), 3, dtype=jnp.float32),
        "rewards": jax.random.normal(rngs[2], (T, B, 1)),
        "terminated": jnp.zeros((T, B, 1)),
        # an episode start inside the sequence: the reset path runs mid-scan
        "is_first": jnp.zeros((T, B, 1)).at[0].set(1.0).at[3, 1].set(1.0),
        # what the player would have stored; any values do, both forms read the same
        "rssm_recurrent": jnp.tanh(jax.random.normal(rngs[3], (T, B, REC))),
        "rssm_posterior": jax.nn.one_hot(
            jax.random.randint(rngs[4], (T, B, STOCH), 0, DISCRETE), DISCRETE, dtype=jnp.float32
        ).reshape(T, B, STOCH * DISCRETE),
        "rssm_valid": jnp.ones((T, B, 1)).at[T // 2 - 1, 0].set(0.0),
    }


def one_step_dynamic_scan(world_model_def, wm_params, batch_actions, embedded, is_first, key, *, chunks=1, **scan_spec):
    """The dynamic-learning pass as the one-step `RSSM.dynamic` a scan step:
    everything inside the loop, the prior head and the draws' noise too.
    Takes what `dynamic_learning_scan` takes."""

    def scan_body(carry, x):
        posterior, recurrent = carry
        recurrent, posterior, _, post_logits, prior_logits = world_model_def.apply(
            wm_params, posterior, recurrent, *x, method="dynamic"
        )
        return (posterior, recurrent), (recurrent, posterior, post_logits, prior_logits)

    if chunks == 1:  # hand-inlined: no helper between the test and lax.scan
        init = (jnp.zeros((B, STOCH * DISCRETE)), jnp.zeros((B, REC)))
        return jax.lax.scan(scan_body, init, (batch_actions, embedded, is_first, jax.random.split(key, T)))[1]
    return chunked_dynamic_scan(functools.partial(jax.lax.scan, scan_body), batch_actions, embedded, is_first, key, chunks=chunks, **scan_spec)


def _world_model(*overrides, **fields):
    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent

    cfg = compose(["exp=dreamer_v3", *TINY, *overrides])
    wm_def, _, _, params = build_agent(None, ACTIONS_DIM, False, cfg, OBS_SPACE)
    wm_params = params["world_model"]
    if fields:  # module fields no config key reaches
        wm_def = wm_def.clone(**fields)
        wm_params = wm_def.init(
            jax.random.PRNGKey(0), {"state": jnp.zeros((1, 10))}, jnp.zeros((1, 3)), jnp.zeros((1, 1)), jax.random.PRNGKey(1)
        )
    # a learned initial state that is not the zeros it starts as
    rssm = wm_params["params"]["rssm"]
    if "initial_recurrent_state" in rssm:
        rssm["initial_recurrent_state"] = jnp.linspace(-1.0, 1.0, REC)
    return wm_def, wm_params


SCAN_CASES = {
    "chunks1": ((), {}, {}),
    "chunks2": ((), {}, {"chunks": 2}),
    "chunks4_burn_in1": ((), {}, {"chunks": 4, "burn_in": 1}),
    "chunks2_burn_in2": ((), {}, {"chunks": 2, "burn_in": 2}),
    "decoupled": (("algo.world_model.decoupled_rssm=True",), {}, {}),
    "decoupled_chunks2_burn_in1": (("algo.world_model.decoupled_rssm=True",), {}, {"chunks": 2, "burn_in": 1}),
    "biases_for_layer_norm": ((), {"layer_norm": False}, {}),
    "unroll4": ((), {}, {"unroll": 4}),  # `algo.scan_unroll`: the reference's loop takes no notice of it
}


def _scan_case(case):
    """``run(scan, params, embedded, actions) -> the four outputs`` of a form of the scan."""
    overrides, fields, spec = SCAN_CASES[case]
    wm_def, wm_params = _world_model(*overrides, **fields)
    batch = _batch()
    key = jax.random.PRNGKey(11)
    scan_spec = dict(stoch_flat=STOCH * DISCRETE, recurrent_size=REC, cdt=jnp.float32, **spec)
    if spec:
        scan_spec.update(
            stored_recurrent=batch["rssm_recurrent"],
            stored_posterior=batch["rssm_posterior"],
            stored_valid=batch["rssm_valid"],
        )

    def run(scan, params, embedded, actions, **stored):
        return scan(wm_def, params, actions, embedded, batch["is_first"], key, **{**scan_spec, **stored})

    embedded = wm_def.apply(wm_params, {"state": batch["state"]}, method="encode")
    return run, wm_params, embedded, batch["actions"]


def _scalar(outputs):
    recurrents, posteriors, post_logits, prior_logits = outputs
    weights = jnp.arange(1.0, STOCH * DISCRETE + 1.0)
    return (
        jnp.sum(recurrents**2)
        + jnp.sum(posteriors * weights)
        + jnp.sum(jnp.sin(post_logits))
        + jnp.sum(jnp.cos(prior_logits) * weights)
    )


@pytest.mark.parametrize("case", list(SCAN_CASES))
def test_scan_equals_one_step_dynamic(case):
    run, wm_params, embedded, actions = _scan_case(case)
    want = jax.jit(lambda p: run(one_step_dynamic_scan, p, embedded, actions))(wm_params)
    got = jax.jit(lambda p: run(dynamic_learning_scan, p, embedded, actions))(wm_params)
    names = ("recurrents", "posteriors", "post_logits", "prior_logits")
    for name, w, g in zip(names, want, got):
        assert w.shape == g.shape, name
        np.testing.assert_allclose(np.asarray(w), np.asarray(g), rtol=1e-5, atol=1e-6, err_msg=name)
    # the same draws, exactly (a straight-through value is (one_hot + p) - p: the one-hot to an ulp)
    draws = np.rint(np.asarray(got[1])).reshape(T, B, STOCH, DISCRETE)
    np.testing.assert_array_equal(draws, np.rint(np.asarray(want[1])).reshape(draws.shape))
    assert ((draws == 0) | (draws == 1)).all() and (draws.sum(-1) == 1).all()
    assert len({tuple(d.ravel()) for d in draws.reshape(T * B, -1)}) > 1, "every draw the same: nothing was sampled"


def _dv3(cfg):
    from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as mod
    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent

    wm, actor, critic, params = build_agent(None, ACTIONS_DIM, False, cfg, OBS_SPACE)
    optimizers, opt_states = mod._default_make_optimizers(cfg, params, None)
    return mod, (wm, actor, critic), params, optimizers, opt_states, init_moments_state()


def _jepa(cfg):
    from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as dv3
    from sheeprl_tpu.algos.dreamer_v3_jepa import dreamer_v3_jepa as mod

    wm, actor, critic, params = mod._build_agent(None, ACTIONS_DIM, False, cfg, OBS_SPACE, None)
    optimizers, opt_states = dv3._default_make_optimizers(cfg, params, None, mod._extra_opt_setup)
    return mod, (wm, actor, critic), params, optimizers, opt_states, init_moments_state()


def _p2e(cfg):
    from sheeprl_tpu.algos.p2e_dv3 import p2e_dv3_exploration as mod

    wm, actor, critic, params = mod._build_agent(None, ACTIONS_DIM, False, cfg, OBS_SPACE, None)
    optimizers, opt_states = mod._make_optimizers(cfg, params, None)
    return mod, (wm, actor, critic), params, optimizers, opt_states, mod._init_moments(cfg, None)


# builder, overrides, and what of the module sets the world-model optimizer's state up after `init`
BUILDERS = {
    "dreamer_v3_jepa": (_jepa, ["exp=dreamer_v3_jepa", "algo.jepa_proj_dim=8", "algo.jepa_hidden=8"], "_extra_opt_setup"),
    "p2e_dv3_exploration": (
        _p2e,
        ["exp=p2e_dv3_exploration", "algo.ensembles.n=2", "algo.ensembles.dense_units=8", "algo.ensembles.mlp_layers=1"],
        None,
    ),
}


@pytest.mark.parametrize("algo", list(BUILDERS))
def test_builders_train_step_equals_one_step_dynamic(algo, monkeypatch):
    """One gradient step of the JEPA and the P2E builders, with the shared
    scan and with `RSSM.dynamic` a step in its place: the same losses and
    gradient norms."""
    build, overrides, _ = BUILDERS[algo]
    cfg = compose([*overrides, *TINY])
    batch = {k: v for k, v in _batch().items() if not k.startswith("rssm_")}
    metrics = {}
    for form in ("shared", "one_step"):
        mod, defs, params, optimizers, opt_states, moments = build(cfg)
        if form == "one_step":
            monkeypatch.setattr(mod, "dynamic_learning_scan", one_step_dynamic_scan)
        step = mod.make_train_step(*defs, optimizers, cfg, ACTIONS_DIM, False)
        out = step(params, opt_states, moments, batch, jax.random.PRNGKey(5), jnp.float32(0.02))
        metrics[form] = np.asarray(out[3])
    assert np.isfinite(metrics["shared"]).all()
    assert metrics["shared"][0] != 0 and metrics["shared"][8] != 0  # a world-model loss and its gradient's norm
    np.testing.assert_allclose(metrics["shared"], metrics["one_step"], rtol=2e-5, atol=1e-6)


def _assert_gradients_equal(want, got, at_least):
    """Leaf by leaf to 1e-5 of the leaf's largest magnitude: the two differ by summation order only."""
    moved = 0
    for (path, w), g in zip(jax.tree_util.tree_leaves_with_path(want), jax.tree_util.tree_leaves(got), strict=True):
        w, g = np.asarray(w), np.asarray(g)
        scale = np.abs(w).max()
        np.testing.assert_allclose(w, g, rtol=0, atol=1e-5 * scale + 1e-9, err_msg=jax.tree_util.keystr(path))
        moved += scale > 0
    assert moved > at_least, "the loss reached almost no leaf"


# an optimizer that moves nothing and keeps the gradient it was given as its state
RECORDER = optax.GradientTransformation(
    init=lambda params: jax.tree_util.tree_map(jnp.zeros_like, params),
    update=lambda grads, state, params=None: (jax.tree_util.tree_map(jnp.zeros_like, grads), grads),
)


def _builder_gradients(algo, monkeypatch):
    """The world-model gradient of one train step of a builder, with the
    shared scan and with `RSSM.dynamic` a step in its place."""
    build, overrides, extra_opt_setup = BUILDERS[algo]
    cfg = compose([*overrides, *TINY])
    batch = {k: v for k, v in _batch().items() if not k.startswith("rssm_")}
    grads = {}
    for form in ("shared", "one_step"):
        mod, defs, params, optimizers, opt_states, moments = build(cfg)
        optimizers["world_model"] = RECORDER
        opt_states["world_model"] = RECORDER.init(params["world_model"])
        if extra_opt_setup is not None:
            opt_states = getattr(mod, extra_opt_setup)(optimizers, opt_states, params)
        if form == "one_step":
            monkeypatch.setattr(mod, "dynamic_learning_scan", one_step_dynamic_scan)
        step = mod.make_train_step(*defs, optimizers, cfg, ACTIONS_DIM, False)
        out = step(params, opt_states, moments, batch, jax.random.PRNGKey(5), jnp.float32(0.02))
        grads[form] = out[1]["world_model"]
    return grads["one_step"], grads["shared"]


def _burn_in_passes_no_gradient():
    """``chunks=2, burn_in=2``: nothing reaches the stored states the burn-in
    loop starts from, and of the loops of its length none runs backward."""
    run, wm_params, embedded, actions = _scan_case("chunks2_burn_in2")
    batch = _batch()

    def loss(params, stored_recurrent, stored_posterior):
        outputs = run(
            dynamic_learning_scan,
            params,
            embedded,
            actions,
            stored_recurrent=stored_recurrent,
            stored_posterior=stored_posterior,
        )
        return _scalar(outputs)

    leaves = (wm_params, batch["rssm_recurrent"], batch["rssm_posterior"])
    for grad in jax.grad(loss, argnums=(1, 2))(*leaves):
        assert not np.asarray(grad).any()
    jaxpr = jax.make_jaxpr(jax.grad(loss))(*leaves).jaxpr
    loops = {length: [loop.params["reverse"] for loop in _loops(jaxpr, length)] for length in (2, T // 2)}
    assert loops == {2: [False], T // 2: [False, True]}, loops


@pytest.mark.parametrize("case", [*SCAN_CASES, *BUILDERS, "burn_in_passes_no_gradient"])
def test_gradients_equal_plain_autodiff(case, monkeypatch):
    """The scan's backward takes the four kernels' gradients once after the
    loop; plain autodiff through `RSSM.dynamic` a step accumulates them in it."""
    if case == "burn_in_passes_no_gradient":
        return _burn_in_passes_no_gradient()
    if case in BUILDERS:
        return _assert_gradients_equal(*_builder_gradients(case, monkeypatch), at_least=30)
    run, wm_params, embedded, actions = _scan_case(case)

    def gradients(scan):
        return jax.jit(jax.grad(lambda *leaves: _scalar(run(scan, *leaves)), argnums=(0, 1, 2)))(
            wm_params, embedded, actions
        )

    _assert_gradients_equal(gradients(one_step_dynamic_scan), gradients(dynamic_learning_scan), at_least=10)


# ---------------------------------------------------------------------------
# structure


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for item in value if isinstance(value, (tuple, list)) else (value,):
            if isinstance(item, jax.extend.core.ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, jax.extend.core.Jaxpr):
                yield item


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of what it nests."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _eqns(sub)


def _loops(jaxpr, length):
    """The `lax.scan` equations of ``length`` steps, in order."""
    for eqn in _eqns(jaxpr):
        if eqn.primitive.name == "scan" and eqn.params["length"] == length:
            yield eqn


def _body(loop):
    return loop.params["jaxpr"].jaxpr


def _dots(jaxpr):
    for eqn in _eqns(jaxpr):
        if eqn.primitive.name == "dot_general":
            yield tuple(tuple(v.aval.shape) for v in eqn.invars)


def _random_primitives(jaxpr):
    return {n for n in (eqn.primitive.name for eqn in _eqns(jaxpr)) if "random" in n or "threefry" in n}


STOCH_FLAT = STOCH * DISCRETE
# the products a step of `RSSM.dynamic` needs from its carry, as (lhs, rhs) shapes
CARRIED_PRODUCTS = sorted(
    [
        ((B, STOCH_FLAT), (STOCH_FLAT, DENSE)),  # the posterior's rows of the recurrent model's input product
        ((B, REC + DENSE), (REC + DENSE, 3 * REC)),  # the LayerNorm-GRU's joint product
        ((B, REC), (REC, HIDDEN)),  # the recurrent state's rows of the representation model's input product
        ((B, HIDDEN), (HIDDEN, STOCH_FLAT)),  # the representation head
    ]
)
# ... and the three it does not: the transition head repeats the last two shapes
EMBED_PRODUCT = ((T, B, EMBED), (EMBED, HIDDEN))
ACTION_PRODUCT = ((T, B, ACTIONS_DIM[0]), (ACTIONS_DIM[0], DENSE))
PRIOR_PRODUCTS = [((T, B, REC), (REC, HIDDEN)), ((T, B, HIDDEN), (HIDDEN, STOCH_FLAT))]


# the four kernels' gradients, once after the loop: (the products' stacked inputs, the stacked cotangents of their outputs)
KERNEL_GRADIENTS = [((T, B, x[-1]), (T, B, kernel[-1])) for x, kernel in CARRIED_PRODUCTS]


def _kernel_shaped_in_backward(loop, kernel_shapes):
    """What the transposed loop computes or carries at a kernel's size: the
    shapes of every product's output (either way round: a kernel's gradient is
    taken as its transpose) and of every carried value that are a kernel's
    (for the kernel read by its leading rows, the whole one's too)."""
    body = _body(loop)
    consts, carried = loop.params["num_consts"], loop.params["num_carry"]
    shapes = [tuple(v.aval.shape) for v in body.invars[consts : consts + carried]]
    shapes += [tuple(eqn.outvars[0].aval.shape) for eqn in _eqns(body) if eqn.primitive.name == "dot_general"]
    return [shape for shape in shapes if {shape, shape[::-1]} & kernel_shapes]


def _kernel_shapes(wm_params):
    rssm = wm_params["params"]["rssm"]
    return {tuple(leaf.shape) for leaf in jax.tree_util.tree_leaves(rssm) if leaf.ndim == 2} | {
        kernel for _, kernel in CARRIED_PRODUCTS
    }


def test_train_step_dynamic_loops_hold_only_what_their_carry_needs():
    cfg = compose(["exp=dreamer_v3", *TINY])
    mod, defs, params, optimizers, opt_states, moments = _dv3(cfg)
    step = mod.make_train_step(*defs, optimizers, cfg, ACTIONS_DIM, False)
    batch = {k: v for k, v in _batch().items() if not k.startswith("rssm_")}
    jaxpr = jax.make_jaxpr(step)(params, opt_states, moments, batch, jax.random.PRNGKey(0), jnp.float32(0.02)).jaxpr
    forward, backward = _loops(jaxpr, T)  # horizon=3 is the imagination's length: the forward loop runs once
    assert not forward.params["reverse"] and backward.params["reverse"]
    # forward: the four carried products and no other; so no embed product
    # and no second (REC, HIDDEN) or (HIDDEN, STOCH_FLAT): no transition head
    assert sorted(_dots(_body(forward))) == CARRIED_PRODUCTS
    # backward: an input's gradient for each of the four, (the output's cotangent, the kernel) ...
    assert sorted(_dots(_body(backward))) == sorted(((B, kernel[-1]), kernel) for _, kernel in CARRIED_PRODUCTS)
    # ... and nothing of a kernel's shape computed or carried
    assert not _kernel_shaped_in_backward(backward, _kernel_shapes(params["world_model"]))
    for body in (_body(forward), _body(backward)):
        assert not _random_primitives(body)
        widths = {d for shapes in _dots(body) for shape in shapes for d in shape}
        assert not widths & {EMBED, REC + EMBED, ACTIONS_DIM[0], STOCH_FLAT + ACTIONS_DIM[0]}, widths
    # what left the loops runs outside them, once on all T x B rows
    outside = list(_dots(jaxpr))
    for product in (EMBED_PRODUCT, ACTION_PRODUCT, *PRIOR_PRODUCTS, *KERNEL_GRADIENTS):
        assert product in outside, product
    assert _random_primitives(jaxpr), "the draws' noise is drawn nowhere"


def test_one_step_form_fails_the_structural_check():
    """The check above sees what it looks for: with `RSSM.dynamic` a step the
    loop holds the embed's and the action's rows, the transition head (for
    the prior and again for the initial state) and the random bits; and plain
    autodiff through it computes and carries the kernels' gradients in the
    transposed loop."""
    wm_def, wm_params = _world_model()
    batch = _batch()

    def run(params):
        embedded = wm_def.apply(params, {"state": batch["state"]}, method="encode")
        return one_step_dynamic_scan(
            wm_def, params, batch["actions"], embedded, batch["is_first"], jax.random.PRNGKey(0)
        )

    (forward,) = _loops(jax.make_jaxpr(run)(wm_params).jaxpr, T)
    dots = list(_dots(_body(forward)))
    assert ((B, REC + EMBED), (REC + EMBED, HIDDEN)) in dots
    assert ((B, STOCH_FLAT + ACTIONS_DIM[0]), (STOCH_FLAT + ACTIONS_DIM[0], DENSE)) in dots
    assert dots.count(((B, REC), (REC, HIDDEN))) == 2 and dots.count(((B, HIDDEN), (HIDDEN, STOCH_FLAT))) == 3
    assert _random_primitives(_body(forward))

    _, backward = _loops(jax.make_jaxpr(jax.grad(lambda p: _scalar(run(p))))(wm_params).jaxpr, T)
    in_loop = _kernel_shaped_in_backward(backward, _kernel_shapes(wm_params))
    for kernel in ((REC + DENSE, 3 * REC), (HIDDEN, STOCH_FLAT)):  # the GRU's and the representation head's
        assert kernel in in_loop and kernel[::-1] in in_loop, (kernel, in_loop)  # carried, and computed
    outside = list(_dots(jax.make_jaxpr(jax.grad(lambda p: _scalar(run(p))))(wm_params).jaxpr))
    assert not set(KERNEL_GRADIENTS) & set(outside)


PARAM_TREE = Path(__file__).with_name("dv3_s_param_tree.json")


def _dv3_s_param_tree():
    """Paths and shapes of DV3-S's four trees (`exp=dreamer_v3`, `algo=dreamer_v3_S`,
    one 64x64 RGB key, 9 discrete actions), abstractly: nothing is initialised."""
    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent

    cfg = compose(
        [
            "exp=dreamer_v3",
            "algo=dreamer_v3_S",
            "env=dummy",
            "env.id=discrete_dummy",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.cnn_keys.decoder=[rgb]",
            "algo.mlp_keys.encoder=[]",
            "algo.mlp_keys.decoder=[]",
            "metric.log_level=0",
        ]
    )
    obs_space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (3, 64, 64), np.uint8)})
    params = jax.eval_shape(lambda: build_agent(None, (9,), False, cfg, obs_space)[3])
    return {
        jax.tree_util.keystr(path): list(leaf.shape) for path, leaf in jax.tree_util.tree_leaves_with_path(params)
    }


def test_parameter_tree_is_the_one_checked_in():
    """Checkpoints, the benchmark's weights and its plain reference read the
    tree by name: the list was written from the commit before the scan moved."""
    assert _dv3_s_param_tree() == json.loads(PARAM_TREE.read_text())
