"""The hybrid language model against its plain reference, tiny sizes, CPU, seeded weights.

The reference is the benchmark's (``benchmarks/chip/olmo_hybrid_reference.py``:
``jax.numpy``, float32 at ``highest``, the delta rule as its recurrence, one
masked softmax an episode, no cache).  Held here: each layer's forward; the
delta rule's one-step, chunked and reference forms, values and gradients, with
resets inside a chunk and at its edge and write strengths over 1; a player
decoding token by token through state and cache against the full-sequence
forward from the same snapshot, across episode ends; the shares of heads and
of the vocabulary adding up to the uncut layer and logits; the gradient of a
loss through the whole model."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import olmo_hybrid_reference as reference
from sheeprl_tpu.models.hybrid_lm import FULL, LINEAR, Block, FullAttention, GatedDeltaNet, HybridConfig, HybridLM, take_share
from sheeprl_tpu.ops.delta_rule import delta_rule_chunked, delta_rule_step

CONFIG = HybridConfig(
    hidden_size=32, intermediate_size=48, layer_types=(LINEAR, LINEAR, LINEAR, FULL), heads_total=4, heads_held=2, head_share=0,
    linear_key_head_dim=6, linear_value_head_dim=12, linear_conv_kernel_dim=4, linear_allow_neg_eigval=True, rms_norm_eps=1e-6,
    vocab_total=64, vocab_held=16, vocab_share=0, cache_len=40, chunk_size=8,
)
B, T = 3, 24


def shapes_of(config):
    return {**dataclasses.asdict(config), "layer_types": list(config.layer_types)}


def resets_of(rows):
    """``[B, T]`` resets with ones at the listed ``(row, step)`` pairs."""
    out = np.zeros((B, T), np.int32)
    for row, step in rows:
        out[row, step] = 1
    return jnp.asarray(out)


# inside a chunk (5), two in one chunk (5, 7), at a chunk's first position (8, 16), at the sequence's first (0)
RESETS = resets_of([(0, 5), (0, 7), (1, 8), (1, 16), (2, 0)])


@pytest.fixture(scope="module")
def model():
    lm = HybridLM(CONFIG)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (B, T), 0, CONFIG.vocab_held)
    params = lm.init(jax.random.PRNGKey(1), tokens, RESETS, lm.init_state(B))
    decode = jax.jit(lambda p, t, r, s: lm.apply(p, t[:, None], r[:, None], s, decode=True))
    # a carried state worth the name: ten decoded tokens, an episode end among them
    state = lm.init_state(B)
    prefix = jax.random.randint(jax.random.PRNGKey(2), (B, 10), 0, CONFIG.vocab_held)
    for t in range(10):
        _, _, state = decode(params, prefix[:, t], jnp.asarray([0, int(t == 4), 0], jnp.int32), state)
    return lm, params, tokens, state, decode


def test_the_delta_rule_forms_agree_in_values_and_gradients():
    H, dk, dv = 2, 6, 12
    keys = jax.random.split(jax.random.PRNGKey(3), 7)
    q = jax.random.normal(keys[0], (B, T, H, dk))
    k = jax.random.normal(keys[1], (B, T, H, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    k = k.at[:, 3].set(k[:, 2])  # a repeated key: the write strength over 1 flips what it stored
    v = jax.random.normal(keys[2], (B, T, H, dv))
    log_a = -jax.nn.softplus(jax.random.normal(keys[3], (B, T, H)))
    b = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[4], (B, T, H)) + 1.0)  # most of them over 1
    S0 = jax.random.normal(keys[5], (B, H, dv, dk))
    w = jax.random.normal(keys[6], (B, T, H, dv))
    assert float(jnp.mean(b > 1.0)) > 0.5

    def stepped(S, q, k, v, log_a, b):
        out = []
        for t in range(T):
            S, o = delta_rule_step(S, q[:, t], k[:, t], v[:, t], log_a[:, t], b[:, t], RESETS[:, t])
            out.append(o)
        return S, jnp.stack(out, 1)

    def plain(S, q, k, v, log_a, b):
        one = lambda S, q, k, v, log_a, b, r: reference.Model(shapes_of(CONFIG)).delta_rule(S, q, k, v, log_a, b, r)  # noqa: E731
        heads = jax.vmap(one, in_axes=(0, 1, 1, 1, 1, 1, None), out_axes=1)
        return None, jax.vmap(heads)(S, q, k, v, log_a, b, RESETS)

    forms = {
        "chunk 8": lambda *a: delta_rule_chunked(*a, RESETS, chunk=8),
        "chunk 4": lambda *a: delta_rule_chunked(*a, RESETS, chunk=4),
        "one chunk": lambda *a: delta_rule_chunked(*a, RESETS, chunk=64),
        "stepped": stepped,
    }
    args = (S0, q, k, v, log_a, b)
    want = plain(*args)[1]
    want_grads = jax.grad(lambda *a: jnp.sum(plain(*a)[1] * w), argnums=tuple(range(6)))(*args)
    final = stepped(*args)[0]
    for name, form in forms.items():
        S, o = form(*args)
        np.testing.assert_allclose(o, want, atol=2e-5, err_msg=name)
        np.testing.assert_allclose(S, final, atol=2e-5, err_msg=name)
        grads = jax.grad(lambda *a: jnp.sum(form(*a)[1] * w), argnums=tuple(range(6)))(*args)
        for g, wg in zip(grads, want_grads):
            np.testing.assert_allclose(g, wg, atol=1e-4, err_msg=name)
    with pytest.raises(ValueError, match="multiple of the chunk"):
        delta_rule_chunked(*args, RESETS, chunk=7)


@pytest.mark.parametrize("kind", [LINEAR, FULL])
def test_each_layer_alone_is_the_references(model, kind):
    lm, params, _, state, _ = model
    index = list(CONFIG.layer_types).index(kind)
    layer_params = params["params"][f"layers_{index}"]
    x = jax.random.normal(jax.random.PRNGKey(4), (B, T, CONFIG.hidden_size))
    block = Block(CONFIG, kind)
    got, _ = block.apply({"params": layer_params}, x, RESETS, state["layers"][index], state["pos"], False, True)
    plain = reference.Model(shapes_of(CONFIG))

    def one(x, resets, layer_state, pos):
        h = reference.rms_norm(layer_params["mixer_norm"]["scale"], x, 1e-6)
        if kind == LINEAR:
            x = x + plain.linear_layer(layer_params["mixer"], h, resets, layer_state)
        else:
            x = x + plain.full_layer(layer_params["mixer"], h, resets, layer_state, pos)
        return x + plain.mlp(layer_params["mlp"], reference.rms_norm(layer_params["mlp_norm"]["scale"], x, 1e-6))

    want = jax.vmap(one)(x, RESETS, state["layers"][index], state["pos"])
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_decoding_through_state_and_cache_is_the_full_sequence_forward(model):
    """Logits, not samples: the player token by token, the learner's chunked
    sequence form and the reference's whole-sequence forward, all from one
    snapshot, across episode ends."""
    lm, params, tokens, snapshot, decode = model
    want_logits, want_values = reference.Model(shapes_of(CONFIG)).batch(params, tokens, RESETS, snapshot)
    seq_logits, seq_values, _ = lm.apply(params, tokens, RESETS, snapshot)
    state, logits, values = snapshot, [], []
    for t in range(T):
        lg, v, state = decode(params, tokens[:, t], RESETS[:, t], state)
        logits.append(lg[:, 0])
        values.append(v[:, 0])
    for got in (seq_logits, jnp.stack(logits, 1)):
        np.testing.assert_allclose(got, want_logits, atol=3e-5)
    for got in (seq_values, jnp.stack(values, 1)):
        np.testing.assert_allclose(got, want_values, atol=3e-5)
    # the cache restarted where the last reset fell, and holds the episode's positions since
    last_reset = [max([-1] + [t for t in range(T) if int(RESETS[b, t])]) for b in range(B)]
    want_pos = [T - r if r >= 0 else int(snapshot["pos"][b]) + T for b, r in enumerate(last_reset)]
    assert [int(p) for p in state["pos"]] == want_pos
    # a value read without a write leaves the state as it was
    peek = lm.apply(params, tokens[:, :1], RESETS[:, :1] * 0, state, decode=True, write=False)
    full = lm.apply(params, tokens[:, :1], RESETS[:, :1] * 0, state, decode=True)
    np.testing.assert_allclose(peek[1], full[1], atol=1e-6)


def test_a_dropped_carry_is_another_result(model):
    lm, params, tokens, snapshot, _ = model
    kept = lm.apply(params, tokens, RESETS, snapshot)[0]
    dropped = lm.apply(params, tokens, RESETS, lm.init_state(B))[0]
    assert float(jnp.abs(kept[0] - dropped[0]).max()) > 1e-3  # row 0 reads its carried state until step 5
    np.testing.assert_allclose(kept[2], dropped[2], atol=1e-6)  # row 2 is reset at its first step: nothing carried is read


def test_the_shares_add_up():
    """At 4 heads and 64 ids: the two head-shares' partial outputs of every
    layer sum to the uncut reference layer, and the vocabulary's slices
    concatenate to the uncut logits."""
    whole = dataclasses.replace(CONFIG, heads_held=4, vocab_held=64)
    lm = HybridLM(whole)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (B, T), 0, 16)
    params = lm.init(jax.random.PRNGKey(6), tokens, RESETS, lm.init_state(B))
    x = jax.random.normal(jax.random.PRNGKey(7), (B, T, whole.hidden_size))
    plain = reference.Model(shapes_of(whole))
    zero = lm.init_state(B)
    for index, kind in enumerate(whole.layer_types):
        mixer = params["params"][f"layers_{index}"]["mixer"]
        if kind == LINEAR:
            uncut = jax.vmap(lambda x, r, s: plain.linear_layer(mixer, x, r, s))(x, RESETS, zero["layers"][index])
        else:
            uncut = jax.vmap(lambda x, r, s, p: plain.full_layer(mixer, x, r, s, p))(x, RESETS, zero["layers"][index], zero["pos"])
        parts = []
        for share in range(2):
            config = dataclasses.replace(CONFIG, head_share=share)
            held = {"params": take_share(params, whole, config)["params"][f"layers_{index}"]["mixer"]}
            state = HybridLM(config).init_state(B)
            if kind == LINEAR:
                y, _ = GatedDeltaNet(config).apply(held, x, RESETS, state["layers"][index], False)
            else:
                y, _ = FullAttention(config).apply(held, x, RESETS, state["layers"][index], state["pos"], False)
            parts.append(y)
        np.testing.assert_allclose(parts[0] + parts[1], uncut, atol=3e-5, err_msg=f"layer {index}")
    # the vocabulary: each slice's model reads its own rows of the embedding, so feed ids of slice 0 and compare the heads
    uncut_logits = plain.batch(params, tokens, RESETS, zero)[0]
    slices = []
    for share in range(4):
        config = dataclasses.replace(whole, vocab_held=16, vocab_share=share)
        held = take_share(params, whole, config)
        held["params"]["embed_tokens"] = take_share(params, whole, dataclasses.replace(config, vocab_share=0))["params"]["embed_tokens"]
        slices.append(HybridLM(config).apply(held, tokens, RESETS, HybridLM(config).init_state(B))[0])
    np.testing.assert_allclose(jnp.concatenate(slices, -1), uncut_logits, atol=3e-5)


def test_the_gradient_through_the_whole_model_is_the_references(model):
    lm, params, tokens, snapshot, _ = model
    keys = jax.random.split(jax.random.PRNGKey(8), 2)
    w_logits = jax.random.normal(keys[0], (B, T, CONFIG.vocab_held))
    w_values = jax.random.normal(keys[1], (B, T))

    def program(p):
        logits, values, _ = lm.apply(p, tokens, RESETS, snapshot)
        return jnp.sum(jax.nn.log_softmax(logits) * w_logits) + jnp.sum(values * w_values)

    def plain(p):
        logits, values = reference.Model(shapes_of(CONFIG)).batch(p, tokens, RESETS, snapshot)
        return jnp.sum(jax.nn.log_softmax(logits) * w_logits) + jnp.sum(values * w_values)

    got, want = jax.grad(program)(params), jax.grad(plain)(params)
    flat_got, flat_want = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want) == len(jax.tree_util.tree_leaves(params))
    for g, w in zip(flat_got, flat_want):
        assert float(jnp.linalg.norm(w)) > 0  # no leaf the loss does not reach
        np.testing.assert_allclose(g, w, atol=2e-4 * max(1.0, float(jnp.abs(w).max())))


def test_what_cannot_work_is_said():
    assert dataclasses.replace(CONFIG, heads_held=3).problems() == ["heads_held (3) must divide heads_total (4)"]
    assert dataclasses.replace(CONFIG, vocab_held=10).problems() == ["vocab_held (10) must divide vocab_total (64)"]
    assert "head_share" in dataclasses.replace(CONFIG, head_share=2).problems()[0]
    assert CONFIG.problems() == []
