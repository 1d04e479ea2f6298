"""The sparse-attention, routed-expert policy (``models/sparse_moe_lm.py``, ``ops/sparse_index.py``,
``ops/moe.py``) against the plain reference (``benchmarks/chip/sparse_moe_reference.py``) on seeded weights.

Small widths, a ``topk`` smaller than the episodes so that the selection
bites, an episode end inside a sequence, caches carried from a decoded
prefix: log-probabilities, values, the PPO losses, the indexers' loss and the
gradient of every leaf; the two forms of the selection against
``jax.lax.top_k`` with planted ties; the expert shares' partial sums; the two
disjoint gradient paths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import sparse_moe_reference as ref
from sheeprl_tpu.models import sparse_moe_lm
from sheeprl_tpu.models.sparse_moe_lm import SparseMoEConfig, SparseMoELM, take_share
from sheeprl_tpu.ops.sparse_attention import key_tile
from sheeprl_tpu.ops.sparse_index import select_indices, select_mask

TINY = dict(hidden_size=32, num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8, rope_theta=1e4, mrope_section=(1, 1, 2),
            indexer_heads=2, indexer_head_dim=4, topk=6, experts_total=8, experts_held=4, expert_share=1, experts_per_token=2,
            expert_width=16, norm_topk_prob=True, rms_norm_eps=1e-6, vocab_total=64, vocab_held=16, vocab_share=0, cache_len=32,
            query_block=4)
HYPER = {"clip_coef": 0.2, "vf_coef": 0.2, "ent_coef": 0.001}
B, PREFIX, T = 3, 16, 8


def _shapes(**changed):
    return dict(TINY, **changed, num_envs=B, rollout_steps=T, sequence_length=T, update_epochs=1, num_minibatches=1, index_loss_coef=1.0)


@pytest.fixture(scope="module")
def decoded():
    """A model, seeded weights with no scale left at one, and ``B`` envs
    decoded ``PREFIX`` tokens through their caches, an episode end inside
    (env 0) and two more inside the sequence that follows."""
    with jax.default_matmul_precision("highest"):
        model = SparseMoELM(SparseMoEConfig(**TINY))
        key = jax.random.PRNGKey(0)
        tokens = jax.random.randint(key, (B, PREFIX + T), 0, 16)
        resets = jnp.zeros((B, PREFIX + T), jnp.int32).at[0, 13].set(1).at[1, 18].set(1).at[2, 16].set(1)
        params = model.init(key, tokens[:, :1], resets[:, :1], model.init_state(B))
        params = jax.tree_util.tree_map(lambda x: x + 0.1 * jax.random.normal(jax.random.PRNGKey(x.size), x.shape), params)
        step = jax.jit(lambda p, t, r, s: model.apply(p, t, r, s, decode=True))
        state = model.init_state(B)
        for t in range(PREFIX):
            _, _, state = step(params, tokens[:, t:t + 1], resets[:, t:t + 1], state)
    return model, params, tokens, resets, state, step


def test_decoding_through_the_caches_is_the_sequence_form_and_the_references(decoded):
    """The sequence form (attention through ``ops/sparse_attention.py``'s kernel) token by token against the decode form and the reference."""
    model, params, tokens, resets, snapshot, step = decoded
    with jax.default_matmul_precision("highest"):
        assert list(np.asarray(snapshot["pos"])) == [3, 16, 16]  # env 0 restarted at 13; topk is 6: every query selects
        logits, values, _, report = jax.jit(lambda p, t, r, s: model.apply(p, t, r, s, aux=True))(params, tokens[:, PREFIX:], resets[:, PREFIX:], snapshot)
        state, by_token = snapshot, []
        for t in range(PREFIX, PREFIX + T):
            one, value, state = step(params, tokens[:, t:t + 1], resets[:, t:t + 1], state)
            by_token.append((one[:, 0], value[:, 0]))
        np.testing.assert_allclose(jnp.stack([a for a, _ in by_token], 1), logits, atol=5e-6)
        np.testing.assert_allclose(jnp.stack([b for _, b in by_token], 1), values, atol=5e-6)
        ref_logits, ref_values, kl, counts = ref.Model(_shapes()).batch(params, tokens[:, PREFIX:], resets[:, PREFIX:], snapshot)
    np.testing.assert_allclose(ref_logits, logits, atol=5e-6)
    np.testing.assert_allclose(ref_values, values, atol=5e-6)
    shares = ref.shares(_shapes(), jnp.sum(counts, 0), B * T)
    assert float(report["index_loss"]) == pytest.approx(float(jnp.sum(kl)) / (B * T), rel=1e-5)
    assert float(report["attended_share"]) == pytest.approx(float(shares["attended_share"]), abs=1e-7) and float(report["attended_share"]) < 0.8
    assert float(report["picks_held_share"]) == pytest.approx(float(shares["picks_held_share"]), abs=1e-7)


def test_the_update_counts_the_key_tiles_its_blocks_computed(decoded, monkeypatch):
    """``attention_tiles_share`` is the tiles any query of a block selected from
    over the tiles there are, counted over layers, sequences and blocks: here
    from the selections the kernel was handed, counted with NumPy."""
    model, params, tokens, resets, snapshot, _ = decoded
    handed, kernel = [], sparse_moe_lm.selected_attention

    def recording(q, cache_k, cache_v, k, v, selected):
        jax.debug.callback(lambda s: handed.append(np.asarray(s)), selected)
        return kernel(q, cache_k, cache_v, k, v, selected)

    monkeypatch.setattr(sparse_moe_lm, "selected_attention", recording)
    with jax.default_matmul_precision("highest"):
        report = model.apply(params, tokens[:, PREFIX:], resets[:, PREFIX:], snapshot, aux=True)[3]
    jax.effects_barrier()
    assert len(handed) == TINY["num_layers"] * B * T // TINY["query_block"]
    tile = key_tile(TINY["cache_len"], T)
    live = [s.reshape(s.shape[0], -1, tile).any(axis=(0, 2)) for s in handed]
    assert float(report["attention_tiles_share"]) == pytest.approx(float(np.mean(live)))
    assert 0 < float(report["attention_tiles_share"]) < 1  # env 0 restarted 3 positions before the sequence: its cache tiles are skipped


def test_three_unequal_position_streams_turn_their_own_pairs(decoded):
    model, params, tokens, resets, snapshot, _ = decoded
    positions = jnp.stack([jnp.arange(T)[None] + snapshot["pos"][:, None], 3 * jnp.arange(T)[None] + jnp.arange(B)[:, None],
                           jnp.arange(T)[None] % 3 + 7 * jnp.ones((B, 1), jnp.int32)])
    with jax.default_matmul_precision("highest"):
        logits, values, _ = model.apply(params, tokens[:, PREFIX:], jnp.zeros((B, T), jnp.int32), snapshot, positions=positions)
        plain, _, _ = model.apply(params, tokens[:, PREFIX:], jnp.zeros((B, T), jnp.int32), snapshot)
        want = jax.vmap(ref.Model(_shapes()).sequence, in_axes=(None, 0, 0, 0, 1))(
            params, tokens[:, PREFIX:], jnp.zeros((B, T), jnp.int32), snapshot, positions)
    np.testing.assert_allclose(want[0], logits, atol=5e-6)
    np.testing.assert_allclose(want[1], values, atol=5e-6)
    assert float(jnp.max(jnp.abs(logits - plain))) > 1e-3  # and the height and width streams are read


def _batch(tokens, resets, seed=5):
    rng = np.random.RandomState(seed)
    return {"tokens": tokens[:, PREFIX:], "resets": resets[:, PREFIX:], "actions": jnp.asarray(rng.randint(0, 16, (B, T))),
            "logprobs": jnp.asarray(-2.7 + 0.1 * rng.randn(B, T), jnp.float32), "values": jnp.asarray(0.1 * rng.randn(B, T), jnp.float32),
            "advantages": jnp.asarray(rng.randn(B, T), jnp.float32), "returns": jnp.asarray(0.3 * rng.randn(B, T), jnp.float32)}


def _program_losses(model, params, batch, snapshot, index_coef=1.0, ppo_coef=1.0):
    logits, values, _, report = model.apply(params, batch["tokens"], batch["resets"], snapshot, aux=True)
    policy, value, entropy = ref.ppo_terms(logits, values, batch, HYPER["clip_coef"])
    ppo = policy + HYPER["vf_coef"] * value + HYPER["ent_coef"] * entropy
    return ppo_coef * ppo + index_coef * report["index_loss"], jnp.stack([policy, value, entropy, report["index_loss"]])


def test_the_losses_and_every_leafs_gradient_are_the_references(decoded):
    model, params, tokens, resets, snapshot, _ = decoded
    batch = _batch(tokens, resets)
    with jax.default_matmul_precision("highest"):
        grads, losses = jax.jit(jax.grad(lambda p: _program_losses(model, p, batch, snapshot), has_aux=True))(params)
        want = ref.Gradient(_shapes(), HYPER)(params, batch, snapshot, rows=2)
    np.testing.assert_allclose(want["losses"][:4], losses, rtol=2e-5, atol=1e-6)
    got, expected = jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree_util.tree_leaves(want["grads"])
    assert len(got) == len(expected)
    for (path, g), w in zip(got, expected):
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, path  # no leaf is left without a gradient: the comparison is of something
        np.testing.assert_allclose(g, w, atol=2e-4 * scale + 1e-8, err_msg=str(path))


def test_the_indexer_learns_from_its_own_loss_and_nothing_else_sees_it(decoded):
    model, params, tokens, resets, snapshot, _ = decoded
    batch = _batch(tokens, resets)
    of = jax.jit(jax.grad(lambda p, index_coef, ppo_coef: _program_losses(model, p, batch, snapshot, index_coef, ppo_coef)[0]))
    from_index, from_ppo = of(params, 1.0, 0.0), of(params, 0.0, 1.0)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(from_index)[0], jax.tree_util.tree_leaves(from_ppo)):
        name = "/".join(str(k.key) for k in path)
        if "/indexer/" in name:
            assert float(jnp.max(jnp.abs(a))) > 0 and float(jnp.max(jnp.abs(b))) == 0, name
        else:
            assert float(jnp.max(jnp.abs(a))) == 0 and float(jnp.max(jnp.abs(b))) > 0, name


@pytest.mark.parametrize("k", [1, 5, 9, 40])
def test_both_forms_of_the_selection_are_top_k_of_the_scores_with_planted_ties(k):
    rng = np.random.RandomState(k)
    scores = rng.randint(-2, 3, (7, 40)).astype(np.float32)  # five values over forty positions: ties everywhere
    scores[0, :] = 0.0
    scores[1, ::2] = -0.0
    visible = jnp.asarray(np.arange(40)[None] < np.asarray([40, 40, 33, 12, 4, 1, 27])[:, None])
    masked = jnp.where(visible, jnp.asarray(scores), -jnp.inf)
    _, want = jax.lax.top_k(masked, k)  # ties to the lower position
    want_mask = np.zeros((7, 40), bool)
    for row in range(7):
        want_mask[row, np.asarray(want[row])] = True
    want_mask &= np.asarray(visible)
    got = np.asarray(jax.jit(lambda s, v: select_mask(s, v, k))(jnp.asarray(scores), visible))
    np.testing.assert_array_equal(got, want_mask)
    assert list(got.sum(1)) == [min(k, n) for n in (40, 40, 33, 12, 4, 1, 27)]
    idx, valid = select_indices(jnp.asarray(scores), visible, k)
    from_indices = np.zeros((7, 40), bool)
    for row in range(7):
        from_indices[row, np.asarray(idx[row])[np.asarray(valid[row])]] = True
    np.testing.assert_array_equal(from_indices, want_mask)


def test_the_expert_shares_partial_sums_add_up_to_the_uncut_layer():
    """Eight chips of two experts each: what each adds to the residual stream
    sums to the uncut reference's expert layer, and a share's cut tree is its
    slice of the uncut one."""
    whole = SparseMoEConfig(**dict(TINY, experts_total=16, experts_held=16, expert_share=0, num_layers=1))
    model = SparseMoELM(whole)
    token = jnp.zeros((1, 1), jnp.int32)
    with jax.default_matmul_precision("highest"):
        params = model.init(jax.random.PRNGKey(3), token, token, model.init_state(1))
        x = jax.random.normal(jax.random.PRNGKey(4), (24, 32))
        uncut, picks = ref.Model(_shapes(experts_total=16, experts_held=16, expert_share=0)).experts(params["params"]["layers_0"]["moe"], x)
        total, held_picks = jnp.zeros_like(x), 0
        for share in range(8):
            held = SparseMoEConfig(**dict(TINY, experts_total=16, experts_held=2, expert_share=share, num_layers=1))
            cut = take_share(params, whole, held)["params"]["layers_0"]["moe"]
            assert cut["w1"].shape == (2, 32, 16) and cut["router"]["kernel"].shape == (32, 16)
            part, n = ref.Model(_shapes(experts_total=16, experts_held=2, expert_share=share)).experts(cut, x)
            total, held_picks = total + part, held_picks + int(n)
            from sheeprl_tpu.ops.moe import held_experts, route

            gates, n_program = route(x @ cut["router"]["kernel"], 2, True, share * 2, 2)
            np.testing.assert_allclose(held_experts(x, gates, cut["w1"], cut["w3"], cut["w2"]), part, atol=2e-6)
            assert int(n_program) == int(n)
    np.testing.assert_allclose(total, uncut, atol=5e-6)
    assert held_picks == int(picks) == 24 * 2  # every pick is held by exactly one share
