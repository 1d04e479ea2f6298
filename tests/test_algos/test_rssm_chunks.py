"""Chunked sequence-parallel RSSM scan (PERF.md §5, ROADMAP item 2).

The contract under test, layer by layer:

* ``rssm_chunks=1`` is the sequential scan — the golden test runs the
  train steps' ``dynamic_learning_scan`` (whose loop holds only what depends
  on its carry) against a hand-inlined ``jax.lax.scan`` of the real tiny
  one-step ``WorldModel.dynamic``: the same draws exactly, the rest to
  float32 re-association; with a body of its own ``chunked_dynamic_scan`` at
  ``chunks=1`` is bit-identical to ``jax.lax.scan``;
* stored-state slicing: with the exact sequential carries stored per row,
  the chunked scan reproduces the sequential trajectory (deterministic body
  — the per-step RNG key layout legitimately differs once chunks fold into
  the batch axis);
* chunk-boundary ``is_first`` handling: an episode start on a boundary row,
  and an invalid stored state (``rssm_valid=0``: prefill/bookkeeping rows),
  both reset to the ``is_first`` path instead of consuming garbage;
* burn-in: the refreshed chunk inits equal a separately computed
  stop-gradient burn rollout, and **no gradient** flows through the burn
  region or the stored states;
* the whole lever end-to-end through the real CLI: a tiny DV3 run with
  ``rssm_chunks=2`` trains finite and lands ``Telemetry/mfu``.
"""

from __future__ import annotations

import functools
import json
import sys
from pathlib import Path
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.algos.dreamer_v3.utils import RSSM_STATE_KEYS, chunked_dynamic_scan, dynamic_learning_scan

T, B, Z, H = 8, 3, 6, 5
A, E = 2, 4


def _inputs(seed: int = 0):
    rngs = jax.random.split(jax.random.PRNGKey(seed), 3)
    actions = jax.random.normal(rngs[0], (T, B, A))
    embedded = jax.random.normal(rngs[1], (T, B, E))
    is_first = jnp.zeros((T, B, 1)).at[0].set(1.0)
    return actions, embedded, is_first


def _deterministic_body():
    """A GRU-shaped but RNG-free body: exact per-row continuations can be
    precomputed, so stored-state slicing is testable bit-for-bit."""
    w = jnp.asarray([[0.7, -0.2]])

    def body(carry, x):
        z, h = carry
        a, e, f, _key = x
        z2 = (1 - f) * (0.9 * z + (a @ w.T) * 0.1 + e[..., :1] * 0.05) + f * 0.25
        z2 = jnp.broadcast_to(z2[..., :1], z.shape) * jnp.arange(1.0, Z + 1.0)
        h2 = (1 - f) * (0.8 * h + e[..., :1] * 0.3) + f * 1.0
        h2 = jnp.broadcast_to(h2[..., :1], h.shape)
        return (z2, h2), (h2, z2, z2 + 1.0, h2 - 1.0)

    return body


def _sequential(body, actions, embedded, is_first, key):
    keys_t = jax.random.split(key, T)
    init = (jnp.zeros((B, Z)), jnp.zeros((B, H)))
    return jax.lax.scan(body, init, (actions, embedded, is_first, keys_t))


def _sequential_carries(body, actions, embedded, is_first, key):
    """Per-row post-step carries — exactly what the player stores in replay."""
    keys_t = jax.random.split(key, T)
    z, h = jnp.zeros((B, Z)), jnp.zeros((B, H))
    zs, hs = [], []
    for t in range(T):
        (z, h), _ = body((z, h), (actions[t], embedded[t], is_first[t], keys_t[t]))
        zs.append(z)
        hs.append(h)
    return jnp.stack(zs), jnp.stack(hs)


# ---------------------------------------------------------------------------
# golden: chunks=1 is the sequential scan


def test_chunks1_equals_hand_inlined_scan_of_real_rssm_dynamic():
    """The train steps' scan at chunks=1 vs a hand-inlined ``lax.scan`` of the
    real one-step ``WorldModel.dynamic`` (straight-through categorical
    sampling and all): the same draws, exactly; logits and recurrent states
    to float32 re-association (the two input products are summed in halves)."""
    import gymnasium as gym

    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.config import compose

    cfg = compose(
        [
            "exp=dreamer_v3",
            "env=dummy",
            "env.id=discrete_dummy",
            "algo.per_rank_batch_size=2",
            "algo.per_rank_sequence_length=8",
            "algo.dense_units=8",
            "algo.mlp_layers=1",
            "algo.world_model.encoder.cnn_channels_multiplier=2",
            "algo.world_model.recurrent_model.recurrent_state_size=8",
            "algo.world_model.representation_model.hidden_size=8",
            "algo.world_model.transition_model.hidden_size=8",
            "algo.world_model.discrete_size=4",
            "algo.world_model.stochastic_size=4",
            "algo.cnn_keys.encoder=[]",
            "algo.cnn_keys.decoder=[]",
            "algo.mlp_keys.encoder=[state]",
            "algo.mlp_keys.decoder=[state]",
            "metric.log_level=0",
        ]
    )
    obs_space = gym.spaces.Dict({"state": gym.spaces.Box(-np.inf, np.inf, (10,), np.float32)})
    wm_def, _, _, params = build_agent(None, (3,), False, cfg, obs_space)
    wm_params = params["world_model"]
    stoch_flat = 16
    rec_size = 8
    t, b = 8, 2
    rngs = jax.random.split(jax.random.PRNGKey(3), 3)
    obs = {"state": jax.random.normal(rngs[0], (t, b, 10))}
    actions = jax.nn.one_hot(
        jax.random.randint(rngs[1], (t, b), 0, 3), 3, dtype=jnp.float32
    )
    is_first = jnp.zeros((t, b, 1)).at[0].set(1.0)
    embedded = wm_def.apply(wm_params, obs, method="encode")

    def scan_body(carry, x):
        posterior, recurrent = carry
        action_t, embed_t, is_first_t, key_t = x
        recurrent, posterior, _, post_logits, prior_logits = wm_def.apply(
            wm_params, posterior, recurrent, action_t, embed_t, is_first_t, key_t, method="dynamic"
        )
        return (posterior, recurrent), (recurrent, posterior, post_logits, prior_logits)

    key = jax.random.PRNGKey(11)
    keys_t = jax.random.split(key, t)
    init = (jnp.zeros((b, stoch_flat)), jnp.zeros((b, rec_size)))
    _, ref = jax.lax.scan(scan_body, init, (actions, embedded, is_first, keys_t))
    got = dynamic_learning_scan(
        wm_def,
        wm_params,
        actions,
        embedded,
        is_first,
        key,
        stoch_flat=stoch_flat,
        recurrent_size=rec_size,
        cdt=jnp.float32,
        chunks=1,
    )
    for name, r, g in zip(("recurrents", "posteriors", "post_logits", "prior_logits"), ref, got):
        np.testing.assert_allclose(np.asarray(r), np.asarray(g), rtol=1e-5, atol=1e-6, err_msg=name)
    # a straight-through value is (one_hot + p) - p: the one-hot to an ulp
    assert (np.rint(np.asarray(ref[1])) == np.rint(np.asarray(got[1]))).all(), "another draw at chunks=1"


def test_chunks1_ignores_stored_state_and_matches_same_unroll():
    body = _deterministic_body()
    actions, embedded, is_first = _inputs()
    key = jax.random.PRNGKey(5)
    for unroll in (1, 4):
        # bit-identity is per unroll factor: an unrolled lax.scan is a
        # different XLA graph whose fusions may round differently (exactly
        # why PERF.md §5 compares step_ms, not values, across unrolls) — so
        # each arm is compared against the plain scan at the SAME unroll
        keys_t = jax.random.split(key, T)
        init = (jnp.zeros((B, Z)), jnp.zeros((B, H)))
        _, ref = jax.lax.scan(
            body, init, (actions, embedded, is_first, keys_t), unroll=unroll
        )
        got = chunked_dynamic_scan(
            functools.partial(jax.lax.scan, body, unroll=unroll),
            actions,
            embedded,
            is_first,
            key,
            stoch_flat=Z,
            recurrent_size=H,
            cdt=jnp.float32,
            chunks=1,
            stored_recurrent=jnp.full((T, B, H), 777.0),  # must be ignored at K=1
            stored_posterior=jnp.full((T, B, Z), 777.0),
            stored_valid=jnp.ones((T, B, 1)),
        )
        for r, g in zip(ref, got):
            assert (np.asarray(r) == np.asarray(g)).all()


# ---------------------------------------------------------------------------
# stored-state slicing


@pytest.mark.parametrize("chunks", [2, 4])
def test_exact_stored_states_reproduce_sequential_trajectory(chunks):
    body = _deterministic_body()
    actions, embedded, is_first = _inputs()
    key = jax.random.PRNGKey(7)
    _, ref = _sequential(body, actions, embedded, is_first, key)
    zs, hs = _sequential_carries(body, actions, embedded, is_first, key)
    got = chunked_dynamic_scan(
        functools.partial(jax.lax.scan, body),
        actions,
        embedded,
        is_first,
        key,
        stoch_flat=Z,
        recurrent_size=H,
        cdt=jnp.float32,
        chunks=chunks,
        stored_recurrent=hs,
        stored_posterior=zs,
        stored_valid=jnp.ones((T, B, 1)),
    )
    for r, g in zip(ref, got):
        np.testing.assert_allclose(np.asarray(r), np.asarray(g), rtol=1e-6, atol=1e-6)


def test_chunked_output_layout_unfolds_to_time_major():
    """Row t of the unfolded output is chunk t//C's step t%C — checked via a
    body that just echoes its inputs."""

    def echo(carry, x):
        a, e, f, _ = x
        return carry, (a, e, f, a)

    actions, embedded, is_first = _inputs()
    zs = jnp.zeros((T, B, Z))
    hs = jnp.zeros((T, B, H))
    got = chunked_dynamic_scan(
        functools.partial(jax.lax.scan, echo),
        actions,
        embedded,
        is_first,
        jax.random.PRNGKey(0),
        stoch_flat=Z,
        recurrent_size=H,
        cdt=jnp.float32,
        chunks=4,
        stored_recurrent=hs,
        stored_posterior=zs,
        stored_valid=jnp.ones((T, B, 1)),
    )
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(actions))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(embedded))


def test_missing_stored_state_raises_with_key_names():
    body = _deterministic_body()
    actions, embedded, is_first = _inputs()
    with pytest.raises(ValueError, match="rssm_recurrent"):
        chunked_dynamic_scan(
            functools.partial(jax.lax.scan, body),
            actions,
            embedded,
            is_first,
            jax.random.PRNGKey(0),
            stoch_flat=Z,
            recurrent_size=H,
            cdt=jnp.float32,
            chunks=2,
        )
    assert RSSM_STATE_KEYS == ("rssm_recurrent", "rssm_posterior", "rssm_valid")


def test_chunks_must_divide_sequence_and_burn_in_must_fit():
    body = _deterministic_body()
    actions, embedded, is_first = _inputs()
    zs = jnp.zeros((T, B, Z))
    hs = jnp.zeros((T, B, H))
    common = dict(
        stoch_flat=Z,
        recurrent_size=H,
        cdt=jnp.float32,
        stored_recurrent=hs,
        stored_posterior=zs,
    )
    with pytest.raises(ValueError, match="must divide"):
        chunked_dynamic_scan(
            functools.partial(jax.lax.scan, body), actions, embedded, is_first, jax.random.PRNGKey(0), chunks=3, **common
        )
    with pytest.raises(ValueError, match="rssm_chunk_burn_in"):
        chunked_dynamic_scan(
            functools.partial(jax.lax.scan, body), actions, embedded, is_first, jax.random.PRNGKey(0), chunks=2, burn_in=4, **common
        )


# ---------------------------------------------------------------------------
# chunk-boundary is_first handling


def test_episode_start_on_chunk_boundary_resets():
    """An ``is_first`` row landing exactly on a chunk boundary must reset to
    the learned-initial path (f=1 branch), stored state notwithstanding."""
    body = _deterministic_body()
    actions, embedded, is_first = _inputs()
    C = T // 2
    is_first = is_first.at[C].set(1.0)
    zs = jnp.full((T, B, Z), 123.0)  # garbage stored states: must not leak
    hs = jnp.full((T, B, H), 123.0)
    _, ref = _sequential(body, actions, embedded, is_first, jax.random.PRNGKey(0))
    got = chunked_dynamic_scan(
        functools.partial(jax.lax.scan, body),
        actions,
        embedded,
        is_first,
        jax.random.PRNGKey(0),
        stoch_flat=Z,
        recurrent_size=H,
        cdt=jnp.float32,
        chunks=2,
        stored_recurrent=hs,
        stored_posterior=zs,
        stored_valid=jnp.ones((T, B, 1)),
    )
    # the boundary row resets in both; its value must match the sequential
    # scan's reset value exactly (the f=1 branch ignores the carry)
    np.testing.assert_allclose(np.asarray(ref[0][C]), np.asarray(got[0][C]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ref[1][C]), np.asarray(got[1][C]), rtol=1e-6)


def test_invalid_stored_state_falls_back_to_reset():
    """``rssm_valid=0`` on the row feeding a chunk boundary (prefill /
    bookkeeping rows) turns the chunk start into an ``is_first`` reset — the
    chunk trains like a fresh sequence start, never on garbage."""
    body = _deterministic_body()
    actions, embedded, is_first = _inputs()
    C = T // 2
    zs, hs = _sequential_carries(body, actions, embedded, is_first, jax.random.PRNGKey(0))
    zs = zs.at[C - 1].set(1e9)  # poison the boundary-feeding row ...
    hs = hs.at[C - 1].set(1e9)
    valid = jnp.ones((T, B, 1)).at[C - 1].set(0.0)  # ... and mark it invalid
    got = chunked_dynamic_scan(
        functools.partial(jax.lax.scan, body),
        actions,
        embedded,
        is_first,
        jax.random.PRNGKey(0),
        stoch_flat=Z,
        recurrent_size=H,
        cdt=jnp.float32,
        chunks=2,
        stored_recurrent=hs,
        stored_posterior=zs,
        stored_valid=valid,
    )
    # reference: same inputs with a REAL is_first reset at the boundary
    is_first_reset = is_first.at[C].set(1.0)
    _, ref = _sequential(body, actions, embedded, is_first_reset, jax.random.PRNGKey(0))
    for t in range(C, T):
        np.testing.assert_allclose(
            np.asarray(ref[0][t]), np.asarray(got[0][t]), rtol=1e-6, atol=1e-6
        )
    assert np.isfinite(np.asarray(got[0])).all()  # the poison never leaked


# ---------------------------------------------------------------------------
# burn-in


def test_burn_in_refresh_equals_manual_stop_gradient_rollout():
    """burn_in=b must equal: run the b rows before each boundary from the
    stored state, stop the gradient, seed the chunk with the result."""
    body = _deterministic_body()
    actions, embedded, is_first = _inputs()
    key = jax.random.PRNGKey(9)
    zs, hs = _sequential_carries(body, actions, embedded, is_first, key)
    burn = 2
    C = T // 2
    got = chunked_dynamic_scan(
        functools.partial(jax.lax.scan, body),
        actions,
        embedded,
        is_first,
        key,
        stoch_flat=Z,
        recurrent_size=H,
        cdt=jnp.float32,
        chunks=2,
        burn_in=burn,
        stored_recurrent=hs,
        stored_posterior=zs,
        stored_valid=jnp.ones((T, B, 1)),
    )
    # manual burn: rows [C-burn, C) from the state stored at C-burn-1
    z, h = zs[C - burn - 1], hs[C - burn - 1]
    keys_burn = jax.random.split(jax.random.split(key)[1], burn)
    for j in range(burn):
        t = C - burn + j
        (z, h), _ = body((z, h), (actions[t], embedded[t], is_first[t], keys_burn[j]))
    manual = chunked_dynamic_scan(
        functools.partial(jax.lax.scan, body),
        actions,
        embedded,
        is_first,
        key,
        stoch_flat=Z,
        recurrent_size=H,
        cdt=jnp.float32,
        chunks=2,
        burn_in=0,
        stored_recurrent=hs.at[C - 1].set(jax.lax.stop_gradient(h)),
        stored_posterior=zs.at[C - 1].set(jax.lax.stop_gradient(z)),
        stored_valid=jnp.ones((T, B, 1)),
    )
    for g, m in zip(got, manual):
        np.testing.assert_allclose(np.asarray(g), np.asarray(m), rtol=1e-6, atol=1e-6)


def test_no_gradient_through_burn_in_or_stored_states():
    """The gradient region is the chunks, full stop: d(loss)/d(stored state)
    is exactly zero with and without burn-in."""
    body = _deterministic_body()
    actions, embedded, is_first = _inputs()
    key = jax.random.PRNGKey(13)
    zs, hs = _sequential_carries(body, actions, embedded, is_first, key)

    def loss(stored_h, stored_z, burn_in):
        ys = chunked_dynamic_scan(
            functools.partial(jax.lax.scan, body),
            actions,
            embedded,
            is_first,
            key,
            stoch_flat=Z,
            recurrent_size=H,
            cdt=jnp.float32,
            chunks=2,
            burn_in=burn_in,
            stored_recurrent=stored_h,
            stored_posterior=stored_z,
            stored_valid=jnp.ones((T, B, 1)),
        )
        return sum(jnp.sum(y**2) for y in ys)

    for burn in (0, 2):
        gh, gz = jax.grad(lambda h, z: loss(h, z, burn), argnums=(0, 1))(hs, zs)
        assert float(jnp.abs(gh).max()) == 0.0, f"gradient leaked into stored h (burn={burn})"
        assert float(jnp.abs(gz).max()) == 0.0, f"gradient leaked into stored z (burn={burn})"


# ---------------------------------------------------------------------------
# end-to-end CLI drill (tier-1's chunked-scan acceptance)


@pytest.mark.slow
def test_dv3_cli_drill_chunks2_trains_finite_with_live_mfu(tmp_path, monkeypatch):
    """Tiny DV3-XS-style run through the real CLI with ``rssm_chunks=2`` +
    burn-in 1: training must stay finite past the prefill boundary (rows with
    ``rssm_valid=0`` AND player-written rows both get sampled) and the live
    ``Telemetry/mfu`` gauge must land on the metric intervals (CPU has no
    peak table entry, so the drill pins ``peak_tflops_per_device``)."""
    from sheeprl_tpu.cli import run

    monkeypatch.chdir(tmp_path)
    args = [
        "exp=dreamer_v3",
        "env=dummy",
        "env.id=discrete_dummy",
        "env.num_envs=2",
        "env.capture_video=False",
        "buffer.memmap=False",
        "buffer.size=64",
        "metric.log_level=1",
        "metric.log_every=1",
        "checkpoint.every=0",
        "checkpoint.save_last=False",
        "algo.per_rank_batch_size=2",
        "algo.per_rank_sequence_length=8",
        "algo.rssm_chunks=2",
        "algo.rssm_chunk_burn_in=1",
        "algo.learning_starts=20",
        "algo.replay_ratio=0.5",
        "algo.total_steps=48",
        "algo.horizon=4",
        "algo.dense_units=8",
        "algo.mlp_layers=1",
        "algo.world_model.encoder.cnn_channels_multiplier=2",
        "algo.world_model.recurrent_model.recurrent_state_size=8",
        "algo.world_model.representation_model.hidden_size=8",
        "algo.world_model.transition_model.hidden_size=8",
        "algo.cnn_keys.encoder=[rgb]",
        "algo.cnn_keys.decoder=[rgb]",
        "algo.mlp_keys.encoder=[state]",
        "algo.mlp_keys.decoder=[state]",
        "algo.run_test=False",
        "fabric.devices=1",
        "fabric.accelerator=cpu",
        "diagnostics.telemetry.mfu.peak_tflops_per_device=1.0",
    ]
    with mock.patch.object(sys, "argv", ["sheeprl_tpu"] + args):
        run(args)

    journals = sorted(Path("logs").rglob("journal.jsonl"))
    assert journals, "no journal written"
    mfu_rows = 0
    loss_rows = 0
    for line in journals[-1].read_text().splitlines():
        ev = json.loads(line)
        if ev.get("event") != "metrics":
            continue
        metrics = ev.get("metrics", {})
        if "Telemetry/mfu" in metrics:
            mfu_rows += 1
            assert metrics["Telemetry/mfu"] > 0.0
        losses = [v for k, v in metrics.items() if k.startswith("Loss/")]
        if losses:
            loss_rows += 1
            assert all(np.isfinite(v) for v in losses), f"non-finite loss in {metrics}"
    assert mfu_rows > 0, "Telemetry/mfu never landed"
    assert loss_rows > 0, "no loss rows journaled"
