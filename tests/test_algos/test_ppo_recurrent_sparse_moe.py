"""``exp=ppo_recurrent_sparse_moe`` through the real CLI at tiny sizes (ISSUE 38), and what its
arrival must leave alone: the three compiled programs of ``exp=ppo_recurrent_olmo_hybrid``.

The sparse-attention expert model trains as the policy of the recurrent
on-policy loop on the CPU, checkpoints and resumes; three iterations compile
nothing after the first; ``cli.check_configs`` refuses what cannot work at
compose time; the player's bfloat16 view holds the operands of MXU products
and leaves the indexer's and the router's kernels float32; the page carries
the new gauges and counters."""

import functools
import json
import os
from pathlib import Path
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

from sheeprl_tpu.algos.ppo_recurrent import players
from sheeprl_tpu.models.sparse_moe_lm import SparseMoEConfig, SparseMoELM

from olmo_programs import program_hashes
from test_ppo_recurrent_olmo import _run_cli, compiles  # noqa: F401  (``compiles`` is a fixture)

TINY_MODEL = dict(hidden_size=32, num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8, mrope_section=[1, 1, 2], indexer_heads=2,
                  indexer_head_dim=4, topk=6, experts_total=8, experts_held=4, experts_per_token=2, expert_width=16, vocab_total=64,
                  vocab_held=16, cache_len=32, query_block=4)
TINY = [
    "exp=ppo_recurrent_sparse_moe", "fabric.accelerator=cpu", "env.num_envs=2", "algo.rollout_steps=16",
    "algo.per_rank_sequence_length=8", "algo.per_rank_num_batches=2", "env.wrapper.episode_min=5", "env.wrapper.episode_max=24",
    "metric.log_level=0", "buffer.memmap=False",
] + [f"algo.sparse_moe.{k}={json.dumps(v).replace(' ', '')}" for k, v in TINY_MODEL.items()]
GOLDEN = os.path.join(os.path.dirname(__file__), "..", "golden", "olmo_hybrid_programs.json")


@pytest.mark.parametrize("program", ["jit_policy_step", "jit_update", "jit_policy_view"])
def test_the_standing_backbones_programs_are_what_they_were(program):
    """The lowered text of the three programs of ``exp=ppo_recurrent_olmo_hybrid``
    at the tests' small size, hashed, against the file written from the commit
    before this backbone came: the vector step of the standing language-model
    cell runs the programs it ran."""
    assert _hashes()[program] == json.load(open(GOLDEN))[program]


@functools.lru_cache(maxsize=1)
def _hashes():
    return program_hashes()


def test_the_policy_trains_checkpoints_and_resumes_through_the_cli():
    _run_cli(*TINY, "dry_run=True", "checkpoint.save_last=True")
    saved = sorted(Path("logs").rglob("*.ckpt"))
    assert saved, "no checkpoint written"
    _run_cli(*TINY, "dry_run=True", f"checkpoint.resume_from={saved[-1]}")


def test_three_iterations_compile_nothing_after_the_first(compiles):
    from sheeprl_tpu.ops import numerics

    calls, original = [], numerics.gae

    def counting(*args, **kwargs):
        calls.append(len(compiles.names))
        return original(*args, **kwargs)

    with mock.patch("sheeprl_tpu.algos.ppo_recurrent.ppo_recurrent.gae", counting):
        _run_cli(*TINY, "algo.total_steps=96", "checkpoint.save_last=False")
    assert len(calls) == 3
    assert compiles.names[calls[1]:] == []  # nothing at all from the second iteration's bootstrap on


@pytest.mark.parametrize("overrides, complaint", [
    (["algo.sparse_moe.cache_len=20"], "cache_len .20. is shorter than the env's longest episode .24 tokens."),
    (["algo.sparse_moe.experts_held=3"], "experts_held .3. must divide experts_total .8."),
    (["algo.sparse_moe.topk=33"], "topk .33. must be between 1 and cache_len .32."),
    (["algo.sparse_moe.query_block=3"], "query_block .3. must divide algo.per_rank_sequence_length .8."),
    (["algo.sparse_moe.expert_share=2"], "expert_share .2. must be one of the 2 shares"),
    (["algo.name=ppo"], "is a backbone of ppo_recurrent"),
])
def test_what_cannot_work_is_refused_at_compose_time(overrides, complaint):
    with pytest.raises(ValueError, match=complaint):
        _run_cli(*TINY, "dry_run=True", *overrides)


class _Cfg(dict):
    __getattr__ = dict.get


def test_the_view_rounds_the_mxu_operands_and_leaves_the_indexer_and_the_router_float32():
    agent = SparseMoELM(SparseMoEConfig(**{**TINY_MODEL, "mrope_section": (1, 1, 2)}, rope_theta=1e4, expert_share=0, norm_topk_prob=True,
                                        rms_norm_eps=1e-6, vocab_share=0))
    token = jnp.zeros((1, 1), jnp.int32)
    params = agent.init(jax.random.PRNGKey(0), token, token, agent.init_state(1))
    with mock.patch.object(players, "products_round_to_bfloat16", lambda cfg: True):
        view_of, nbytes = players.make_policy_view(agent, _Cfg(fabric=_Cfg(precision="32-true"), matmul_precision="default"), 2)
    names = lambda tree: {"/".join(str(k.key) for k in path[1:]): x for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}  # noqa: E731
    view, before = names(view_of(params)), names(params)
    rounded = {name for name, x in view.items() if x.dtype == jnp.bfloat16}
    assert rounded == {f"layers_{i}/{leaf}" for i in range(2) for leaf in (
        "attn/q_proj/kernel", "attn/k_proj/kernel", "attn/v_proj/kernel", "attn/o_proj/kernel", "moe/w1", "moe/w3", "moe/w2")} | {"lm_head/kernel"}
    for name in set(view) - rounded:  # the indexer's three kernels, the router's, every norm, the embedding, the value head's one column
        assert view[name] is before[name], name
    assert {"layers_0/attn/indexer/q_proj/kernel", "layers_0/attn/indexer/k_proj/kernel", "layers_0/attn/indexer/w_proj/kernel",
            "layers_0/moe/router/kernel"} <= set(view) - rounded
    assert nbytes == 2 * sum(before[name].size for name in rounded)


def test_the_selection_and_the_updates_reports_are_on_the_metrics_page():
    from sheeprl_tpu.diagnostics.metrics_server import render_prometheus
    from sheeprl_tpu.diagnostics.telemetry import Telemetry

    telemetry = Telemetry.__new__(Telemetry)
    telemetry._lock, telemetry._policy_state, telemetry._policy_more = mock.MagicMock(), {}, {}
    telemetry.note_policy_gauges(carry_bytes_by_kind={"kv": 800, "index": 200})
    for visible, attended in ((40, 12), (42, 12)):
        telemetry.note_policy_state(0, visible, 1008, 500)
        telemetry.note_policy_selection(visible, attended, 64)
    telemetry.note_policy_update(index_loss=0.5, attended_share=0.75, picks_held_share=0.125, attention_tiles_share=0.375,
                                 index_tiles_share=0.5)
    # the update's losses row as the loop hands it over: the three PPO terms, then the report's columns in AUX's order
    player = players.SparseTokenPlayer.__new__(players.SparseTokenPlayer)
    player.diag = mock.Mock(note_policy_update=telemetry.note_policy_update)
    player.after_update([0.1, 0.2, 0.3, 0.3, 0.25, 0.125, 0.25, 0.375])
    page = render_prometheus({"policy_state": {**telemetry._policy_state, **telemetry._policy_more}})
    for line in ("sheeprl_policy_cache_positions 42", "sheeprl_policy_attended_positions 12", "sheeprl_policy_visible_positions_total 82",
                 "sheeprl_policy_attended_positions_total 24", "sheeprl_policy_decode_cache_rows_total 128", "sheeprl_policy_carry_bytes 1008", 'sheeprl_policy_carry_bytes{kind="kv"} 800',
                 'sheeprl_policy_carry_bytes{kind="index"} 200', "sheeprl_policy_updates_total 2", "sheeprl_policy_attended_share_sum 1",
                 "sheeprl_policy_picks_held_share_sum 0.25", "sheeprl_policy_index_loss_sum 0.8",
                 "sheeprl_policy_attention_tiles_share_sum 0.625", "sheeprl_policy_index_tiles_share_sum 0.875"):
        assert line in page.splitlines(), line
    assert page.count("# TYPE sheeprl_policy_carry_bytes ") == 1  # one family, the kinds under it


def test_the_decode_kernels_cache_rows_are_counted_from_the_hosts_mirror_without_a_fetch():
    """Two vector steps of a player whose caches hold 1,536 positions (tiles of
    512): ``sheeprl_policy_decode_cache_rows_total`` grows by every tile up to
    each env's positions before the token it decodes, whole, from the host's
    mirror; and the decode program that this counts for holds no host callback."""
    from unittest.mock import MagicMock

    import numpy as np

    from sheeprl_tpu.diagnostics.metrics_server import render_prometheus
    from sheeprl_tpu.diagnostics.telemetry import Telemetry

    config = dict(TINY_MODEL, mrope_section=(1, 1, 2), rope_theta=1e4, expert_share=0, norm_topk_prob=True, rms_norm_eps=1e-6, vocab_share=0)
    telemetry = Telemetry.__new__(Telemetry)
    telemetry._lock, telemetry._policy_state, telemetry._policy_more = MagicMock(), {}, {}
    player = players.SparseTokenPlayer.__new__(players.SparseTokenPlayer)
    player.agent = SparseMoELM(SparseMoEConfig(**dict(config, cache_len=1536)))
    player.diag = mock.Mock(note_policy_state=telemetry.note_policy_state, note_policy_selection=telemetry.note_policy_selection)
    player.positions, player.steps, player.seq_len, player.carry_nbytes, player.view_nbytes = np.asarray([0, 510, 1100]), 1, 8, 0, 0
    held = []
    for dones in ([0, 0, 1], [0, 0, 0]):
        player.begin_step(np.asarray(dones)[:, None])
        held.append(player.positions - 1)
    assert [list(h) for h in held] == [[0, 510, 0], [1, 511, 1]]
    want = sum(int(np.ceil(h / 512)) * 512 for h in np.concatenate(held))
    assert want == 512 + 3 * 512
    page = render_prometheus({"policy_state": {**telemetry._policy_state, **telemetry._policy_more}})
    assert f"sheeprl_policy_decode_cache_rows_total {want}" in page.splitlines()
    assert "# TYPE sheeprl_policy_decode_cache_rows_total counter" in page.splitlines()

    agent = SparseMoELM(SparseMoEConfig(**config))
    n, steps = 2, 4
    carry = {"state": agent.init_state(n), "key": jax.random.PRNGKey(0), "t": jnp.zeros((), jnp.int32),
             "logprobs": jnp.zeros((steps, n), jnp.float32), "values": jnp.zeros((steps, n), jnp.float32)}
    token = jnp.zeros((n, 1), jnp.int32)
    params = agent.init(jax.random.PRNGKey(1), token, token, agent.init_state(n))
    text = players.make_token_player(agent, None, steps)[0].lower(params, carry, jnp.zeros((2, n), jnp.int32)).as_text()
    assert "callback" not in text and "outfeed" not in text
