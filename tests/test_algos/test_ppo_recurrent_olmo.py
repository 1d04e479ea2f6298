"""``exp=ppo_recurrent_olmo_hybrid`` through the real CLI at tiny sizes, and the loop's faults that ISSUE 31 mends.

The hybrid language model trains as the policy of the recurrent on-policy
loop on the CPU; ``cli.check_configs`` refuses what cannot work at compose
time; ``ops.numerics.gae`` compiles once however often the loop calls it;
the loop counts its env steps and names its phases, for both backbones."""

import logging
import sys
from pathlib import Path
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

from sheeprl_tpu.cli import run

TINY_MODEL = [
    "algo.olmo_hybrid.hidden_size=32", "algo.olmo_hybrid.intermediate_size=48", "algo.olmo_hybrid.heads_total=4",
    "algo.olmo_hybrid.heads_held=2", "algo.olmo_hybrid.linear_key_head_dim=6", "algo.olmo_hybrid.linear_value_head_dim=12",
    "algo.olmo_hybrid.vocab_total=64", "algo.olmo_hybrid.vocab_held=16", "algo.olmo_hybrid.cache_len=24",
    "algo.olmo_hybrid.chunk_size=4",
]
TINY = [
    "exp=ppo_recurrent_olmo_hybrid", "fabric.accelerator=cpu", "env.num_envs=2", "algo.rollout_steps=16",
    "algo.per_rank_sequence_length=8", "algo.per_rank_num_batches=2", "env.wrapper.episode_min=5", "env.wrapper.episode_max=20",
    "metric.log_level=0", "buffer.memmap=False",
] + TINY_MODEL
LSTM = [
    "exp=ppo_recurrent", "env=dummy", "env.id=discrete_dummy", "env.num_envs=2", "env.capture_video=False", "fabric.accelerator=cpu",
    "algo.rollout_steps=8", "algo.per_rank_sequence_length=4", "algo.per_rank_num_batches=2", "algo.update_epochs=1",
    "algo.mlp_keys.encoder=[state]", "algo.cnn_keys.encoder=[]", "buffer.memmap=False", "metric.log_level=0",
]


def _run_cli(*args):
    argv = ["sheeprl_tpu"] + list(args)
    with mock.patch.object(sys, "argv", argv):
        run(argv[1:])


class _Compiles(logging.Handler):
    """What ``jax_log_compiles`` reports, in order."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.names = []

    def emit(self, record):
        message = record.getMessage()
        if message.startswith("Finished XLA compilation of "):
            self.names.append(message.split("Finished XLA compilation of ")[1].split(" in ")[0])


@pytest.fixture
def compiles():
    handler = _Compiles()
    logger = logging.getLogger("jax")
    old_level = logger.level
    logger.addHandler(handler)
    logger.setLevel(logging.DEBUG)
    with jax.log_compiles():
        yield handler
    logger.removeHandler(handler)
    logger.setLevel(old_level)


def test_the_hybrid_policy_trains_through_the_cli():
    _run_cli(*TINY, "dry_run=True", "checkpoint.save_last=True")
    assert sorted(Path("logs").rglob("*.ckpt")), "no checkpoint written"


@pytest.mark.parametrize("overrides", [TINY + ["algo.total_steps=96", "checkpoint.save_last=False"],
                                       LSTM + ["algo.total_steps=48", "checkpoint.save_last=False", "algo.run_test=False"]],
                         ids=["olmo_hybrid", "lstm"])
def test_three_iterations_compile_nothing_after_the_first(compiles, overrides):
    """The loop calls ``gae`` from the host once an iteration: jitted, it is
    compiled in the first and found in the second and third (as a bare scan it
    was compiled again every iteration: PERF.md section 7 0, before PR 31)."""
    from sheeprl_tpu.ops import numerics

    calls = []
    original = numerics.gae

    def counting(*args, **kwargs):
        calls.append(len(compiles.names))
        return original(*args, **kwargs)

    with mock.patch("sheeprl_tpu.algos.ppo_recurrent.ppo_recurrent.gae", counting):
        _run_cli(*overrides)
    assert len(calls) == 3
    assert compiles.names[calls[1]:] == []  # nothing at all from the second iteration's bootstrap on


def test_gae_called_from_the_host_compiles_once(compiles):
    from sheeprl_tpu.ops.numerics import gae

    seen = []
    for seed in range(3):
        r, v, d = (jax.random.uniform(jax.random.PRNGKey(seed + i), (7, 3, 1)) for i in range(3))  # a shape no loop of the tests has
        gae(r, v, (d > 0.8).astype(jnp.float32), v[0], 7, 0.99, 0.95)
        seen.append([n for n in compiles.names if "gae" in n or "scan" in n])
    assert seen[0] == ["jit(gae)"] and seen[2] == seen[0]  # under its own name, once: fresh arrays compile nothing


@pytest.mark.parametrize("overrides", [TINY + ["dry_run=True"], LSTM + ["dry_run=True"]], ids=["olmo_hybrid", "lstm"])
def test_the_loop_counts_its_env_steps_and_names_its_phases(overrides):
    from sheeprl_tpu.diagnostics import Diagnostics
    from sheeprl_tpu.diagnostics.tracing import KNOWN_PHASES

    spans, steps = [], []
    span, note = Diagnostics.span, Diagnostics.note_env_steps
    with mock.patch.object(Diagnostics, "span", lambda self, name, **kw: (spans.append(name), span(self, name, **kw))[1]), \
            mock.patch.object(Diagnostics, "note_env_steps", lambda self, n: (steps.append(n), note(self, n))[1]):
        _run_cli(*overrides)
    rollout_steps = 16 if "exp=ppo_recurrent_olmo_hybrid" in overrides else 8
    assert steps == [2] * rollout_steps  # num_envs a vector step
    assert set(spans) <= set(KNOWN_PHASES)
    assert {"rollout", "rollout/obs-stage", "rollout/player-forward", "rollout/action-fetch", "rollout/replay-add", "gae", "train",
            "bookkeeping"} <= set(spans)
    assert spans.count("rollout/action-fetch") == rollout_steps and spans.count("gae") == spans.count("train") == 1


def test_the_policys_carried_state_is_on_the_metrics_page():
    from sheeprl_tpu.diagnostics.metrics_server import render_prometheus

    page = render_prometheus({"policy_state": {"state_resets_total": 7, "cache_positions": 4100, "carry_bytes": 1154893312}})
    assert "# TYPE sheeprl_policy_state_resets_total counter\nsheeprl_policy_state_resets_total 7" in page
    assert "# TYPE sheeprl_policy_cache_positions gauge\nsheeprl_policy_cache_positions 4100" in page
    assert "sheeprl_policy_carry_bytes 1.15489e+09" in page
    assert "sheeprl_policy" not in render_prometheus({})  # a loop that carries no such state reports none


@pytest.mark.parametrize("override, message", [
    ("algo.olmo_hybrid.cache_len=16", "cache_len \\(16\\) is shorter than the env's longest episode \\(20 tokens\\)"),
    ("algo.olmo_hybrid.heads_held=3", "heads_held \\(3\\) must divide heads_total \\(4\\)"),
    ("algo.olmo_hybrid.vocab_held=10", "vocab_held \\(10\\) must divide vocab_total \\(64\\)"),
    ("algo.olmo_hybrid.chunk_size=3", "chunk_size \\(3\\) must divide"),
    ("algo.backbone=gru", "algo.backbone must be"),
])
def test_check_configs_refuses_what_cannot_work(override, message):
    with pytest.raises(ValueError, match=message):
        _run_cli(*TINY, "dry_run=True", override)
    assert not list(Path("logs").rglob("version_*"))  # at compose time: no run directory was made
