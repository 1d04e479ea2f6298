"""End-to-end algorithm tests through the real CLI — the backbone of the suite
(reference /root/reference/tests/test_algos/test_algos.py:21-566): every
algorithm runs one full dry-run iteration with tiny models on dummy envs, on 1
device and on a 2-device mesh (the reference simulates multi-node with
2-process Gloo DDP; here it is 2 virtual CPU devices, SURVEY §4).
"""

from __future__ import annotations

import os
import sys
from pathlib import Path
from unittest import mock

import pytest

from sheeprl_tpu.cli import run

COMMON = [
    "dry_run=True",
    "checkpoint.save_last=True",
    "env=dummy",
    "env.num_envs=2",
    "env.capture_video=False",
    "buffer.memmap=False",
    "metric.log_level=1",
    "metric.log_every=1",
]


@pytest.fixture(params=["1", "2"])
def devices(request):
    return request.param


# The dreamer-family e2e runs compile multi-minute shard_mapped graphs at 2
# virtual devices.  The tier-1 smoke (-m 'not slow') keeps
# the cheap 2-device proofs (ppo / a2c / sac / recurrent / decoupled / the
# sharding-HLO checks) inside its wall-clock budget and defers these heavy
# ones to the CI e2e suite: tests/run_tests.py runs tests/test_algos/ WITHOUT
# the marker filter, so they stay fully covered there.
@pytest.fixture(params=["1", pytest.param("2", marks=pytest.mark.slow)])
def devices_heavy(request):
    return request.param


def _run_cli(*args: str) -> None:
    argv = ["sheeprl_tpu"] + list(args)
    with mock.patch.object(sys, "argv", argv):
        run(argv[1:])


def _checkpoint_paths(root: str = "logs") -> list:
    return sorted(Path(root).rglob("*.ckpt"))


@pytest.mark.parametrize("env_id", ["discrete_dummy", "multidiscrete_dummy", "continuous_dummy"])
def test_ppo(devices, env_id):
    _run_cli(
        "exp=ppo",
        *COMMON,
        f"fabric.devices={devices}",
        "fabric.accelerator=cpu",
        f"env.id={env_id}",
        "algo.rollout_steps=8",
        "algo.per_rank_batch_size=4",
        "algo.update_epochs=1",
        "algo.mlp_keys.encoder=[state]",
        "algo.cnn_keys.encoder=[rgb]",
    )
    assert _checkpoint_paths(), "no checkpoint written"


def test_ppo_resume(devices):
    _run_cli(
        "exp=ppo",
        *COMMON,
        f"fabric.devices={devices}",
        "fabric.accelerator=cpu",
        "env.id=discrete_dummy",
        "algo.rollout_steps=8",
        "algo.per_rank_batch_size=4",
        "algo.update_epochs=1",
        "algo.mlp_keys.encoder=[state]",
        "algo.cnn_keys.encoder=[]",
    )
    ckpts = _checkpoint_paths()
    assert ckpts
    _run_cli(
        "exp=ppo",
        *COMMON,
        f"fabric.devices={devices}",
        "fabric.accelerator=cpu",
        "env.id=discrete_dummy",
        "algo.rollout_steps=8",
        "algo.per_rank_batch_size=4",
        "algo.update_epochs=1",
        "algo.mlp_keys.encoder=[state]",
        "algo.cnn_keys.encoder=[]",
        f"checkpoint.resume_from={ckpts[-1]}",
    )


def test_ppo_vector_only():
    _run_cli(
        "exp=ppo",
        *COMMON,
        "fabric.devices=1",
        "fabric.accelerator=cpu",
        "env.id=discrete_dummy",
        "algo.rollout_steps=8",
        "algo.per_rank_batch_size=4",
        "algo.update_epochs=1",
        "algo.mlp_keys.encoder=[state]",
        "algo.cnn_keys.encoder=[]",
    )


@pytest.mark.parametrize("env_id", ["discrete_dummy", "multidiscrete_dummy", "continuous_dummy"])
def test_a2c(devices, env_id):
    _run_cli(
        "exp=a2c",
        *COMMON,
        f"fabric.devices={devices}",
        "fabric.accelerator=cpu",
        f"env.id={env_id}",
        "algo.rollout_steps=8",
        "algo.per_rank_batch_size=8",
        "algo.mlp_keys.encoder=[state]",
    )
    assert _checkpoint_paths(), "no checkpoint written"


def test_sac(devices):
    _run_cli(
        "exp=sac",
        *COMMON,
        f"fabric.devices={devices}",
        "fabric.accelerator=cpu",
        "env.id=continuous_dummy",
        "buffer.size=64",
        "algo.learning_starts=0",
        "algo.per_rank_batch_size=4",
        "algo.mlp_keys.encoder=[state]",
    )
    assert _checkpoint_paths(), "no checkpoint written"


def test_sac_sample_next_obs(devices):
    _run_cli(
        "exp=sac",
        *COMMON,
        "dry_run=False",
        "algo.total_steps=8",
        "algo.run_test=False",
        f"fabric.devices={devices}",
        "fabric.accelerator=cpu",
        "env.id=continuous_dummy",
        "buffer.size=64",
        "buffer.sample_next_obs=True",
        "algo.learning_starts=6",
        "algo.per_rank_batch_size=4",
        "algo.mlp_keys.encoder=[state]",
    )


DV3_TINY = [
    "algo.per_rank_batch_size=1",
    "algo.per_rank_sequence_length=1",
    "algo.learning_starts=0",
    "algo.replay_ratio=1",
    "algo.horizon=8",
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
]


@pytest.mark.parametrize("env_id", ["discrete_dummy", "multidiscrete_dummy", "continuous_dummy"])
def test_dreamer_v3(devices_heavy, env_id):
    devices = devices_heavy
    _run_cli(
        "exp=dreamer_v3",
        *COMMON,
        *DV3_TINY,
        f"fabric.devices={devices}",
        "fabric.accelerator=cpu",
        "env=dummy",
        f"env.id={env_id}",
        "buffer.size=8",
    )
    assert _checkpoint_paths(), "no checkpoint written"


def test_dreamer_v3_resume(devices_heavy):
    devices = devices_heavy
    args = [
        "exp=dreamer_v3",
        *COMMON,
        *DV3_TINY,
        f"fabric.devices={devices}",
        "fabric.accelerator=cpu",
        "env=dummy",
        "env.id=discrete_dummy",
        "buffer.size=8",
    ]
    _run_cli(*args)
    ckpts = _checkpoint_paths()
    assert ckpts
    _run_cli(*args, f"checkpoint.resume_from={ckpts[-1]}")


DV2_TINY = [
    "algo.per_rank_batch_size=1",
    "algo.per_rank_sequence_length=1",
    "algo.learning_starts=0",
    "algo.per_rank_pretrain_steps=0",
    "algo.replay_ratio=1",
    "algo.horizon=8",
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
]


@pytest.mark.parametrize("env_id", ["discrete_dummy", "continuous_dummy"])
def test_dreamer_v2(devices_heavy, env_id):
    devices = devices_heavy
    _run_cli(
        "exp=dreamer_v2",
        *COMMON,
        *DV2_TINY,
        f"fabric.devices={devices}",
        "fabric.accelerator=cpu",
        "env=dummy",
        f"env.id={env_id}",
        "buffer.size=8",
    )
    assert _checkpoint_paths(), "no checkpoint written"


def test_dreamer_v2_use_continues(devices_heavy):
    devices = devices_heavy
    _run_cli(
        "exp=dreamer_v2",
        *COMMON,
        *DV2_TINY,
        f"fabric.devices={devices}",
        "fabric.accelerator=cpu",
        "env=dummy",
        "env.id=discrete_dummy",
        "buffer.size=8",
        "algo.world_model.use_continues=True",
    )


@pytest.mark.parametrize("env_id", ["discrete_dummy", "continuous_dummy"])
def test_dreamer_v1(devices_heavy, env_id):
    devices = devices_heavy
    _run_cli(
        "exp=dreamer_v1",
        *COMMON,
        "algo.per_rank_batch_size=1",
        "algo.per_rank_sequence_length=1",
        "algo.learning_starts=0",
        "algo.replay_ratio=1",
        "algo.horizon=8",
        "algo.dense_units=8",
        "algo.mlp_layers=1",
        "algo.world_model.encoder.cnn_channels_multiplier=2",
        "algo.world_model.recurrent_model.recurrent_state_size=8",
        "algo.world_model.representation_model.hidden_size=8",
        "algo.world_model.transition_model.hidden_size=8",
        "algo.world_model.stochastic_size=4",
        "algo.cnn_keys.encoder=[rgb]",
        "algo.cnn_keys.decoder=[rgb]",
        "algo.mlp_keys.encoder=[state]",
        "algo.mlp_keys.decoder=[state]",
        f"fabric.devices={devices}",
        "fabric.accelerator=cpu",
        "env=dummy",
        f"env.id={env_id}",
        "buffer.size=8",
    )
    assert _checkpoint_paths(), "no checkpoint written"


def test_dreamer_v3_jepa(devices_heavy):
    devices = devices_heavy
    _run_cli(
        "exp=dreamer_v3_jepa",
        *COMMON,
        *DV3_TINY,
        "algo.cnn_keys.decoder=[]",
        "algo.mlp_keys.decoder=[]",
        "algo.jepa_proj_dim=8",
        "algo.jepa_hidden=8",
        f"fabric.devices={devices}",
        "fabric.accelerator=cpu",
        "env=dummy",
        "env.id=discrete_dummy",
        "buffer.size=8",
    )
    assert _checkpoint_paths(), "no checkpoint written"


def test_droq(devices_heavy):
    devices = devices_heavy
    _run_cli(
        "exp=droq",
        *COMMON,
        f"fabric.devices={devices}",
        "fabric.accelerator=cpu",
        "env.id=continuous_dummy",
        "buffer.size=64",
        "algo.learning_starts=0",
        "algo.per_rank_batch_size=4",
        "algo.mlp_keys.encoder=[state]",
    )
    assert _checkpoint_paths(), "no checkpoint written"


@pytest.mark.parametrize("env_id", ["discrete_dummy", "continuous_dummy"])
def test_ppo_recurrent(devices, env_id):
    _run_cli(
        "exp=ppo_recurrent",
        *COMMON,
        f"fabric.devices={devices}",
        "fabric.accelerator=cpu",
        f"env.id={env_id}",
        "algo.rollout_steps=8",
        "algo.per_rank_sequence_length=4",
        "algo.per_rank_num_batches=2",
        "algo.update_epochs=1",
        "algo.mlp_keys.encoder=[state]",
        "algo.cnn_keys.encoder=[]",
    )
    assert _checkpoint_paths(), "no checkpoint written"


@pytest.mark.parametrize("n_devices", ["2", "5"])  # one player and one trainer; one player and a sub-mesh of four
def test_ppo_decoupled(n_devices):
    _run_cli(
        "exp=ppo_decoupled",
        *COMMON,
        f"fabric.devices={n_devices}",
        "fabric.accelerator=cpu",
        "env.id=discrete_dummy",
        "algo.rollout_steps=8",
        "algo.per_rank_batch_size=4",
        "algo.update_epochs=1",
        "algo.mlp_keys.encoder=[state]",
        "algo.cnn_keys.encoder=[]",
    )
    assert _checkpoint_paths(), "no checkpoint written"


def test_ppo_decoupled_single_device_raises():
    with pytest.raises(Exception):
        _run_cli(
            "exp=ppo_decoupled",
            *COMMON,
            "fabric.devices=1",
            "fabric.accelerator=cpu",
            "env.id=discrete_dummy",
            "algo.mlp_keys.encoder=[state]",
            "algo.cnn_keys.encoder=[]",
        )


def test_sac_decoupled():
    _run_cli(
        "exp=sac_decoupled",
        *COMMON,
        "fabric.devices=2",
        "fabric.accelerator=cpu",
        "env.id=continuous_dummy",
        "buffer.size=64",
        "algo.learning_starts=0",
        "algo.per_rank_batch_size=4",
        "algo.mlp_keys.encoder=[state]",
    )
    assert _checkpoint_paths(), "no checkpoint written"


def test_sac_ae(devices_heavy):
    devices = devices_heavy
    _run_cli(
        "exp=sac_ae",
        *COMMON,
        "dry_run=False",
        "algo.total_steps=8",
        "algo.run_test=False",
        "algo.learning_starts=6",
        f"fabric.devices={devices}",
        "fabric.accelerator=cpu",
        "env.id=continuous_dummy",
        "env.frame_stack=1",
        "buffer.size=64",
        "algo.per_rank_batch_size=4",
        "algo.hidden_size=16",
        "algo.dense_units=8",
        "algo.encoder.features_dim=8",
        "algo.mlp_keys.encoder=[state]",
        "algo.cnn_keys.encoder=[rgb]",
    )
    assert _checkpoint_paths(), "no checkpoint written"


def test_unknown_algorithm_raises():
    with pytest.raises(Exception):
        _run_cli("exp=ppo", "algo.name=not_a_real_algo", "env=dummy", "fabric.accelerator=cpu")


def test_evaluation_roundtrip():
    _run_cli(
        "exp=ppo",
        *COMMON,
        "fabric.devices=1",
        "fabric.accelerator=cpu",
        "env.id=discrete_dummy",
        "algo.rollout_steps=8",
        "algo.per_rank_batch_size=4",
        "algo.update_epochs=1",
        "algo.mlp_keys.encoder=[state]",
        "algo.cnn_keys.encoder=[]",
    )
    ckpts = _checkpoint_paths()
    assert ckpts
    from sheeprl_tpu.cli import evaluation

    evaluation([f"checkpoint_path={ckpts[-1]}", "fabric.accelerator=cpu", "metric.log_level=1"])
    # eval metrics must land under the *_evaluation run dir, not append to the
    # trained run's event stream (round-5 logger re-root fix)
    eval_events = [p for p in Path("logs").rglob("events.out.tfevents.*") if "_evaluation" in str(p)]
    assert eval_events, "evaluation wrote no event file under the *_evaluation run dir"
    train_dir = ckpts[-1].parent.parent
    train_events = list(train_dir.parent.rglob("events.out.tfevents.*"))
    assert all("_evaluation" not in str(p) for p in train_events), (
        f"evaluation appended events inside the training run dir: {train_events}"
    )


def test_external_algorithm_template_example():
    """The runnable extension-API example registers an external algorithm and
    dispatches it through the real CLI (howto/register_new_algorithm.md /
    register_external_algorithm.md contract)."""
    import subprocess

    repo_root = Path(__file__).resolve().parents[2]
    script = repo_root / "examples" / "architecture_template.py"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(script)],
        capture_output=True,
        text=True,
        timeout=900,
        env=env,
        cwd=os.getcwd(),  # tmp dir from the autouse fixture — logs stay out of the repo
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "final mean episodic return" in proc.stdout, proc.stdout[-2000:]


P2E_TINY = [
    "algo.per_rank_batch_size=1",
    "algo.per_rank_sequence_length=2",
    "algo.learning_starts=4",
    "algo.replay_ratio=1",
    "algo.horizon=4",
    "algo.dense_units=8",
    "algo.mlp_layers=1",
    "algo.world_model.encoder.cnn_channels_multiplier=2",
    "algo.world_model.recurrent_model.recurrent_state_size=8",
    "algo.world_model.representation_model.hidden_size=8",
    "algo.world_model.transition_model.hidden_size=8",
    "algo.ensembles.n=3",
    "algo.ensembles.dense_units=8",
    "algo.ensembles.mlp_layers=1",
    "algo.cnn_keys.encoder=[rgb]",
    "algo.cnn_keys.decoder=[rgb]",
    "algo.mlp_keys.encoder=[state]",
    "algo.mlp_keys.decoder=[state]",
]
# dry_run runs a single iteration, which can never fill a sequence-length-2
# buffer; run a real tiny loop instead so the train step actually executes
P2E_RUN = [
    "dry_run=False",
    "algo.total_steps=12",
    "checkpoint.save_last=True",
    "env=dummy",
    "env.num_envs=2",
    "env.capture_video=False",
    "buffer.memmap=False",
    "buffer.size=64",
    "metric.log_level=1",
    "metric.log_every=4",
]


@pytest.mark.parametrize("env_id", ["discrete_dummy", "continuous_dummy"])
def test_p2e_dv3_exploration(devices_heavy, env_id):
    devices = devices_heavy
    _run_cli(
        "exp=p2e_dv3_exploration",
        *P2E_RUN,
        *P2E_TINY,
        f"fabric.devices={devices}",
        "fabric.accelerator=cpu",
        f"env.id={env_id}",
        "algo.run_test=True",
    )
    assert _checkpoint_paths(), "no checkpoint written"


def test_p2e_dv3_finetuning_from_exploration_checkpoint(devices_heavy):
    devices = devices_heavy
    """Exploration -> finetuning checkpoint flow (reference cli.py:117-148)."""
    _run_cli(
        "exp=p2e_dv3_exploration",
        *P2E_RUN,
        *P2E_TINY,
        f"fabric.devices={devices}",
        "fabric.accelerator=cpu",
        "env.id=discrete_dummy",
        "algo.run_test=False",
    )
    ckpts = _checkpoint_paths()
    assert ckpts, "no exploration checkpoint written"
    _run_cli(
        "exp=p2e_dv3_finetuning",
        *P2E_RUN,
        *P2E_TINY,
        f"fabric.devices={devices}",
        "fabric.accelerator=cpu",
        "env.id=discrete_dummy",
        f"checkpoint.exploration_ckpt_path={ckpts[-1]}",
        "algo.learning_starts=4",
        "algo.run_test=False",
    )
    fine_ckpts = [p for p in _checkpoint_paths() if p not in ckpts]
    assert fine_ckpts, "no finetuning checkpoint written"


@pytest.mark.parametrize("version", ["1", "2"])
def test_p2e_dv1_dv2_exploration_and_finetuning(devices_heavy, version):
    devices = devices_heavy
    """P2E DV1/DV2: exploration run, then finetuning from its checkpoint."""
    tiny = [
        "algo.per_rank_batch_size=1",
        "algo.per_rank_sequence_length=2",
        "algo.learning_starts=4",
        "algo.replay_ratio=1",
        "algo.horizon=4",
        "algo.dense_units=8",
        "algo.mlp_layers=1",
        "algo.world_model.encoder.cnn_channels_multiplier=2",
        "algo.world_model.recurrent_model.recurrent_state_size=8",
        "algo.world_model.representation_model.hidden_size=8",
        "algo.world_model.transition_model.hidden_size=8",
        "algo.ensembles.n=3",
        "algo.ensembles.dense_units=8",
        "algo.ensembles.mlp_layers=1",
        "algo.cnn_keys.encoder=[rgb]",
        "algo.cnn_keys.decoder=[rgb]",
        "algo.mlp_keys.encoder=[state]",
        "algo.mlp_keys.decoder=[state]",
    ]
    if version == "1":
        tiny.append("algo.world_model.stochastic_size=8")
    _run_cli(
        f"exp=p2e_dv{version}_exploration",
        *P2E_RUN,
        *tiny,
        f"fabric.devices={devices}",
        "fabric.accelerator=cpu",
        "env.id=discrete_dummy",
        "algo.run_test=False",
    )
    ckpts = _checkpoint_paths()
    assert ckpts, "no exploration checkpoint written"
    _run_cli(
        f"exp=p2e_dv{version}_finetuning",
        *P2E_RUN,
        *tiny,
        f"fabric.devices={devices}",
        "fabric.accelerator=cpu",
        "env.id=discrete_dummy",
        f"checkpoint.exploration_ckpt_path={ckpts[-1]}",
        "algo.run_test=False",
    )
    fine_ckpts = [p for p in _checkpoint_paths() if p not in ckpts]
    assert fine_ckpts, "no finetuning checkpoint written"


def test_dreamer_v3_long_sequences_with_mid_episode_dones(devices_heavy):
    devices = devices_heavy
    """Exercise the hard path the tiny dry-runs skip (VERDICT r1 item 7): a
    real T=8 scan over sequences that contain episode boundaries
    (max_episode_steps=5 < sequence length), so in-scan `is_first` resets and
    sequence sampling across episodes actually run end-to-end."""
    _run_cli(
        "exp=dreamer_v3",
        "dry_run=False",
        "checkpoint.save_last=True",
        "env=dummy",
        "env.id=discrete_dummy",
        "env.num_envs=2",
        "env.capture_video=False",
        "env.max_episode_steps=5",
        "buffer.memmap=False",
        "buffer.size=64",
        "metric.log_level=1",
        "metric.log_every=1",
        f"fabric.devices={devices}",
        "fabric.accelerator=cpu",
        "algo.total_steps=48",
        "algo.learning_starts=24",
        "algo.replay_ratio=0.25",
        "algo.per_rank_batch_size=2",
        "algo.per_rank_sequence_length=8",
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
    )
    assert _checkpoint_paths(), "no checkpoint written"
