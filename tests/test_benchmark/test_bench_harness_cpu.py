"""The rest of a run with the look for a chip skipped: the real CLI loop at a
tiny size on the CPU, bounded by the watcher, ended by SIGTERM, compared with
the reference.  Sound, it comes out correct; with the timed path broken
underneath (a step that returns its state unchanged, half of the batch left
out; the family's ``faults``) ``correct`` comes out false, and so it does with the
control, the program's own path in the nearest lower precision, in the
cell's place: all at a size a test run can hold."""

import json
import os
import shutil
import threading
import time

import pytest

from benchmarks.chip.manifest import ROOT, Manifest

BENCH = os.path.join(ROOT, "benchmarks", "chip")
TINY = [
    "buffer.size=4096", "algo.dense_units=16", "algo.mlp_layers=2",
    "algo.world_model.encoder.cnn_channels_multiplier=2",
    "algo.world_model.recurrent_model.recurrent_state_size=16",
    "algo.world_model.transition_model.hidden_size=16",
    "algo.world_model.representation_model.hidden_size=16",
    "algo.world_model.stochastic_size=4", "algo.world_model.discrete_size=4",
    "algo.per_rank_batch_size=4", "algo.per_rank_sequence_length=8", "algo.horizon=3",
    "algo.learning_starts=48", "metric.log_level=0",
]
GAPS = ("player_gap", "loss_gap.world_model", "loss_gap.critic", "grad_gap.world_model", "grad_gap.actor", "grad_gap.critic",
        "change_gap.world_model", "change_gap.actor", "change_gap.critic")


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The benchmark's files with a tiny configuration and cell beside them."""
    if threading.current_thread() is not threading.main_thread():
        pytest.skip("the run is ended by SIGTERM, which only the main thread can take")
    root = tmp_path_factory.mktemp("bench")
    shutil.copytree(BENCH, root / "benchmarks" / "chip")
    config = json.load(open(os.path.join(BENCH, "configs", "dv3_s.json")))
    config["precision"] = "32-true"
    config["overrides"] = [
        o.replace("=tpu", "=cpu").replace("sync_env=False", "sync_env=True")
        for o in config["overrides"] if not o.startswith("buffer.size")
    ] + TINY
    config["shapes"].update(cnn_channels_multiplier=2, dense_units=16, mlp_layers=2, recurrent_state_size=16,
                            hidden_size=16, stochastic_size=4, discrete_size=4, n_actions=5,
                            sequence_length=8, batch_size=4, horizon=3)
    (root / "benchmarks" / "chip" / "configs" / "tiny.json").write_text(json.dumps(config))
    cell = {"name": "tiny.cpu", "warmup_steps": 6, "overrides": [], "limits": {name: 1e-3 for name in GAPS},
            "env": {"n_actions": 5, "episode_min": 20, "episode_max": 40, "first_episodes": [16, 40], "step_ms": 0.0, "reward_pct": 20.0}}
    (root / "benchmarks" / "chip" / "workloads" / "tiny.cpu.json").write_text(json.dumps(cell))
    data = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    data["configs"].append({"name": "tiny", "source": "x", "file": "benchmarks/chip/configs/tiny.json", "reduced": [], "why": "y"})
    data["workloads"].append({"name": "tiny.cpu", "config": "tiny", "traffic": "cpu", "chips": 1, "why": "z"})
    (root / "BENCHMARK.json").write_text(json.dumps(data))
    return Manifest(str(root)), str(root / "runs")


def _run(tiny, fault=None, seed=2_200_000_123, precision=None):
    from benchmarks.chip.harness import run_cell

    manifest, work_dir = tiny
    return run_cell(manifest, "tiny.cpu", seed, 4.0, False, time.time(), work_dir, fault=fault, precision=precision)


# what only a chip gives: a compile-free window is the chip cells' own check,
# and the CPU backend donates nothing
CPU_ONLY = {"window_compiles", "donation_miss_leaves"}


def _failed(result):
    return {k for k, c in result["_run"]["checks"].items() if not c["ok"]} - CPU_ONLY


def test_a_sound_run_is_correct(tiny):
    result = _run(tiny)
    assert _failed(result) == set(), result["checks"]
    assert set(GAPS) | {"replay_frame_mismatches", "replay_order_breaks", "replay_label_mismatches",
                        "verified_checkpoints", "preempted_exit", "env_steps_counter_gap"} <= set(result["checks"])
    assert set(result["metrics"]) == {"setup_s", "env_steps_per_s", "action_gap_p95_ms"}
    assert result["attempted"] == result["window"]["steps"] > 0 and result["window"]["gradient_steps"] > 0


@pytest.mark.parametrize("fault,caught_by", [
    ("unchanged", {"change_gap.world_model", "change_gap.actor", "change_gap.critic"}),
    ("half_batch", {"loss_gap.world_model", "grad_gap.world_model", "grad_gap.actor"}),
])
def test_a_broken_step_is_not_correct(tiny, fault, caught_by):
    result = _run(tiny, fault=fault)
    assert result["correct"] is False
    assert caught_by & _failed(result), result["checks"]
    # the replay path is whole in both: the fault is the step's
    assert result["checks"]["replay_frame_mismatches"]["value"] == 0.0


def test_the_control_is_not_correct(tiny):
    """The program's own path in the nearest lower precision, put in the cell's place."""
    result = _run(tiny, precision="bf16-mixed")
    assert result["correct"] is False
    assert {"grad_gap.world_model", "change_gap.world_model"} & _failed(result), result["checks"]
    assert result["checks"]["replay_frame_mismatches"]["value"] == 0.0
