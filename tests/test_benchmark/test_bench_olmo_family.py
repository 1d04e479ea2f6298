"""The Olmo-Hybrid family through ``run_cell`` on the CPU at a tiny size.

The fixture copies ``benchmarks/chip`` to a temporary root and adds a tiny
configuration and cell of the family (the widths of a test, the same files
otherwise).  ``run_cell`` then drives ``exp=ppo_recurrent_olmo_hybrid`` through
the real CLI loop: the window is bounded, the replay path of the three
recorded rollouts is exact, the player's stored log-probabilities and values
agree with the plain reference's full-sequence forward from the same snapshot,
every gradient step's losses and gradient norms and the parameters' change
over the first update with the reference's, which follows it through AdamW, and
``correct`` turns false with each of the family's faults planted."""

import json
import os
import shutil
import threading
import time

import pytest

import benchmarks.chip
from benchmarks.chip.manifest import FAMILY_ANSWERS, ROOT, Manifest

BENCH = os.path.join(ROOT, "benchmarks", "chip")
TINY_MODEL = {"hidden_size": 32, "intermediate_size": 48, "heads_total": 4, "heads_held": 2, "linear_key_head_dim": 6,
              "linear_value_head_dim": 12, "vocab_total": 64, "vocab_held": 16, "cache_len": 32}
TINY = [f"algo.olmo_hybrid.{k}={v}" for k, v in TINY_MODEL.items()] + [
    "algo.olmo_hybrid.chunk_size=4", "env.num_envs=2", "algo.rollout_steps=16", "algo.per_rank_sequence_length=8",
    "algo.per_rank_num_batches=2", "algo.update_epochs=2",
]
# what only a chip gives (as in test_bench_harness_cpu.py)
NOT_HELD_HERE = {"window_compiles", "donation_miss_leaves"}
# float32 on the CPU: the program and the reference differ by rounding alone
LIMITS = {"logprob_gap": 1e-4, "logprob_gap.worst": 1e-3, "value_gap": 1e-4, "loss_gap.policy": 1e-3, "loss_gap.value": 1e-3, "loss_gap.entropy": 1e-4,
          "grad_gap": 1e-2, "change_gap": 1e-2}


@pytest.fixture(scope="module")
def olmo(tmp_path_factory):
    if threading.current_thread() is not threading.main_thread():
        pytest.skip("the run is ended by SIGTERM, which only the main thread can take")
    root = tmp_path_factory.mktemp("olmo")
    bench = root / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__"))
    config = json.load(open(os.path.join(BENCH, "configs", "olmo_hybrid_7b.json")))
    config["overrides"] = [o.replace("=tpu", "=cpu") for o in config["overrides"] if "_held" not in o and "num_envs" not in o] + TINY
    config["shapes"].update(TINY_MODEL, num_envs=2, rollout_steps=16, sequence_length=8, update_epochs=2, num_minibatches=2)
    (bench / "configs" / "olmo_tiny.json").write_text(json.dumps(config))
    cell = json.load(open(os.path.join(BENCH, "workloads", "olmo_hybrid_7b.token_ppo_32x256.json")))
    cell.update(name="olmo_tiny.cpu", overrides=[], limits=LIMITS,
                env={**cell["env"], "episode_min": 5, "episode_max": 24, "first_episodes": [6, 14], "stagger": 2})
    (bench / "workloads" / "olmo_tiny.cpu.json").write_text(json.dumps(cell))
    data = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    data["configs"].append({"name": "olmo_tiny", "source": "x", "file": "benchmarks/chip/configs/olmo_tiny.json", "reduced": [], "why": "y"})
    data["workloads"].append({"name": "olmo_tiny.cpu", "config": "olmo_tiny", "traffic": "cpu", "chips": 1, "why": "z"})
    (root / "BENCHMARK.json").write_text(json.dumps(data))
    patch = pytest.MonkeyPatch()
    patch.setattr(benchmarks.chip, "__path__", list(benchmarks.chip.__path__) + [str(bench)])
    yield Manifest(str(root)), str(root / "runs")
    patch.undo()


def _run(olmo, seed, fault=None, controls=None):
    from benchmarks.chip.harness import run_cell

    manifest, work_dir = olmo
    return run_cell(manifest, "olmo_tiny.cpu", seed, 4.0, False, time.time(), work_dir, fault=fault, controls=controls)


def _failed(result):
    return {k for k, c in result["_run"]["checks"].items() if not c["ok"]} - NOT_HELD_HERE


def test_the_family_answers_what_a_family_answers(olmo):
    manifest, _ = olmo
    config = manifest.config("olmo_hybrid_7b")
    family = manifest.family(config)
    assert all(hasattr(family, answer) for answer in FAMILY_ANSWERS)
    assert set(family.faults) == {"unchanged", "half_batch", "carry_dropped", "epochs_twice", "resets_ignored"}
    # ISSUE 31's arithmetic: 766M parameters held, 718M of them in matrix multiplications
    counts = family.parameter_counts(config["shapes"])
    assert round(sum(counts.values()) / 1e6) == 766 and round(sum(v for k, v in counts.items() if k.endswith("_matmul")) / 1e6) == 718
    flops = family.train_step_flops(config)
    assert flops["total"] == pytest.approx(16384 * 3 * 2 * 718e6, rel=0.01)
    assert 0 < flops["delta_rule"] / flops["total"] < 0.005
    assert family.decode_bytes(config) == pytest.approx(3.08e9, rel=0.01)


def test_a_sound_run_is_bounded_measured_and_held(olmo):
    result = _run(olmo, 3_100_000_123, controls=["bfloat16"])
    assert _failed(result) == set(), result["checks"]
    assert {"replay_row_mismatches", "replay_action_mismatches", "replay_label_mismatches", "params_moved", "losses_finite",
            "logprob_gap", "logprob_gap.worst", "value_gap", "loss_gap.policy", "loss_gap.value", "loss_gap.entropy", "grad_gap", "change_gap",
            "verified_checkpoints", "preempted_exit", "env_steps_counter_gap"} <= set(result["checks"])
    assert set(result["metrics"]) == {"setup_s", "env_steps_per_s", "action_gap_p95_ms"}
    window = result["window"]
    assert result["attempted"] == window["steps"] > 0 and window["gradient_steps"] > 0 and window["steps"] % 2 == 0


@pytest.mark.parametrize("fault, caught_by", [
    ("unchanged", {"params_moved", "change_gap"}),
    ("half_batch", {"loss_gap.value", "grad_gap"}),
    ("carry_dropped", {"loss_gap.value", "grad_gap"}),
    ("epochs_twice", {"change_gap"}),
])
def test_each_fault_of_the_step_is_not_correct(olmo, fault, caught_by):
    result = _run(olmo, 3_100_000_200 + len(fault), fault=fault)
    assert result["correct"] is False
    assert caught_by <= _failed(result), result["checks"]
    assert result["checks"]["replay_row_mismatches"]["value"] == 0.0  # the replay path is whole: the fault is the step's
    assert result["_run"]["checks"]["logprob_gap"]["ok"]  # and so is the player: what it stored is the reference's
    if fault == "epochs_twice":  # every gradient step it reports is sound: only where the parameters end up gives it away
        assert _failed(result) == {"change_gap"}, result["checks"]


def test_a_fault_of_the_player_is_not_correct(olmo):
    result = _run(olmo, 3_100_000_321, fault="resets_ignored")
    assert result["correct"] is False
    assert {"logprob_gap", "logprob_gap.worst", "value_gap"} <= _failed(result), result["checks"]
    assert result["checks"]["replay_row_mismatches"]["value"] == 0.0  # the env and the rows are sound: the fault is the player's
