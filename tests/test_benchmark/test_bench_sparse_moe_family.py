"""The sparse-attention expert-policy family through ``run_cell`` on the CPU at a tiny size.

As ``test_bench_olmo_family.py`` drives the standing family: the fixture copies
``benchmarks/chip`` to a temporary root and adds a tiny configuration and cell
(the widths of a test, a ``topk`` the episodes outgrow, the same files
otherwise).  ``run_cell`` then drives ``exp=ppo_recurrent_sparse_moe`` through
the real CLI loop: sound, and ``correct`` turning false with each of the four
faults this family adds and the two standing ones it makes anew without a copy of the snapshot."""

import json
import os
import shutil
import threading
import time

import pytest

import benchmarks.chip
from benchmarks.chip.manifest import FAMILY_ANSWERS, ROOT, Manifest

BENCH = os.path.join(ROOT, "benchmarks", "chip")
CELL = "keye_vl2_30b.token_ppo_16x1024"
TINY_MODEL = {"hidden_size": 32, "num_layers": 2, "num_heads": 4, "num_kv_heads": 2, "head_dim": 8, "mrope_section": [1, 1, 2],
              "indexer_heads": 2, "indexer_head_dim": 4, "topk": 6, "experts_total": 8, "experts_held": 4, "expert_share": 1,
              "experts_per_token": 2, "expert_width": 16, "vocab_total": 64, "vocab_held": 16, "cache_len": 32, "query_block": 4}
TINY = [f"algo.sparse_moe.{k}={json.dumps(v).replace(' ', '')}" for k, v in TINY_MODEL.items()] + [
    "env.num_envs=2", "algo.rollout_steps=16", "algo.per_rank_sequence_length=8", "algo.per_rank_num_batches=2", "algo.update_epochs=2",
]
# what only a chip gives (as in test_bench_harness_cpu.py), and the program's env-step counter against the env's clock: the two
# scrapes are tens of milliseconds off the window's edges when six workers share the CPU, 40 steps at this size's 1,400 steps/s
NOT_HELD_HERE = {"window_compiles", "donation_miss_leaves", "env_steps_counter_gap"}
# float32 on the CPU: the program and the reference differ by rounding alone
LIMITS = {"logprob_gap": 1e-4, "logprob_gap.worst": 1e-3, "value_gap": 1e-4, "loss_gap.policy": 1e-3, "loss_gap.value": 1e-3,
          "loss_gap.entropy": 1e-4, "loss_gap.index": 1e-3, "grad_gap": 1e-2, "grad_gap.indexer": 1e-2, "grad_gap.experts": 1e-2,
          "attended_share_gap": 1e-6, "change_gap": 1e-2}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    if threading.current_thread() is not threading.main_thread():
        pytest.skip("the run is ended by SIGTERM, which only the main thread can take")
    root = tmp_path_factory.mktemp("smoe")
    bench = root / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__"))
    config = json.load(open(os.path.join(BENCH, "configs", "keye_vl2_30b.json")))
    config["overrides"] = [o.replace("=tpu", "=cpu") for o in config["overrides"] if "_held" not in o and "_share" not in o and "num_envs" not in o] + TINY
    config["shapes"].update(TINY_MODEL, num_envs=2, rollout_steps=16, sequence_length=8, update_epochs=2, num_minibatches=2)
    (bench / "configs" / "smoe_tiny.json").write_text(json.dumps(config))
    cell = json.load(open(os.path.join(BENCH, "workloads", CELL + ".json")))
    cell.update(name="smoe_tiny.cpu", overrides=[], limits=LIMITS,
                env={**cell["env"], "vocab": 16, "episode_min": 9, "episode_max": 28, "first_episodes": [10, 22], "stagger": 3})
    (bench / "workloads" / "smoe_tiny.cpu.json").write_text(json.dumps(cell))
    data = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    data["configs"].append({"name": "smoe_tiny", "source": "x", "file": "benchmarks/chip/configs/smoe_tiny.json", "reduced": [], "why": "y"})
    data["workloads"].append({"name": "smoe_tiny.cpu", "config": "smoe_tiny", "traffic": "cpu", "chips": 1, "why": "z"})
    for metric in data["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("smoe_tiny.cpu")
    (root / "BENCHMARK.json").write_text(json.dumps(data))
    patch = pytest.MonkeyPatch()
    patch.setattr(benchmarks.chip, "__path__", list(benchmarks.chip.__path__) + [str(bench)])
    yield Manifest(str(root)), str(root / "runs")
    patch.undo()


def _run(tiny, seed, fault=None, controls=None):
    from benchmarks.chip.harness import run_cell

    manifest, work_dir = tiny
    return run_cell(manifest, "smoe_tiny.cpu", seed, 3.0, False, time.time(), work_dir, fault=fault, controls=controls)


def _failed(result):
    return {k for k, c in result["_run"]["checks"].items() if not c["ok"]} - NOT_HELD_HERE


def test_the_family_answers_what_a_family_answers(tiny):
    manifest, _ = tiny
    config = manifest.config("keye_vl2_30b")
    family = manifest.family(config)
    assert all(hasattr(family, answer) for answer in FAMILY_ANSWERS)
    assert set(family.faults) == {"unchanged", "half_batch", "carry_dropped", "epochs_twice", "resets_ignored",
                                  "selection_skipped", "topk_halved", "index_loss_dropped", "gates_unnormalised"}
    # ISSUE 38's arithmetic: 465.4M parameters held, a layer 96.9M
    counts = family.parameter_counts(config["shapes"])
    assert sum(counts.values()) == 465_393_152
    assert round((sum(counts.values()) - counts["embedding"] - counts["head_matmul"]) / 4 / 1e6, 1) == 96.9
    # a token's forward pass, a layer, in MFLOP: 38 of attention projections, 4.5 of the indexer's, 9.4 of the one pick in eight on a held expert
    per_layer = {k: v / 4e6 for k, v in family.forward_flops_per_token(config["shapes"], 6000, 2048, 1.0).items()}
    assert round(per_layer["index_score"] + per_layer["sparse_attention"]) == 46 and round(per_layer["experts"], 1) == 9.4
    flops = family.train_step_flops(config)
    assert flops["total"] == sum(v for k, v in flops.items() if k != "total") > 0
    # the readers of the new counters, on two scrapes made by hand: 3,000 positions seen and 2,048 attended a query at the mean
    run = {"config": config, "scrapes": [
        {"sheeprl_env_steps_total": 100.0, family.VISIBLE: 0.0, family.ATTENDED: 0.0, "sheeprl_policy_updates_total": 1.0,
         "sheeprl_policy_picks_held_share_sum": 0.125, "sheeprl_policy_attended_share_sum": 0.9},
        {"sheeprl_env_steps_total": 1100.0, family.VISIBLE: 3.0e6, family.ATTENDED: 2.048e6, "sheeprl_policy_updates_total": 3.0,
         "sheeprl_policy_picks_held_share_sum": 0.375, "sheeprl_policy_attended_share_sum": 2.3}]}
    assert family.window_positions(run) == {"visible": 3000.0, "attended": 2048.0}
    assert family.picks_held_share(run) == pytest.approx(0.125) and family.attended_share(run) == pytest.approx(0.7)
    work = family.update_work(run)
    assert work["sparse_attention"]["flops"] == pytest.approx(3 * 32768 * 4 * 4 * 32 * 128 * 2048)
    assert work["moe_experts"]["flops"] == pytest.approx(3 * 32768 * 4 * 3 * 2 * 2048 * 768)  # one pick a token a layer at 12.5%
    assert 1.0e9 < family.decode_bytes(run) < 1.5e9  # 0.87 GB of kernels as the view holds them and 0.5 GB of cache rows
    assert family.update_work({"config": config, "scrapes": [{}, {}]}) is None and family.decode_bytes({"config": config, "scrapes": [{}, {}]}) is None


def test_a_sound_run_is_bounded_measured_and_held(tiny):
    result = _run(tiny, 3_800_000_123)
    assert _failed(result) == set(), result["checks"]
    assert {"replay_row_mismatches", "replay_action_mismatches", "replay_label_mismatches", "params_moved", "losses_finite",
            "logprob_gap", "logprob_gap.worst", "value_gap", "loss_gap.policy", "loss_gap.value", "loss_gap.index", "grad_gap",
            "grad_gap.indexer", "grad_gap.experts", "attended_share_gap", "change_gap",
            "verified_checkpoints", "preempted_exit", "env_steps_counter_gap"} <= set(result["checks"])
    assert result["checks"]["env_steps_counter_gap"]["value"] <= 0.1 * result["window"]["steps"]  # the counter counts: not held to the scrape's skew
    assert set(result["metrics"]) == {"setup_s", "env_steps_per_s", "action_gap_p95_ms"}
    run = result["_run"]
    family = run["family"]
    # the counters the new readers divide: the selection bites in this traffic, and some picks are held
    positions = family.window_positions(run)
    assert positions is not None and 6 > positions["attended"] > 1 and positions["visible"] > positions["attended"]
    assert 0 < family.attended_share(run) < 1 and 0 < family.picks_held_share(run) < 1
    page = run["scrapes"][1]
    assert page['sheeprl_policy_carry_bytes{kind="kv"}'] + page['sheeprl_policy_carry_bytes{kind="index"}'] + 2 * 4 == page["sheeprl_policy_carry_bytes"]
    assert 0 < page["sheeprl_policy_attended_positions"] <= min(page["sheeprl_policy_cache_positions"], 2 * 6)


@pytest.mark.parametrize("fault, caught_by", [
    ("selection_skipped", {"logprob_gap", "attended_share_gap"}),
    ("topk_halved", {"logprob_gap", "attended_share_gap"}),
    ("index_loss_dropped", {"params_moved", "grad_gap.indexer"}),
    ("gates_unnormalised", {"logprob_gap", "value_gap"}),
    ("half_batch", {"loss_gap.value", "grad_gap"}),  # this family's own forms of two standing faults: nothing of the snapshot is copied
    ("carry_dropped", {"loss_gap.value", "grad_gap"}),
])
def test_each_fault_is_not_correct(tiny, fault, caught_by):
    result = _run(tiny, 3_800_000_200 + len(fault), fault=fault)
    assert result["correct"] is False
    assert caught_by <= _failed(result), result["checks"]
    assert result["checks"]["replay_row_mismatches"]["value"] == 0.0  # the replay path is whole: the fault is the model's


def _smoe_metrics(manifest):
    return [m["name"] for m in manifest.metrics_for(CELL, "per_layer") if "workloads" in m]


def test_the_new_readers_read_none_where_there_is_nothing_to_read():
    """What the driver runs on the parent's program with this benchmark laid over it: no counter, no scope, no line."""
    manifest = Manifest(ROOT)
    config = manifest.config("keye_vl2_30b")
    names = _smoe_metrics(manifest)
    assert len(names) == 23 and all(n.startswith("smoe.") for n in names)
    untraced = {"trace": None, "family": manifest.family(config), "config": config, "cell": {"name": "no_such.cell"}, "scrapes": [{}, {}],
                "device": {"kind": "TPU v5 lite", "count": 1}}
    for name in names:
        assert manifest.reader(name)(untraced) is None and manifest.reader(name)({**untraced, "trace": {"busy_s": 1.0}}) is None, name


def test_the_new_readers_find_their_numbers(monkeypatch):
    from benchmarks.chip import lm_reduce, span_reduce

    manifest = Manifest(ROOT)
    config = manifest.config("keye_vl2_30b")
    family = manifest.family(config)
    scopes = {scope: 100.0 * (i + 1) for i, scope in enumerate(family.train_step_scopes + ("unscoped",))}
    monkeypatch.setattr(lm_reduce, "_reduced", lambda *a: scopes)
    monkeypatch.setattr(lm_reduce, "find_xplane", lambda path: "x")
    monkeypatch.setattr(span_reduce, "for_run", lambda run: {"module_ms": {"player": 7.0}, "scope_ms": None, "idle_ms": None})
    steps, calls = "sheeprl_env_steps_total", 'sheeprl_phase_calls_total{phase="rollout/action-fetch"}'
    s0 = {steps: 0.0, calls: 0.0, family.VISIBLE: 0.0, family.ATTENDED: 0.0, "sheeprl_policy_updates_total": 0.0,
          "sheeprl_policy_picks_held_share_sum": 0.0, "sheeprl_policy_attended_share_sum": 0.0,
          'sheeprl_phase_seconds_total{phase="rollout"}': 0.0, 'sheeprl_phase_seconds_total{phase="rollout/action-fetch"}': 0.0,
          'sheeprl_phase_seconds_total{phase="train"}': 0.0, 'sheeprl_instrumented_calls_total{fn="train_step"}': 0.0}
    s1 = {steps: 16384.0, calls: 1024.0, family.VISIBLE: 16384 * 3000.0, family.ATTENDED: 16384 * 2000.0, "sheeprl_policy_updates_total": 2.0,
          "sheeprl_policy_picks_held_share_sum": 0.25, "sheeprl_policy_attended_share_sum": 1.5,
          'sheeprl_phase_seconds_total{phase="rollout"}': 10.24, 'sheeprl_phase_seconds_total{phase="rollout/action-fetch"}': 8.192,
          'sheeprl_phase_seconds_total{phase="train"}': 0.05, 'sheeprl_instrumented_calls_total{fn="train_step"}': 2.0}
    run = {"trace": {"busy_s": 1.0}, "family": family, "config": config, "cell": {"name": "c"}, "device": {"kind": "TPU v5 lite", "count": 1},
           "scrapes": [s0, s1]}
    read = lambda name: manifest.reader(name)(run)  # noqa: E731
    assert read("smoe.embed_ms") == 100.0 and read("smoe.optim_ms") == 1100.0 and read("smoe.unscoped_ms") == 1200.0
    assert read("smoe.decode_device_ms") == 7.0
    assert read("smoe.rollout_host_ms") == pytest.approx(10.0) and read("smoe.action_fetch_wait_ms") == pytest.approx(8.0)
    assert read("smoe.update_dispatch_ms") == pytest.approx(25.0)
    assert read("smoe.attended_share_pct") == pytest.approx(75.0) and read("smoe.picks_held_pct") == pytest.approx(12.5)
    work = family.update_work(run)
    # 32,768 tokens x 4 layers: attention over 2,000 positions 3 x 32.8 MFLOP a token a layer, over the scope's 500 ms
    assert read("smoe.sparse_attention_roofline_pct") == pytest.approx(100 * work["sparse_attention"]["flops"] / 197e12 / 0.5)
    assert read("smoe.index_score_roofline_pct") == pytest.approx(100 * work["index_score"]["flops"] / 197e12 / 0.3)
    assert read("smoe.select_roofline_pct") == pytest.approx(100 * work["select"]["bytes"] / 819e9 / 0.4)
    assert read("smoe.experts_roofline_pct") == pytest.approx(100 * work["moe_experts"]["flops"] / 197e12 / 0.8)
    assert read("smoe.decode_hbm_roofline_pct") == pytest.approx(100 * family.decode_bytes(run) / 819e9 / 0.007)
    for name in _smoe_metrics(manifest):
        if name.endswith("roofline_pct"):
            assert 0 < read(name) < 100, name
