"""The last line's key set in both trace modes, and the refusal off the TPU."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.chip.harness import assemble_result, parse_metrics_text, program_seed
from benchmarks.chip.manifest import ROOT, Manifest
from test_bench_manifest import _run

CELL = "dv3_s.hbm_replay"
CHECKS = {"loss_gap.world_model": {"value": 0.001, "limit": 0.01, "ok": True}}


@pytest.fixture(scope="module")
def manifest():
    return Manifest(ROOT)


def test_end_to_end_line(manifest):
    run = {**_run(False), "config": manifest.config("dv3_s")}
    result = assemble_result(manifest, CELL, run, CHECKS, trace=False)
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics", "device"] and list(result)[-1] == "checks"
    assert "breakdown" not in result and result["correct"] is True and result["attempted"] == 300
    assert set(result["metrics"]) == {"setup_s", "env_steps_per_s", "action_gap_p95_ms"}
    assert result["metrics"]["setup_s"] == {"value": 60.0, "unit": "s"}
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert result["checks"] == {"loss_gap.world_model": {"value": 0.001, "limit": 0.01}}
    json.dumps(result)


# the readers that need no trace file and no scrape: what a hand-built run can feed
NO_TRACE_FILE = {"entry.first_train_step_s", "entry.compile_s", "loop.rollout_host_ms", "loop.train_host_ms",
                 "train_step.device_ms", "train_step.mfu_pct", "device.idle_pct", "device.hbm_peak_gb"}


def _traced_run(manifest):
    config = manifest.config("dv3_s")
    return {**_run(True), "config": config, "family": manifest.family(config)}


def test_traced_line(manifest):
    run = _traced_run(manifest)
    result = assemble_result(manifest, CELL, run, CHECKS, trace=True)
    names = {m["name"] for m in manifest.metrics_for(CELL, "per_layer")}
    assert len(names) == 26 and NO_TRACE_FILE <= names
    assert set(result["metrics"]) == NO_TRACE_FILE  # the other 18 find nothing to read and are left out
    assert {"busy_s", "window_s"} <= set(result["device"]) and result["device"]["busy_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    units = {m["name"]: m["unit"] for m in manifest.data["per_layer"]}
    assert all(v["unit"] == units[k] for k, v in result["metrics"].items())
    assert result["metrics"]["train_step.device_ms"]["value"] == 17.0
    assert result["metrics"]["loop.train_host_ms"]["value"] == pytest.approx(10.0)
    assert result["metrics"]["loop.rollout_host_ms"]["value"] == pytest.approx(10.0)
    assert result["metrics"]["entry.first_train_step_s"]["value"] == 40.0
    assert 0 < result["metrics"]["train_step.mfu_pct"]["value"] < 100


def test_a_reader_that_finds_nothing_leaves_its_metric_out(manifest):
    run = _traced_run(manifest)
    run["trace"] = {**run["trace"], "module_device_ms": None}
    result = assemble_result(manifest, CELL, run, CHECKS, trace=True)
    assert "train_step.device_ms" not in result["metrics"] and "device.idle_pct" in result["metrics"]


def test_a_failed_check_makes_the_run_incorrect(manifest):
    run = {**_run(False), "config": manifest.config("dv3_s")}
    bad = {**CHECKS, "replay_frame_mismatches": {"value": 1.0, "limit": 0.0, "ok": False}}
    assert assemble_result(manifest, CELL, run, bad, trace=False)["correct"] is False


def test_the_command_refuses_anything_but_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    command = Manifest(ROOT).data["command"]
    assert command[0] == "python3" and command[1].startswith("benchmarks/chip/")
    done = subprocess.run([sys.executable, command[1], "--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0"],
                          cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert done.returncode != 0 and done.stdout == ""
    assert "refusing to start" in done.stderr.strip().splitlines()[-1]


def test_an_unknown_cell_is_refused_before_jax_is_touched():
    command = Manifest(ROOT).data["command"]
    done = subprocess.run([sys.executable, command[1], "--workload", "nope", "--seed", "1", "--seconds", "1", "--trace", "0"],
                          cwd=ROOT, capture_output=True, text=True, timeout=60)
    assert done.returncode == 2 and done.stdout == ""


def test_metrics_text_and_seed_folding():
    text = '# TYPE x counter\nsheeprl_env_steps_total 1234\nsheeprl_phase_seconds_total{phase="rollout"} 12.5\n'
    assert parse_metrics_text(text) == {"sheeprl_env_steps_total": 1234.0, 'sheeprl_phase_seconds_total{phase="rollout"}': 12.5}
    assert program_seed(5) == 5 and 0 <= program_seed(2**31 + 12345) < 2**31 - 1
    assert program_seed(2**31 + 12345) == program_seed(2**31 + 12345)
