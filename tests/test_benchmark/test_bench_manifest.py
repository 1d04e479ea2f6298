"""The harness is driven by data: a new configuration, cell and per-layer
metric are found by name with no edit to any file that is there, and the
repo's own BENCHMARK.json keeps to the contract's naming rules."""

import json
import os
import shutil

import pytest

from benchmarks.chip.harness import assemble_result, compose_overrides
from benchmarks.chip.manifest import FAMILY_ANSWERS, ROOT, Manifest, ManifestError, check_names

BENCH = os.path.join(ROOT, "benchmarks", "chip")


@pytest.fixture()
def grown(tmp_path):
    """A copy of the benchmark with one more configuration, cell and metric: new files and entries only."""
    root = tmp_path / "repo"
    shutil.copytree(BENCH, root / "benchmarks" / "chip")
    data = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    config = json.load(open(os.path.join(BENCH, "configs", "dv3_s.json")))
    config["overrides"].append("algo.horizon=7")
    (root / "benchmarks" / "chip" / "configs" / "dv3_new.json").write_text(json.dumps(config))
    cell = json.load(open(os.path.join(BENCH, "workloads", "dv3_s.hbm_replay.json")))
    cell.update(name="dv3_new.slow_env", env={**cell["env"], "step_ms": 9.0})
    (root / "benchmarks" / "chip" / "workloads" / "dv3_new.slow_env.json").write_text(json.dumps(cell))
    (root / "benchmarks" / "chip" / "metrics" / "loop.steps_per_gradient_step.py").write_text(
        "def read(run):\n    return run['window']['steps'] / run['window']['gradient_steps']\n"
    )
    data["configs"].append({"name": "dv3_new", "source": "x", "file": "benchmarks/chip/configs/dv3_new.json", "reduced": [], "why": "y"})
    data["workloads"].append({"name": "dv3_new.slow_env", "config": "dv3_new", "traffic": "slow_env", "chips": 1, "why": "z"})
    data["per_layer"].append({"name": "loop.steps_per_gradient_step", "unit": "steps", "better": "lower", "source": "program_counter",
                              "layer": "loop", "moves": "env_steps_per_s", "workloads": ["dv3_new.slow_env"]})
    (root / "BENCHMARK.json").write_text(json.dumps(data))
    manifest = Manifest(str(root))
    manifest.root_path = root
    return manifest


def _run(trace):
    run = {
        "t_start": 0.0, "t0": 60.0, "first_train_step_t": 40.0,
        "window": {"steps": 300, "gaps": 300, "gradient_steps": 150, "seconds": 10.0, "env_steps_per_s": 30.0,
                   "action_gap_p95_ms": 40.0, "action_gap_p50_ms": 30.0, "steps_by_10s": [300]},
        "phase_delta_s": {"rollout": 3.0, "train": 1.0, "buffer-sample": 0.5},
        "journal": {"telemetry_summary": [{"compile_time_s": 12.5}]},
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 9_000_000_000},
        "trace": None,
    }
    if trace:
        run["trace"] = {"busy_s": 2.0, "window_s": 3.0, "idle_pct": 33.3, "module_device_ms": 17.0,
                        "device_ops": [["fusion", 1.0]], "idle_by_span": [["unattributed", 1.0]]}
    return run


def test_new_files_are_found_by_name(grown):
    cell = grown.workload("dv3_new.slow_env")
    config = grown.config(cell["config"])
    run = {**_run(True), "config": config, "family": grown.family(config)}
    assert cell["env"]["step_ms"] == 9.0 and "algo.horizon=7" in config["overrides"]
    overrides = compose_overrides(grown.family(config), config, cell, 5, "/tmp/log.npz")
    assert "env.wrapper.step_ms=9.0" in overrides and "algo.horizon=7" in overrides and "seed=5" in overrides
    assert "env.wrapper.log_path=/tmp/log.npz" in overrides and sum(o.startswith("env=") for o in overrides) == 1
    result = assemble_result(grown, "dv3_new.slow_env", run, {}, trace=True)
    assert result["metrics"]["loop.steps_per_gradient_step"] == {"value": 2.0, "unit": "steps"}
    # the cell that was there does not report the new cell's metric
    old = assemble_result(grown, "dv3_s.hbm_replay", {**run, "config": grown.config("dv3_s")}, {}, trace=True)
    assert "loop.steps_per_gradient_step" not in old["metrics"] and "train_step.mfu_pct" in old["metrics"]


def test_unknown_names_are_errors(grown):
    with pytest.raises(ManifestError):
        grown.workload("no_such.cell")
    with pytest.raises(ManifestError):
        grown.reader("no.such.metric")
    with pytest.raises(ManifestError, match="families/no_such_family.py"):
        grown.family({**grown.config("dv3_new"), "family": "no_such_family"})
    with pytest.raises(ManifestError, match="names no family"):
        grown.family({"name": "dv3_new"})
    (grown.root_path / "benchmarks" / "chip" / "families" / "half.py").write_text("def install(seed, recorder):\n    pass\n")
    with pytest.raises(ManifestError, match="does not answer"):
        grown.family({"name": "dv3_new", "family": "half"})


def test_the_repos_benchmark_keeps_to_the_naming_rules():
    manifest = Manifest(ROOT)
    data = manifest.data
    assert check_names(data) == []
    assert set(data) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert isinstance(data["run_seconds"], int) and 10 <= data["run_seconds"] <= 51
    e2e = {m["name"] for m in data["end_to_end"]}
    assert "setup_s" in e2e
    for m in data["end_to_end"]:
        assert 0 < m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    layers = set()
    for m in data["per_layer"]:
        assert m["moves"] in e2e and callable(manifest.reader(m["name"]))
        layers.add(m["layer"])
    assert layers <= {"entry", "loop", "train step", "kernels", "device"}
    for cell in data["workloads"]:
        merged = manifest.workload(cell["name"])
        assert merged["chips"] in (1, 4) and len(cell["why"]) <= 200 and "limits" in merged
        config = manifest.config(cell["config"])
        assert {"source", "family", "reference", "overrides", "reduced", "assumed", "shapes", "hyper"} <= set(config)
    for config in data["configs"]:
        assert config["file"].startswith(tuple(data["paths"]))
        assert config["reduced"] == manifest.config(config["name"])["reduced"]


def test_every_configuration_names_a_family_whose_file_answers_for_it():
    """The whole of what a family brings: a file found by the name in the configuration's file."""
    manifest = Manifest(ROOT)
    for entry in manifest.data["configs"]:
        config = manifest.config(entry["name"])
        assert os.path.isfile(os.path.join(BENCH, "families", config["family"] + ".py"))
        assert os.path.isfile(config["reference"]) and config["reference"].startswith(BENCH)
        family = manifest.family(config)
        assert all(hasattr(family, answer) for answer in FAMILY_ANSWERS)
        assert all(callable(getattr(family, name)) for name in ("install", "split_step", "compare", "train_step_flops", "env_overrides"))
        assert isinstance(family.env_group, str) and os.path.isfile(os.path.join(BENCH, "hydra", "env", family.env_group + ".yaml"))
        assert "train_step" in family.executables and family.train_step_flops(config)["total"] > 0
        # the faults its comparison has to catch, and the scopes its step is split by (none is an answer too)
        assert family.faults and all(callable(break_step) for break_step in family.faults.values())
        assert isinstance(family.train_step_scopes, tuple) and all(isinstance(s, str) for s in family.train_step_scopes)
    # a per-layer metric with no ``workloads`` key is asked of every cell, of whatever family
    for metric in manifest.data["per_layer"]:
        if "workloads" not in metric:
            assert metric["layer"] in ("entry", "train step", "device"), metric["name"]


SEAM_FILES = ["harness.py", "check.py", "run.py", "trace_reduce.py", "span_reduce.py"]


@pytest.mark.parametrize("path", SEAM_FILES + sorted("metrics/" + f for f in os.listdir(os.path.join(BENCH, "metrics")) if f.endswith(".py")))
def test_the_harness_names_no_algorithm(path):
    """What knows which algorithm runs sits in ``families/``: ISSUE 29's grep, a file a case."""
    text = open(os.path.join(BENCH, path)).read()
    for word in ("dreamer", "PlayerDV3", "jit_train_step", "chipbench"):
        assert word not in text, f"{path} names {word!r}"


@pytest.mark.parametrize("entry,complaint", [
    ({"name": "has space", "unit": "ms", "better": "lower"}, "bad name"),
    ({"name": "ok", "unit": "tokens per second", "better": "lower"}, "bad unit"),
    ({"name": "ok", "unit": "µs", "better": "lower"}, "bad unit"),
    ({"name": "ok", "unit": "ms", "better": "faster"}, "needs better"),
    ({"name": "a" * 65, "unit": "ms", "better": "lower"}, "bad name"),
    ({"name": "ok", "unit": "x" * 17, "better": "lower"}, "bad unit"),
])
def test_what_the_contract_refuses_is_caught(entry, complaint):
    assert any(complaint in line for line in check_names({"end_to_end": [entry]}))


def test_units_may_hold_slash_and_percent():
    ok = [{"name": "a.b-c_d", "unit": "steps/s", "better": "higher"}, {"name": "x", "unit": "%", "better": "higher"}]
    assert check_names({"per_layer": ok}) == []
