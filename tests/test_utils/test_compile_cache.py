"""Where JAX's persistent compilation cache goes (utils/compile_cache.py) and
the two launch gates that keep a CPU run from passing for a chip run:
``chip_smoke.py`` and ``fabric.accelerator=tpu``."""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest

from sheeprl_tpu.utils import compile_cache

REPO_ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture
def recorded_updates(monkeypatch):
    """Every ``jax.config.update`` the code under test makes, without applying it."""
    import jax

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda name, value: calls.append((name, value)))
    return calls


def test_environment_variable_wins_and_nothing_is_set(monkeypatch, recorded_updates):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
    assert compile_cache.compile_cache_dir_to_set() is None
    assert compile_cache.compile_cache_dir_to_set("configured/dir") is None
    assert compile_cache.enable_compile_cache("configured/dir") == "/somewhere/else"
    assert recorded_updates == []
    # a process that may use an accelerator keys the cache on the metadata too
    # (a profile must show this checkout's scopes), and still places nothing
    import jax

    with monkeypatch.context() as m:
        m.setattr(type(jax.config), "jax_platforms", "tpu,cpu", raising=False)
        assert compile_cache.enable_compile_cache() == "/somewhere/else"
    assert recorded_updates == [
        ("jax_compilation_cache_include_metadata_in_key", True),
        ("jax_traceback_in_locations_limit", 1),
    ]


def test_default_is_one_absolute_in_checkout_path_from_any_cwd(monkeypatch, tmp_path):
    monkeypatch.delenv(compile_cache.ENV_VAR)
    monkeypatch.chdir(tmp_path)
    first = compile_cache.compile_cache_dir_to_set()
    (tmp_path / "elsewhere").mkdir()
    monkeypatch.chdir(tmp_path / "elsewhere")
    assert compile_cache.compile_cache_dir_to_set() == first == str(REPO_ROOT / ".jax_cache")
    # a configured (deployment) path is honoured, made absolute where it is set
    assert compile_cache.compile_cache_dir_to_set("jit") == str(tmp_path / "elsewhere" / "jit")


def test_enable_places_the_cache_unless_the_process_is_cpu_only(monkeypatch, recorded_updates):
    import jax

    monkeypatch.delenv(compile_cache.ENV_VAR)
    monkeypatch.setattr(os, "makedirs", lambda *a, **k: None)
    assert jax.config.jax_platforms == "cpu"  # the suite's own platform (conftest)
    assert compile_cache.enable_compile_cache() is None and recorded_updates == []
    with monkeypatch.context() as m:
        m.setattr(type(jax.config), "jax_platforms", "tpu,cpu", raising=False)
        assert compile_cache.enable_compile_cache() == str(REPO_ROOT / ".jax_cache")
    assert ("jax_compilation_cache_dir", str(REPO_ROOT / ".jax_cache")) in recorded_updates


def test_chip_smoke_refuses_to_start_without_a_tpu(tmp_path):
    """Under JAX_PLATFORMS=cpu the smoke exits non-zero before building
    anything: one line on stderr, no result line."""
    proc = subprocess.run(
        [sys.executable, str(REPO_ROOT / "chip_smoke.py")],
        cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "refusing to start" in proc.stderr and "'cpu'" in proc.stderr
    assert list(tmp_path.iterdir()) == []


@pytest.mark.parametrize("passes", [True, False])
def test_chip_smoke_last_stdout_line_is_the_verdict(passes, monkeypatch, capsys):
    """The last stdout line is exactly {"ok", "device": {"platform", "kind",
    "count"}} whether the run passed or a phase failed; the measurements ride
    on the line before it."""
    import importlib.util
    import json
    import types

    import jax

    spec = importlib.util.spec_from_file_location("chip_smoke", REPO_ROOT / "chip_smoke.py")
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    chip = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [chip])

    def run_and_check(run, t0):
        if not passes:
            raise chip_smoke.SmokeFailure("no gradient step was taken")
        return {"gradient_steps": 49}

    monkeypatch.setattr(chip_smoke, "_run_and_check", run_and_check)
    assert chip_smoke.main() == (0 if passes else 1)
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {
        "ok": passes,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    assert [json.loads(line) for line in lines[:-1]] == (
        [{"report": {"gradient_steps": 49}}] if passes else []
    )


def test_accelerator_tpu_raises_without_a_tpu():
    from sheeprl_tpu.parallel.runtime import Runtime

    with pytest.raises(RuntimeError, match="tpu"):
        Runtime(accelerator="tpu")
