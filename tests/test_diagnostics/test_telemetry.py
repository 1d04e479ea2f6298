"""Performance-telemetry tests (ISSUE 3 acceptance).

Fast, tier-1 eligible:

* recompile watchdog: fires exactly once on a forced shape change, never on
  steady-state dispatches, and storms trip the window warning;
* MFU / sps / phase-breakdown math against an injected deterministic clock;
* ``/metrics`` endpoint serves valid Prometheus text on an ephemeral port and
  shuts down cleanly with the run;
* decoupled player+trainer trace pair merges into one coherent timeline via
  the ``clock_sync`` anchors (``tools/trace_report.py``);
* the whole layer end-to-end through the real CLI on a tiny dummy-env PPO
  run: ``Telemetry/mfu`` / ``Telemetry/sps`` / phase rows in the journal, a
  ``recompile`` event for the injected shape change, gauges on ``/metrics``.
"""

from __future__ import annotations

import json
import os
import sys
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from sheeprl_tpu.diagnostics import build_diagnostics, read_journal
from sheeprl_tpu.diagnostics.metrics_server import MetricsServer, render_prometheus
from sheeprl_tpu.diagnostics.telemetry import TELEMETRY_PREFIX, Telemetry
from sheeprl_tpu.diagnostics.tracing import PhaseTracer

REPO_ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO_ROOT / "tools"))


def _diag_cfg(**telemetry):
    return {
        "diagnostics": {
            "enabled": True,
            "journal": {"enabled": True},
            "sentinel": {"enabled": False},
            "trace": {"enabled": False},
            "telemetry": {"enabled": True, **telemetry},
        },
        "fabric": {"precision": "32-true"},
        "algo": {"name": "ppo"},
        "env": {"id": "discrete_dummy"},
        "seed": 0,
    }


# ---------------------------------------------------------------------------
# recompile watchdog


def test_watchdog_fires_exactly_once_on_shape_change(tmp_path):
    import jax
    import jax.numpy as jnp

    diag = build_diagnostics(_diag_cfg()).open(str(tmp_path))
    step = diag.instrument("train_step", jax.jit(lambda x: (x * 2).sum()), kind="train")

    for _ in range(4):  # steady state: one compile, zero recompiles
        step(jnp.ones((4, 4)))
    step(jnp.ones((8, 4)))  # forced shape change -> exactly one recompile
    for _ in range(3):  # new steady state: still just the one
        step(jnp.ones((8, 4)))
    step(jnp.ones((4, 4)))  # back to a cached signature: jit cache hit, no event
    diag.close()

    events = read_journal(str(tmp_path / "journal.jsonl"))
    recompiles = [e for e in events if e["event"] == "recompile"]
    assert len(recompiles) == 1, [e["event"] for e in events]
    assert recompiles[0]["fn"] == "train_step"
    assert any("[4, 4]" in d and "[8, 4]" in d for d in recompiles[0]["diff"])
    summary = next(e for e in events if e["event"] == "telemetry_summary")
    assert summary["recompiles"] == 1
    assert summary["recompile_storms"] == 0


def test_watchdog_storm_warns_and_journals(tmp_path):
    import jax
    import jax.numpy as jnp

    diag = build_diagnostics(
        _diag_cfg(watchdog={"storm_threshold": 3, "storm_window_s": 3600.0})
    ).open(str(tmp_path))
    step = diag.instrument("train_step", jax.jit(lambda x: x.sum()), kind="train")
    step(jnp.ones((2, 2)))
    with pytest.warns(RuntimeWarning, match="Recompile storm"):
        for n in (3, 4, 5):  # three fresh signatures inside the window
            step(jnp.ones((n, 2)))
    diag.close()
    events = read_journal(str(tmp_path / "journal.jsonl"))
    assert sum(1 for e in events if e["event"] == "recompile") == 3
    (storm,) = [e for e in events if e["event"] == "recompile_storm"]
    assert storm["recompiles_in_window"] == 3


def test_instrumented_train_step_captures_cost_and_stays_correct(tmp_path):
    """The AOT dispatch path returns the same values as the bare jit fn and
    journals the compiled step's FLOPs once per signature."""
    import jax
    import jax.numpy as jnp

    diag = build_diagnostics(_diag_cfg()).open(str(tmp_path))
    fn = jax.jit(lambda x: (x @ x.T).sum())
    step = diag.instrument("train_step", fn, kind="train")
    x = jnp.arange(16.0).reshape(4, 4)
    np.testing.assert_allclose(np.asarray(step(x)), np.asarray(fn(x)), rtol=1e-6)
    diag.close()
    events = read_journal(str(tmp_path / "journal.jsonl"))
    (cost,) = [e for e in events if e["event"] == "telemetry_cost"]
    assert cost["fn"] == "train_step" and cost["flops_per_call"] > 0
    assert "note" not in cost  # no caveat unless the caller declares one


def test_cost_note_caveat_rides_the_telemetry_cost_event(tmp_path):
    """Callers with inflated cost_analysis FLOPs (unrolled scans — PERF.md §5)
    declare it via instrument(cost_note=...); the caveat must land on the
    journaled telemetry_cost event so MFU is never silently over-read."""
    import jax
    import jax.numpy as jnp

    diag = build_diagnostics(_diag_cfg()).open(str(tmp_path))
    note = "cost_analysis FLOPs inflate under scan unrolling (scan_unroll=8); compare step_ms, not MFU"
    step = diag.instrument(
        "train_step", jax.jit(lambda x: (x @ x.T).sum()), kind="train", cost_note=note
    )
    step(jnp.arange(16.0).reshape(4, 4))
    diag.close()
    events = read_journal(str(tmp_path / "journal.jsonl"))
    (cost,) = [e for e in events if e["event"] == "telemetry_cost"]
    assert cost["note"] == note


# ---------------------------------------------------------------------------
# MFU / sps / phase math (deterministic injected clock)


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_interval_math_mfu_sps_and_phase_breakdown():
    clock = FakeClock()
    tele = Telemetry(
        {
            "diagnostics": {
                "telemetry": {"enabled": True, "mfu": {"peak_tflops_per_device": 2.0}}
            },
            "fabric": {"precision": "32-true"},
        },
        clock=clock,
    )
    tele.open()
    tele._device_count = 1
    tele._peak_flops_total = 2.0e12  # force: CPU device kind resolves to None

    class Inst:  # stand-in instrumented fn: 1e9 FLOPs per call
        name, kind = "train_step", "train"
        flops_per_call = 1.0e9

    # synthetic interval: 10 s wall, 200 policy steps, 4 train calls,
    # train span 4 s (with a nested 1 s buffer-sample), env_wait 2 s
    tele.interval_metrics(0)  # baseline tick at step 0
    for _ in range(4):
        tele._record_call(Inst())
    outer = tele.span_enter("train")
    clock.t += 3.0
    inner = tele.span_enter("buffer-sample")
    clock.t += 1.0
    tele.span_exit(inner)
    tele.span_exit(outer)  # train self-time = 3 s, buffer-sample = 1 s
    wait = tele.span_enter("env_wait")
    clock.t += 2.0
    tele.span_exit(wait)
    clock.t += 4.0  # idle tail -> 10 s total
    out = tele.interval_metrics(200)

    assert out[TELEMETRY_PREFIX + "sps"] == pytest.approx(20.0)
    assert out[TELEMETRY_PREFIX + "tflops_per_sec"] == pytest.approx(4.0e9 / 10 / 1e12)
    assert out[TELEMETRY_PREFIX + "mfu"] == pytest.approx((4.0e9 / 10) / 2.0e12)
    assert out[TELEMETRY_PREFIX + "phase_pct/train"] == pytest.approx(30.0)
    # buffer-sample + env_wait both land in the `fetch` bucket
    assert out[TELEMETRY_PREFIX + "phase_pct/fetch"] == pytest.approx(30.0)
    assert out[TELEMETRY_PREFIX + "phase_pct/unspanned"] == pytest.approx(40.0)
    # interval accumulators reset: an empty follow-up interval has no rates
    clock.t += 1.0
    again = tele.interval_metrics(200)
    assert again[TELEMETRY_PREFIX + "sps"] == 0.0
    assert TELEMETRY_PREFIX + "tflops_per_sec" not in again


def test_interval_math_env_throughput_and_fetch_amortization():
    """ISSUE 7 gauges: env_steps_per_sec over the interval wall-clock and
    env steps per blocking fetch — rollout dispatches and Dreamer-style
    direct ``note_fetch`` calls both count as fetches."""
    clock = FakeClock()
    tele = Telemetry({"diagnostics": {"telemetry": {"enabled": True}}}, clock=clock)
    tele.open()

    class Roll:  # stand-in instrumented rollout fn (one dispatch == one fetch)
        name, kind = "policy_step", "rollout"
        flops_per_call = None

    tele.interval_metrics(0)
    for _ in range(5):  # 5 vector steps of 64 envs through the rollout path
        tele.note_env_steps(64)
        tele._record_call(Roll())
    for _ in range(3):  # 3 Dreamer-style vector steps with direct fetches
        tele.note_env_steps(64)
        tele.note_fetch()
    clock.t += 16.0
    out = tele.interval_metrics(512)
    assert out[TELEMETRY_PREFIX + "env_steps_per_sec"] == pytest.approx(8 * 64 / 16.0)
    assert out[TELEMETRY_PREFIX + "fetch_amortization"] == pytest.approx(64.0)
    assert tele.snapshot()["counters"]["env_steps_total"] == 8 * 64
    # exported on /metrics under the registered names
    from sheeprl_tpu.diagnostics.metrics_server import render_prometheus

    text = render_prometheus(tele.snapshot())
    assert "sheeprl_env_steps_per_sec" in text
    assert "sheeprl_fetch_amortization" in text
    assert "sheeprl_env_steps_total 512" in text
    # interval accumulators reset
    clock.t += 1.0
    again = tele.interval_metrics(512)
    assert TELEMETRY_PREFIX + "env_steps_per_sec" not in again
    tele.close()


def test_unknown_device_kind_reports_no_mfu():
    clock = FakeClock()
    tele = Telemetry(_diag_cfg(), clock=clock)  # no peak override; CPU kind
    tele.open()
    assert tele._peak_flops_total is None

    class Inst:
        name, kind = "train_step", "train"
        flops_per_call = 1.0e9

    tele.interval_metrics(0)
    tele._record_call(Inst())
    clock.t += 1.0
    out = tele.interval_metrics(10)
    assert TELEMETRY_PREFIX + "tflops_per_sec" in out
    assert TELEMETRY_PREFIX + "mfu" not in out  # no silent guessing


# ---------------------------------------------------------------------------
# /metrics endpoint


def test_metrics_endpoint_serves_prometheus_and_shuts_down():
    snapshot = {
        "info": {"run_id": "r/v0", "algo": "ppo"},
        "gauges": {"Telemetry/mfu": 0.25, "Telemetry/phase_pct/train": 60.0},
        "counters": {"recompiles_total": 2},
        "policy_steps": 128,
        "phase_seconds_total": {"train": 1.5},
        "journal_lag_seconds": 0.5,
    }
    server = MetricsServer(lambda: snapshot, port=0)
    host, port = server.start()
    assert port > 0  # ephemeral bind
    with urllib.request.urlopen(f"http://{host}:{port}/metrics") as resp:
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("text/plain; version=0.0.4")
        body = resp.read().decode()
    assert 'sheeprl_run_info{algo="ppo",run_id="r/v0"} 1' in body
    assert "sheeprl_mfu 0.25" in body
    assert "sheeprl_phase_pct_train 60" in body
    assert "sheeprl_recompiles_total 2" in body
    assert 'sheeprl_phase_seconds_total{phase="train"} 1.5' in body
    assert "sheeprl_journal_lag_seconds 0.5" in body
    # every non-comment line parses as <name>[{labels}] <float>
    for line in body.splitlines():
        if not line or line.startswith("#"):
            continue
        name, value = line.rsplit(" ", 1)
        float(value)
        assert name.startswith("sheeprl_")
    with urllib.request.urlopen(f"http://{host}:{port}/healthz") as resp:
        health = json.loads(resp.read())
    assert health["status"] == "ok" and health["policy_steps"] == 128
    server.close()
    with pytest.raises(OSError):
        urllib.request.urlopen(f"http://{host}:{port}/healthz", timeout=0.5)


def test_render_prometheus_escapes_labels():
    text = render_prometheus({"info": {"run_id": 'a"b\\c'}})
    assert 'run_id="a\\"b\\\\c"' in text


def test_render_prometheus_one_type_line_per_label_family():
    """Multiple phase labels must share ONE `# TYPE` line — a duplicate TYPE
    line for the same metric name is a Prometheus parse error."""
    text = render_prometheus(
        {"phase_seconds_total": {"train": 1.0, "rollout": 2.0, "env_wait": 3.0}}
    )
    type_lines = [l for l in text.splitlines() if l.startswith("# TYPE sheeprl_phase_seconds_total")]
    assert len(type_lines) == 1
    assert 'sheeprl_phase_seconds_total{phase="rollout"} 2' in text
    assert 'sheeprl_phase_seconds_total{phase="env_wait"} 3' in text


# ---------------------------------------------------------------------------
# trace merge (decoupled player + trainer)


def test_trace_merge_produces_one_coherent_timeline(tmp_path):
    from trace_report import collect_trace_files, merge_traces, phase_table

    player = PhaseTracer(str(tmp_path / "player" / "trace.json"), pid=0, run_id="r/v0", role="player")
    trainer = PhaseTracer(str(tmp_path / "trainer" / "trace.json"), pid=1, run_id="r/v0", role="trainer")
    import time

    for i in range(3):  # strictly alternating on the wall clock
        with player.span("rollout", iter=i):
            time.sleep(0.002)
        with trainer.span("train", iter=i):
            time.sleep(0.002)
    player.close()
    trainer.close()

    files = collect_trace_files([str(tmp_path)])
    assert len(files) == 2
    merged, sources = merge_traces(files)
    assert {s["role"] for s in sources} == {"player", "trainer"}
    assert all(s["run_id"] == "r/v0" for s in sources)
    spans = [e for e in merged if e.get("ph") == "X"]
    order = [(e["name"], e["args"]["role"]) for e in sorted(spans, key=lambda e: e["ts"])]
    assert order == [("rollout", "player"), ("train", "trainer")] * 3
    rows = phase_table(merged)
    assert {(r["role"], r["phase"]) for r in rows} == {("player", "rollout"), ("trainer", "train")}
    assert all(r["count"] == 3 and r["total_ms"] > 0 for r in rows)


def test_trace_report_loads_crash_truncated_trace(tmp_path):
    """A SIGKILL can leave an unterminated array ending in a half-serialized
    event; load_trace must drop the partial tail, not crash."""
    from trace_report import load_trace

    path = tmp_path / "trace.json"
    tracer = PhaseTracer(str(path), pid=0, run_id="r/v0", role="main")
    with tracer.span("rollout"):
        pass
    tracer._fp.flush()  # no close(): unterminated array, then mangle the tail
    raw = path.read_text()
    path.write_text(raw + ',\n{"name":"tra')
    meta, events = load_trace(str(path))
    assert meta["run_id"] == "r/v0"
    assert any(e.get("name") == "rollout" for e in events)
    tracer.close()


def test_trace_rotation_keeps_files_loadable(tmp_path):
    path = tmp_path / "trace.json"
    tracer = PhaseTracer(str(path), pid=0, max_events=8, rotate_keep=2, run_id="r/v0", role="main")
    for i in range(30):
        with tracer.span("train", iter=i):
            pass
    tracer.close()
    assert path.exists() and (tmp_path / "trace.json.1").exists()
    from trace_report import collect_trace_files, load_trace

    total_spans = 0
    for p in [path, tmp_path / "trace.json.1", tmp_path / "trace.json.2"]:
        if not p.exists():
            continue
        meta, events = load_trace(str(p))  # every generation parses standalone
        assert meta["run_id"] == "r/v0"
        total_spans += sum(1 for e in events if e.get("ph") == "X")
    assert 0 < total_spans <= 30  # capped: old generations beyond keep are dropped
    # ts stays monotonic across generations -> they merge into one timeline
    from trace_report import merge_traces

    merged, _ = merge_traces(collect_trace_files([str(path)]))
    spans = [e for e in merged if e.get("ph") == "X"]
    iters = [e["args"]["iter"] for e in sorted(spans, key=lambda e: e["ts"])]
    assert iters == sorted(iters)


# ---------------------------------------------------------------------------
# end-to-end through the real CLI (ISSUE 3 acceptance)

PPO_TINY = [
    "exp=ppo",
    "env=dummy",
    "env.id=discrete_dummy",
    "env.num_envs=2",
    "env.capture_video=False",
    "buffer.memmap=False",
    "metric.log_level=1",
    "metric.log_every=1",
    "fabric.devices=1",
    "fabric.accelerator=cpu",
    "algo.rollout_steps=8",
    "algo.per_rank_batch_size=4",
    "algo.update_epochs=1",
    "algo.dense_units=8",
    "algo.mlp_layers=1",
    "algo.mlp_keys.encoder=[state]",
    "algo.cnn_keys.encoder=[]",
    "algo.run_test=False",
]


def test_cli_ppo_journals_telemetry_serves_metrics_and_catches_recompile(run_cli):
    """One tiny PPO run exercises the whole layer: Telemetry/* journal rows,
    live /metrics gauges, and a recompile event from the injected shape
    change."""
    run_cli(
        *PPO_TINY,
        "algo.total_steps=48",
        "checkpoint.save_last=False",
        "diagnostics.telemetry.mfu.peak_tflops_per_device=0.001",
        "diagnostics.telemetry.watchdog.inject_shape_change_iter=2",
        "diagnostics.telemetry.http.enabled=True",
    )
    (journal_path,) = sorted(Path("logs").rglob("journal.jsonl"))
    events = read_journal(str(journal_path))
    kinds = [e["event"] for e in events]
    assert kinds[0] == "run_start" and kinds[-1] == "run_end"

    # (1) telemetry rides the metric intervals into the journal
    metrics_rows = [e["metrics"] for e in events if e["event"] == "metrics"]
    assert len(metrics_rows) >= 2
    last = metrics_rows[-1]
    assert last["Telemetry/mfu"] > 0
    assert last["Telemetry/tflops_per_sec"] > 0
    assert last["Telemetry/sps"] > 0  # needs a previous interval as baseline
    phase_keys = [k for k in last if k.startswith("Telemetry/phase_pct/")]
    assert {"Telemetry/phase_pct/train", "Telemetry/phase_pct/unspanned"} <= set(phase_keys)
    shares = sum(last[k] for k in phase_keys)
    assert shares == pytest.approx(100.0, abs=1.0)

    # (2) the injected shape change produced a real recompile event with diff
    (fault,) = [e for e in events if e["event"] == "fault_injection"]
    assert fault["kind"] == "shape_change"
    recompiles = [e for e in events if e["event"] == "recompile"]
    assert len(recompiles) == 1 and recompiles[0]["fn"] == "train_step"
    assert any("17" in d for d in recompiles[0]["diff"])  # 16 rows + 1 pad
    assert last["Telemetry/recompiles"] == 1

    # (3) the endpoint served on the journaled ephemeral port while running
    (server_event,) = [e for e in events if e["event"] == "metrics_server"]
    assert server_event["status"] == "serving" and server_event["port"] > 0
    # ... and died with the run
    with pytest.raises(OSError):
        urllib.request.urlopen(
            f"http://{server_event['host']}:{server_event['port']}/healthz", timeout=0.5
        )

    # (4) cost capture happened at first compile (exact compiled-step FLOPs)
    costs = [e for e in events if e["event"] == "telemetry_cost"]
    assert costs and all(c["flops_per_call"] > 0 for c in costs)
    summary = next(e for e in events if e["event"] == "telemetry_summary")
    assert summary["train_flops_total"] > 0
    assert summary["instrumented_calls"]["train_step"] == 3  # one per iteration


def test_cli_run_monitor_and_follow_render_telemetry(run_cli):
    """The dashboard + --follow tail read a finished run's journal and show
    the telemetry columns (shared formatting)."""
    run_cli(*PPO_TINY, "dry_run=True", "checkpoint.save_last=False")
    import subprocess

    (journal_path,) = sorted(Path("logs").rglob("journal.jsonl"))
    monitor = subprocess.run(
        [sys.executable, str(REPO_ROOT / "tools" / "run_monitor.py"), str(journal_path)],
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert monitor.returncode == 0, monitor.stderr[-2000:]
    assert "ppo on discrete_dummy" in monitor.stdout
    assert "ended: completed" in monitor.stdout
    follow = subprocess.run(
        [
            sys.executable,
            str(REPO_ROOT / "tools" / "journal_report.py"),
            str(journal_path),
            "--follow",
            "--interval",
            "0.1",
        ],
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert follow.returncode == 0, follow.stderr[-2000:]
    lines = follow.stdout.splitlines()
    assert any(line.lstrip().startswith("[") and "run_start" in line for line in lines)
    assert any("metrics" in line and "step" in line for line in lines)
    assert any("run_end" in line for line in lines)  # tail exits at run_end
