#!/usr/bin/env python
"""One-chip smoke: DreamerV3-S trains on the TPU through the real CLI.

Drives ``sheeprl_tpu.cli.run`` in-process exactly as ``python sheeprl.py``
does, at the full Atari-100K width (``exp=dreamer_v3_100k_ms_pacman``: GRU
512, dense 512, conv multiplier 32, batch 16 x sequence 64, horizon 15, HBM
replay) in bf16-mixed on the seeded dummy pixel env (``ale_py`` is not
installed; same 3x64x64 uint8 ``rgb``).  Only the run length shrinks: the run
prefills, takes a few tens of gradient steps, writes one checkpoint and ends.
Then the run's own journal is read back and the smoke fails unless the run
ended ``completed`` on the TPU with gradient steps, finite losses, a verified
checkpoint and no ``telemetry_fallback``.

Refuses to start (exit 1, one line on stderr, nothing on stdout, nothing
built) unless ``jax.devices()[0].platform == "tpu"``; likewise when the
package is not beside it.  Once the run starts, the last two stdout lines are
JSON objects: ``{"report": {...}}`` with what was measured (versions, widths,
gradient steps, seconds to the first train step, cache directory, scalar-fetch
latency) and then, last, exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with ``ok`` false and exit 1 if the run or any journal check failed.  One
process, so one owner of the chip; the env workers it spawns never open a
backend.

    python chip_smoke.py
"""

from __future__ import annotations

import glob
import json
import math
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
# the run directory: <checkout>/logs/runs/chip_smoke/dv3_s/version_N (git-ignored)
ROOT_DIR, RUN_NAME = "chip_smoke", "dv3_s"

OVERRIDES = [
    "exp=dreamer_v3_100k_ms_pacman",
    "env=dummy",
    "env.id=discrete_dummy",
    "env.sync_env=False",  # the framework default: spawned env workers
    "fabric.accelerator=tpu",
    "fabric.precision=bf16-mixed",
    # run length only — every width stays as the experiment file sets it
    "algo.total_steps=176",
    "algo.learning_starts=128",
    "buffer.size=4096",
    "metric.log_every=16",
    f"root_dir={ROOT_DIR}",
    f"run_name={RUN_NAME}",
]


def _fetch_latency_ms(repeats: int = 200) -> float:
    """Median wall time of dispatching a trivial jitted op and blocking on its
    scalar value — the per-vector-step cost every hot loop pays once."""
    import jax
    import jax.numpy as jnp

    step = jax.jit(lambda x: x + 1.0)
    x = step(jnp.float32(0.0))
    float(x)
    samples = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        x = step(x)
        float(x)
        samples.append(time.perf_counter() - t0)
    return round(sorted(samples)[len(samples) // 2] * 1e3, 4)


class SmokeFailure(RuntimeError):
    """The run did not do what the smoke requires; the message says what."""


def _require(condition: bool, message: str) -> None:
    if not condition:
        raise SmokeFailure(message)


def _check_journal(log_dir: str) -> dict:
    """Verdict from the run's own journal; raises SmokeFailure on a miss."""
    with open(os.path.join(log_dir, "journal.jsonl")) as fh:
        events = [json.loads(line) for line in fh if line.strip()]
    by_kind: dict = {}
    for event in events:
        by_kind.setdefault(event["event"], []).append(event)

    start = by_kind["run_start"][0]
    _require(start.get("platform") == "tpu", f"run_start platform: {start.get('platform')!r}")
    end = by_kind.get("run_end", [{}])[-1]
    _require(end.get("status") == "completed", f"run ended {end.get('status')!r}")
    fallbacks = by_kind.get("telemetry_fallback", [])
    _require(not fallbacks, f"telemetry_fallback: {fallbacks}")

    summary = by_kind["telemetry_summary"][-1]
    grad_steps = int(summary["instrumented_calls"].get("train_step", 0))
    _require(grad_steps > 0, "no gradient step was taken")
    losses = [
        (event["step"], key, value)
        for event in by_kind.get("metrics", [])
        for key, value in event["metrics"].items()
        if key.startswith(("Loss/", "Grads/", "State/"))
    ]
    _require(bool(losses), "no loss was logged")
    bad = [row for row in losses if not math.isfinite(row[2])]
    _require(not bad, f"non-finite losses: {bad[:5]}")

    ckpt_ok = [e for e in by_kind.get("ckpt_end", []) if e.get("status") == "ok" and e.get("verified")]
    _require(
        bool(ckpt_ok) and os.path.isfile(ckpt_ok[-1]["path"]), "no verified checkpoint on disk"
    )

    memory = by_kind["memory_summary"][-1]
    _require(memory["donation_miss_leaves"] == 0, f"donation audit: {memory}")
    cost = by_kind["telemetry_cost"][0]

    import yaml

    with open(os.path.join(log_dir, "config.yaml")) as fh:
        cfg = yaml.safe_load(fh)
    algo, world_model = cfg["algo"], cfg["algo"]["world_model"]
    return {
        # the widths the run actually composed, from its archived config
        "model": {
            "algo": algo["name"],
            "recurrent_state_size": world_model["recurrent_model"]["recurrent_state_size"],
            "dense_units": algo["dense_units"],
            "cnn_channels_multiplier": world_model["encoder"]["cnn_channels_multiplier"],
            "batch_size": algo["per_rank_batch_size"],
            "sequence_length": algo["per_rank_sequence_length"],
            "horizon": algo["horizon"],
            "precision": cfg["fabric"]["precision"],
            "buffer_device": cfg["buffer"]["device"],
            "env": cfg["env"]["id"],
        },
        "gradient_steps": grad_steps,
        "first_train_step_t": cost["t"],
        "train_step_compile_s": cost["compile_s"],
        "checkpoint_bytes": ckpt_ok[-1]["bytes"],
        "hbm_source": memory["hbm_source"],
        "last_world_model_loss": [v for _, k, v in losses if k == "Loss/world_model_loss"][-1],
    }


def _run_and_check(run, t0: float) -> dict:
    """Train through the CLI, read the journal back, return the report."""
    import jax

    os.chdir(HERE)  # the CLI writes logs/runs/... under the cwd
    run(OVERRIDES)
    total_s = time.time() - t0

    versions = glob.glob(os.path.join(HERE, "logs", "runs", ROOT_DIR, RUN_NAME, "version_*"))
    log_dir = max(versions, key=lambda p: int(p.rsplit("_", 1)[1]))
    verdict = _check_journal(log_dir)

    from importlib import metadata

    import jaxlib

    try:
        libtpu_version = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu_version = None
    return {
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__, "libtpu": libtpu_version},
        "model": verdict["model"],
        "gradient_steps": verdict["gradient_steps"],
        "seconds_to_first_train_step": round(verdict["first_train_step_t"] - t0, 2),
        "train_step_compile_s": verdict["train_step_compile_s"],
        "seconds_total": round(total_s, 2),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "scalar_fetch_ms": _fetch_latency_ms(),
        "last_world_model_loss": verdict["last_world_model_loss"],
        "checkpoint_bytes": verdict["checkpoint_bytes"],
        "hbm_source": verdict["hbm_source"],
        "run_dir": os.path.relpath(log_dir, HERE),
    }


def main() -> int:
    t0 = time.time()  # wall clock: compared with the journal's event times
    import jax

    try:
        device = jax.devices()[0]
    except RuntimeError as err:
        print(f"chip_smoke: refusing to start: JAX found no device ({err})", file=sys.stderr)
        return 1
    if device.platform != "tpu":
        print(
            f"chip_smoke: refusing to start: jax.devices()[0].platform is {device.platform!r}, not 'tpu'",
            file=sys.stderr,
        )
        return 1

    from sheeprl_tpu.cli import run  # beside this script, so on sys.path[0]

    device_record = {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": len(jax.devices()),
    }
    ok = True
    try:
        report = _run_and_check(run, t0)
    except Exception:  # any failed phase is the verdict, not a crash
        traceback.print_exc()
        ok = False
    else:
        print(json.dumps({"report": report}), flush=True)
    sys.stderr.flush()
    # the contract's last stdout line: exactly these keys
    print(json.dumps({"ok": ok, "device": device_record}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
