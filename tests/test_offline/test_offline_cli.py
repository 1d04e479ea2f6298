"""The offline training mode through the real CLI: config validation, the
env-construction guard, resume-into-offline overrides, and the
acceptance drill — tiny SAC collect → export → (planted corrupt shard) →
env-free offline train → verified final checkpoint with finite losses
(howto/offline_rl.md)."""

from __future__ import annotations

import glob
import os
from pathlib import Path

import numpy as np
import pytest
import yaml

from sheeprl_tpu.cli import check_configs, resume_from_checkpoint
from sheeprl_tpu.config import compose
from sheeprl_tpu.utils.utils import dotdict

SAC_TINY = [
    "exp=sac",
    "env=dummy",
    "env.id=continuous_dummy",
    "env.num_envs=2",
    "env.capture_video=False",
    "buffer.memmap=False",
    "buffer.size=64",
    "metric.log_level=1",
    "metric.log_every=1",
    "fabric.devices=1",
    "fabric.accelerator=cpu",
    "algo.per_rank_batch_size=4",
    "algo.mlp_keys.encoder=[state]",
    "algo.run_test=False",
]


def _compose(*extra):
    return compose([*SAC_TINY, "algo.total_steps=8", *extra])


def test_check_configs_validates_offline_knobs():
    check_configs(_compose("algo.offline.enabled=true", "algo.offline.dataset_dir=/tmp/ds"))
    with pytest.raises(ValueError, match="dataset_dir"):
        check_configs(_compose("algo.offline.enabled=true"))
    with pytest.raises(ValueError, match="cql_alpha"):
        check_configs(
            _compose(
                "algo.offline.enabled=true", "algo.offline.dataset_dir=/tmp/ds", "algo.offline.cql_alpha=-1"
            )
        )
    with pytest.raises(ValueError, match="grad_steps_per_iter"):
        check_configs(
            _compose(
                "algo.offline.enabled=true",
                "algo.offline.dataset_dir=/tmp/ds",
                "algo.offline.grad_steps_per_iter=0",
            )
        )
    with pytest.raises(ValueError, match="supports"):
        cfg = compose(
            [
                "exp=ppo",
                "env=dummy",
                "env.id=discrete_dummy",
                "algo.offline.enabled=true",
                "algo.offline.dataset_dir=/tmp/ds",
            ]
        )
        check_configs(cfg)
    with pytest.warns(UserWarning, match="cql_alpha"):
        check_configs(_compose("algo.offline.cql_alpha=0.5"))


def test_offline_mode_refuses_env_construction():
    from sheeprl_tpu.envs.env import pipelined_vector_env

    cfg = dotdict({"algo": {"offline": {"enabled": True}}, "env": {}})
    with pytest.raises(RuntimeError, match="env-free"):
        pipelined_vector_env(cfg, [])


def test_resume_allows_offline_overrides(tmp_path, monkeypatch):
    """The resume allowed-override set gains ``algo.offline``: a collected
    run resumes straight into offline fine-tuning; env.id/algo.name stay
    pinned."""
    from sheeprl_tpu.resilience.manifest import save_verified_checkpoint

    version = tmp_path / "run" / "version_0"
    (version / "checkpoint").mkdir(parents=True)
    archived = _compose().as_dict()
    with open(version / "config.yaml", "w") as fp:
        yaml.safe_dump(archived, fp)
    ckpt = version / "checkpoint" / "ckpt_8_0.ckpt"
    save_verified_checkpoint(str(ckpt), {"agent": {}, "policy_step": 8})

    overrides = [
        *SAC_TINY,
        "algo.total_steps=4",
        f"checkpoint.resume_from={ckpt}",
        "algo.offline.enabled=true",
        "algo.offline.dataset_dir=/data/sets/x",
        "algo.offline.cql_alpha=0.25",
    ]
    merged = resume_from_checkpoint(compose(overrides), overrides)
    assert merged.algo.offline.enabled is True
    assert merged.algo.offline.dataset_dir == "/data/sets/x"
    assert merged.algo.offline.cql_alpha == 0.25
    # untouched offline knobs keep their archived defaults, identity pinned
    assert merged.algo.offline.grad_steps_per_iter == 16
    assert merged.algo.name == "sac" and merged.env.id == "continuous_dummy"
    # ... while other algo.* keys stay archived even if re-typed
    overrides2 = [*SAC_TINY, "algo.total_steps=4", f"checkpoint.resume_from={ckpt}", "algo.gamma=0.5"]
    merged2 = resume_from_checkpoint(compose(overrides2), overrides2)
    assert merged2.algo.gamma == archived["algo"]["gamma"]


def test_sac_offline_acceptance_drill(run_cli, tmp_path):
    """The end-to-end offline drill through the real CLI: collect a tiny SAC
    run, export it, plant a corrupt shard, then train env-free — asserting
    no env processes (the pipelined_vector_env guard would raise), exactly
    one journaled ``dataset_shard_skipped``, finite losses, a live
    ``Telemetry/dataset_read_sps`` gauge and a manifest-verified final
    checkpoint."""
    from sheeprl_tpu.data.datasets import OfflineDataset
    from sheeprl_tpu.diagnostics.journal import find_journal, read_journal
    from sheeprl_tpu.offline.export import export_run_dir
    from sheeprl_tpu.resilience.manifest import newest_verified_checkpoint, verify_checkpoint

    # 1. collect: prefill-only actions (the dummy env's ±inf bounds make the
    #    tanh actor's rescale non-finite, a pre-existing env quirk)
    run_cli(
        *SAC_TINY,
        "algo.total_steps=16",
        "algo.learning_starts=100",
        "buffer.checkpoint=True",
        "checkpoint.save_last=True",
        "run_name=collect",
    )
    collect_dir = Path("logs/runs/sac/continuous_dummy/collect")
    assert collect_dir.is_dir()

    # 2. export with small shards so a planted corruption costs one shard,
    #    not a whole stream
    out = export_run_dir(str(collect_dir), shard_rows=4)
    assert out["rows"] == 16 and out["shards"] == 4
    shard = sorted(glob.glob(os.path.join(out["path"], "shard-*.npz")))[0]
    with open(shard, "r+b") as fp:
        fp.seek(12)
        fp.write(b"\xde\xad\xbe\xef")

    # 3. offline train on the fixed dataset (conservative penalty armed)
    run_cli(
        *SAC_TINY,
        "algo.total_steps=8",
        "checkpoint.save_last=True",
        "run_name=offline",
        "algo.offline.enabled=true",
        f"algo.offline.dataset_dir={out['path']}",
        # 2 grad steps x batch 4 = 8 rows per draw — fits the 12 usable
        # transitions the corruption left
        "algo.offline.grad_steps_per_iter=2",
        "algo.offline.cql_alpha=0.5",
    )
    offline_dir = "logs/runs/sac/continuous_dummy/offline"
    events = read_journal(find_journal(offline_dir))
    kinds = [e["event"] for e in events]
    assert kinds.count("dataset_shard_skipped") == 1
    skipped = next(e for e in events if e["event"] == "dataset_shard_skipped")
    assert skipped["reason"] == "digest_mismatch" and os.path.basename(shard) in skipped["path"]
    opened = next(e for e in events if e["event"] == "dataset_open")
    assert opened["rows"] == 12 and opened["skipped"] == 1
    assert kinds[-1] == "run_end" and events[-1]["status"] == "completed"

    metrics_events = [e for e in events if e["event"] == "metrics"]
    assert metrics_events, "offline run journaled no metric intervals"
    last = metrics_events[-1]["metrics"]
    for key in ("Loss/value_loss", "Loss/policy_loss", "Loss/alpha_loss"):
        assert isinstance(last.get(key), (int, float)) and np.isfinite(last[key]), key
    assert any(
        isinstance((e["metrics"] or {}).get("Telemetry/dataset_read_sps"), (int, float))
        for e in metrics_events
    ), "Telemetry/dataset_read_sps gauge never went live"
    # no env ever ran: zero env-throughput gauges, and the env-free guard
    # would have raised had any loop tried to construct one
    assert not any("Telemetry/env_steps_per_sec" in (e["metrics"] or {}) for e in metrics_events)

    best, rejected = newest_verified_checkpoint(offline_dir, deep=True)
    assert best is not None and not rejected
    assert verify_checkpoint(best, deep=True) == (True, "verified")

    # 4. the loader still streams deterministically around the hole
    ds = OfflineDataset(out["path"])
    assert ds.total_rows == 12 and len(ds.skipped) == 1

    # 5. resume-into-offline: the COLLECT run's checkpoint (online counters)
    #    fine-tunes on the dataset with a fresh offline gradient budget —
    #    the advertised `checkpoint.resume_from + algo.offline.*` path
    collect_ckpt, _ = newest_verified_checkpoint(str(collect_dir), deep=True)
    run_cli(
        *SAC_TINY,
        "algo.total_steps=4",
        "checkpoint.save_last=True",
        "run_name=finetune",
        f"checkpoint.resume_from={collect_ckpt}",
        "algo.offline.enabled=true",
        f"algo.offline.dataset_dir={out['path']}",
        "algo.offline.grad_steps_per_iter=2",
    )
    ft_events = read_journal(find_journal("logs/runs/sac/continuous_dummy/finetune"))
    ft_metrics = [e for e in ft_events if e["event"] == "metrics"]
    assert ft_metrics, "resumed offline fine-tune performed no training"
    assert np.isfinite(ft_metrics[-1]["metrics"]["Loss/value_loss"])
    assert ft_events[-1]["event"] == "run_end" and ft_events[-1]["status"] == "completed"
    best_ft, _ = newest_verified_checkpoint("logs/runs/sac/continuous_dummy/finetune", deep=True)
    assert best_ft is not None and "finetune" in best_ft
