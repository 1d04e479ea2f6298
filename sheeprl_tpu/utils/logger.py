"""Run loggers + versioned log dirs.

Reference: /root/reference/sheeprl/utils/logger.py:12-114 (rank-0 logger
creation, versioned run dir ``logs/runs/{root_dir}/{run_name}/version_N`` and
the log-dir broadcast).  Single-controller JAX: the "broadcast" is a
`Runtime.broadcast` (no-op on one host).  TensorBoard is the default backend
(torch's SummaryWriter, CPU); W&B / MLflow are optional and gated.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

from sheeprl_tpu.utils.imports import _IS_MLFLOW_AVAILABLE, _IS_WANDB_AVAILABLE


class NoOpLogger:
    log_dir: Optional[str] = None
    name = "noop"

    def log_metrics(self, metrics: Dict[str, Any], step: Optional[int] = None) -> None:
        pass

    def log_hyperparams(self, params: Dict[str, Any]) -> None:
        pass

    def finalize(self, status: str = "success") -> None:
        pass


class TensorBoardLogger(NoOpLogger):
    name = "tensorboard"

    def __init__(self, root_dir: str, name: str = "", version: Optional[str] = None, **_: Any):
        sub = os.path.join(root_dir, name) if name else root_dir
        self.log_dir = os.path.join(sub, version) if version else sub
        os.makedirs(self.log_dir, exist_ok=True)
        from torch.utils.tensorboard import SummaryWriter

        self._writer = SummaryWriter(log_dir=self.log_dir)

    def log_metrics(self, metrics: Dict[str, Any], step: Optional[int] = None) -> None:
        for key, value in metrics.items():
            try:
                self._writer.add_scalar(key, float(value), global_step=step)
            except (TypeError, ValueError):
                pass

    def log_hyperparams(self, params: Dict[str, Any]) -> None:
        import yaml

        try:
            self._writer.add_text("hparams", "```yaml\n" + yaml.safe_dump(_plain(params)) + "\n```")
        except Exception:
            pass

    def finalize(self, status: str = "success") -> None:
        self._writer.flush()
        self._writer.close()


class WandbLogger(NoOpLogger):  # stub-tested: tests/test_utils/test_logger_stubs.py
    name = "wandb"

    def __init__(self, project: str = "sheeprl_tpu", save_dir: str = ".", **kwargs: Any):
        if not _IS_WANDB_AVAILABLE:
            raise ModuleNotFoundError("wandb is not installed; use the tensorboard logger")
        import wandb

        self._run = wandb.init(project=project, dir=save_dir, **kwargs)
        self.log_dir = save_dir

    def log_metrics(self, metrics: Dict[str, Any], step: Optional[int] = None) -> None:
        self._run.log(metrics, step=step)

    def log_hyperparams(self, params: Dict[str, Any]) -> None:
        self._run.config.update(_plain(params), allow_val_change=True)

    def finalize(self, status: str = "success") -> None:
        self._run.finish()


class MLFlowLogger(NoOpLogger):  # stub-tested: tests/test_utils/test_logger_stubs.py
    name = "mlflow"

    def __init__(self, experiment_name: str = "sheeprl_tpu", tracking_uri: Optional[str] = None, **kwargs: Any):
        if not _IS_MLFLOW_AVAILABLE:
            raise ModuleNotFoundError("mlflow is not installed; use the tensorboard logger")
        import mlflow

        mlflow.set_tracking_uri(tracking_uri or os.environ.get("MLFLOW_TRACKING_URI"))
        mlflow.set_experiment(experiment_name)
        self._run = mlflow.start_run(**kwargs)

    def log_metrics(self, metrics: Dict[str, Any], step: Optional[int] = None) -> None:
        import mlflow

        mlflow.log_metrics({k: float(v) for k, v in metrics.items()}, step=step)

    def log_hyperparams(self, params: Dict[str, Any]) -> None:
        import mlflow

        flat = {}

        def walk(node, prefix=""):
            for k, v in node.items():
                if isinstance(v, dict):
                    walk(v, f"{prefix}{k}.")
                else:
                    flat[f"{prefix}{k}"] = v

        walk(_plain(params))
        mlflow.log_params(flat)

    def finalize(self, status: str = "success") -> None:
        import mlflow

        mlflow.end_run()


def _plain(params: Any) -> Any:
    if hasattr(params, "as_dict"):
        return params.as_dict()
    return params


def get_log_dir(runtime, root_dir: str, run_name: str, share: bool = True) -> str:
    """Versioned run dir creation + cross-host share
    (reference utils/logger.py:66-114)."""
    base = os.path.join("logs", "runs", root_dir, run_name)
    log_dir: Optional[str] = None
    if runtime.is_global_zero:
        os.makedirs(base, exist_ok=True)
        versions = [
            int(d.split("_")[1]) for d in os.listdir(base) if d.startswith("version_") and d.split("_")[1].isdigit()
        ]
        version = max(versions) + 1 if versions else 0
        log_dir = os.path.join(base, f"version_{version}")
        os.makedirs(log_dir, exist_ok=True)
    if share:
        log_dir = runtime.broadcast(log_dir)
    # Run-health plumbing: every training loop resolves its run dir here, so
    # opening the diagnostics journal here (idempotent, rank-0 gated) is what
    # gives ALL algorithms — not just the loops with explicit hooks — a
    # crash-safe journal under the CLI, which attaches the facade pre-launch.
    diagnostics = getattr(runtime, "diagnostics", None)
    if diagnostics is not None:
        diagnostics.open(log_dir, rank_zero=runtime.is_global_zero, device=runtime.device_info)
    return log_dir


class JournalingLogger(NoOpLogger):
    """Transparent proxy that mirrors every ``log_metrics`` call into the
    run-health journal (``sheeprl_tpu/diagnostics``).

    This is the plumbing that gives *every* algorithm — not just the flagship
    loops with explicit diagnostics hooks — a crash-safe record of each
    aggregated metric interval: the journal captures exactly what the
    TensorBoard/W&B backend received, at the moment it received it.  The
    diagnostics facade is looked up lazily on the runtime because loggers are
    created before the run dir (and hence the journal) exists; it no-ops
    until the facade is opened, and only rank 0 ever holds an open journal.
    """

    def __init__(self, inner: NoOpLogger, runtime):
        self._inner = inner
        self._runtime = runtime

    def __getattr__(self, name):
        return getattr(self._inner, name)

    @property
    def log_dir(self):
        return self._inner.log_dir

    @property
    def name(self):
        return self._inner.name

    def log_metrics(self, metrics: Dict[str, Any], step: Optional[int] = None) -> None:
        diagnostics = getattr(self._runtime, "diagnostics", None)
        if diagnostics is not None:
            # close the telemetry accounting interval and merge its live
            # Telemetry/* gauges (MFU, tflops/s, sps, phase breakdown) so the
            # TensorBoard/W&B backend AND the journal both receive them —
            # every algorithm inherits perf telemetry through this one proxy
            metrics = diagnostics.augment_metrics(step, metrics)
        self._inner.log_metrics(metrics, step)
        if diagnostics is not None:
            diagnostics.log_metrics(step, metrics)

    def log_hyperparams(self, params: Dict[str, Any]) -> None:
        self._inner.log_hyperparams(params)

    def finalize(self, status: str = "success") -> None:
        self._inner.finalize(status)


def get_logger(runtime, cfg) -> NoOpLogger:
    """Rank-0 logger instantiation from config (reference utils/logger.py:12-63)."""
    from sheeprl_tpu.config import instantiate

    if not runtime.is_global_zero or cfg.metric.get("log_level", 1) == 0 or cfg.metric.get("logger") is None:
        return NoOpLogger()
    logger_cfg = dict(cfg.metric.logger)
    return JournalingLogger(instantiate(logger_cfg), runtime)
