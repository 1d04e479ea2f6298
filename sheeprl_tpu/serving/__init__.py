"""Policy-as-a-service: a batched, hot-reloading, stateful multi-model
inference tier for checkpointed agents (ROADMAP item 3).

Training produces checkpoints; this package serves them.  The architecture is
SEED-RL-style centralized batched inference (Espeholt et al., 2020) adapted to
a single-process XLA server on the repo's own building blocks:

* :mod:`~sheeprl_tpu.serving.loader` — checkpoint discovery + per-algo policy
  adapters: stateless (``ppo`` / ``a2c`` / ``sac``) and stateful
  (``ppo_recurrent`` LSTM carries, ``dreamer_v3`` RSSM state, served through
  the session layer) built on ``utils/checkpoint.py`` and the existing agent
  builders, plus the health gate that reads the *training* run's journal
  (``active_anomalies``) before a checkpoint may be promoted;
* :mod:`~sheeprl_tpu.serving.batcher` — the dynamic request batcher: requests
  queue for up to ``serving.max_delay_ms``, are padded to the nearest
  MXU-friendly bucket width (``serving.batch_buckets``, defaults derived from
  the PERF.md §5 batch-width table) and dispatched as ONE device step; padded
  rows never leak into responses; beyond ``serving.max_queue`` load is shed
  with 503 + ``Retry-After``;
* :mod:`~sheeprl_tpu.serving.sessions` — device-resident recurrent state for
  stateful policies: a fixed-capacity state slab gathered/scattered inside
  the compiled step, keyed by client session id, LRU-evicted (journaled
  ``session_evict``) when full;
* :mod:`~sheeprl_tpu.serving.registry` — N resident models on one server:
  per-model services/watchers/request logs, ``/act`` routing on the request's
  ``model`` field, per-model ``{model="..."}`` metric series;
* :mod:`~sheeprl_tpu.serving.request_log` — dispatched ``/act`` traffic
  appended to per-model offline dataset shards (``data/datasets.py`` format,
  journaled ``request_log_rotate``) that ``OfflineDataset`` opens directly;
* :mod:`~sheeprl_tpu.serving.server` — :class:`PolicyService` (AOT-compiled
  per-bucket policy steps, atomic params hot-swap under the dispatch lock,
  journaled ``ckpt_promote``/``ckpt_reject``), the stdlib HTTP tier
  (``POST /act`` + ``/metrics`` + ``/healthz``, same pattern as
  ``diagnostics/metrics_server.py``) and the checkpoint-directory watcher.

Entrypoints: ``python -m sheeprl_tpu serve checkpoint_path=...`` /
``tools/serve.py`` / the ``sheeprl-serve`` console script.  See
``howto/serving.md``.
"""

from __future__ import annotations

from sheeprl_tpu.serving.batcher import DynamicBatcher, ServeError, pick_bucket
from sheeprl_tpu.serving.loader import (
    PolicyHandle,
    agent_state_from_checkpoint,
    build_policy,
    checkpoint_health,
    checkpoint_step,
    latest_checkpoint,
    load_policy,
)
from sheeprl_tpu.serving.registry import ModelEntry, ModelRegistry, render_registry_metrics
from sheeprl_tpu.serving.request_log import RequestLog
from sheeprl_tpu.serving.server import PolicyService, ServeApp, serve_checkpoint
from sheeprl_tpu.serving.sessions import SessionStore, make_slab_step

__all__ = [
    "DynamicBatcher",
    "ModelEntry",
    "ModelRegistry",
    "PolicyHandle",
    "PolicyService",
    "RequestLog",
    "ServeApp",
    "ServeError",
    "SessionStore",
    "agent_state_from_checkpoint",
    "build_policy",
    "checkpoint_health",
    "checkpoint_step",
    "latest_checkpoint",
    "load_policy",
    "make_slab_step",
    "pick_bucket",
    "render_registry_metrics",
    "serve_checkpoint",
]
