"""CLI entrypoints: train / evaluate / register / list-agents.

TPU-native equivalent of /root/reference/sheeprl/cli.py:23-450.  The reference
wraps Hydra (`@hydra.main`) and Lightning Fabric (`fabric.launch` spawns one
process per device); here config composition is :func:`sheeprl_tpu.config.compose`
and there is nothing to spawn — JAX is single-controller, the `Runtime` mesh
already spans every local chip (ICI) and, under `jax.distributed`, every host.
"""

from __future__ import annotations

import importlib
import os
import pathlib
import sys
import time
import warnings
from typing import Any, Dict, List, Optional, Sequence

import yaml

from sheeprl_tpu.config import compose, instantiate
from sheeprl_tpu.utils.registry import algorithm_registry, evaluation_registry, find_algorithm, find_evaluation
from sheeprl_tpu.utils.utils import dotdict, nest_dotted, print_config


def resume_from_checkpoint(cfg: dotdict, overrides: Sequence[str] = ()) -> dotdict:
    """Merge the saved run config when resuming (reference cli.py:23-57).

    The checkpoint's archived ``config.yaml`` is the base; the user may only
    change a restricted set of keys (the reference warns and keeps the ckpt
    value for the rest).  ``overrides`` is the raw CLI override list: for the
    ``env`` / ``diagnostics`` groups only the keys the user *explicitly*
    passed are applied — replacing those whole blocks with the freshly
    composed ones would silently revert every archived setting the user did
    not re-type to its group default (and could change observation shapes
    under the checkpoint).

    ``checkpoint.resume_from`` may be a checkpoint file or any directory
    above one (run dir, ``version_N``, checkpoint dir): selection is "newest
    checkpoint whose manifest verifies" — corrupt/truncated/partial files are
    skipped with a journaled ``ckpt_skipped`` reason, never crashed on
    (howto/resilience.md).  The resolved file is protected from ``keep_last``
    pruning for the lifetime of the resumed run.
    """
    from sheeprl_tpu.resilience.manifest import resolve_resume_from
    from sheeprl_tpu.utils.checkpoint import protect_checkpoint

    resolved = resolve_resume_from(str(cfg.checkpoint.resume_from))
    protect_checkpoint(resolved)
    ckpt_path = pathlib.Path(resolved)
    old_cfg_path = ckpt_path.parent.parent / "config.yaml"
    if not old_cfg_path.is_file():
        raise FileNotFoundError(
            f"Cannot resume from '{ckpt_path}': archived config '{old_cfg_path}' not found"
        )
    with open(old_cfg_path) as fp:
        old_cfg = dotdict(yaml.safe_load(fp))
    if old_cfg.env.id != cfg.env.id:
        raise ValueError(
            f"This experiment is run with a different environment from the one of the experiment "
            f"you want to restart: got '{cfg.env.id}', expected '{old_cfg.env.id}'"
        )
    if old_cfg.algo.name != cfg.algo.name:
        raise ValueError(
            f"This experiment is run with a different algorithm from the one of the experiment "
            f"you want to restart: got '{cfg.algo.name}', expected '{old_cfg.algo.name}'"
        )
    # keys the user is allowed to override on resume
    allowed = {"checkpoint", "fabric", "metric", "run_name", "exp_name", "seed", "dry_run", "total_steps"}
    merged = dotdict(old_cfg)
    for key in allowed:
        if key in cfg:
            merged[key] = cfg[key]
    # `diagnostics` and `env` are also overridable — a resumed run must be
    # able to e.g. raise a stall threshold, point at a new compilation-cache
    # dir, or retune env host knobs (num_envs, capture_video, executor) —
    # and so is `algo.offline`, so a collected run can be resumed straight
    # into offline fine-tuning on its own exported dataset
    # (howto/offline_rl.md) — but ONLY the dotted keys the user explicitly
    # passed: the env/algo identity stays pinned by the env.id / algo.name
    # equality checks above, and everything the user did not mention keeps
    # its archived value
    from sheeprl_tpu.config import deep_merge, yaml_load

    explicit: Dict[str, Any] = {}
    for ov in overrides:
        key, _, value = ov.partition("=")
        key = key.lstrip("+~")
        offline_key = key == "algo.offline" or key.startswith("algo.offline.")
        if key.split(".", 1)[0] not in ("env", "diagnostics") and not offline_key:
            continue
        if "." in key and key != "algo.offline":
            explicit[key] = yaml_load(value) if value != "" else None
        else:
            # group swap (env=atari / algo.offline={...}): take the whole
            # freshly composed block
            explicit[key] = cfg.get(key) if "." not in key else yaml_load(value)
    if explicit:
        deep_merge(merged, dotdict(nest_dotted(explicit)))
    merged.checkpoint.resume_from = str(ckpt_path)
    merged.root_dir = old_cfg.root_dir
    return merged


def check_configs(cfg: dotdict) -> None:
    """Config validation (reference cli.py:271-345)."""
    import warnings

    algo_name = cfg.algo.name
    entry = find_algorithm(algo_name)
    if entry is None:
        registered = sorted({m["name"] for v in algorithm_registry.values() for m in v})
        raise ValueError(
            f"Algorithm '{algo_name}' is not registered. Available algorithms: {registered}"
        )
    if cfg.get("matmul_precision", "default") not in ("default", "high", "highest", "tensorfloat32", "bfloat16", "float32"):
        raise ValueError(
            f"Invalid 'matmul_precision' value {cfg.get('matmul_precision')!r}; "
            "must be one of: default, high, highest, tensorfloat32, bfloat16, float32"
        )
    devices = cfg.fabric.devices
    strategy = str(cfg.fabric.get("strategy", "auto")).lower()
    known_strategies = ("auto", "dp", "ddp", "single")
    if entry["decoupled"]:
        if strategy not in ("auto", "dp", "ddp"):
            raise ValueError(
                f"Decoupled algorithm '{algo_name}' needs a data-parallel mesh "
                f"(fabric.strategy=auto|dp), got {strategy!r}"
            )
        n = devices if isinstance(devices, int) else 0
        if isinstance(devices, str) and devices not in ("auto", "-1"):
            n = int(devices)
        if isinstance(n, int) and 0 < n < 2:
            raise RuntimeError(
                f"Decoupled algorithm '{algo_name}' needs at least 2 devices "
                f"(1 player + >=1 trainer), got fabric.devices={devices}"
            )
    elif strategy not in known_strategies:
        warnings.warn(
            f"Unknown fabric.strategy {strategy!r}; the mesh runtime treats it as 'auto' "
            f"(known: {known_strategies})",
            UserWarning,
        )
    if cfg.metric.log_level not in (0, 1):
        raise ValueError(f"metric.log_level must be 0 or 1, got {cfg.metric.log_level}")
    # telemetry knobs fail here, not hours into a run (the endpoint binds and
    # the watchdog arms only after the log dir exists)
    telemetry_cfg = (cfg.get("diagnostics") or {}).get("telemetry") or {}
    http_cfg = telemetry_cfg.get("http") or {}
    port = http_cfg.get("port", 0) or 0
    if not isinstance(port, int) or port < 0 or port > 65535:
        raise ValueError(
            f"diagnostics.telemetry.http.port must be an integer in [0, 65535] (0 = ephemeral), got {port!r}"
        )
    watchdog_cfg = telemetry_cfg.get("watchdog") or {}
    storm_threshold = watchdog_cfg.get("storm_threshold")
    if storm_threshold is not None and int(storm_threshold) < 1:
        raise ValueError(
            f"diagnostics.telemetry.watchdog.storm_threshold must be >= 1, got {storm_threshold!r}"
        )
    # goodput watchdog knobs: >0-or-null, here AND in the GoodputMonitor
    # constructor (direct entrypoint callers skip check_configs) —
    # Event.wait(<=0) degenerates into a busy-spin, so it must never arm
    goodput_cfg = (cfg.get("diagnostics") or {}).get("goodput") or {}
    goodput_wd_cfg = goodput_cfg.get("watchdog") or {}
    for knob in ("heartbeat_s", "stall_threshold_s"):
        value = goodput_wd_cfg.get(knob)
        if value is not None and float(value) <= 0:
            raise ValueError(
                f"diagnostics.goodput.watchdog.{knob} must be > 0 or null "
                f"(null disables the watchdog), got {value!r}"
            )
    profile_cfg = goodput_cfg.get("profile") or {}
    # validated only while the pillar can actually run: the remedy the error
    # suggests (profile.enabled=False) must itself pass validation, and the
    # enabled default must match the GoodputMonitor ctor's (opt-in: False)
    if goodput_cfg.get("enabled", True) and profile_cfg.get("enabled", False):
        max_ms = profile_cfg.get("max_ms")
        if max_ms is not None and float(max_ms) < 10:
            raise ValueError(
                f"diagnostics.goodput.profile.max_ms must be >= 10 (the capture floor), "
                f"got {max_ms!r}; set diagnostics.goodput.profile.enabled=False instead"
            )
    # resilience knobs: validated here AND in the ResilienceMonitor ctor
    # (direct entrypoint callers skip check_configs) — a zero snapshot-buffer
    # depth would deadlock the first async submit
    res_cfg = (cfg.get("diagnostics") or {}).get("resilience") or {}
    max_pending = res_cfg.get("max_pending_snapshots")
    if max_pending is not None and int(max_pending) < 1:
        raise ValueError(
            f"diagnostics.resilience.max_pending_snapshots must be >= 1, got {max_pending!r}"
        )
    inject_preempt = res_cfg.get("inject_preempt_iter")
    if inject_preempt is not None and int(inject_preempt) < 1:
        raise ValueError(
            f"diagnostics.resilience.inject_preempt_iter must be >= 1 (1 = first "
            f"iteration) or null, got {inject_preempt!r}"
        )
    # fault-isolation / chaos knobs: validated here AND in their monitor
    # ctors (direct entrypoint callers skip check_configs) so a bad budget or
    # schedule fails before the run dir exists
    iso_cfg = res_cfg.get("isolation") or {}
    max_staleness = iso_cfg.get("max_staleness")
    if max_staleness is not None and int(max_staleness) < 1:
        raise ValueError(
            f"diagnostics.resilience.isolation.max_staleness must be >= 1, got {max_staleness!r}"
        )
    retry_budget = iso_cfg.get("retry_budget")
    if retry_budget is not None and int(retry_budget) < 0:
        raise ValueError(
            f"diagnostics.resilience.isolation.retry_budget must be >= 0, got {retry_budget!r}"
        )
    refresh_every = iso_cfg.get("refresh_every")
    if refresh_every is not None and int(refresh_every) < 1:
        raise ValueError(
            f"diagnostics.resilience.isolation.refresh_every must be >= 1, got {refresh_every!r}"
        )
    chaos_cfg = res_cfg.get("chaos") or {}
    from sheeprl_tpu.resilience.chaos import parse_schedule

    parse_schedule(chaos_cfg.get("schedule"))  # raises ValueError on a bad entry
    slow_write_s = chaos_cfg.get("slow_write_s")
    if slow_write_s is not None and float(slow_write_s) <= 0:
        raise ValueError(
            f"diagnostics.resilience.chaos.slow_write_s must be > 0, got {slow_write_s!r}"
        )
    # learning-health knobs: validated here AND in the HealthMonitor ctor
    # (direct entrypoint callers skip check_configs) so a bad band/window
    # fails before the run dir exists
    health_cfg = (cfg.get("diagnostics") or {}).get("health") or {}
    confirm = health_cfg.get("confirm")
    if confirm is not None and int(confirm) < 1:
        raise ValueError(f"diagnostics.health.confirm must be >= 1, got {confirm!r}")
    health_det_cfg = health_cfg.get("detectors") or {}
    ratio_low = health_det_cfg.get("update_ratio_low")
    ratio_high = health_det_cfg.get("update_ratio_high")
    if ratio_low is not None and ratio_high is not None and float(ratio_low) >= float(ratio_high):
        raise ValueError(
            "diagnostics.health.detectors.update_ratio_low must be < update_ratio_high, "
            f"got {ratio_low!r} >= {ratio_high!r}"
        )
    plateau_window = health_det_cfg.get("plateau_window")
    if plateau_window is not None and int(plateau_window) < 2:
        raise ValueError(
            f"diagnostics.health.detectors.plateau_window must be >= 2, got {plateau_window!r}"
        )
    if (
        health_cfg.get("inject_entropy_collapse_iter") is not None
        and health_det_cfg.get("entropy_floor") is None
    ):
        raise ValueError(
            "diagnostics.health.inject_entropy_collapse_iter requires "
            "diagnostics.health.detectors.entropy_floor — a drill against a disarmed "
            "detector could never fire"
        )
    # chunked RSSM scan knobs (DV3-family): fail at compose time, not at the
    # first train-step trace hours into a run
    rssm_chunks = cfg.algo.get("rssm_chunks")
    if rssm_chunks is not None:
        rssm_chunks = int(rssm_chunks)
        if rssm_chunks < 1:
            raise ValueError(f"algo.rssm_chunks must be >= 1, got {rssm_chunks}")
        burn_in = int(cfg.algo.get("rssm_chunk_burn_in", 0) or 0)
        if burn_in < 0:
            raise ValueError(f"algo.rssm_chunk_burn_in must be >= 0, got {burn_in}")
        seq_len = cfg.algo.get("per_rank_sequence_length")
        if rssm_chunks > 1 and isinstance(seq_len, int):
            if seq_len % rssm_chunks != 0:
                raise ValueError(
                    f"algo.rssm_chunks ({rssm_chunks}) must divide "
                    f"algo.per_rank_sequence_length ({seq_len})"
                )
            if burn_in >= seq_len // rssm_chunks:
                raise ValueError(
                    f"algo.rssm_chunk_burn_in ({burn_in}) must be < the chunk length "
                    f"({seq_len // rssm_chunks} = per_rank_sequence_length / rssm_chunks)"
                )
    # the recurrent on-policy loop's backbone (howto/olmo_hybrid_policy.md, howto/sparse_moe_policy.md): what
    # cannot work is refused here, not at the first trace of a 7B layer
    backbone = str(cfg.algo.get("backbone", "lstm") or "lstm")
    if backbone not in ("lstm", "olmo_hybrid", "sparse_moe"):
        raise ValueError(f"algo.backbone must be 'lstm', 'olmo_hybrid' or 'sparse_moe', got {backbone!r}")
    if backbone != "lstm":
        if algo_name != "ppo_recurrent":
            raise ValueError(f"algo.backbone={backbone} is a backbone of ppo_recurrent, got algo.name={algo_name!r}")
        from sheeprl_tpu.algos.ppo_recurrent.agent import token_backbone
        from sheeprl_tpu.envs.token import longest_episode

        _, model_cfg = token_backbone(cfg)
        problems = model_cfg.problems()
        if problems:
            raise ValueError(f"algo.{backbone}: {problems[0]}")
        seq_len = int(cfg.algo.per_rank_sequence_length)
        # what a training sequence is worked in pieces of: the delta rule's chunks, the sparse attention's blocks of queries
        piece = "chunk_size" if backbone == "olmo_hybrid" else "query_block"
        if seq_len > getattr(model_cfg, piece) and seq_len % getattr(model_cfg, piece):
            raise ValueError(
                f"algo.{backbone}.{piece} ({getattr(model_cfg, piece)}) must divide "
                f"algo.per_rank_sequence_length ({seq_len})"
            )
        wrapper = cfg.env.get("wrapper") or {}
        if "episode_max" in wrapper:
            longest = longest_episode(
                wrapper["episode_max"], wrapper.get("first_episodes") or (), wrapper.get("stagger", 0) or 0, cfg.env.num_envs
            )
            if longest > model_cfg.cache_len:
                raise ValueError(
                    f"algo.{backbone}.cache_len ({model_cfg.cache_len}) is shorter than the env's longest "
                    f"episode ({longest} tokens): an attention layer keeps every key of the running episode"
                )
    # FSDP knobs (howto/sharding.md): fail at compose time — a bad axis size
    # would otherwise surface as an opaque mesh-reshape error inside Runtime
    fsdp_raw = cfg.fabric.get("fsdp", 1)
    fsdp = 1 if fsdp_raw is None else int(fsdp_raw)
    if fsdp < 1:
        raise ValueError(f"distribution.fsdp_axis_size must be >= 1, got {fsdp}")
    min_shard = cfg.fabric.get("fsdp_min_shard_bytes")
    if min_shard is not None and int(min_shard) < 0:
        raise ValueError(
            f"distribution.fsdp_min_shard_bytes must be >= 0, got {min_shard!r}"
        )
    if fsdp > 1:
        # literal set (mirrors the offline gate below): the global-view FSDP
        # step is wired through _dreamer_main only
        fsdp_supported = ("dreamer_v3", "dreamer_v3_jepa", "p2e_dv1", "p2e_dv2", "p2e_dv3")
        if algo_name not in fsdp_supported:
            raise ValueError(
                f"distribution.fsdp_axis_size > 1 supports the DV3 family "
                f"{list(fsdp_supported)}, got algo.name={algo_name!r}"
            )
        if (cfg.algo.get("offline") or {}).get("enabled"):
            raise ValueError(
                "distribution.fsdp_axis_size > 1 is not supported with "
                "algo.offline.enabled=true (the offline loop is single-device)"
            )
        n_dev = devices
        if isinstance(n_dev, str) and n_dev not in ("auto", "-1"):
            n_dev = int(n_dev)
        if isinstance(n_dev, int) and n_dev > 0 and n_dev % fsdp != 0:
            raise ValueError(
                f"distribution.fsdp_axis_size ({fsdp}) must divide "
                f"fabric.devices ({n_dev})"
            )
    # offline training mode (howto/offline_rl.md): fail at compose time, not
    # after the log dir exists — the mode swaps the whole entrypoint
    offline_cfg = cfg.algo.get("offline") or {}
    if offline_cfg.get("enabled"):
        # literal set (not an import) so config validation never pays the
        # offline subsystem's jax imports
        supported = ("sac", "droq", "dreamer_v3")
        if algo_name not in supported:
            raise ValueError(
                f"algo.offline.enabled=true supports {list(supported)}, got algo.name={algo_name!r}"
            )
        if not offline_cfg.get("dataset_dir"):
            raise ValueError(
                "algo.offline.enabled=true requires algo.offline.dataset_dir "
                "(an exported dataset — see sheeprl-export / howto/offline_rl.md)"
            )
        if float(offline_cfg.get("cql_alpha", 0.0) or 0.0) < 0:
            raise ValueError(
                f"algo.offline.cql_alpha must be >= 0, got {offline_cfg.get('cql_alpha')!r}"
            )
        cql_samples = offline_cfg.get("cql_samples")
        if cql_samples is not None and int(cql_samples) < 1:
            raise ValueError(f"algo.offline.cql_samples must be >= 1, got {cql_samples!r}")
        grad_steps = offline_cfg.get("grad_steps_per_iter")
        if grad_steps is not None and int(grad_steps) < 1:
            raise ValueError(
                f"algo.offline.grad_steps_per_iter must be >= 1, got {grad_steps!r}"
            )
        if int(offline_cfg.get("prefetch", 2) or 0) < 0:
            raise ValueError(
                f"algo.offline.prefetch must be >= 0 (0 disables the prefetch thread), "
                f"got {offline_cfg.get('prefetch')!r}"
            )
        seq = offline_cfg.get("sequence_length")
        if seq is not None and int(seq) < 1:
            raise ValueError(f"algo.offline.sequence_length must be >= 1 or null, got {seq!r}")
        if entry["decoupled"]:
            raise ValueError(
                "algo.offline.enabled=true drives the coupled train step; decoupled "
                f"algorithm '{algo_name}' has no offline mode"
            )
    elif float(offline_cfg.get("cql_alpha", 0.0) or 0.0) != 0.0:
        warnings.warn(
            "algo.offline.cql_alpha is set but algo.offline.enabled=false: the conservative "
            "penalty WILL apply to the online run's critic update too (it is a train-step "
            "knob); set it to 0 unless that is intended",
            UserWarning,
        )
    learning_starts = cfg.algo.get("learning_starts")
    if learning_starts is not None and learning_starts < 0:
        raise ValueError("The `algo.learning_starts` parameter must be greater or equal to zero")
    if cfg.env.get("action_repeat", 1) < 1:
        warnings.warn(
            f"env.action_repeat={cfg.env.action_repeat} is below the minimum of 1; clamping to 1",
            UserWarning,
        )
        cfg.env.action_repeat = 1
    if not cfg.model_manager.get("disabled", True):
        from sheeprl_tpu.utils.imports import _IS_MLFLOW_AVAILABLE

        if not _IS_MLFLOW_AVAILABLE:
            warnings.warn(
                "MLFlow is not installed; setting model_manager.disabled=True", UserWarning
            )
            cfg.model_manager.disabled = True


def check_configs_evaluation(cfg: dotdict) -> None:
    if cfg.checkpoint_path is None:
        raise ValueError("You must specify the evaluation checkpoint path: checkpoint_path=...")


def run_algorithm(cfg: dotdict):
    """Registry lookup → runtime instantiation → entrypoint launch
    (reference cli.py:60-199).  Returns whatever the entrypoint returns —
    training mains return the final test reward when ``algo.run_test`` is on,
    which the search harness uses as its objective."""
    entry = find_algorithm(cfg.algo.name)
    if entry is None:
        raise ValueError(f"Algorithm '{cfg.algo.name}' is not registered")
    if (cfg.algo.get("offline") or {}).get("enabled"):
        # env-free offline mode: same runtime/diagnostics scaffold, but the
        # dataset loader replaces the env/player entirely
        # (sheeprl_tpu/offline/train.py; pipelined_vector_env refuses to run)
        from sheeprl_tpu.offline.train import offline_main

        entrypoint = offline_main
    else:
        module = importlib.import_module(entry["module"])
        entrypoint = getattr(module, entry["entrypoint"])

    # Algo utils module exposes AGGREGATOR_KEYS / MODELS_TO_REGISTER
    # (reference cli.py:151-181): prune metric + model-manager config to what
    # the algorithm actually produces.
    utils_module_name = entry["module"].rsplit(".", 1)[0] + ".utils"
    try:
        algo_utils = importlib.import_module(utils_module_name)
    except ModuleNotFoundError:
        algo_utils = None
    if algo_utils is not None:
        keys = getattr(algo_utils, "AGGREGATOR_KEYS", None)
        metrics_cfg = cfg.metric.aggregator.get("metrics", {})
        if keys is not None and isinstance(metrics_cfg, dict):
            cfg.metric.aggregator.metrics = dotdict(
                {k: v for k, v in metrics_cfg.items() if k in keys}
            )
        models = getattr(algo_utils, "MODELS_TO_REGISTER", None)
        mm = cfg.model_manager.get("models", {})
        if models is not None and isinstance(mm, dict):
            cfg.model_manager.models = dotdict({k: v for k, v in mm.items() if k in models})

    runtime = instantiate(cfg.fabric)
    # Run-health facade (journal / sentinel / tracing): built here, attached
    # to the runtime, opened by the training loop once the run dir exists
    # (utils.get_diagnostics / utils.logger plumbing).
    from sheeprl_tpu.diagnostics import SentinelHalt, build_diagnostics

    diagnostics = runtime.diagnostics = build_diagnostics(cfg)
    status = "completed"
    try:
        profiler_cfg = cfg.metric.get("profiler", {})
        if profiler_cfg.get("enabled", False):
            # one trace around the whole run: compile + steps + host gaps all
            # land in the same Perfetto timeline (SURVEY §5 profiling upgrade)
            import jax

            trace_dir = profiler_cfg.get("trace_dir") or os.path.join("logs", "profiler_trace")
            os.makedirs(trace_dir, exist_ok=True)
            jax.profiler.start_trace(trace_dir)
            try:
                return runtime.launch(entrypoint, cfg)
            finally:
                jax.profiler.stop_trace()
        return runtime.launch(entrypoint, cfg)
    except SentinelHalt:
        status = "halted"
        raise
    except BaseException as err:
        from sheeprl_tpu.resilience.preemption import PreemptedExit

        # a graceful preemption already journaled `preempted` and closed the
        # facade with status="preempted" before raising; the close() in the
        # finally block is idempotent, so "aborted" never overwrites it
        status = "preempted" if isinstance(err, PreemptedExit) else "aborted"
        raise
    finally:
        # idempotent: a loop that finished cleanly already closed with
        # status="completed"; this covers exceptions (journal gets run_end)
        diagnostics.close(status)


def _force_cpu_platform_if_selected(cfg: dotdict) -> None:
    """``fabric.accelerator=cpu`` restricts JAX to the CPU platform BEFORE any
    backend initializes — the config-level ``JAX_PLATFORMS=cpu``.  Selecting
    cpu devices for the mesh is not enough on a TPU host: enumerating them
    initializes every platform (this process would hold the chip), and
    default-placed arrays (PRNG keys, freshly initialized params) would still
    land on the TPU.  Shared by run/evaluation/serve/registration; callers
    must invoke it before anything touches jax."""
    if cfg.fabric.get("accelerator") == "cpu":
        import jax

        jax.config.update("jax_platforms", "cpu")


def run(args: Optional[Sequence[str]] = None):
    """Train entrypoint (reference cli.py:358-366).  ``args`` defaults to
    ``sys.argv[1:]`` — Hydra-style ``group=option``/``a.b=v`` overrides."""
    overrides = list(args if args is not None else sys.argv[1:])
    cfg = compose(overrides)
    _force_cpu_platform_if_selected(cfg)
    n_threads = cfg.get("num_threads")
    if n_threads and int(n_threads) > 0:
        # host-side thread budget.  BLAS pools already initialized in this
        # process ignore these (sheeprl.py sets them pre-import for the CLI
        # path); they still cap async-env subprocesses, which inherit the env.
        for var in ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS", "MKL_NUM_THREADS"):
            os.environ.setdefault(var, str(int(n_threads)))
    if cfg.checkpoint.resume_from:
        cfg = resume_from_checkpoint(cfg, overrides)
    print_config(cfg)
    check_configs(cfg)
    _apply_global_flags(cfg)
    return run_algorithm(cfg)


def _apply_global_flags(cfg: dotdict) -> None:
    """Determinism/precision flags (reference cli.py:187-197 seeds torch and
    sets deterministic algorithms; here: matmul precision + PRNG seeding is
    done per-runtime in `seed_everything`)."""
    import jax

    precision = cfg.get("matmul_precision", "default")
    if precision and precision != "default":
        jax.config.update("jax_default_matmul_precision", precision)
    # persistent compilation cache: placed BEFORE the first compile, which is
    # why it lives here and not in the diagnostics facade (opened only once
    # the run dir exists).  The facade journals the directory in force as a
    # `compilation_cache` event at open.
    from sheeprl_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache((cfg.get("diagnostics") or {}).get("compilation_cache_dir"))


def eval_algorithm(cfg: dotdict) -> None:
    """Evaluation launch (reference cli.py:202-268)."""
    entry = find_evaluation(cfg.algo.name)
    if entry is None:
        registered = sorted({m["name"] for v in evaluation_registry.values() for m in v})
        raise ValueError(
            f"Evaluation for algorithm '{cfg.algo.name}' is not registered. Available: {registered}"
        )
    module = importlib.import_module(entry["module"])
    entrypoint = getattr(module, entry["entrypoint"])
    runtime = instantiate(cfg.fabric)
    state = runtime.load(cfg.checkpoint_path)
    runtime.launch(entrypoint, cfg, state)


def evaluation(args: Optional[Sequence[str]] = None) -> None:
    """Eval entrypoint ``sheeprl-eval`` (reference cli.py:369-405): loads the
    checkpoint's archived config, merges user overrides, forces one device."""
    overrides = list(args if args is not None else sys.argv[1:])
    flat: Dict[str, Any] = {}
    for ov in overrides:
        key, _, value = ov.partition("=")
        flat[key.lstrip("+")] = yaml.safe_load(value) if value != "" else None
    if "checkpoint_path" not in flat or flat["checkpoint_path"] is None:
        raise ValueError("You must specify the evaluation checkpoint path: checkpoint_path=...")
    ckpt_path = pathlib.Path(flat.pop("checkpoint_path"))
    cfg_path = ckpt_path.parent.parent / "config.yaml"
    if not cfg_path.is_file():
        raise FileNotFoundError(f"Archived run config not found at '{cfg_path}'")
    with open(cfg_path) as fp:
        cfg = dotdict(yaml.safe_load(fp))
    from sheeprl_tpu.config import deep_merge

    deep_merge(cfg, dotdict(nest_dotted(flat)))
    if not any(k == "run_name" for k in flat):
        cfg.run_name = f"{os.path.basename(str(ckpt_path.parent.parent))}_evaluation"
    user_logger_override = any(
        k == "metric.logger" or k.startswith("metric.logger.") for k in flat
    ) or (isinstance(flat.get("metric"), dict) and "logger" in flat["metric"])
    logger_cfg = cfg.metric.get("logger")
    if logger_cfg is not None and not user_logger_override:
        # the archived logger paths are fully resolved and point INSIDE the
        # training run; re-root them at the FINAL (post-override) evaluation
        # run dir so eval metrics don't append to the trained run's event
        # stream — unless the user pointed the logger somewhere explicitly
        if "root_dir" in logger_cfg:
            logger_cfg.root_dir = os.path.join("logs", "runs", str(cfg.root_dir))
        if "name" in logger_cfg:
            logger_cfg.name = cfg.run_name
        if "save_dir" in logger_cfg:
            logger_cfg.save_dir = os.path.join("logs", "runs", str(cfg.root_dir))
        # wandb/mlflow don't carry a `name` key in their archived configs, so
        # the branch above leaves their eval runs indistinguishable from the
        # training run; inject the backend's run-name kwarg so they show up
        # as `*_evaluation` like the tensorboard layout does
        target = str(logger_cfg.get("_target_", ""))
        if target.endswith("WandbLogger"):
            logger_cfg.name = cfg.run_name  # wandb.init(name=...)
        elif target.endswith("MLFlowLogger"):
            logger_cfg.run_name = cfg.run_name  # mlflow.start_run(run_name=...)
    cfg.checkpoint_path = str(ckpt_path)
    # honors the ARCHIVED config too; nothing has touched jax before this point
    _force_cpu_platform_if_selected(cfg)
    # force single-device, strategy-free evaluation (reference cli.py:388-401)
    cfg.fabric = dotdict(
        {
            "_target_": "sheeprl_tpu.parallel.runtime.Runtime",
            "devices": 1,
            "num_nodes": 1,
            "strategy": "auto",
            "accelerator": cfg.fabric.get("accelerator", "auto"),
            "precision": cfg.fabric.get("precision", "32-true"),
        }
    )
    cfg.env.num_envs = 1
    check_configs_evaluation(cfg)
    eval_algorithm(cfg)


def serve(args: Optional[Sequence[str]] = None) -> None:
    """Inference-tier entrypoint ``sheeprl-serve`` / ``python -m sheeprl_tpu
    serve`` (howto/serving.md): load a checkpoint with its archived run
    config, start the batched policy server and the health-gated hot-reload
    watcher.

    Overrides follow the eval/registration pattern: ``checkpoint_path=...``
    is required, everything else (``serving.port=8080``,
    ``serving.reload.enabled=False``, ``fabric.accelerator=cpu``, ...) is a
    dotted override on top of the archived config.
    """
    overrides = list(args if args is not None else sys.argv[1:])
    flat: Dict[str, Any] = {}
    for ov in overrides:
        key, _, value = ov.partition("=")
        flat[key.lstrip("+")] = yaml.safe_load(value) if value != "" else None
    ckpt = flat.pop("checkpoint_path", None)
    if ckpt is None:
        raise ValueError("You must specify the checkpoint path: checkpoint_path=...")
    ckpt_path = pathlib.Path(ckpt)
    cfg_path = ckpt_path.parent.parent / "config.yaml"
    if not cfg_path.is_file():
        raise FileNotFoundError(f"Archived run config not found at '{cfg_path}'")
    with open(cfg_path) as fp:
        cfg = dotdict(yaml.safe_load(fp))
    from sheeprl_tpu.config import compose_group, deep_merge

    deep_merge(cfg, dotdict(nest_dotted(flat)))
    # checkpoints archived before the serving group existed (or with a
    # partial block): the group defaults underpin whatever the archive /
    # overrides carry, so every knob has a value
    serving = compose_group("serving", "default")
    deep_merge(serving, cfg.get("serving") or {})
    cfg.serving = serving
    # honors the archived config too; nothing has touched jax before this point
    _force_cpu_platform_if_selected(cfg)
    from sheeprl_tpu.serving.server import serve_checkpoint
    from sheeprl_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache((cfg.get("diagnostics") or {}).get("compilation_cache_dir"))
    serve_checkpoint(cfg, str(ckpt_path))


def registration(args: Optional[Sequence[str]] = None) -> None:
    """Model-registry entrypoint ``sheeprl-registration``
    (reference cli.py:408-450): publish checkpointed models to MLflow."""
    overrides = list(args if args is not None else sys.argv[1:])
    flat: Dict[str, Any] = {}
    for ov in overrides:
        key, _, value = ov.partition("=")
        flat[key.lstrip("+")] = yaml.safe_load(value) if value != "" else None
    ckpt = flat.pop("checkpoint_path", None)
    if ckpt is None:
        raise ValueError("You must specify the checkpoint path: checkpoint_path=...")
    ckpt_path = pathlib.Path(ckpt)
    cfg_path = ckpt_path.parent.parent / "config.yaml"
    with open(cfg_path) as fp:
        cfg = dotdict(yaml.safe_load(fp))
    from sheeprl_tpu.config import deep_merge

    deep_merge(cfg, dotdict(nest_dotted(flat)))
    cfg.checkpoint_path = str(ckpt_path)
    # honors the archived config too; nothing has touched jax before this point
    _force_cpu_platform_if_selected(cfg)
    from sheeprl_tpu.utils.mlflow import register_model_from_checkpoint

    register_model_from_checkpoint(cfg)
