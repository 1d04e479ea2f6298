"""Benchmark harness: prints ONE JSON line
``{"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}``.

Flagship: DreamerV3-S on 64x64x3 pixels, batch 16 x sequence 64 — the
Atari-100K training configuration (reference
configs/exp/dreamer_v3_100k_ms_pacman.yaml; BASELINE.md §C names
end-to-end steps/sec/chip as the DreamerV3 north-star metric).

Three honest measurements (VERDICT r1 item 3):

1. **compute grad-steps/s** — per-step wall time with a per-step
   ``block_until_ready`` (no async-dispatch pipelining flattery), median of
   ``MEASURE_STEPS``.
2. **MFU** — XLA ``cost_analysis()`` FLOPs of the compiled train step vs the
   chip's peak for the precision in use.
3. **end-to-end grad-steps/s** — the real loop: player inference + env step +
   replay add/sample + host->device staging + train step, replay_ratio 1 on a
   dummy pixel env.  This is like-for-like with the reference baseline.

Baseline: the reference trains Atari-100K (MsPacman, DV3-S, replay_ratio 1,
action_repeat 4 -> 25_000 gradient steps == policy steps) in 14 h on one
RTX-3080 *end-to-end* (reference README.md:46-53) -> 25_000 / 50_400 s
= 0.496 grad-steps/s.  ``vs_baseline`` compares our END-TO-END number
against it; the compute-only number is reported separately.

Precision defaults to bf16-mixed (TPU-native); override with
``BENCH_PRECISION=32-true|bf16-mixed|bf16-true``.
"""

from __future__ import annotations

import json
import os
import time

BASELINE_E2E_GRAD_STEPS_PER_SEC = 25_000 / (14 * 3600)
WARMUP_STEPS = 3
# large enough that the single value-fetch barrier at the end of the chain
# amortizes to noise (see measure_compute's timing discipline note)
MEASURE_STEPS = 150
E2E_WARMUP_ITERS = 8
E2E_MEASURE_ITERS = 200

# Peak dense-matmul FLOP/s per chip, keyed by the ``device_kind`` JAX reports.
# Source: Google Cloud TPU documentation (v5e: 197 TFLOP/s bf16; v4: 275; v5p:
# 459); fp32 runs at half rate through the same systolic array.  A kind that
# is not in the table is an error, never a default.
_PEAKS = {
    "TPU v5 lite": {"bf16": 197e12, "f32": 98.5e12},
    "TPU v5e": {"bf16": 197e12, "f32": 98.5e12},
    "TPU v4": {"bf16": 275e12, "f32": 137.5e12},
    "TPU v5p": {"bf16": 459e12, "f32": 229.5e12},
}


def _chip_peak(device_kind: str, precision: str) -> float:
    """Peak FLOP/s of ``device_kind`` at ``precision``; raises on a kind the
    table does not hold."""
    if device_kind not in _PEAKS:
        raise ValueError(
            f"no peak FLOP/s on record for device_kind {device_kind!r} (known: {sorted(_PEAKS)}); "
            "add it to bench._PEAKS with its source"
        )
    return _PEAKS[device_kind]["bf16" if "16" in precision else "f32"]


def _build(cfg_overrides, actions_dim=(6,), mesh=None):
    import gymnasium as gym
    import numpy as np
    import optax

    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import make_train_step
    from sheeprl_tpu.algos.dreamer_v3.utils import init_moments_state
    from sheeprl_tpu.config import compose, instantiate
    from sheeprl_tpu.parallel.precision import cast_floating, resolve_precision

    cfg = compose(cfg_overrides)
    obs_space = gym.spaces.Dict(
        {
            "rgb": gym.spaces.Box(0, 255, (3, 64, 64), np.uint8),
            "state": gym.spaces.Box(-np.inf, np.inf, (10,), np.float32),
        }
    )
    world_model_def, actor_def, critic_def, params = build_agent(
        None, actions_dim, False, cfg, obs_space
    )
    params = cast_floating(params, resolve_precision(cfg.fabric.precision)[0])
    optimizers = {
        k: optax.chain(
            optax.clip_by_global_norm(getattr(cfg.algo, k).clip_gradients),
            instantiate(getattr(cfg.algo, k).optimizer),
        )
        for k in ("world_model", "actor", "critic")
    }
    opt_states = {k: optimizers[k].init(params[k]) for k in optimizers}
    moments_state = init_moments_state()
    train_step = make_train_step(
        world_model_def, actor_def, critic_def, optimizers, cfg, actions_dim, False, mesh=mesh
    )
    return cfg, world_model_def, actor_def, critic_def, params, opt_states, moments_state, train_step


def build_train_step_and_batch(
    precision: str,
    size: str = "S",
    batch_size: int = 16,
    sequence_length: int = 64,
    extra_overrides=(),
    mesh=None,
):
    """One compiled-workload recipe, shared by ``measure_compute`` and
    ``tools/perf_study.py``'s lever study so the two can never drift: the
    flagship DV3 pixel config + a synthetic batch derived from the composed
    config's obs keys.  ``mesh`` builds the distributed step (DP shard_map or
    FSDP global-view jit — state/batch placement is the caller's job).
    Returns ``(cfg, train_step, state, batch)`` with ``state = {params,
    opt_states, moments_state}``."""
    import jax.numpy as jnp
    import numpy as np

    cfg, _, _, _, params, opt_states, moments_state, train_step = _build(
        [
            "exp=dreamer_v3",
            "env=dummy",
            "env.id=discrete_dummy",
            f"algo=dreamer_v3_{size}",
            f"algo.per_rank_batch_size={batch_size}",
            f"algo.per_rank_sequence_length={sequence_length}",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.cnn_keys.decoder=[rgb]",
            "algo.mlp_keys.encoder=[]",
            "algo.mlp_keys.decoder=[]",
            "env.capture_video=False",
            "metric.log_level=0",
            f"fabric.precision={precision}",
            *extra_overrides,
        ],
        mesh=mesh,
    )
    T, B = cfg.algo.per_rank_sequence_length, cfg.algo.per_rank_batch_size
    rng = np.random.default_rng(0)
    batch = {
        "actions": jnp.asarray(rng.integers(0, 2, (T, B, 6)), jnp.float32),
        "rewards": jnp.asarray(rng.normal(size=(T, B, 1)), jnp.float32),
        "terminated": jnp.zeros((T, B, 1), jnp.float32),
        "is_first": jnp.zeros((T, B, 1), jnp.float32),
    }
    for k in set(cfg.algo.cnn_keys.encoder) | set(cfg.algo.cnn_keys.decoder):
        batch[k] = jnp.asarray(rng.integers(0, 255, (T, B, 3, 64, 64)), jnp.float32) / 255.0 - 0.5
    for k in set(cfg.algo.mlp_keys.encoder) | set(cfg.algo.mlp_keys.decoder):
        batch[k] = jnp.asarray(rng.normal(size=(T, B, 10)), jnp.float32)
    from sheeprl_tpu.algos.dreamer_v3.utils import rssm_scan_spec

    if rssm_scan_spec(cfg)[0] > 1:
        # chunked-scan variants consume replay-stored RSSM states; synthetic
        # stand-ins keep the compiled graph and its shapes honest (values
        # only matter for convergence, not for the perf measurement)
        recurrent_size = cfg.algo.world_model.recurrent_model.recurrent_state_size
        stoch_flat = cfg.algo.world_model.stochastic_size * cfg.algo.world_model.discrete_size
        batch["rssm_recurrent"] = jnp.asarray(
            rng.normal(size=(T, B, recurrent_size)) * 0.01, jnp.float32
        )
        batch["rssm_posterior"] = jnp.zeros((T, B, stoch_flat), jnp.float32)
        batch["rssm_valid"] = jnp.ones((T, B, 1), jnp.float32)
    state = {"params": params, "opt_states": opt_states, "moments_state": moments_state}
    return cfg, train_step, state, batch


def measure_compute(
    precision: str,
    size: str = "S",
    batch_size: int = 16,
    measure_steps: int = MEASURE_STEPS,
    extra_overrides=(),
):
    """Per-step timed gradient steps + MFU on random device-resident data.
    ``extra_overrides`` lets the perf study isolate phases (horizon=1, short
    sequences, vector-only observations)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg, train_step, state, batch = build_train_step_and_batch(
        precision, size=size, batch_size=batch_size, extra_overrides=extra_overrides
    )
    params, opt_states, moments_state = state["params"], state["opt_states"], state["moments_state"]
    key = jax.random.PRNGKey(0)
    tau = jnp.float32(0.02)

    # FLOPs of one compiled step (XLA cost analysis)
    flops = None
    try:
        compiled = train_step.lower(params, opt_states, moments_state, batch, key, tau).compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        flops = float(cost.get("flops", 0.0)) or None
    except Exception:
        pass

    for _ in range(WARMUP_STEPS):
        key, sub = jax.random.split(key)
        params, opt_states, moments_state, metrics = train_step(
            params, opt_states, moments_state, batch, sub, tau
        )[:4]
    _ = np.asarray(metrics)  # warmup barrier: fetch real values

    # Timing discipline (VERDICT r1: a dispatch-only measurement implied
    # >chip-peak FLOP/s): the barrier is fetching VALUES that depend on the
    # work.  Each step's params feed the next, so fetching the final metrics
    # forces the entire N-step chain; amortized time per step carries one
    # blocking fetch across all N steps.
    t0 = time.perf_counter()
    for _ in range(measure_steps):
        key, sub = jax.random.split(key)
        params, opt_states, moments_state, metrics = train_step(
            params, opt_states, moments_state, batch, sub, tau
        )[:4]
    final_metrics = np.asarray(metrics)
    elapsed = time.perf_counter() - t0
    assert np.isfinite(final_metrics).all()
    step_s = elapsed / measure_steps
    device_kind = jax.devices()[0].device_kind
    peak = _chip_peak(device_kind, precision)
    tflops = (flops / step_s / 1e12) if flops else None
    mfu = (flops / step_s) / peak if flops else None
    out = {
        "grad_steps_per_sec_compute": round(1.0 / step_s, 3),
        "step_ms": round(step_s * 1e3, 2),
        "flops_per_step": flops,
        "tflops_per_sec": round(tflops, 2) if tflops else None,
        "mfu": round(mfu, 4) if mfu else None,
        # same field names as the live telemetry layer journals
        # (sheeprl_tpu/diagnostics/telemetry.py), so offline bench numbers
        # and a live run's journal rows diff directly (ISSUE 3)
        "Telemetry/tflops_per_sec": round(tflops, 4) if tflops else None,
        "Telemetry/mfu": round(mfu, 4) if mfu else None,
        "device_kind": device_kind,
    }
    if tflops and tflops * 1e12 > peak:
        out["timing_suspect"] = (
            "implied FLOP/s exceeds chip peak — treat compute timing as unreliable"
        )
    return out


#: The PERF.md §5 MFU levers as config-override variants; `mfu_levers`
#: sweeps them against the base graph.  rssm_chunks folds the chunk axis
#: into the batch axis (GRU GEMM at B*K rows), scan_unroll amortizes scan
#: overhead.  (No `algo.rssm_pallas` arm: the menu sweeps XL, whose weight
#: block the kernel cannot hold in VMEM — ops/pallas_gru.py — so that arm
#: only ever timed the unfused cell; it raises at XL now.)
MFU_LEVER_VARIANTS = {
    "base": [],
    "rssm_chunks2": ["algo.rssm_chunks=2"],
    "rssm_chunks4": ["algo.rssm_chunks=4"],
    "unroll8": ["algo.scan_unroll=8"],
}


def measure_mfu_levers(
    precision: str,
    size: str = "S",
    batch_size: int = 16,
    sequence_length: int = 64,
    warmup_steps: int = 2,
    measure_steps: int = 8,
    variants=None,
):
    """The scan-lever close-out sweep (ROADMAP item 2): step time of the DV3
    train step under each MFU lever vs the base graph, one variant at a time
    (build → warm → time → free, so HBM holds ONE variant's state — unlike
    the interleaved perf_study harness this is a coarse menu stage; for
    drift-proof A/Bs use ``tools/perf_study.py --unroll-ab``).

    Reports ``step_ms`` per variant and the speedup vs base.  Deliberately
    NOT MFU per variant: ``cost_analysis()`` FLOPs inflate under unrolled
    scans (PERF.md §5), so step time on the identical batch is the only
    honest cross-variant number — the note field says so in the JSON.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    variants = dict(MFU_LEVER_VARIANTS) if variants is None else dict(variants)
    out = {
        "size": size,
        "batch_size": batch_size,
        "sequence_length": sequence_length,
        "measure_steps": measure_steps,
        "note": (
            "step_ms on the identical batch is the cross-variant metric; "
            "cost_analysis FLOPs (and therefore MFU) inflate under unrolled "
            "scans, and chunked variants change the stored-state batch keys"
        ),
        "points": {},
    }
    base_step_s = None
    for name, extra in variants.items():
        try:
            cfg, train_step, state, batch = build_train_step_and_batch(
                precision,
                size=size,
                batch_size=batch_size,
                sequence_length=sequence_length,
                extra_overrides=list(extra),
            )
            params, opt_states, moments_state = (
                state["params"],
                state["opt_states"],
                state["moments_state"],
            )
            key = jax.random.PRNGKey(0)
            tau = jnp.float32(0.02)
            for _ in range(warmup_steps):
                key, sub = jax.random.split(key)
                params, opt_states, moments_state, metrics = train_step(
                    params, opt_states, moments_state, batch, sub, tau
                )[:4]
            np.asarray(metrics)  # compile + warmup barrier
            t0 = time.perf_counter()
            for _ in range(measure_steps):
                key, sub = jax.random.split(key)
                params, opt_states, moments_state, metrics = train_step(
                    params, opt_states, moments_state, batch, sub, tau
                )[:4]
            final = np.asarray(metrics)  # value barrier forces the chain
            step_s = (time.perf_counter() - t0) / measure_steps
            point = {"step_ms": round(step_s * 1e3, 2), "finite": bool(np.isfinite(final).all())}
            if name == "base":
                base_step_s = step_s
            elif base_step_s:
                point["vs_base"] = round(base_step_s / step_s, 4)
            out["points"][name] = point
        except Exception as err:  # noqa: BLE001 — one variant must not kill the sweep
            out["points"][name] = {"error": repr(err)[:200]}
        finally:
            # drop this variant's params/opt state/batch references before
            # the next build — at XL shapes two variants do not co-reside in
            # HBM (rebinding to None releases the arrays to the allocator)
            params = opt_states = moments_state = batch = state = metrics = None
    return out


def measure_e2e(
    precision: str,
    num_envs: int = 1,
    size: str = "S",
    batch_size: int = 16,
    sequence_length: int = 64,
    pixels: bool = True,
    warmup_iters: int = E2E_WARMUP_ITERS,
    measure_iters: int = E2E_MEASURE_ITERS,
):
    """End-to-end DV3 loop on a dummy env: player inference + env
    step + replay add/sample + one gradient step per policy step
    (replay_ratio 1) — BASELINE.md §C's metric, like the reference's 14 h
    Atari-100K wall clock.  Uses the HBM-resident replay buffer (the
    framework's intended TPU path): per-step host->device traffic is one
    frame, and training batches are gathered inside HBM.

    The defaults are the flagship DV3-S pixel configuration.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sheeprl_tpu.algos.dreamer_v3.agent import PlayerDV3
    from sheeprl_tpu.algos.dreamer_v3.utils import prepare_obs
    from sheeprl_tpu.data.device_buffer import DeviceSequentialReplayBuffer
    from sheeprl_tpu.envs.env import make_env, vectorized_env

    from sheeprl_tpu.config import compose

    cnn = "[rgb]" if pixels else "[]"
    mlp = "[]" if pixels else "[state]"
    overrides = [
        "exp=dreamer_v3",
        "env=dummy",
        "env.id=discrete_dummy",
        f"algo=dreamer_v3_{size}",
        f"algo.per_rank_batch_size={batch_size}",
        f"algo.per_rank_sequence_length={sequence_length}",
        f"algo.cnn_keys.encoder={cnn}",
        f"algo.cnn_keys.decoder={cnn}",
        f"algo.mlp_keys.encoder={mlp}",
        f"algo.mlp_keys.decoder={mlp}",
        f"env.num_envs={num_envs}",
        "env.capture_video=False",
        "metric.log_level=0",
        f"fabric.precision={precision}",
    ]
    env_cfg = compose(overrides)
    envs = vectorized_env(
        [make_env(env_cfg, 42 + i, 0, None, "bench", vector_env_idx=i) for i in range(num_envs)],
        sync=True,
    )
    actions_dim = (envs.single_action_space.n,)
    cfg, wm_def, actor_def, _, params, opt_states, moments_state, train_step = _build(
        overrides, actions_dim=actions_dim
    )
    obs_keys = ["rgb"] if pixels else ["state"]
    cnn_obs_keys = obs_keys if pixels else []
    mlp_obs_keys = [] if pixels else obs_keys
    rb = DeviceSequentialReplayBuffer(4096, n_envs=num_envs, obs_keys=tuple(obs_keys))
    player = PlayerDV3(wm_def, actor_def, actions_dim, num_envs)
    player.init_states(params["world_model"])
    key = jax.random.PRNGKey(0)
    T, B = cfg.algo.per_rank_sequence_length, cfg.algo.per_rank_batch_size

    obs = envs.reset(seed=42)[0]
    step_data = {k: np.asarray(obs[k])[np.newaxis] for k in obs_keys}
    step_data["rewards"] = np.zeros((1, num_envs, 1), np.float32)
    step_data["truncated"] = np.zeros((1, num_envs, 1), np.float32)
    step_data["terminated"] = np.zeros((1, num_envs, 1), np.float32)
    step_data["is_first"] = np.ones((1, num_envs, 1), np.float32)

    # prefill so sequence sampling is valid
    for _ in range(T + 8):
        actions = np.asarray(envs.action_space.sample())
        onehot = np.eye(actions_dim[0], dtype=np.float32)[actions].reshape(1, num_envs, -1)
        step_data["actions"] = onehot
        rb.add(step_data)
        obs, rewards, term, trunc, _ = envs.step(actions.reshape(envs.action_space.shape))
        for k in obs_keys:
            step_data[k] = np.asarray(obs[k])[np.newaxis]
        step_data["rewards"] = np.asarray(rewards, np.float32).reshape(1, num_envs, 1)
        step_data["terminated"] = np.asarray(term, np.float32).reshape(1, num_envs, 1)
        step_data["truncated"] = np.asarray(trunc, np.float32).reshape(1, num_envs, 1)
        step_data["is_first"] = np.zeros((1, num_envs, 1), np.float32)

    from sheeprl_tpu.parallel.dp import normalize_staged

    # the SAME phase accounting the live telemetry layer runs (nesting-aware
    # self-time per span), so the bench's phase breakdown and a live run's
    # Telemetry/phase_pct/* rows are directly comparable
    from sheeprl_tpu.diagnostics.telemetry import Telemetry

    tele = Telemetry({})
    tele.open()

    def one_iter(params, opt_states, moments_state, step_data, obs, key, pipelined):
        """One policy step + one gradient step (ratio 1).

        ``pipelined=True`` replicates the shipped hot loop's dispatch order
        (sheeprl_tpu/algos/dreamer_v3/dreamer_v3.py:600-681): the player
        forward is dispatched, its DEVICE-RESIDENT action array is written
        into the HBM replay ring, the gradient step is dispatched, and only
        then is the action value fetched for ``envs.step`` — the blocking
        fetch and host env stepping overlap device compute.
        ``pipelined=False`` is the reference-style serialized order (fetch
        action -> env.step -> train) for an apples-to-apples overlap number.
        """
        key, k_step, k_train = jax.random.split(key, 3)
        with tele.span("rollout"):
            torch_obs = prepare_obs(obs, cnn_keys=cnn_obs_keys, mlp_keys=mlp_obs_keys, num_envs=num_envs)
            actions_jnp = player.get_actions(params["world_model"], params["actor"], torch_obs, k_step)

        def fetch_and_step_envs(step_data, obs):
            actions = np.asarray(actions_jnp)
            real_actions = np.argmax(actions, axis=-1)
            obs, rewards, term, trunc, _ = envs.step(real_actions.reshape(envs.action_space.shape))
            for k in obs_keys:
                step_data[k] = np.asarray(obs[k])[np.newaxis]
            step_data["rewards"] = np.asarray(rewards, np.float32).reshape(1, num_envs, 1)
            step_data["terminated"] = np.asarray(term, np.float32).reshape(1, num_envs, 1)
            step_data["truncated"] = np.asarray(trunc, np.float32).reshape(1, num_envs, 1)
            step_data["is_first"] = np.zeros((1, num_envs, 1), np.float32)
            return step_data, obs

        if pipelined:
            with tele.span("rollout"):
                step_data["actions"] = jnp.reshape(actions_jnp, (1, num_envs, -1))
                rb.add(step_data)
                # device->host copy overlaps the train dispatch below
                actions_jnp.copy_to_host_async()
        else:
            with tele.span("rollout"):
                actions = np.asarray(actions_jnp)
                step_data["actions"] = actions.reshape(1, num_envs, -1)
                rb.add(step_data)
            with tele.span("env_wait"):
                step_data, obs = fetch_and_step_envs(step_data, obs)

        # in-HBM sequence gather + ratio-1 gradient steps (one per policy
        # step, so num_envs of them per iteration)
        with tele.span("train"):
            for staged in rb.sample(B, sequence_length=T, n_samples=num_envs):
                batch = normalize_staged(staged, obs_keys)
                k_train, sub = jax.random.split(k_train)
                params, opt_states, moments_state, metrics = train_step(
                    params, opt_states, moments_state, batch, sub, jnp.float32(0.02)
                )[:4]

        if pipelined:
            with tele.span("env_wait"):
                step_data, obs = fetch_and_step_envs(step_data, obs)
        return params, opt_states, moments_state, step_data, obs, key, metrics

    results = {}
    for mode, pipelined in (("serialized", False), ("pipelined", True)):
        for _ in range(warmup_iters):
            params, opt_states, moments_state, step_data, obs, key, metrics = one_iter(
                params, opt_states, moments_state, step_data, obs, key, pipelined
            )
        _ = np.asarray(metrics)  # value barrier (see measure_compute note)

        tele.interval_metrics(None)  # drop warmup from the phase accounting
        t0 = time.perf_counter()
        for _ in range(measure_iters):
            params, opt_states, moments_state, step_data, obs, key, metrics = one_iter(
                params, opt_states, moments_state, step_data, obs, key, pipelined
            )
        _ = np.asarray(metrics)
        elapsed = time.perf_counter() - t0
        results[f"grad_steps_per_sec_e2e_{mode}"] = round(measure_iters * num_envs / elapsed, 3)
        if pipelined:  # phase breakdown of the shipped (pipelined) hot loop
            phases = tele.interval_metrics(None)
            results.update(
                {k: round(v, 2) for k, v in phases.items() if k.startswith("Telemetry/phase_pct/")}
            )
            # ISSUE 8: train share of the pipelined e2e window.  Informational
            # — the bench's async-dispatch loop is mostly idle host-side by
            # design, so this is tiny; the LIVE Telemetry/goodput gauge of a
            # real run is the production number.
            results["goodput"] = round(
                phases.get("Telemetry/phase_pct/train", 0.0) / 100.0, 4
            )
    tele.close()  # detach from the process-global compile-listener registry
    envs.close()
    return {
        "grad_steps_per_sec_e2e": results["grad_steps_per_sec_e2e_pipelined"],
        **results,
        "replay": "device (HBM-resident ring)",
    }


def measure_env_overlap(
    precision: str,
    sleep_ms: float = 80.0,
    iters: int = 25,
    warmup_iters: int = 3,
    size: str = "XS",
    batch_size: int = 4,
    sequence_length: int = 16,
):
    """Within-run serialized-vs-pipelined env-overlap pair (ISSUE 2).

    One compiled DV3 train step + one ``sleep_ms`` dummy env through the
    split-phase ``PipelinedVectorEnv`` layer.  ``serialized`` steps the env,
    then dispatches the gradient step and fetches its metrics (the reference
    order); ``pipelined`` issues ``step_async``, dispatches + fetches, and
    only then ``step_wait``s — the env's wall-clock hides behind the train
    dispatch and the blocking metric fetch.  Same graphs, same env, same
    process, back to back, so machine drift cancels within the pair; every
    timing uses the value-fetch barrier discipline of measure_compute.  The deterministic ``sleep_ms`` makes the
    expected gap exact: serialized ≈ pipelined + sleep_ms per iteration.

    ``pipelined`` is the ``env_overlap`` order of ``_dreamer_main``; where the
    env step is shorter than the host's sample and dispatch the loop has a
    second order, ``train_first``, and times the two itself
    (``algos/dreamer_v3/loop_order.py``): this pair does not measure that.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sheeprl_tpu.envs.dummy import DiscreteDummyEnv
    from sheeprl_tpu.envs.env import vectorized_env
    from sheeprl_tpu.envs.pipeline import PipelinedVectorEnv

    _, train_step, state, batch = build_train_step_and_batch(
        precision,
        size=size,
        batch_size=batch_size,
        sequence_length=sequence_length,
        extra_overrides=[
            "algo.cnn_keys.encoder=[]",
            "algo.cnn_keys.decoder=[]",
            "algo.mlp_keys.encoder=[state]",
            "algo.mlp_keys.decoder=[state]",
        ],
    )
    params, opt_states, moments_state = state["params"], state["opt_states"], state["moments_state"]
    key = jax.random.PRNGKey(0)
    tau = jnp.float32(0.02)

    def mk():
        return DiscreteDummyEnv(n_steps=1_000_000, image_size=(3, 8, 8), sleep_ms=sleep_ms)

    envs = PipelinedVectorEnv(vectorized_env([mk], sync=True))
    envs.reset(seed=0)
    actions = np.zeros(1, np.int64)

    def one_iter(pipelined, params, opt_states, moments_state, key):
        key, sub = jax.random.split(key)
        if not pipelined:
            envs.step(actions)
        else:
            envs.step_async(actions)
        params, opt_states, moments_state, metrics = train_step(
            params, opt_states, moments_state, batch, sub, tau
        )[:4]
        _ = np.asarray(metrics)  # per-iter value barrier
        if pipelined:
            envs.step_wait()
        return params, opt_states, moments_state, key

    results = {}
    for mode, pipelined in (("serialized", False), ("pipelined", True)):
        for _ in range(warmup_iters):
            params, opt_states, moments_state, key = one_iter(
                pipelined, params, opt_states, moments_state, key
            )
        t0 = time.perf_counter()
        for _ in range(iters):
            params, opt_states, moments_state, key = one_iter(
                pipelined, params, opt_states, moments_state, key
            )
        results[f"grad_steps_per_sec_env_{mode}"] = round(iters / (time.perf_counter() - t0), 3)
    envs.close()
    return {
        **results,
        "env_overlap_workload": (
            f"DV3-{size} vector obs, batch {batch_size} x seq {sequence_length}, "
            f"1 dummy env sleep_ms={sleep_ms:g}, thread-backed PipelinedVectorEnv"
        ),
        "env_sleep_ms": sleep_ms,
        "env_overlap_iters": iters,
    }


def measure_env_scale(
    num_envs_list=(4, 16, 64, 256),
    iters: int = 30,
    warmup_iters: int = 3,
    sleep_ms: float = 0.5,
    envs_per_worker=None,
    with_train: bool = True,
    precision: str = "bf16-mixed",
    train_size: str = "XS",
):
    """Many-env player scaling sweep (ISSUE 7): sharded shm executor +
    device-resident batched inference over ``num_envs`` ∈ {4..256}.

    Per env count the loop is the rewired hot-loop shape — stage the batched
    obs slab with ONE ``device_put``, run a tiny jitted policy, fetch the
    actions with ONE blocking ``device_get``, ``step_async``/``step_wait``
    the sharded ``SharedMemoryVectorEnv`` (optionally dispatching a DV3-XS
    gradient step inside the overlap window).  Reported per N:

    * ``env_steps_per_sec`` — N * iters / wall-clock; the acceptance signal
      is monotonic growth 4 → 64 (per-step fixed costs amortize over the
      slab instead of multiplying with it);
    * ``fetch_amortization`` — env steps per blocking d2h fetch (= N by
      construction of the batched-inference path; reported measured, not
      assumed);
    * ``grad_steps_per_sec`` — gradient steps landed inside the env-overlap
      windows (None when ``with_train`` is off, e.g. the CPU liveness probe).

    ``sleep_ms`` gives the dummy envs a deterministic per-step latency so the
    sweep exercises real worker parallelism, not just IPC overhead.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sheeprl_tpu.envs.dummy import DiscreteDummyEnv
    from sheeprl_tpu.envs.executor import SharedMemoryVectorEnv
    from sheeprl_tpu.envs.pipeline import PipelinedVectorEnv

    train_step = state = batch = None
    if with_train:
        _, train_step, state, batch = build_train_step_and_batch(
            precision,
            size=train_size,
            batch_size=4,
            sequence_length=16,
            extra_overrides=[
                "algo.cnn_keys.encoder=[]",
                "algo.cnn_keys.decoder=[]",
                "algo.mlp_keys.encoder=[state]",
                "algo.mlp_keys.decoder=[state]",
            ],
        )
        state["key"] = jax.random.PRNGKey(0)

    key = jax.random.PRNGKey(1)
    w = jax.device_put(jax.random.normal(key, (8, 4), jnp.float32))
    stage_sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])

    @jax.jit
    def policy(w, obs):  # tiny batched policy: [N, 8] -> [N] actions
        return jnp.argmax(obs @ w, axis=-1) % 2

    results = {
        "num_envs": [],
        "env_steps_per_sec": [],
        "fetch_amortization": [],
        "grad_steps_per_sec": [],
        "envs_per_worker": [],
        "sleep_ms": sleep_ms,
        "iters": iters,
    }
    for n in num_envs_list:
        fns = [
            (lambda: DiscreteDummyEnv(n_steps=1_000_000, image_size=(3, 8, 8), vector_shape=(8,), sleep_ms=sleep_ms))
            for _ in range(n)
        ]
        envs = PipelinedVectorEnv(SharedMemoryVectorEnv(fns, envs_per_worker=envs_per_worker))
        try:
            obs, _ = envs.reset(seed=0)

            def one_iter(obs, fetches, grad_steps):
                obs_dev = jax.device_put(
                    np.asarray(obs["state"], np.float32).reshape(n, -1), stage_sharding
                )
                acts = policy(w, obs_dev)
                (actions,) = jax.device_get((acts,))  # the ONE blocking d2h
                fetches += 1
                envs.step_async(actions.astype(np.int64))
                if train_step is not None:
                    state["key"], sub = jax.random.split(state["key"])
                    state["params"], state["opt_states"], state["moments_state"], metrics = train_step(
                        state["params"], state["opt_states"], state["moments_state"], batch, sub, jnp.float32(0.02)
                    )[:4]
                    np.asarray(metrics)  # value barrier inside the overlap window
                    grad_steps += 1
                obs = envs.step_wait()[0]
                return obs, fetches, grad_steps

            fetches = grad_steps = 0
            for _ in range(warmup_iters):
                obs, fetches, grad_steps = one_iter(obs, fetches, grad_steps)
            fetches = grad_steps = 0
            t0 = time.perf_counter()
            for _ in range(iters):
                obs, fetches, grad_steps = one_iter(obs, fetches, grad_steps)
            elapsed = time.perf_counter() - t0
        finally:
            envs.close()
        results["num_envs"].append(int(n))
        results["env_steps_per_sec"].append(round(n * iters / elapsed, 1))
        results["fetch_amortization"].append(round(n * iters / max(1, fetches), 1))
        results["grad_steps_per_sec"].append(
            round(grad_steps / elapsed, 3) if train_step is not None else None
        )
        results["envs_per_worker"].append(int(envs.envs.envs_per_worker))
    sps = results["env_steps_per_sec"]
    upto64 = [v for n, v in zip(results["num_envs"], sps) if n <= 64]
    results["monotonic_4_to_64"] = all(b >= a for a, b in zip(upto64, upto64[1:]))
    return results


def measure_fetch_rtt():
    """Dispatch a trivial jitted op and block on its values, in ms (mean of
    10): the one blocking device->host fetch every vector step pays."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    f = jax.jit(lambda x: x + 1.0)
    x = f(jnp.zeros((256,)))
    np.asarray(x)
    t0 = time.perf_counter()
    for _ in range(10):
        x = f(x)
        np.asarray(x)
    return round((time.perf_counter() - t0) * 100.0, 1)


def measure_learn_health(total_steps: int = 96, timeout_s: float = 240.0):
    """Informational learn-health block for the always-lands JSON (ISSUE 9).

    Runs a tiny vector-only ppo CLI training run in a SUBPROCESS (forced CPU
    — cheap, deterministic, and it cannot disturb this process's initialized
    backend) with the default-on ``diagnostics.health`` layer, then sources
    the block from THAT run's own crash-safe journal: the final policy loss,
    the mean in-graph global grad norm, and how many learning-health
    ``anomaly`` events the detectors journaled.  Not a performance number —
    it exists so every bench round also records whether the instrumented
    loop is *learning-shaped* (finite losses, live gradients, no anomalies).
    """
    import subprocess
    import sys
    import tempfile
    from pathlib import Path

    from sheeprl_tpu.diagnostics.journal import read_journal

    repo_root = os.path.dirname(os.path.abspath(__file__))
    overrides = [
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
        "checkpoint.save_last=False",
        f"algo.total_steps={int(total_steps)}",
    ]
    with tempfile.TemporaryDirectory() as td:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        subprocess.run(
            [sys.executable, os.path.join(repo_root, "sheeprl.py"), *overrides],
            cwd=td,
            env=env,
            check=True,
            timeout=timeout_s,
            stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
        )
        journals = sorted(Path(td).rglob("journal.jsonl"))
        if not journals:
            raise RuntimeError("learn-health drill run left no journal")
        events = read_journal(str(journals[-1]))
    metrics_events = [e for e in events if e.get("event") == "metrics"]
    final_loss = None
    grad_norms = []
    for e in metrics_events:
        m = e.get("metrics") or {}
        loss = m.get("Loss/policy_loss")
        if isinstance(loss, (int, float)):
            final_loss = float(loss)
        gnorm = m.get("Telemetry/health/grad_norm", m.get("Grads/global_norm"))
        if isinstance(gnorm, (int, float)):
            grad_norms.append(float(gnorm))
    return {
        "final_loss": round(final_loss, 6) if final_loss is not None else None,
        "mean_grad_norm": round(sum(grad_norms) / len(grad_norms), 6) if grad_norms else None,
        "anomalies": sum(1 for e in events if e.get("event") == "anomaly"),
        "workload": f"ppo discrete_dummy CPU drill, {int(total_steps)} policy steps",
    }


def measure_offline(
    rows: int = 4096,
    obs_dim: int = 16,
    batch: int = 256,
    read_batches: int = 40,
    drill: bool = True,
    drill_timeout_s: float = 420.0,
):
    """Offline-RL block (ISSUE 15), always-lands: dataset read throughput
    with the host-prefetch thread off vs on, plus offline grad-steps/s
    through the real env-free CLI in a CPU subprocess.

    * ``read_sps`` — a synthetic in-memory-sized dataset (``rows`` SAC-shaped
      transitions, sharded) streamed as ``read_batches`` flat batches of
      ``batch`` rows by the deterministic loader, prefetch 0 vs 2.  The pure
      read pair has no device step to hide behind, so prefetch can only add
      queue-handoff overhead here (speedup <= 1 is expected); the drill's
      ``dataset_read_sps`` below is the overlapped number that matters.  The
      batch *sequence* is bit-identical either way (pinned by
      tests/test_offline/);
    * ``drill`` — a tiny SAC collect → ``export_run_dir`` → offline train
      (``algo.offline.enabled=true``, CQL armed) in CPU subprocesses, the
      grad-steps/s sourced from the offline run's own journal
      (``Time/sps_train`` at the last metric interval) — the D4RL-style
      workload measured end-to-end, not as a microbench.
    """
    import shutil
    import subprocess
    import sys
    import tempfile
    from pathlib import Path

    import numpy as np

    from sheeprl_tpu.data.buffers import ReplayBuffer
    from sheeprl_tpu.data.datasets import OfflineDataset
    from sheeprl_tpu.offline.export import export_buffer, export_run_dir

    out: dict = {"rows": int(rows), "batch": int(batch)}
    rng = np.random.default_rng(0)
    tmp_root = tempfile.mkdtemp(prefix="bench_offline_")
    try:
        rb = ReplayBuffer(rows, 1, obs_keys=("observations",))
        chunk = 256
        for start in range(0, rows, chunk):
            n = min(chunk, rows - start)
            rb.add(
                {
                    "observations": rng.standard_normal((n, 1, obs_dim)).astype(np.float32),
                    "next_observations": rng.standard_normal((n, 1, obs_dim)).astype(np.float32),
                    "actions": rng.standard_normal((n, 1, 4)).astype(np.float32),
                    "rewards": rng.standard_normal((n, 1, 1)).astype(np.float32),
                    "terminated": np.zeros((n, 1, 1), np.float32),
                    "truncated": np.zeros((n, 1, 1), np.float32),
                }
            )
        export_buffer(rb, os.path.join(tmp_root, "ds"), shard_rows=1024)
        ds = OfflineDataset(os.path.join(tmp_root, "ds"), deep_verify=False)
        for prefetch, label in ((0, "read_sps_no_prefetch"), (2, "read_sps_prefetch")):
            it = ds.batches(batch, seed=1, prefetch=prefetch)
            next(it)  # warm the shard cache / spin the thread up
            t0 = time.perf_counter()
            for _ in range(int(read_batches)):
                next(it)
            out[label] = round(int(read_batches) * batch / (time.perf_counter() - t0), 1)
        if out["read_sps_no_prefetch"] > 0:
            out["prefetch_speedup"] = round(
                out["read_sps_prefetch"] / out["read_sps_no_prefetch"], 3
            )
    finally:
        shutil.rmtree(tmp_root, ignore_errors=True)
    if not drill:
        return out

    repo_root = os.path.dirname(os.path.abspath(__file__))
    common = [
        "exp=sac",
        "env=dummy",
        "env.id=continuous_dummy",
        "env.num_envs=2",
        "env.capture_video=False",
        "buffer.memmap=False",
        "buffer.size=128",
        "metric.log_level=1",
        "metric.log_every=1",
        "fabric.devices=1",
        "fabric.accelerator=cpu",
        "algo.per_rank_batch_size=16",
        "algo.mlp_keys.encoder=[state]",
        "algo.run_test=False",
        "checkpoint.save_last=True",
    ]
    with tempfile.TemporaryDirectory() as td:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        subprocess.run(
            [
                sys.executable,
                os.path.join(repo_root, "sheeprl.py"),
                *common,
                "algo.total_steps=64",
                "algo.learning_starts=1000",  # prefill-only collect
                "buffer.checkpoint=True",
                "run_name=bench_collect",
            ],
            cwd=td,
            env=env,
            check=True,
            timeout=drill_timeout_s,
            stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
        )
        collect_dir = Path(td) / "logs" / "runs" / "sac" / "continuous_dummy" / "bench_collect"
        exported = export_run_dir(str(collect_dir), shard_rows=1024)
        out["drill_dataset_rows"] = exported["rows"]
        subprocess.run(
            [
                sys.executable,
                os.path.join(repo_root, "sheeprl.py"),
                *common,
                "algo.total_steps=96",
                "run_name=bench_offline",
                "algo.offline.enabled=true",
                f"algo.offline.dataset_dir={exported['path']}",
                "algo.offline.grad_steps_per_iter=4",  # 16x4=64 rows/draw == the collected set
                "algo.offline.cql_alpha=0.5",
            ],
            cwd=td,
            env=env,
            check=True,
            timeout=drill_timeout_s,
            stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
        )
        from sheeprl_tpu.diagnostics.journal import find_journal, read_journal

        journal = find_journal(str(collect_dir.parent / "bench_offline"))
        if journal is None:
            raise RuntimeError("offline drill run left no journal")
        events = read_journal(journal)
        metrics_events = [e for e in events if e.get("event") == "metrics"]
        last = (metrics_events[-1].get("metrics") or {}) if metrics_events else {}
        out["drill_grad_steps_per_sec"] = (
            round(float(last["Time/sps_train"]), 3)
            if isinstance(last.get("Time/sps_train"), (int, float))
            else None
        )
        out["drill_dataset_read_sps"] = (
            round(float(last["Telemetry/dataset_read_sps"]), 1)
            if isinstance(last.get("Telemetry/dataset_read_sps"), (int, float))
            else None
        )
        losses = [
            last.get(k)
            for k in ("Loss/value_loss", "Loss/policy_loss", "Loss/alpha_loss")
            if isinstance(last.get(k), (int, float))
        ]
        out["drill_losses_finite"] = bool(losses) and all(np.isfinite(v) for v in losses)
        out["drill_shards_skipped"] = sum(
            1 for e in events if e.get("event") == "dataset_shard_skipped"
        )
        out["workload"] = "sac offline, batch 16 x 4 grad-steps/iter, cql_alpha 0.5, CPU drill"
    return out


def measure_recovery(
    state_mb: float = 32.0,
    interval_iters: int = 12,
    train_tick_s: float = 0.01,
    kill_drill: bool = True,
    drill_timeout_s: float = 420.0,
):
    """Resilience block (ISSUE 13), always-lands: checkpoint cost on vs off
    the critical path, and measured time-to-recover from one injected kill.

    * ``blocking_write_ms`` vs ``async_critical_path_ms`` — one ~``state_mb``
      synthetic state saved synchronously (serialize+fsync on the caller)
      vs submitted to the :class:`AsyncCheckpointWriter` (the caller pays
      only the host snapshot + enqueue);
    * ``interval_goodput`` — a simulated checkpointing interval
      (``interval_iters`` train ticks of ``train_tick_s``, one checkpoint
      every 4 ticks): productive share of wall-clock with blocking saves vs
      the async writer overlapping them — the mechanism behind the
      acceptance claim that async checkpointing raises train-span goodput;
    * ``kill_drill`` — a tiny supervised ppo CLI run (CPU subprocess) whose
      first child is SIGKILLed by ``tools/supervise.py
      --kill-after-first-checkpoint`` the moment a verified checkpoint
      exists, auto-restarted, and resumed to completion; time-to-recover and
      the segment labels come from ``tools/goodput_report.py``'s own
      analysis of the run's journals.
    """
    import subprocess
    import sys
    import tempfile
    from pathlib import Path

    from sheeprl_tpu.resilience.async_writer import AsyncCheckpointWriter
    from sheeprl_tpu.resilience.manifest import save_verified_checkpoint

    import numpy as np

    repo_root = os.path.dirname(os.path.abspath(__file__))
    n = max(1, int(state_mb * (1 << 20) / 4))
    rng = np.random.default_rng(0)
    state = {"params": {"w": rng.standard_normal(n).astype(np.float32)}, "policy_step": 1}
    out: dict = {"state_bytes": n * 4}
    with tempfile.TemporaryDirectory() as td:
        t0 = time.perf_counter()
        save_verified_checkpoint(os.path.join(td, "ckpt_1_0.ckpt"), state)
        out["blocking_write_ms"] = round((time.perf_counter() - t0) * 1e3, 3)
        writer = AsyncCheckpointWriter()
        t0 = time.perf_counter()
        crit_s = writer.submit(os.path.join(td, "ckpt_2_0.ckpt"), state, step=2)
        out["async_critical_path_ms"] = round(crit_s * 1e3, 3)
        writer.drain()
        writer.close()
        out["async_write_ms"] = writer.stats()["last_write_ms"]
        if out["async_critical_path_ms"] > 0:
            out["critical_path_speedup"] = round(
                out["blocking_write_ms"] / out["async_critical_path_ms"], 2
            )

        def interval_goodput(use_async: bool) -> float:
            ckpt_dir = os.path.join(td, "async" if use_async else "blocking")
            interval_writer = AsyncCheckpointWriter() if use_async else None
            wall0 = time.perf_counter()
            train_s = 0.0
            for i in range(int(interval_iters)):
                t = time.perf_counter()
                time.sleep(train_tick_s)  # stands in for the train span
                train_s += time.perf_counter() - t
                if i % 4 == 3:
                    path = os.path.join(ckpt_dir, f"ckpt_{i}_0.ckpt")
                    if interval_writer is not None:
                        interval_writer.submit(path, state, step=i)
                    else:
                        save_verified_checkpoint(path, state, step=i)
            wall = time.perf_counter() - wall0
            if interval_writer is not None:
                interval_writer.close()  # writes finish off the measured window
            return round(train_s / wall, 4) if wall > 0 else 0.0

        out["interval_goodput"] = {
            "blocking": interval_goodput(False),
            "async": interval_goodput(True),
        }

    if not kill_drill:
        return out
    overrides = [
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
        "run_name=bench_recovery",
        "algo.total_steps=512",
        "checkpoint.every=16",
        "checkpoint.save_last=False",
    ]
    with tempfile.TemporaryDirectory() as td:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [
                sys.executable,
                os.path.join(repo_root, "tools", "supervise.py"),
                "--max-restarts",
                "2",
                "--backoff",
                "0.5",
                "--kill-after-first-checkpoint",
                *overrides,
            ],
            cwd=td,
            env=env,
            timeout=drill_timeout_s,
            stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
        )
        run_dir = Path(td) / "logs" / "runs" / "ppo" / "discrete_dummy" / "bench_recovery"
        sys.path.insert(0, os.path.join(repo_root, "tools"))
        try:
            from goodput_report import analyze_segments, read_supervisor

            from sheeprl_tpu.diagnostics.journal import collect_journals

            journals = collect_journals([str(run_dir)])
            analysis = analyze_segments(journals)
            supervisor = read_supervisor(str(run_dir))
        finally:
            sys.path.pop(0)
        out["kill_drill"] = {
            "supervise_rc": proc.returncode,
            "segments": [s["label"] for s in analysis["segments"]],
            "time_to_recover_s": analysis["time_to_recover_s"],
            "recovered_train_s": analysis["recovered_train_s"],
            "restarts": (supervisor or {}).get("restarts"),
            "measured_down_s": (supervisor or {}).get("measured_down_s"),
        }
    return out


def measure_decoupled(iters: int = 8, timeout_s: float = 420.0):
    """Decoupled-topology overhead pair (VERDICT item 7), always-lands:
    coupled PPO on a 7-device mesh vs decoupled PPO at 1 player + 7 trainers
    on an 8-device mesh — same 7-way trainer parallelism, same per-device
    minibatch (56-sample rollouts, batch 8), so the pair isolates exactly
    what decoupling adds: the rollout scatter onto the trainer sub-mesh and
    the params hop back to the player.

    Both runs are subprocesses on a FORCED virtual-8-device CPU platform
    (``--xla_force_host_platform_device_count=8`` — the dryrun-validated
    MULTICHIP topology): a pathological serialization regression in the
    decoupled loop is caught before real hardware ever sees it.  Steady-state
    per-iteration wall times come from each run's own journal (`metrics`
    event timestamps at ``log_every=1``), first two iterations dropped as
    compile tail.  CPU liveness numbers — the overhead RATIO is the signal,
    not the absolute iters/s.
    """
    import subprocess
    import sys
    import tempfile
    from pathlib import Path

    from sheeprl_tpu.diagnostics.journal import read_journal

    total_steps = 14 * 4 * int(iters)
    common = [
        "env=dummy",
        "env.id=discrete_dummy",
        "env.num_envs=4",
        "env.capture_video=False",
        "buffer.memmap=False",
        "metric.log_level=1",
        "metric.log_every=1",
        "fabric.accelerator=cpu",
        "algo.rollout_steps=14",
        "algo.per_rank_batch_size=8",
        "algo.update_epochs=1",
        "algo.dense_units=16",
        "algo.mlp_layers=1",
        "algo.mlp_keys.encoder=[state]",
        "algo.cnn_keys.encoder=[]",
        "algo.run_test=False",
        f"algo.total_steps={total_steps}",
        "checkpoint.every=0",
        "checkpoint.save_last=False",
    ]
    variants = {
        "coupled": ["exp=ppo", "fabric.devices=7"],
        "decoupled": ["exp=ppo_decoupled", "fabric.devices=8"],
    }
    out: dict = {
        "workload": (
            "ppo discrete_dummy, 56-sample rollouts (14 steps x 4 envs), batch 8, "
            f"{iters} iters on the virtual 8-device CPU mesh: coupled@7dev vs decoupled@1+7"
        )
    }
    from sheeprl_tpu.utils.utils import subprocess_cli_env

    env = subprocess_cli_env(device_count=8)
    for name, extra in variants.items():
        with tempfile.TemporaryDirectory() as td:
            proc = subprocess.run(
                [sys.executable, "-m", "sheeprl_tpu", *extra, *common, f"run_name=bench_{name}"],
                cwd=td,
                env=env,
                timeout=timeout_s,
                stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL,
            )
            journals = sorted(Path(td).rglob("journal.jsonl"))
            events = read_journal(str(journals[0])) if journals else []
            stamps = [
                e["t"] for e in events if e.get("event") == "metrics" and isinstance(e.get("t"), (int, float))
            ]
            gaps = sorted(b - a for a, b in zip(stamps, stamps[1:]))[: max(1, len(stamps) - 3)]
            # median of the steady-state gaps (compile-inflated outliers are
            # the largest gaps, already clipped off the sorted tail above).
            # A crashed child (rc != 0) publishes NO timing: a partial run's
            # gaps would read as a plausible regression/improvement signal.
            steady = gaps[len(gaps) // 2] if gaps and proc.returncode == 0 else None
            out[name] = {
                "rc": proc.returncode,
                "n_iters_logged": len(stamps),
                "steady_iter_ms": round(steady * 1e3, 1) if steady else None,
                "iters_per_sec": round(1.0 / steady, 2) if steady else None,
            }
    coupled_ms = (out.get("coupled") or {}).get("steady_iter_ms")
    decoupled_ms = (out.get("decoupled") or {}).get("steady_iter_ms")
    if coupled_ms and decoupled_ms:
        # > 1.0 = decoupling costs; the scatter + params-hop overhead line
        out["decoupled_vs_coupled_iter_ratio"] = round(decoupled_ms / coupled_ms, 3)
    return out


_FSDP_CHILD_SRC = r"""
import json, sys, time
import numpy as np
import jax
import jax.numpy as jnp

size, precision = sys.argv[1], sys.argv[2]
batch_size, seq_len, iters = int(sys.argv[3]), int(sys.argv[4]), int(sys.argv[5])

from bench import build_train_step_and_batch
from sheeprl_tpu.parallel.dp import stage
from sheeprl_tpu.parallel.fsdp import shard_tree, tree_bytes_per_device
from sheeprl_tpu.parallel.mesh import make_mesh, replicated_sharding

def tree_bytes(t):
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree_util.tree_leaves(t))

MIN_SHARD = 1024
out = {}
meshes = {
    "dp": make_mesh(n_devices=8, axis_names=("data",)),
    "fsdp": make_mesh(n_devices=8, axis_names=("data", "model"), axis_sizes=(1, 8)),
}
for name, mesh in meshes.items():
    cfg, step, state, batch = build_train_step_and_batch(
        precision, size=size, batch_size=batch_size, sequence_length=seq_len,
        extra_overrides=["distribution.fsdp_min_shard_bytes=%d" % MIN_SHARD], mesh=mesh,
    )
    params, opt_states, moments = state["params"], state["opt_states"], state["moments_state"]
    if name == "fsdp":
        params = shard_tree(params, mesh, MIN_SHARD)
        opt_states = shard_tree(opt_states, mesh, MIN_SHARD)
    else:
        params = jax.device_put(params, replicated_sharding(mesh))
        opt_states = jax.device_put(opt_states, replicated_sharding(mesh))
    moments = jax.device_put(moments, replicated_sharding(mesh))
    batch = stage({k: np.asarray(v) for k, v in batch.items()}, mesh, batch_axis=1)
    key = jax.random.PRNGKey(0)
    tau = jnp.float32(0.02)
    for _ in range(2):
        key, sub = jax.random.split(key)
        params, opt_states, moments, metrics = step(params, opt_states, moments, batch, sub, tau)[:4]
    np.asarray(metrics)
    t0 = time.perf_counter()
    for _ in range(iters):
        key, sub = jax.random.split(key)
        params, opt_states, moments, metrics = step(params, opt_states, moments, batch, sub, tau)[:4]
    final = np.asarray(metrics)
    elapsed = time.perf_counter() - t0
    assert np.isfinite(final).all(), name
    out[name] = {
        "step_ms": round(elapsed / iters * 1e3, 2),
        "params_bytes": tree_bytes(params),
        "params_bytes_per_device": tree_bytes_per_device(params),
        "opt_bytes_per_device": tree_bytes_per_device(opt_states),
    }
print("BENCH_FSDP_JSON " + json.dumps(out), flush=True)
"""


def measure_fsdp(
    precision: str,
    size: str = "XS",
    batch_size: int = 8,
    sequence_length: int = 8,
    iters: int = 4,
    timeout_s: float = 420.0,
):
    """FSDP-vs-DP pair (ISSUE 17), always-lands: the SAME DV3 train step on
    the virtual 8-device CPU mesh twice — replicated state over a 1-D
    ``("data",)`` mesh (shard_map DP) vs partition-rule-sharded state over a
    2-D ``(1, 8)`` ``("data", "model")`` mesh (global-view FSDP jit) — same
    global batch, so the pair isolates exactly what sharding the train state
    costs in step time and buys in per-device bytes.

    One subprocess runs both variants (``subprocess_cli_env`` forces the
    8-device virtual platform regardless of the parent's backend).  CPU
    liveness numbers — ``params_per_device_shrink`` is the memory signal and
    ``fsdp_vs_dp_step_ratio`` the serialization canary, not the absolute ms.
    """
    import re
    import subprocess
    import sys

    from sheeprl_tpu.utils.utils import subprocess_cli_env

    proc = subprocess.run(
        [
            sys.executable,
            "-c",
            _FSDP_CHILD_SRC,
            size,
            precision,
            str(batch_size),
            str(sequence_length),
            str(iters),
        ],
        env=subprocess_cli_env(device_count=8),
        timeout=timeout_s,
        capture_output=True,
        text=True,
    )
    out: dict = {
        "workload": (
            f"dreamer_v3_{size} pixels, batch {batch_size} x seq {sequence_length}, "
            f"{iters} iters on the virtual 8-device CPU mesh: replicated DP@8 vs "
            "FSDP (1x8 model axis, min_shard_bytes=1024)"
        ),
        "rc": proc.returncode,
    }
    m = re.search(r"^BENCH_FSDP_JSON (.*)$", proc.stdout, re.MULTILINE)
    if proc.returncode != 0 or m is None:
        # a crashed child publishes NO timing (the measure_decoupled lesson);
        # the stderr tail makes the failure diagnosable from the JSON line
        out["error"] = (proc.stderr or proc.stdout or "")[-400:]
        return out
    out.update(json.loads(m.group(1)))
    dp, fsdp = out.get("dp") or {}, out.get("fsdp") or {}
    if dp.get("step_ms") and fsdp.get("step_ms"):
        # > 1.0 = sharding costs step time (gather/scatter on the critical path)
        out["fsdp_vs_dp_step_ratio"] = round(fsdp["step_ms"] / dp["step_ms"], 3)
    if dp.get("params_bytes_per_device") and fsdp.get("params_bytes_per_device"):
        # ~axis_size = the ZeRO-3 memory win; < axis_size means replicated
        # small leaves (below min_shard_bytes or with no divisible dim)
        out["params_per_device_shrink"] = round(
            dp["params_bytes_per_device"] / fsdp["params_bytes_per_device"], 2
        )
    return out


def measure_serving(
    loads=(1, 4, 16),
    duration_s: float = 3.0,
    buckets=(4, 8, 16),
    max_delay_ms: float = 2.0,
):
    """Serving-tier block (ISSUE 11): requests/sec, p50/p99 latency and mean
    batch width at several offered-load points, measured through the REAL
    HTTP tier (``POST /act``) by an in-process client swarm.  Each point also
    carries the per-phase breakdown (queue/dispatch p50·p99) and the SLO
    burn-rate gauge from the service's phase stats, and the overload point
    reports the mean shed-wait (ISSUE 19).

    The policy is a tiny randomly-initialized vector ppo agent — serving
    throughput is a property of the batcher + compiled-step pipeline, not of
    the weights, so no checkpoint/training is needed.
    """
    import json as _json
    import threading
    import urllib.request

    import gymnasium as gym
    import numpy as np

    from sheeprl_tpu.config import compose
    from sheeprl_tpu.serving.loader import build_policy
    from sheeprl_tpu.serving.server import PolicyService

    cfg = compose(
        [
            "exp=ppo",
            "env=dummy",
            "env.id=discrete_dummy",
            "algo.mlp_keys.encoder=[state]",
            "algo.cnn_keys.encoder=[]",
            "algo.dense_units=64",
            "algo.mlp_layers=2",
        ]
    )
    obs_dim = 10
    obs_space = gym.spaces.Dict({"state": gym.spaces.Box(-20, 20, (obs_dim,), np.float32)})
    handle = build_policy(cfg, obs_space, gym.spaces.Discrete(6))
    service = PolicyService(
        handle,
        {
            "batch_buckets": list(buckets),
            "max_delay_ms": float(max_delay_ms),
            # an SLO target so each point also reports the burn-rate gauge
            # (ISSUE 19); generous enough that a healthy CPU box sits near 0
            "slo": {"target_ms": 250.0, "objective": 0.99},
        },
    )
    service.start()
    service.warmup()

    # a minimal HTTP tier rather than direct service calls: latency numbers
    # include JSON parse + socket turnaround, like a production client sees
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class _Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):  # noqa: ANN001
            pass

        def do_POST(self):  # noqa: N802
            try:
                length = int(self.headers.get("Content-Length") or 0)
                payload = _json.loads(self.rfile.read(length) or b"{}")
                result = service.act(payload["obs"])
                status, body = 200, _json.dumps(
                    {"action": np.asarray(result["action"]).tolist()}
                ).encode()
            except Exception as err:  # noqa: BLE001 — a failed request must
                # answer 500, not kill the connection (and with it the swarm
                # client thread whose load the point claims to measure)
                status, body = 500, _json.dumps({"error": repr(err)}).encode()
            self.send_response(status)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _Handler)
    httpd.daemon_threads = True
    http_thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    http_thread.start()
    url = f"http://127.0.0.1:{httpd.server_address[1]}/act"

    def swarm(n_clients: int) -> dict:
        payload = _json.dumps(
            {"obs": {"state": np.linspace(-1, 1, obs_dim).tolist()}}
        ).encode()
        before = service.batcher.stats()
        stop_t = time.monotonic() + duration_s
        # per-WINDOW latency samples, measured client-side: the batcher's own
        # percentile deque is service-lifetime, so reading it here would let
        # earlier (lower-load) points dilute this point's tail
        samples = [[] for _ in range(n_clients)]

        client_errors = [0] * n_clients

        def client(i: int) -> None:
            while time.monotonic() < stop_t:
                t_req = time.perf_counter()
                try:
                    with urllib.request.urlopen(
                        urllib.request.Request(url, data=payload), timeout=30
                    ) as resp:
                        resp.read()
                except Exception:  # noqa: BLE001 — keep offering load; the
                    client_errors[i] += 1  # point reports the error count
                    continue
                samples[i].append((time.perf_counter() - t_req) * 1000.0)

        threads = [threading.Thread(target=client, args=(i,)) for i in range(n_clients)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.monotonic() - t0
        after = service.batcher.stats()
        d_req = after["requests_total"] - before["requests_total"]
        d_disp = after["dispatches_total"] - before["dispatches_total"]
        latencies = sorted(v for chunk in samples for v in chunk)

        def pct(p: float):
            if not latencies:
                return None
            rank = min(len(latencies) - 1, int(round(p / 100.0 * (len(latencies) - 1))))
            return round(latencies[rank], 3)

        # per-phase breakdown + SLO burn from the service's own phase stats
        # (ISSUE 19): the rolling window is dominated by this point's traffic
        # (each point issues far more requests than the window holds), so the
        # snapshot right after the swarm is this point's breakdown
        gauges = (service.snapshot().get("gauges") or {})
        return {
            "clients": n_clients,
            "requests_per_sec": round(len(latencies) / wall, 2) if wall > 0 else None,
            "latency_p50_ms": pct(50.0),
            "latency_p99_ms": pct(99.0),
            "batch_width_mean": round(d_req / d_disp, 3) if d_disp else None,
            "errors": sum(client_errors),
            "queue_ms_p50": gauges.get("Telemetry/serve/queue_ms_p50"),
            "queue_ms_p99": gauges.get("Telemetry/serve/queue_ms_p99"),
            "dispatch_ms_p50": gauges.get("Telemetry/serve/dispatch_ms_p50"),
            "dispatch_ms_p99": gauges.get("Telemetry/serve/dispatch_ms_p99"),
            "slo_burn": gauges.get("Telemetry/serve/slo_burn"),
        }

    def overload_point(offered: int = 32, queue_limit: int = 4) -> dict:
        """Load shedding at the door (ISSUE 16): shrink the request queue,
        slow the dispatcher with its test seam, offer more concurrent
        requests than slots and count the 503s.  Shed requests carry the
        batcher's advisory ``Retry-After`` (seconds) — reported so the
        overload contract is visible in the bench artifact."""
        before = service.batcher.stats()
        old_queue = service.batcher.max_queue
        service.batcher.max_queue = int(queue_limit)
        service._step_delay_s = 0.05
        obs = {"state": np.linspace(-1, 1, obs_dim).tolist()}
        lock = threading.Lock()
        outcome = {"ok": 0, "shed": 0, "retry_after": []}

        def client() -> None:
            try:
                service.act(obs, timeout_s=10.0)
                with lock:
                    outcome["ok"] += 1
            except Exception as err:  # noqa: BLE001 — 503s are the point
                with lock:
                    outcome["shed"] += 1
                    retry_after = getattr(err, "retry_after", None)
                    if retry_after is not None:
                        outcome["retry_after"].append(retry_after)

        threads = [threading.Thread(target=client) for _ in range(int(offered))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        service._step_delay_s = None
        service.batcher.max_queue = old_queue
        after = service.batcher.stats()
        return {
            "offered": int(offered),
            "queue_limit": int(queue_limit),
            "accepted": outcome["ok"],
            "shed_503": outcome["shed"],
            "shed_total_delta": after["shed_total"] - before["shed_total"],
            # mean time a shed request sat queued before its 503 (ISSUE 19):
            # the client-visible cost of hitting the full queue
            "shed_wait_ms": after.get("shed_wait_ms"),
            "retry_after_s": sorted(set(outcome["retry_after"])) or None,
        }

    try:
        points = [swarm(int(n)) for n in loads]
        overload = overload_point()
    finally:
        httpd.shutdown()
        httpd.server_close()
        http_thread.join(timeout=5)
        service.close()
    return {
        "buckets": list(buckets),
        "max_delay_ms": float(max_delay_ms),
        "compiles": service.compile_count,
        "points": points,
        "overload": overload,
    }


def _run_chip_menu(record: dict, precision: str, deadline: float) -> None:
    """Full flagship menu, stage by stage, newest-information-first under a
    wall-clock budget: the headline e2e lands first, optional stages are
    skipped (and named in ``skipped``) once the budget runs low, and a stage
    failure is recorded in ``stage_errors`` without killing the stages after
    it — ``main`` then exits non-zero."""
    record["fetch_rtt_ms"] = measure_fetch_rtt()

    def remaining() -> float:
        return deadline - time.monotonic()

    def stage(name: str, est_s: float, fn):
        if remaining() < est_s:
            record.setdefault("skipped", []).append(f"{name} (budget: {int(remaining())}s left < est {int(est_s)}s)")
            return None
        try:
            return fn()
        except Exception as err:  # noqa: BLE001 — a failed stage must not kill the menu
            record.setdefault("stage_errors", {})[name] = repr(err)
            return None

    # headline stage runs under jax.transfer_guard("log") with fd-level
    # stderr capture: the runtime's transfer-log lines are the only faithful
    # implicit-transfer counter (the guard logs from C++).  None = capture
    # unavailable; the e2e number lands regardless.
    def _guarded_e2e():
        from sheeprl_tpu.diagnostics.memory import count_guard_log_lines

        result, transfers = count_guard_log_lines(lambda: measure_e2e(precision))
        record["host_transfer_count"] = transfers
        return result

    e2e = stage("e2e_S", 240, _guarded_e2e)
    if e2e:
        record["value"] = e2e["grad_steps_per_sec_e2e"]
        record["vs_baseline"] = round(record["value"] / BASELINE_E2E_GRAD_STEPS_PER_SEC, 3)
        record.update({k: v for k, v in e2e.items() if k != "grad_steps_per_sec_e2e"})

    compute = stage("compute_S", 180, lambda: measure_compute(precision))
    if compute:
        record.update(compute)

    # 4-env variant: one action fetch serves 4 policy steps, amortizing the
    # blocking fetch each vector step pays; still
    # ratio 1 — four gradient steps per iteration
    e2e_4env = stage("e2e_S_4env", 240, lambda: measure_e2e(precision, num_envs=4))
    if e2e_4env:
        record["grad_steps_per_sec_e2e_4env"] = e2e_4env["grad_steps_per_sec_e2e_pipelined"]
        record["grad_steps_per_sec_e2e_4env_serialized"] = e2e_4env["grad_steps_per_sec_e2e_serialized"]

    # split-phase env pipeline pair (ISSUE 2): same compiled step + same env,
    # serialized vs step_async/step_wait, within one run so machine drift
    # cancels; fetch_rtt_ms above is the blocking-fetch cost beside it
    env_overlap = stage("env_overlap", 240, lambda: measure_env_overlap(precision))
    if env_overlap:
        record["grad_steps_per_sec_env_serialized"] = env_overlap["grad_steps_per_sec_env_serialized"]
        record["grad_steps_per_sec_env_pipelined"] = env_overlap["grad_steps_per_sec_env_pipelined"]
        record.update({k: v for k, v in env_overlap.items() if not k.startswith("grad_steps")})

    # many-env player scaling sweep (ISSUE 7): sharded shm executor +
    # batched inference over num_envs 4..256, DV3-XS grad steps inside the
    # overlap windows; the acceptance signal is env_steps_per_sec growing
    # monotonically 4 -> 64 with fetch amortization >= 16x at 64 envs
    env_scale = stage("env_scale", 300, lambda: measure_env_scale(precision=precision))
    if env_scale:
        record["env_scale"] = env_scale

    # MFU-lever sweep (ROADMAP item 2 close-out): chunked RSSM scan at 2/4
    # chunks, scan_unroll=8 and the Pallas LN-GRU, each vs the base graph at
    # XL shapes (where the levers matter; PERF.md §5's table is S/XL)
    mfu_levers = stage(
        "mfu_levers",
        300,
        lambda: measure_mfu_levers(precision, size="XL", batch_size=16, measure_steps=6),
    )
    if mfu_levers:
        record["mfu_levers"] = mfu_levers

    # north-star config (BASELINE.md §C): XL single-chip compute + MFU, at the
    # reference batch (16) and at the MXU-saturating batch (64)
    xl = stage("XL_b16", 240, lambda: measure_compute(precision, size="XL", batch_size=16, measure_steps=40))
    if xl:
        record["dreamer_v3_XL"] = {k: v for k, v in xl.items() if k not in ("flops_per_step", "device_kind")}
    xl_b64 = stage("XL_b64", 240, lambda: measure_compute(precision, size="XL", batch_size=64, measure_steps=25))
    if xl_b64:
        record["dreamer_v3_XL_b64"] = {
            k: v for k, v in xl_b64.items() if k not in ("flops_per_step", "device_kind")
        }
    # XL end-to-end (player+replay+train) at the reference batch — the
    # north-star e2e the round-4 PERF.md projection extrapolated to
    # (VERDICT r4 item 9); fewer iters: each is ~8x an S-size step
    xl_e2e = stage(
        "XL_e2e_b16",
        300,
        lambda: measure_e2e(precision, size="XL", warmup_iters=3, measure_iters=30),
    )
    if xl_e2e:
        record["dreamer_v3_XL_e2e"] = {
            "grad_steps_per_sec_e2e": xl_e2e["grad_steps_per_sec_e2e"],
            "grad_steps_per_sec_e2e_serialized": xl_e2e["grad_steps_per_sec_e2e_serialized"],
        }

    # learn-health block (ISSUE 9): a tiny CPU-subprocess ppo drill whose own
    # journal supplies final loss / mean grad norm / anomaly count —
    # informational, cheap, and isolated from the chip backend
    learn_health = stage("learn_health", 180, measure_learn_health)
    if learn_health:
        record["learn_health"] = learn_health

    # serving block (ISSUE 11): the batched inference tier under an
    # in-process client swarm at three offered-load points — requests/sec,
    # p50/p99 latency and the batch-width amortization the dynamic batcher
    # achieves (PERF.md §5 is the capacity model the buckets come from)
    serving = stage("serving", 120, measure_serving)
    if serving:
        record["serving"] = serving

    # recovery block (ISSUE 13): checkpoint write ms off- vs on-critical-path
    # and measured time-to-recover from one injected kill — the drill runs a
    # CPU subprocess by design, so chip rounds carry the same numbers
    recovery = stage("recovery", 240, measure_recovery)
    if recovery:
        record["recovery"] = recovery

    # decoupled-topology overhead pair (ISSUE 14 / VERDICT item 7): coupled@7
    # vs decoupled@1+7 PPO on the virtual 8-device CPU mesh — subprocesses by
    # design, so chip rounds carry the same serialization canary.  est covers
    # the true worst case: two children, each bounded by its own timeout
    decoupled = stage("decoupled", 500, lambda: measure_decoupled(timeout_s=240.0))
    if decoupled:
        record["decoupled"] = decoupled

    # offline-RL block (ISSUE 15): loader read throughput (prefetch off/on)
    # + the env-free SAC drill's grad-steps/s from its own journal — CPU
    # subprocesses by design, so chip rounds carry the same numbers.  est
    # covers the true worst case: two children, each bounded by its own
    # 420 s timeout (the decoupled-stage lesson)
    offline = stage("offline", 860, measure_offline)
    if offline:
        record["offline"] = offline

    # FSDP-vs-DP pair (ISSUE 17): the sharded-train-state memory win and its
    # step-time cost on the virtual 8-device CPU mesh — a subprocess by
    # design, so chip rounds carry the same canary; XL shapes (where the
    # per-device bytes actually matter), short sequences to keep the CPU
    # child inside its timeout
    fsdp = stage(
        "fsdp",
        500,
        lambda: measure_fsdp(
            precision, size="XL", batch_size=8, sequence_length=8, iters=3, timeout_s=420.0
        ),
    )
    if fsdp:
        record["fsdp"] = fsdp


def _require_tpu() -> dict:
    """The device as JAX reports it; exits non-zero unless it is a TPU — a
    CPU timing is never written under the chip's metric names."""
    import sys

    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(
            f"bench: jax.devices()[0].platform is {device.platform!r}, not 'tpu' — "
            "the benchmark measures the chip or fails (run it through the chip tool)",
            file=sys.stderr,
        )
        raise SystemExit(1)
    return {"platform": device.platform, "kind": device.device_kind, "count": len(jax.devices())}


def main() -> None:
    precision = os.environ.get("BENCH_PRECISION", "bf16-mixed")
    device = _require_tpu()
    _chip_peak(device["kind"], precision)  # an unknown device_kind fails before any stage runs
    from sheeprl_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    # hard wall-clock budget: the driver must ALWAYS get the JSON line
    # (round 4's rc=124 meant zero recorded numbers — VERDICT r4 weak #1)
    budget_s = float(os.environ.get("BENCH_BUDGET_S", "1500"))
    deadline = time.monotonic() + budget_s
    record = {
        "metric": "dreamer_v3_S_grad_steps_per_sec_e2e",
        "value": None,
        "unit": "grad-steps/s end-to-end (player+env+replay+train, batch 16 x seq 64, ratio 1)",
        "vs_baseline": None,
        "baseline": "reference DV3-S Atari-100K: 25k grad steps / 14 h on RTX-3080 = 0.496/s e2e",
        "precision": precision,
        "device": device,
        # memory observability (ISSUE 4): always present.  hbm_peak_bytes is
        # the max per-device peak_bytes_in_use after the menu;
        # host_transfer_count counts the runtime's transfer-guard log lines
        # around the headline e2e stage (null when the capture is unavailable).
        "hbm_peak_bytes": None,
        "host_transfer_count": None,
        # run-lifecycle observability (ISSUE 8): train share of the pipelined
        # e2e window (set by the e2e stages).  Informational — see
        # measure_e2e; the live Telemetry/goodput gauge is the meaningful
        # production number.
        "goodput": None,
        # learning-dynamics observability (ISSUE 9): final loss / mean grad
        # norm / anomaly count from a tiny CLI drill run's own journal
        # (measure_learn_health).  Informational — null when the drill stage
        # was skipped.
        "learn_health": None,
        # serving tier (ISSUE 11): requests/sec, p50/p99 latency and mean
        # batch width at several offered loads through the real HTTP /act
        # path (measure_serving).  Null when the stage was skipped.
        "serving": None,
        # resilience (ISSUE 13): blocking vs async checkpoint write cost,
        # simulated-interval goodput with each, and the supervised
        # injected-kill drill's measured time-to-recover (measure_recovery).
        # Null when the stage was skipped.
        "recovery": None,
        # decoupled topology (ISSUE 14 / VERDICT item 7): coupled-vs-decoupled
        # PPO steady-state iteration pair on the virtual 8-device CPU mesh
        # (measure_decoupled) — the scatter/params-hop overhead ratio.  Null
        # when the stage was skipped.
        "decoupled": None,
        # offline RL (ISSUE 15): dataset read-sps with the prefetch thread
        # off vs on, plus the env-free SAC drill's grad-steps/s and live
        # dataset_read_sps from its own journal (measure_offline).  Null when
        # the stage was skipped.
        "offline": None,
        # FSDP sharding (ISSUE 17): DP-vs-FSDP DV3 step pair on the virtual
        # 8-device mesh — per-device param/opt bytes under the partition rule
        # (params_per_device_shrink ~ the ZeRO-3 win) and the step-time ratio
        # (measure_fsdp).  Null when the stage was skipped.
        "fsdp": None,
        # MFU-lever sweep (ROADMAP item 2 close-out): per-variant step_ms for
        # the chunked RSSM scan (rssm_chunks 2/4) and scan_unroll=8 vs the
        # base graph at XL shapes (measure_mfu_levers).  Null when the stage
        # was skipped.
        "mfu_levers": None,
    }
    emitted = False

    def _emit() -> None:
        nonlocal emitted
        if not emitted:
            emitted = True
            print(json.dumps(record), flush=True)

    def _on_term(signum, frame):  # noqa: ANN001
        # best-effort: if the driver times the bench out (SIGTERM) while a
        # stage is still in Python-level code, land the partial record
        # instead of nothing.  (A hang inside a blocking device call cannot
        # be preempted — the budget gates above keep stages short enough
        # that this is the rare case, not the common one.)
        record["terminated"] = f"signal {signum} mid-run — partial results"
        _emit()
        raise SystemExit(124)

    import signal

    signal.signal(signal.SIGTERM, _on_term)
    try:
        _run_chip_menu(record, precision, deadline)
    except Exception as err:  # noqa: BLE001 — the JSON line must land regardless
        record["error"] = repr(err)
    finally:
        try:
            # peak HBM across the whole menu (device allocator high-water mark)
            from sheeprl_tpu.diagnostics.memory import device_memory_stats

            stats = device_memory_stats()
            if stats:
                record["hbm_peak_bytes"] = max(
                    int(s.get("peak_bytes_in_use", 0) or 0) for s in stats
                ) or None
        except Exception:  # noqa: BLE001
            pass
        _emit()
    if record.get("value") is None or record.get("error") or record.get("stage_errors"):
        # the JSON landed, but the headline measurement is missing (budget
        # skipped it) or a stage failed: fail at the process level too so a
        # return-code-gating driver doesn't record success
        raise SystemExit(1)


if __name__ == "__main__":
    main()
