"""Async split-phase env pipeline tests (ISSUE 2 tentpole).

Four pillars:

* **golden equivalence** — the pipelined executors (background-thread sync,
  EnvPool-style shared-memory workers) must produce bit-for-bit the same
  trajectories as the established ``SyncVectorEnv`` path at a fixed seed:
  obs, rewards, done flags, and the SAME_STEP autoreset artifacts
  (``final_obs`` / ``final_info`` layout included);
* **wall-clock overlap** — with ``sleep_ms`` dummies, N pipelined iterations
  (step_async -> host work -> step_wait) must complete in measurably less
  wall-clock than the serialized sum;
* **fault tolerance** — a transient env crash inside a shared-memory worker
  is absorbed by ``RestartOnException`` *inside* the worker and surfaced as
  ``infos["restart_on_exception"]`` without killing the run;
* **CLI e2e smoke** — ``env.executor=shared_memory`` drives real ppo /
  dreamer_v3 dry-runs through the CLI.
"""

from __future__ import annotations

import time
from pathlib import Path

import gymnasium as gym
import numpy as np
import pytest

from sheeprl_tpu.envs.dummy import DiscreteDummyEnv
from sheeprl_tpu.envs.env import make_env_fns, pipelined_vector_env, vectorized_env
from sheeprl_tpu.envs.executor import SharedMemoryVectorEnv
from sheeprl_tpu.envs.pipeline import PipelinedVectorEnv
from sheeprl_tpu.envs.wrappers import RestartOnException
from sheeprl_tpu.utils.utils import dotdict


def _cfg(executor=None, **env_overrides):
    env = {
        "id": "discrete_dummy",
        "num_envs": 2,
        "frame_stack": 1,
        "sync_env": True,
        "executor": executor,
        "screen_size": 16,
        "action_repeat": 1,
        "grayscale": False,
        "clip_rewards": False,
        "capture_video": False,
        "frame_stack_dilation": 1,
        "actions_as_observation": {"num_stack": -1, "noop": 0, "dilation": 1},
        "max_episode_steps": None,
        "reward_as_observation": False,
        "wrapper": {
            "_target_": "sheeprl_tpu.envs.env.get_dummy_env",
            "id": "discrete_dummy",
            "sleep_ms": 0,
        },
    }
    env.update(env_overrides)
    return dotdict(
        {
            "seed": 7,
            "env": env,
            "algo": {"cnn_keys": {"encoder": ["rgb"]}, "mlp_keys": {"encoder": ["state"]}},
        }
    )


def _assert_same_tree(a, b, path=""):
    """Structural equality for nested info dicts, ignoring episode wall time
    (``episode.t`` is elapsed seconds — inherently nondeterministic)."""
    assert set(a.keys()) == set(b.keys()), f"{path}: {sorted(a)} != {sorted(b)}"
    for k in a:
        if k == "t" and path.endswith("episode"):
            continue
        va, vb = a[k], b[k]
        if isinstance(va, dict):
            _assert_same_tree(va, vb, f"{path}.{k}")
        elif isinstance(va, np.ndarray) and va.dtype == object:
            assert len(va) == len(vb)
            for i, (xa, xb) in enumerate(zip(va, vb)):
                assert (xa is None) == (xb is None), f"{path}.{k}[{i}]"
                if isinstance(xa, dict):
                    for kk in xa:
                        np.testing.assert_array_equal(xa[kk], xb[kk])
                elif xa is not None:
                    np.testing.assert_array_equal(xa, xb)
        else:
            np.testing.assert_array_equal(va, vb, err_msg=f"{path}.{k}")


@pytest.mark.parametrize("executor", ["sync", "shared_memory"])
def test_golden_trajectory_sync_vs_pipelined(executor):
    """Same seed, same action sequence -> identical trajectories, including
    the SAME_STEP autoreset boundaries (the dummy env terminates every 5
    steps, so 12 steps cross at least two reset boundaries per env)."""
    reference = vectorized_env(make_env_fns(_cfg(), restartable=False), sync=True)
    pipelined = pipelined_vector_env(_cfg(executor=executor), make_env_fns(_cfg(), restartable=False))
    assert isinstance(pipelined, PipelinedVectorEnv)

    obs_ref, info_ref = reference.reset(seed=7)
    obs_pipe, info_pipe = pipelined.reset(seed=7)
    for k in obs_ref:
        np.testing.assert_array_equal(obs_ref[k], obs_pipe[k])
    _assert_same_tree(info_ref, info_pipe, "reset")

    rng = np.random.default_rng(3)
    boundaries = 0
    for t in range(12):
        actions = rng.integers(0, 2, size=2)
        ref = reference.step(actions)
        pipelined.step_async(actions)
        got = pipelined.step_wait()
        for k in ref[0]:
            np.testing.assert_array_equal(ref[0][k], got[0][k], err_msg=f"step {t} obs[{k}]")
        for j, name in ((1, "rewards"), (2, "terminated"), (3, "truncated")):
            np.testing.assert_array_equal(ref[j], got[j], err_msg=f"step {t} {name}")
        _assert_same_tree(ref[4], got[4], f"step{t}")
        if "final_obs" in ref[4]:
            boundaries += 1
    assert boundaries >= 2, "the golden run must cross SAME_STEP autoreset boundaries"
    pipelined.close()
    reference.close()


def _golden_sharded_run(num_envs: int, envs_per_worker: int, steps: int, min_boundaries: int):
    """Sync-vs-sharded-shm golden equality through the full wrapper stack
    (episode stats, final_obs/final_info mask layout, autoreset boundaries)."""
    cfg = _cfg(executor="shared_memory", num_envs=num_envs, envs_per_worker=envs_per_worker)
    reference = vectorized_env(make_env_fns(_cfg(num_envs=num_envs), restartable=False), sync=True)
    pipelined = pipelined_vector_env(cfg, make_env_fns(_cfg(num_envs=num_envs), restartable=False))
    shm = pipelined.envs
    assert isinstance(shm, SharedMemoryVectorEnv)
    assert shm.envs_per_worker == envs_per_worker
    assert shm.num_workers == -(-num_envs // envs_per_worker)

    obs_ref, info_ref = reference.reset(seed=7)
    obs_pipe, info_pipe = pipelined.reset(seed=7)
    for k in obs_ref:
        np.testing.assert_array_equal(obs_ref[k], obs_pipe[k])
    _assert_same_tree(info_ref, info_pipe, "reset")

    rng = np.random.default_rng(3)
    boundaries = 0
    for t in range(steps):
        actions = rng.integers(0, 2, size=num_envs)
        ref = reference.step(actions)
        pipelined.step_async(actions)
        got = pipelined.step_wait()
        for k in ref[0]:
            np.testing.assert_array_equal(ref[0][k], got[0][k], err_msg=f"step {t} obs[{k}]")
        # rewards: float32 slab end-to-end — values identical to the float64
        # reference under the float32 cast every loop applies anyway
        assert got[1].dtype == np.float32
        np.testing.assert_array_equal(np.asarray(ref[1], np.float32), got[1], err_msg=f"step {t} rewards")
        for j, name in ((2, "terminated"), (3, "truncated")):
            np.testing.assert_array_equal(ref[j], got[j], err_msg=f"step {t} {name}")
        _assert_same_tree(ref[4], got[4], f"step{t}")
        if "final_obs" in ref[4]:
            boundaries += 1
    assert boundaries >= min_boundaries, "the golden run must cross autoreset boundaries"
    pipelined.close()
    reference.close()


def test_golden_trajectory_sharded_shm_multi_env_slabs():
    """16 envs in 4-env worker slabs: one cmd/ack per worker, bit-identical
    trajectories (including two SAME_STEP autoreset waves)."""
    _golden_sharded_run(num_envs=16, envs_per_worker=4, steps=12, min_boundaries=2)


@pytest.mark.slow
def test_golden_trajectory_sharded_shm_64_envs():
    """The acceptance-scale golden: 64 envs, envs_per_worker=16 (4 workers)."""
    _golden_sharded_run(num_envs=64, envs_per_worker=16, steps=12, min_boundaries=2)


def test_auto_envs_per_worker_heuristic():
    from sheeprl_tpu.envs.executor import auto_envs_per_worker

    cores = max(1, __import__("os").cpu_count() or 1)
    assert auto_envs_per_worker(1) == 1
    # one env per worker while workers fit the cores, then slabs grow
    assert auto_envs_per_worker(cores) == 1
    assert auto_envs_per_worker(cores * 8) == 8
    n = cores * 8
    assert -(-n // auto_envs_per_worker(n)) <= cores  # worker count capped at cores


def test_pipelined_overlap_wall_clock():
    """N pipelined iterations (step_async -> host work -> step_wait) finish in
    measurably less wall-clock than the serialized sum: the sleep_ms env step
    overlaps the simulated train-dispatch work."""

    def mk():
        return DiscreteDummyEnv(n_steps=1000, image_size=(3, 8, 8), sleep_ms=60)

    envs = PipelinedVectorEnv(
        gym.vector.SyncVectorEnv([mk, mk], autoreset_mode=gym.vector.AutoresetMode.SAME_STEP)
    )
    envs.reset(seed=0)
    actions = np.zeros(2, np.int64)
    iters, host_work_s = 6, 0.040

    t0 = time.perf_counter()
    for _ in range(iters):
        envs.step(actions)
        time.sleep(host_work_s)  # stand-in for train dispatch + metric fetch
    serialized = time.perf_counter() - t0

    t0 = time.perf_counter()
    for _ in range(iters):
        envs.step_async(actions)
        time.sleep(host_work_s)
        envs.step_wait()
    pipelined = time.perf_counter() - t0
    envs.close()

    # serialized >= N*(60+40)ms, pipelined ~= N*max(60,40)ms; 0.85 leaves
    # headroom for scheduler noise on a contended CI core (sleeps, not spins)
    assert pipelined < 0.85 * serialized, f"no overlap: {pipelined:.3f}s vs {serialized:.3f}s"


class _FlakyEnv(gym.Env):
    """Raises once on the second step — transient sim crash stand-in."""

    observation_space = gym.spaces.Box(-1, 1, (3,), np.float32)
    action_space = gym.spaces.Discrete(2)

    def __init__(self):
        self.n = 0

    def reset(self, seed=None, options=None):
        return np.zeros(3, np.float32), {}

    def step(self, action):
        self.n += 1
        if self.n == 2:
            raise RuntimeError("transient sim crash")
        return np.zeros(3, np.float32), 0.0, False, False, {}


def _flaky_fn():
    return RestartOnException(_FlakyEnv, wait=0)


def test_shared_memory_worker_crash_recovers_via_restart_on_exception():
    envs = SharedMemoryVectorEnv([_flaky_fn])
    envs.reset(seed=0)
    flagged = False
    for _ in range(3):
        obs, rewards, term, trunc, infos = envs.step(np.zeros(1, np.int64))
        assert obs.shape == (1, 3)
        if "restart_on_exception" in infos:
            flagged = True
            assert bool(infos["restart_on_exception"][0])
            assert not term[0] and not trunc[0]
    assert flagged, "the restart must surface info['restart_on_exception']"
    # and the worker process survived: further steps still answer
    envs.step(np.zeros(1, np.int64))
    envs.close()


class _FlakySlabEnv(gym.Env):
    """Same spaces as the Box-obs dummy, raises once on the second step."""

    observation_space = gym.spaces.Box(-20, 20, (10,), np.float32)
    action_space = gym.spaces.Discrete(2)

    def __init__(self):
        self.n = 0

    def reset(self, seed=None, options=None):
        return np.zeros(10, np.float32), {}

    def step(self, action):
        self.n += 1
        if self.n == 2:
            raise RuntimeError("transient sim crash")
        return np.zeros(10, np.float32), 0.0, False, False, {}


def _steady_fn():
    return RestartOnException(
        lambda: DiscreteDummyEnv(n_steps=1000, dict_obs_space=False), wait=0
    )


def _flaky_slab_fn():
    return RestartOnException(_FlakySlabEnv, wait=0)


def test_slab_worker_crash_recovers_via_restart_on_exception():
    """A transient env crash INSIDE a multi-env slab is absorbed in-worker:
    the crashing env restarts, its slab siblings keep their trajectories, and
    the worker process survives."""
    fns = [_steady_fn, _steady_fn, _flaky_slab_fn, _steady_fn]
    envs = SharedMemoryVectorEnv(fns, envs_per_worker=2)  # worker 1 owns envs [2, 3]
    assert envs.num_workers == 2
    envs.reset(seed=0)
    flagged = False
    for _ in range(3):
        obs, rewards, term, trunc, infos = envs.step(np.zeros(4, np.int64))
        assert obs.shape[0] == 4
        if "restart_on_exception" in infos:
            flagged = True
            mask = np.asarray(infos["restart_on_exception"])
            assert bool(mask[2]) and not mask[[0, 1, 3]].any()
            assert not term.any() and not trunc.any()
    assert flagged, "the slab restart must surface info['restart_on_exception'][2]"
    envs.step(np.zeros(4, np.int64))  # both workers still answer
    envs.close()


def test_step_async_misuse_raises():
    envs = PipelinedVectorEnv(
        gym.vector.SyncVectorEnv(
            [lambda: DiscreteDummyEnv(image_size=(3, 8, 8))],
            autoreset_mode=gym.vector.AutoresetMode.SAME_STEP,
        )
    )
    envs.reset(seed=0)
    with pytest.raises(RuntimeError):
        envs.step_wait()
    envs.step_async(np.zeros(1, np.int64))
    with pytest.raises(RuntimeError):
        envs.step_async(np.zeros(1, np.int64))
    with pytest.raises(RuntimeError):
        envs.reset(seed=0)
    envs.step_wait()
    envs.close()


def test_step_of_a_sync_executor_runs_on_the_callers_thread():
    """``step()`` has nothing to overlap: a sync executor's envs step where the
    caller stands (the hand-over to the ``env-step`` thread is ``step_async``'s),
    and ``step()`` while a ``step_async`` is in flight is still refused."""
    import threading

    stepped_on = []

    class Marked(DiscreteDummyEnv):
        def step(self, action):
            stepped_on.append(threading.get_ident())
            return super().step(action)

    envs = PipelinedVectorEnv(
        gym.vector.SyncVectorEnv(
            [lambda: Marked(image_size=(3, 8, 8))], autoreset_mode=gym.vector.AutoresetMode.SAME_STEP
        )
    )
    envs.reset(seed=0)
    envs.step(np.zeros(1, np.int64))
    envs.step_async(np.zeros(1, np.int64))
    with pytest.raises(RuntimeError):
        envs.step(np.zeros(1, np.int64))
    envs.step_wait()
    envs.close()
    assert stepped_on[0] == threading.get_ident() and stepped_on[1] != threading.get_ident()


# ---- CLI e2e smoke: the real training loops over the shm executor ---------

_COMMON_CLI = [
    "dry_run=True",
    "checkpoint.save_last=True",
    "env=dummy",
    "env.id=discrete_dummy",
    "env.num_envs=2",
    "env.executor=shared_memory",
    "env.capture_video=False",
    "buffer.memmap=False",
    "metric.log_level=1",
    "metric.log_every=1",
    "fabric.devices=1",
    "fabric.accelerator=cpu",
]


def test_cli_smoke_ppo_shared_memory(run_cli, tmp_path, monkeypatch):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    # absolute: the cache is process-global and outlives this test's cwd.
    # Without the suite's environment variable the config key places it.
    cache_dir = str(tmp_path / "jit_cache")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    cache_knobs = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs")
    saved = {knob: getattr(jax.config, knob) for knob in cache_knobs}
    try:
        run_cli(
            "exp=ppo",
            *_COMMON_CLI,
            "env.envs_per_worker=2",  # one 2-env slab worker
            "diagnostics.trace.enabled=True",
            f"diagnostics.compilation_cache_dir={cache_dir}",
            "algo.rollout_steps=8",
            "algo.per_rank_batch_size=4",
            "algo.update_epochs=1",
            "algo.mlp_keys.encoder=[state]",
            "algo.cnn_keys.encoder=[]",
        )
    finally:
        # jax config is process-global: hand the suite its own cache back
        for knob, value in saved.items():
            jax.config.update(knob, value)
        compilation_cache.reset_cache()
    assert sorted(Path("logs").rglob("*.ckpt")), "no checkpoint written"

    # env-throughput telemetry (ISSUE 7): the batched-inference loop must
    # report env steps/s and a fetch amortization of exactly num_envs, and
    # the persistent-compilation-cache satellite must journal its directory
    import json as _json

    journal = sorted(Path("logs").rglob("journal.jsonl"))[-1]
    events = [_json.loads(line) for line in journal.read_text().splitlines() if line.strip()]
    cache_events = [e for e in events if e.get("event") == "compilation_cache"]
    assert cache_events and cache_events[0]["dir"] == cache_dir
    assert any(Path(cache_dir).iterdir()), "nothing was cached where the journal says"
    metric_rows = [e["metrics"] for e in events if e.get("event") == "metrics"]
    env_sps = [m["Telemetry/env_steps_per_sec"] for m in metric_rows if "Telemetry/env_steps_per_sec" in m]
    amort = [m["Telemetry/fetch_amortization"] for m in metric_rows if "Telemetry/fetch_amortization" in m]
    assert env_sps and env_sps[-1] > 0
    assert amort and amort[-1] == 2.0  # num_envs per blocking fetch

    # the split-phase spans must be visible in the Perfetto trace, one pair
    # per rollout step, and every emitted phase name must stay in the
    # documented vocabulary
    import json

    from sheeprl_tpu.diagnostics.tracing import KNOWN_PHASES

    traces = sorted(Path("logs").rglob("trace.json"))
    assert traces, "no trace written"
    raw = traces[-1].read_text()
    events = json.loads(raw if raw.rstrip().endswith("]") else raw + "]")
    spans = [e["name"] for e in events if e.get("ph") == "X"]
    assert spans.count("env_step_async") == 8 and spans.count("env_wait") == 8, spans
    assert set(spans) <= set(KNOWN_PHASES), sorted(set(spans) - set(KNOWN_PHASES))


def test_cli_smoke_dreamer_v3_shared_memory(run_cli):
    run_cli(
        "exp=dreamer_v3",
        *_COMMON_CLI,
        "buffer.size=8",
        "algo.per_rank_batch_size=1",
        "algo.per_rank_sequence_length=1",
        "algo.learning_starts=0",
        "algo.replay_ratio=1",
        "algo.horizon=8",
        "algo.dense_units=8",
        "algo.mlp_layers=1",
        "algo.world_model.encoder.cnn_channels_multiplier=2",
        "algo.world_model.recurrent_model.recurrent_state_size=8",
        "algo.world_model.representation_model.hidden_size=8",
        "algo.world_model.transition_model.hidden_size=8",
        "algo.cnn_keys.encoder=[rgb]",
        "algo.cnn_keys.decoder=[rgb]",
        "algo.mlp_keys.encoder=[state]",
        "algo.mlp_keys.decoder=[state]",
    )
    assert sorted(Path("logs").rglob("*.ckpt")), "no checkpoint written"
