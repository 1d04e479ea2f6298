"""Stateful serving acceptance e2e (ISSUE 16): recurrent and model-based
policies trained through the REAL CLI, served over HTTP sessions, and proven
to reproduce the training-side player loop (identical actions; state bit for
bit at equal dispatch width, to a few ulp across widths) — including
``is_first`` resets, LRU eviction + re-init, multi-model routing with independent
promotion gates, and the request-log -> offline-training flywheel.
"""

from __future__ import annotations

import glob
import importlib.util
import json
import os
import shutil
import threading
import time
import urllib.error
import urllib.request
from collections import OrderedDict
from pathlib import Path

import numpy as np
import pytest
import yaml

from sheeprl_tpu.cli import run
from sheeprl_tpu.config import compose_group, deep_merge
from sheeprl_tpu.diagnostics.journal import read_journal
from sheeprl_tpu.serving.server import ServeApp
from sheeprl_tpu.utils.utils import dotdict

REPO_ROOT = Path(__file__).resolve().parents[2]

RECURRENT_TINY = [
    "exp=ppo_recurrent",
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
    "algo.per_rank_sequence_length=4",
    "algo.per_rank_num_batches=2",
    "algo.update_epochs=1",
    "algo.dense_units=8",
    "algo.encoder.dense_units=8",
    "algo.rnn.lstm.hidden_size=8",
    "algo.mlp_keys.encoder=[state]",
    "algo.cnn_keys.encoder=[]",
    "algo.run_test=False",
]

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


def _post_act(url: str, obs: dict, **extra) -> dict:
    payload = json.dumps({"obs": obs, **extra}).encode()
    with urllib.request.urlopen(
        urllib.request.Request(url + "/act", data=payload), timeout=30
    ) as resp:
        return json.loads(resp.read())


def _get_json(url: str, path: str) -> dict:
    with urllib.request.urlopen(url + path, timeout=10) as resp:
        return json.loads(resp.read())


def _serve_cfg(ckpt: Path, **serving_overrides) -> dotdict:
    """The ``cli.serve`` config merge, inlined so the app runs in-process."""
    with open(ckpt.parent.parent / "config.yaml") as fp:
        cfg = dotdict(yaml.safe_load(fp))
    serving = compose_group("serving", "default")
    deep_merge(serving, cfg.get("serving") or {})
    deep_merge(
        serving,
        {
            "batch_buckets": [2, 4],
            "max_delay_ms": 250.0,
            "journal_every_s": 0.0,
            "reload": {"poll_s": 0.1},
            **serving_overrides,
        },
    )
    cfg.serving = serving
    return cfg


def _wait_for(predicate, timeout_s: float = 20.0, what: str = "condition"):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        value = predicate()
        if value:
            return value
        time.sleep(0.05)
    pytest.fail(f"timed out waiting for {what}")


def _run_monitor_module():
    spec = importlib.util.spec_from_file_location(
        "run_monitor", REPO_ROOT / "tools" / "run_monitor.py"
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# ---------------------------------------------------------------------------
# (1) golden parity: HTTP sessions == training player, bit for bit
# ---------------------------------------------------------------------------


def test_ppo_recurrent_http_sessions_bit_identical_to_player():
    """A recurrent policy trained through the real CLI, served over HTTP with
    N interleaved sessions, against the TRAINING player itself
    (``algos/ppo_recurrent/players.py::LSTMPlayer``, greedy, one env a
    session: its reset on done, its one-hot prev-action feed): every
    action identical, including the ``reset`` flag mid-episode and the
    re-initialized state after an LRU eviction; the recurrent state equal to
    a few float32 ulp (the served width-2 dispatch and the player's width-1
    apply are different XLA executables — bit equality across dispatch widths
    is not something the backend promises)."""
    run([*RECURRENT_TINY, "dry_run=True", "checkpoint.save_last=True"])
    (ckpt,) = sorted(Path("logs").rglob("*.ckpt"))

    cfg = _serve_cfg(
        ckpt, sessions={"capacity": 2}, reload={"enabled": False}
    )
    app = ServeApp(cfg, str(ckpt))
    try:
        host, port = app.start()
        url = f"http://{host}:{port}"
        assert app.service.compile_count == 4  # (bucket, mode) executables
        assert app.handle.stateful and app.service.sessions is not None

        import jax

        from sheeprl_tpu.algos.ppo_recurrent.agent import build_agent
        from sheeprl_tpu.algos.ppo_recurrent.players import LSTMPlayer
        from sheeprl_tpu.algos.ppo_recurrent.utils import KeyStream
        from sheeprl_tpu.envs.env import make_env
        from sheeprl_tpu.utils.checkpoint import load_state

        state = load_state(str(ckpt))
        env = make_env(cfg, cfg.seed, 0, None, "test")()
        n_actions = int(env.action_space.n)
        agent, params, _ = build_agent(
            None, (n_actions,), False, cfg, env.observation_space, state["agent"]
        )
        env.close()

        def fresh_player():
            player = LSTMPlayer(agent, cfg, greedy=True)
            player.start(None, KeyStream(jax.random.PRNGKey(0)), num_envs=1, rollout_steps=1, seq_len=1)
            return player

        def player_step(player, obs_row, is_first):
            """One step of the loop's rollout at num_envs=1, as the loop
            drives its player."""
            dones = np.full((1, 1), is_first, np.float32)
            player.begin_step(dones)
            actions, _ = player.fetch(player.act(params, player.stage({"state": obs_row}, dones)))
            return actions[0]

        # interleaved sessions over capacity 2: c's arrival evicts b, b's
        # return evicts a, a's return evicts c — each return re-inits
        ops = [
            ("a", False),
            ("b", False),
            ("a", False),
            ("b", False),
            ("a", True),  # explicit mid-episode reset
            ("c", False),  # evicts b
            ("b", False),  # returns as a NEW session; evicts a
            ("a", False),  # returns as a NEW session; evicts c
        ]
        mirror: "OrderedDict[str, LSTMPlayer]" = OrderedDict()
        rng = np.random.default_rng(7)
        for step_no, (sid, reset) in enumerate(ops):
            obs_row = rng.standard_normal(10).astype(np.float32)
            if sid in mirror:
                mirror.move_to_end(sid)
                player = mirror[sid]
            else:
                if len(mirror) >= 2:
                    mirror.popitem(last=False)
                player = fresh_player()
            is_first = 1.0 if (reset or sid not in mirror) else 0.0
            mirror[sid] = player
            ref_action = player_step(player, obs_row, is_first)

            response = _post_act(
                url, {"state": obs_row.tolist()}, session=sid, reset=reset
            )
            assert response["action"] == ref_action.tolist(), (
                f"step {step_no}: served action diverged from the player "
                f"(session {sid!r}, reset={reset})"
            )
            assert response["batch_rows"] == 1 and response["batch_width"] == 2
            assert response["sessions_active"] <= 2

        # session accounting: 2 resident, 3 deterministic evictions
        store = app.service.sessions
        assert store.sessions() == ["b", "a"]
        assert store.created_total == 5 and store.evictions_total == 3
        # the device-resident slab state itself matches the player mirror
        # to a few ulp (a far stronger parity than the argmax'd actions)
        for sid in ("b", "a"):
            slot = store._lru[sid]
            np.testing.assert_allclose(
                np.asarray(store.slab["hx"])[slot], np.asarray(mirror[sid].hx)[0], rtol=1e-5, atol=1e-7
            )
            np.testing.assert_allclose(
                np.asarray(store.slab["cx"])[slot], np.asarray(mirror[sid].cx)[0], rtol=1e-5, atol=1e-7
            )
            np.testing.assert_array_equal(
                np.asarray(store.slab["prev_actions"])[slot], mirror[sid].prev_actions[0]
            )

        health = _get_json(url, "/healthz")
        model = health["models"]["default"]
        assert model["stateful"] is True
        assert model["sessions"] == {
            "active": 2,
            "capacity": 2,
            "evictions_total": 3,
        }
        with urllib.request.urlopen(url + "/metrics", timeout=10) as resp:
            metrics_text = resp.read().decode()
        assert "\nsheeprl_sessions_active 2" in metrics_text
        assert "\nsheeprl_sessions_capacity 2" in metrics_text
        assert "\nsheeprl_sessions_evictions_total 3" in metrics_text
    finally:
        app.close()

    events = read_journal(os.path.join(app.log_dir, "journal.jsonl"))
    kinds = [e["event"] for e in events]
    assert kinds[0] == "serve_start" and kinds[-1] == "run_end"
    evicts = [e for e in events if e["event"] == "session_evict"]
    assert [e["session"] for e in evicts] == ["b", "a", "c"]
    assert all(e["model"] == "default" and e["capacity"] == 2 for e in evicts)


def test_dreamer_v3_session_steps_match_player():
    """The Dreamer RSSM session step against ``PlayerDV3`` — op for op, on
    the same params, including the masked reset blend.  The representation
    sample depends on each row's POSITION in the batch (one key over [B]),
    so the serving dispatch is pinned to the exact batch composition the
    player sees: two sessions, one width-2 dispatch per round."""
    import gymnasium as gym
    import jax

    from sheeprl_tpu.algos.dreamer_v3.agent import PlayerDV3, build_agent
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.serving.loader import build_policy
    from sheeprl_tpu.serving.server import PolicyService

    cfg = compose(
        [
            "exp=dreamer_v3",
            "env=dummy",
            "env.id=discrete_dummy",
            "algo.per_rank_batch_size=2",
            "algo.per_rank_sequence_length=8",
            "algo.dense_units=8",
            "algo.mlp_layers=1",
            "algo.world_model.encoder.cnn_channels_multiplier=2",
            "algo.world_model.recurrent_model.recurrent_state_size=8",
            "algo.world_model.representation_model.hidden_size=8",
            "algo.world_model.transition_model.hidden_size=8",
            "algo.world_model.discrete_size=4",
            "algo.world_model.stochastic_size=4",
            "algo.cnn_keys.encoder=[]",
            "algo.cnn_keys.decoder=[]",
            "algo.mlp_keys.encoder=[state]",
            "algo.mlp_keys.decoder=[state]",
            "metric.log_level=0",
        ]
    )
    obs_space = gym.spaces.Dict(
        {"state": gym.spaces.Box(-np.inf, np.inf, (10,), np.float32)}
    )
    action_space = gym.spaces.Discrete(3)
    handle = build_policy(cfg, obs_space, action_space, None)
    assert handle.stateful and set(handle.state_spec) == {
        "recurrent",
        "stochastic",
        "actions",
    }
    wm_def, actor_def, _, _ = build_agent(None, (3,), False, cfg, obs_space)
    wm_params = handle.params["world_model"]
    actor_params = handle.params["actor"]
    player = PlayerDV3(wm_def, actor_def, (3,), num_envs=2)

    svc = PolicyService(
        handle,
        {
            "batch_buckets": [2],
            "max_delay_ms": 2000.0,
            "greedy": True,
            "sessions": {"capacity": 4},
        },
        aot=True,
    ).start()
    try:
        rng = np.random.default_rng(11)

        def dispatch_pair(obs_batch, resets):
            """Submit u then v so ONE width-2 dispatch holds rows [u, v] —
            the same batch layout the player's num_envs=2 step uses."""
            out = {}

            def first():
                out["u"] = svc.act(
                    {"state": obs_batch[0].tolist()}, session="u", reset=resets[0]
                )

            thread = threading.Thread(target=first)
            thread.start()
            _wait_for(
                lambda: svc.batcher.queue_depth() == 1, what="row u queued"
            )
            out["v"] = svc.act(
                {"state": obs_batch[1].tolist()}, session="v", reset=resets[1]
            )
            thread.join(timeout=120)
            assert out["u"]["dispatch_id"] == out["v"]["dispatch_id"]
            return np.stack(
                [np.asarray(out["u"]["action"]), np.asarray(out["v"]["action"])]
            )

        # round 1: both sessions fresh (is_first=1) == a full player init
        obs = rng.standard_normal((2, 10)).astype(np.float32)
        player.init_states(wm_params)
        ref = player.get_actions(
            wm_params, actor_params, {"state": obs}, jax.random.PRNGKey(0), greedy=True
        )
        got = dispatch_pair(obs, [False, False])
        np.testing.assert_array_equal(got, np.asarray(ref))

        # round 2: both continue (is_first=0) — carried state must match
        obs = rng.standard_normal((2, 10)).astype(np.float32)
        ref = player.get_actions(
            wm_params, actor_params, {"state": obs}, jax.random.PRNGKey(0), greedy=True
        )
        got = dispatch_pair(obs, [False, False])
        np.testing.assert_array_equal(got, np.asarray(ref))

        # round 3: u resets mid-episode, v continues — the masked blend
        obs = rng.standard_normal((2, 10)).astype(np.float32)
        player.init_states(wm_params, reset_mask=np.asarray([[1.0], [0.0]]))
        ref = player.get_actions(
            wm_params, actor_params, {"state": obs}, jax.random.PRNGKey(0), greedy=True
        )
        got = dispatch_pair(obs, [True, False])
        np.testing.assert_array_equal(got, np.asarray(ref))
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# (2) multi-model: routing, per-model metrics, independent promotion
# ---------------------------------------------------------------------------


def test_multi_model_routing_and_independent_promotion():
    run([*PPO_TINY, "dry_run=True", "checkpoint.save_last=True"])
    (ckpt,) = sorted(Path("logs").rglob("*.ckpt"))
    version_dir = ckpt.parent.parent

    # the canary is its OWN run tree: its archived config, its own journal
    # (so the health gates are independent), its own watch dir
    canary_version = Path("canary_run") / "version_0"
    shutil.copytree(version_dir, canary_version)
    canary_ckpt = canary_version / "checkpoint" / ckpt.name

    cfg = _serve_cfg(ckpt)
    cfg.serving["models"] = {"canary": str(canary_ckpt)}
    app = ServeApp(cfg, str(ckpt))
    try:
        host, port = app.start()
        url = f"http://{host}:{port}"
        assert app.registry.names() == ["canary", "default"]
        canary_service = app.registry.get("canary").service

        # -- routing: same params today, so same action either way ---------
        obs_row = (np.arange(10, dtype=np.float32) / 10.0 - 0.5).tolist()
        via_default = _post_act(url, {"state": obs_row})
        via_canary = _post_act(url, {"state": obs_row}, model="canary")
        assert via_default["action"] == via_canary["action"]

        with pytest.raises(urllib.error.HTTPError) as excinfo:
            _post_act(url, {"state": obs_row}, model="nope")
        assert excinfo.value.code == 404
        body = json.loads(excinfo.value.read())
        assert "canary" in body["error"] and "default" in body["error"]

        health = _get_json(url, "/healthz")
        assert set(health["models"]) == {"canary", "default"}
        assert health["models"]["default"]["requests_total"] == 1
        assert health["models"]["canary"]["requests_total"] == 1

        # -- per-model /metrics series + unlabeled aggregates --------------
        run_monitor = _run_monitor_module()
        with urllib.request.urlopen(url + "/metrics", timeout=10) as resp:
            metrics = run_monitor.parse_prometheus(resp.read().decode())
        assert metrics["sheeprl_serve_models"] == 2
        per_model = {
            labels["model"]: value
            for labels, value in metrics["_labels"]["sheeprl_serve_requests_total"]
        }
        assert per_model == {"canary": 1.0, "default": 1.0}
        assert metrics["sheeprl_serve_requests_total"] == 2  # aggregate
        info = metrics["_labels"]["sheeprl_run_info"][0][0]
        assert info["models"] == "canary,default"

        # -- independent promotion gates -----------------------------------
        step0 = app.service.ckpt_step
        promoted = ckpt.parent / f"ckpt_{step0 * 2}_0.ckpt"
        shutil.copyfile(ckpt, promoted)
        _wait_for(
            lambda: app.service.ckpt_step == step0 * 2, what="default promotion"
        )
        assert canary_service.ckpt_step == step0  # untouched

        # poison ONLY the canary's journal, then offer it a new checkpoint
        with open(canary_version / "journal.jsonl", "a", encoding="utf-8") as fp:
            fp.write(
                json.dumps(
                    {
                        "t": time.time(),
                        "event": "anomaly",
                        "kind": "entropy_collapse",
                        "subject": "Loss/entropy_loss",
                        "step": 40,
                    }
                )
                + "\n"
            )
        shutil.copyfile(ckpt, canary_version / "checkpoint" / f"ckpt_{step0 * 3}_0.ckpt")
        _wait_for(
            lambda: canary_service.rejections_total >= 1, what="canary rejection"
        )
        assert canary_service.ckpt_step == step0
        assert canary_service.last_promote_rejected is True
        assert app.service.last_promote_rejected is False

        # run_monitor shows the per-model panel with the canary flagged
        block = run_monitor.endpoint_status(url)
        assert "model   canary:" in block and "model   default:" in block
        canary_line = next(
            line for line in block.splitlines() if line.startswith("model   canary:")
        )
        assert "REJECTED-CKPT" in canary_line
        default_line = next(
            line for line in block.splitlines() if line.startswith("model   default:")
        )
        assert "REJECTED-CKPT" not in default_line
    finally:
        app.close()

    events = read_journal(os.path.join(app.log_dir, "journal.jsonl"))
    assert sorted(events[0].get("models") or []) == ["canary", "default"]
    promotes = [e for e in events if e["event"] == "ckpt_promote"]
    rejects = [e for e in events if e["event"] == "ckpt_reject"]
    assert [e["model"] for e in promotes] == ["default"]
    assert [e["model"] for e in rejects] == ["canary"]
    assert rejects[0]["anomalies"][0]["kind"] == "entropy_collapse"


# ---------------------------------------------------------------------------
# (3) request log -> offline training flywheel
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_request_log_to_offline_training_flywheel(run_cli, monkeypatch):
    """Production traffic becomes training data with zero conversion: serve a
    CLI-trained SAC checkpoint with request logging on, then drive one real
    env-free offline training run straight off the logged shards."""
    import gymnasium as gym

    from sheeprl_tpu.data.datasets import OfflineDataset
    from sheeprl_tpu.diagnostics.journal import find_journal
    from sheeprl_tpu.envs import dummy as dummy_envs

    # the dummy env's ±inf action bounds make the tanh actor's rescale
    # non-finite (the pre-existing quirk the offline drill notes); bound
    # them so the SERVED policy emits real actions worth logging
    orig_init = dummy_envs.ContinuousDummyEnv.__init__

    def bounded_init(self, *args, **kwargs):
        orig_init(self, *args, **kwargs)
        self.action_space = gym.spaces.Box(
            -1.0, 1.0, shape=self.action_space.shape, dtype=np.float32
        )

    monkeypatch.setattr(dummy_envs.ContinuousDummyEnv, "__init__", bounded_init)

    run_cli(
        *SAC_TINY,
        "algo.total_steps=16",
        "algo.learning_starts=100",
        "checkpoint.save_last=True",
        "run_name=collect",
    )
    (ckpt,) = sorted(Path("logs/runs/sac").rglob("*.ckpt"))

    cfg = _serve_cfg(
        ckpt,
        reload={"enabled": False},
        request_log={"enabled": True, "rotate_rows": 8},
    )
    app = ServeApp(cfg, str(ckpt))
    try:
        host, port = app.start()
        url = f"http://{host}:{port}"
        obs_dim = app.handle.obs_spec["state"][0][0]
        rng = np.random.default_rng(3)
        for _ in range(12):
            response = _post_act(
                url, {"state": rng.standard_normal(obs_dim).astype(np.float32).tolist()}
            )
            assert np.isfinite(np.asarray(response["action"])).all()
    finally:
        app.close()  # flushes + closes the per-model request log

    events = read_journal(os.path.join(app.log_dir, "journal.jsonl"))
    rotates = [e for e in events if e["event"] == "request_log_rotate"]
    assert rotates and all(e["model"] == "default" for e in rotates)
    assert sum(e["rows"] for e in rotates) == 12

    requests_root = os.path.join(app.log_dir, "requests", "default")
    ds = OfflineDataset(requests_root)
    assert ds.total_rows == 12 and not ds.skipped
    assert {"observations", "actions", "rewards", "terminated"} <= set(ds.key_specs)
    assert ds.meta["meta"]["algo"] == "sac"

    # one real env-free offline step on the logged traffic (rewards are
    # zeros at collect time — the losses must still be finite)
    run_cli(
        *SAC_TINY,
        "algo.total_steps=2",
        "checkpoint.save_last=True",
        "run_name=flywheel",
        "algo.offline.enabled=true",
        f"algo.offline.dataset_dir={requests_root}",
        "algo.offline.grad_steps_per_iter=2",
    )
    offline_events = read_journal(find_journal("logs/runs/sac/continuous_dummy/flywheel"))
    kinds = [e["event"] for e in offline_events]
    assert kinds[-1] == "run_end" and offline_events[-1]["status"] == "completed"
    opened = next(e for e in offline_events if e["event"] == "dataset_open")
    assert opened["rows"] == 12 and opened["skipped"] == 0
    metrics_events = [e for e in offline_events if e["event"] == "metrics"]
    last = metrics_events[-1]["metrics"]
    for key in ("Loss/value_loss", "Loss/policy_loss"):
        assert isinstance(last.get(key), (int, float)) and np.isfinite(last[key]), key
