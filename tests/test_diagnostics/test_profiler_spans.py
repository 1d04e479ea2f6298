"""One clock, named work: the facade's spans inside a ``jax.profiler``
session, the slash rule of the phase accounting, the two counter families of
``/metrics``, and the names the DV3 step and the three other executables of
an iteration are lowered under."""

from __future__ import annotations

import glob
import re

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip.span_reduce import SCOPES, UNSCOPED, scope_of
from sheeprl_tpu.diagnostics import build_diagnostics
from sheeprl_tpu.diagnostics.metrics_server import render_prometheus
from sheeprl_tpu.diagnostics.telemetry import TELEMETRY_PREFIX, Telemetry
from sheeprl_tpu.diagnostics.tracing import KNOWN_PHASES, is_part
from test_telemetry import FakeClock


def _facade(tmp_path, trace=False):
    diag = build_diagnostics(
        {
            "diagnostics": {"enabled": True, "journal": {"enabled": True}, "sentinel": {"enabled": False},
                            "trace": {"enabled": trace}},
            "algo": {"name": "t"},
            "env": {"id": "t"},
        }
    )
    return diag.open(str(tmp_path / "run"))


def test_a_span_inside_a_profiler_session_is_on_its_host_plane(tmp_path):
    diag = _facade(tmp_path)
    with diag.span("rollout"):  # no session: a flag test, nothing recorded anywhere
        pass
    jax.profiler.start_trace(str(tmp_path / "profile"))
    try:
        with diag.span("rollout", role="player"):
            with diag.span("rollout/action-fetch"):
                jnp.ones(4).block_until_ready()
        with diag.span("bookkeeping"):
            pass
    finally:
        jax.profiler.stop_trace()
    diag.close()
    (path,) = glob.glob(str(tmp_path / "profile" / "plugins" / "profile" / "*" / "*.xplane.pb"))
    found = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for event in line.events:
                if event.name.startswith("sheeprl/"):
                    found[event.name] = (event.start_ns, event.start_ns + event.duration_ns, dict(event.stats))
    assert set(found) == {"sheeprl/rollout", "sheeprl/rollout/action-fetch", "sheeprl/bookkeeping"}
    assert found["sheeprl/rollout"][2] == {"role": "player"}
    # the part lies inside its phase, on the same clock
    assert found["sheeprl/rollout"][0] <= found["sheeprl/rollout/action-fetch"][0]
    assert found["sheeprl/rollout/action-fetch"][1] <= found["sheeprl/rollout"][1] <= found["sheeprl/bookkeeping"][0]


def test_the_chrome_trace_keeps_its_spans_beside_the_annotation(tmp_path):
    import json

    diag = _facade(tmp_path, trace=True)
    with diag.span("rollout"):
        with diag.span("rollout/replay-add"):
            pass
    diag.close()
    events = json.loads((tmp_path / "run" / "trace.json").read_text())
    assert {"rollout", "rollout/replay-add"} <= {e.get("name") for e in events}


def _span_tree(tele, clock, parts):
    """One iteration of the loop's spans; with ``parts`` the slash spans too."""

    def span(name, seconds, inner=()):
        rec = tele.span_enter(name)
        for child in inner:
            span(*child)
        clock.t += seconds
        tele.span_exit(rec)

    # rollout lasts 3.0 s either way, 0.5 s of it inside the nested phase
    rollout_parts = [("rollout/obs-stage", 0.1), ("rollout/action-fetch", 2.0)] if parts else []
    span("rollout", 0.4 if parts else 2.5, inner=rollout_parts + [("env_step_async", 0.5)])
    span("buffer-sample", 1.0)
    span("train", 3.0)
    span("env_wait", 0.25)
    clock.t += 1.0  # under no span


@pytest.mark.parametrize("parts", [False, True])
def test_parts_leave_every_phase_and_every_bucket_as_they_were(parts):
    clock = FakeClock()
    tele = Telemetry({"diagnostics": {"telemetry": {"enabled": True}}}, clock=clock)
    tele.open()
    tele.interval_metrics(0)
    _span_tree(tele, clock, parts)
    out = tele.interval_metrics(10)
    snap = tele.snapshot()
    phases = {k: v for k, v in snap["phase_seconds_total"].items() if not is_part(k)}
    assert phases == pytest.approx({"rollout": 2.5, "env_step_async": 0.5, "buffer-sample": 1.0, "train": 3.0, "env_wait": 0.25})
    pct = {k.rsplit("/", 1)[1]: v for k, v in out.items() if k.startswith(TELEMETRY_PREFIX + "phase_pct/")}
    assert pct == pytest.approx({"env": 100 * 3.0 / 8.25, "fetch": 100 * 1.25 / 8.25, "train": 100 * 3.0 / 8.25,
                                 "unspanned": 100 * 1.0 / 8.25})
    if parts:  # inclusive seconds and calls, under the full name
        assert snap["phase_seconds_total"]["rollout/action-fetch"] == pytest.approx(2.0)
        assert snap["phase_calls_total"]["rollout/action-fetch"] == 1
    assert snap["phase_calls_total"]["rollout"] == 1 and snap["phase_calls_total"]["train"] == 1


def test_a_part_is_progress_and_no_state_for_the_run_state_machine():
    from sheeprl_tpu.diagnostics.goodput import _SPAN_STATES

    parts = [name for name in KNOWN_PHASES if is_part(name)]
    assert set(parts) == {"rollout/obs-stage", "rollout/player-forward", "rollout/replay-add", "rollout/action-fetch"}
    assert "bookkeeping" in KNOWN_PHASES and not set(parts) & set(_SPAN_STATES)


def test_metrics_renders_the_two_call_counter_families():
    text = render_prometheus(
        {
            "phase_seconds_total": {"rollout": 2.5, "rollout/action-fetch": 2.0},
            "phase_calls_total": {"rollout": 3, "rollout/action-fetch": 3, "train": 1234567},
            "calls_total": {"train_step": 1234567, "player": 2},
        }
    )
    lines = text.splitlines()
    for family in ("sheeprl_phase_seconds_total", "sheeprl_phase_calls_total", "sheeprl_instrumented_calls_total"):
        assert lines.count(f"# TYPE {family} counter") == 1
    assert 'sheeprl_phase_seconds_total{phase="rollout/action-fetch"} 2' in lines
    assert 'sheeprl_phase_calls_total{phase="rollout/action-fetch"} 3' in lines
    # counts are rendered exact, not to six significant digits
    assert 'sheeprl_instrumented_calls_total{fn="train_step"} 1234567' in lines
    assert 'sheeprl_phase_calls_total{phase="train"} 1234567' in lines


def test_the_snapshot_feeds_both_families(tmp_path):
    diag = _facade(tmp_path)
    step = diag.instrument("train_step", jax.jit(lambda x: x + 1), kind="train")
    with diag.span("train"):
        step(jnp.ones(2))
        step(jnp.ones(2))
    text = render_prometheus(diag._server_snapshot())
    diag.close()
    assert 'sheeprl_instrumented_calls_total{fn="train_step"} 2' in text
    assert 'sheeprl_phase_calls_total{phase="train"} 1' in text


# --------------------------------------------------------------------------
# the names the device work is lowered under
# --------------------------------------------------------------------------
def _tiny_dv3():
    import optax

    from sheeprl_tpu.algos.dreamer_v3.agent import PlayerDV3, build_agent
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import make_train_step
    from sheeprl_tpu.algos.dreamer_v3.utils import init_moments_state
    from sheeprl_tpu.config import compose, instantiate

    cfg = compose(
        [
            "exp=dreamer_v3", "env=dummy", "env.id=discrete_dummy", "algo=dreamer_v3_XS",
            "algo.per_rank_batch_size=2", "algo.per_rank_sequence_length=4", "algo.horizon=4",
            "algo.cnn_keys.encoder=[rgb]", "algo.cnn_keys.decoder=[rgb]", "algo.mlp_keys.encoder=[]",
            "algo.mlp_keys.decoder=[]", "env.capture_video=False", "metric.log_level=0",
        ]
    )
    obs_space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (3, 64, 64), np.uint8)})
    actions_dim = (4,)
    wm, actor, critic, params = build_agent(None, actions_dim, False, cfg, obs_space)
    opts = {
        k: optax.chain(optax.clip_by_global_norm(getattr(cfg.algo, k).clip_gradients),
                       instantiate(getattr(cfg.algo, k).optimizer))
        for k in ("world_model", "actor", "critic")
    }
    opt_states = {k: opts[k].init(params[k]) for k in opts}
    step = make_train_step(wm, actor, critic, opts, cfg, actions_dim, False)
    T, B = 4, 2
    batch = {
        "rgb": jnp.zeros((T, B, 3, 64, 64), jnp.float32),
        "actions": jnp.zeros((T, B, 4), jnp.float32),
        "rewards": jnp.zeros((T, B, 1), jnp.float32),
        "terminated": jnp.zeros((T, B, 1), jnp.float32),
        "is_first": jnp.zeros((T, B, 1), jnp.float32),
    }
    player = PlayerDV3(wm, actor, actions_dim, num_envs=1)
    return step, (params, opt_states, init_moments_state(), batch, jax.random.PRNGKey(0), jnp.float32(0.02)), player, params


def test_the_dv3_step_names_its_six_scopes_and_the_player_its_step():
    step, args, player, params = _tiny_dv3()
    lowered = step.lower(*args)
    text = lowered.as_text(debug_info=True)
    assert "module @jit_train_step" in text
    names = set(re.findall(r'loc\("([^"]+)"', text))
    # the benchmark's reduction finds every one of its scopes among the program's names
    assert {scope_of(name) for name in names} == set(SCOPES) | {UNSCOPED}
    # forward and backward carry the same scope name, inside jvp / transpose(jvp)
    assert any("transpose(jvp(rssm_scan))" in name for name in names)
    assert any("jvp(imagination)/while" in name for name in names)
    # the optimizer's scope is outside every differentiated function
    assert any(name.startswith("jit(train_step)/optim/") for name in names)

    player.init_states(params["world_model"])
    obs = {"rgb": jnp.zeros((1, 3, 64, 64), jnp.float32)}
    lowered = player._step.lower(params["world_model"], params["actor"], player.state, obs, jax.random.PRNGKey(1), False, None)
    assert "module @jit_player_step" in lowered.as_text()


def test_the_ring_lowers_under_replay_gather_and_replay_add():
    from sheeprl_tpu.data.device_buffer import replay_add, replay_gather

    buf = {"x": jnp.zeros((8, 2, 3), jnp.float32)}
    gather = replay_gather.lower(buf, jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.int32), 2)
    assert "module @jit_replay_gather" in gather.as_text()
    add = replay_add.lower(buf, {"x": jnp.zeros((2, 3), jnp.float32)}, jnp.zeros((2,), jnp.int32), jnp.arange(2))
    assert "module @jit_replay_add" in add.as_text()
