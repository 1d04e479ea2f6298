"""The Dreamer engine's two iteration orders (``algos/dreamer_v3/loop_order.py``).

Two things are held here: the controller alone, on an injected clock, and the
equivalence that licenses its choice: ``_dreamer_main`` with the HBM ring run
from one seed in each order ends with the same parameters, optimizer state,
moments and ring contents, bit for bit.
"""

import functools
import pickle
from pathlib import Path

import numpy as np
import pytest

from sheeprl_tpu.algos.dreamer_v3.loop_order import ENV_OVERLAP, ORDERS, TRAIN_FIRST, LoopOrder


# --------------------------------------------------------------------------
# the controller alone: an injected clock, no sleeps
# --------------------------------------------------------------------------
class Loop:
    """A loop whose iterations take what ``seconds(order, i)`` says."""

    def __init__(self, seconds, two_orders=True, **kwargs):
        self.now = 0.0
        self.seconds = seconds
        self.counts = {order: 0 for order in ORDERS}
        self.events = []
        self.trained = 0
        self.iterations = 0
        self.orders = []
        self.controller = LoopOrder(
            two_orders,
            clock=lambda: self.now,
            count=self._count,
            journal=lambda **fields: self.events.append(fields),
            **kwargs,
        )

    def _count(self, order):
        self.counts[order] += 1

    def run(self, n, player_acts=True, trains=True):
        for _ in range(n):
            order = self.controller.begin(player_acts, self.trained)
            self.orders.append(order)
            self.now += self.seconds(order, self.iterations)
            self.iterations += 1
            self.trained += int(trains)
        return self


def by_order(train_first_ms, env_overlap_ms):
    return lambda order, i: 1e-3 * (train_first_ms if order == TRAIN_FIRST else env_overlap_ms)


@pytest.mark.parametrize(
    "train_first_ms, env_overlap_ms, kept",
    [(24.0, 28.2, TRAIN_FIRST), (43.0, 30.0, ENV_OVERLAP), (25.0, 25.0, ENV_OVERLAP)],
    ids=["fast-env", "slow-simulator", "tie"],
)
def test_keeps_the_order_with_the_lower_median(train_first_ms, env_overlap_ms, kept):
    loop = Loop(by_order(train_first_ms, env_overlap_ms)).run(400)
    assert loop.controller.kept == kept
    assert loop.orders[-1] == kept and set(loop.orders[130:]) == {kept}
    (event,) = loop.events
    assert event["kept"] == kept and event["decision"] == 1
    assert event["train_first_median_ms"] == pytest.approx(train_first_ms)
    assert event["env_overlap_median_ms"] == pytest.approx(env_overlap_ms)


def test_decides_within_128_training_iterations_and_measures_both_orders_equally():
    loop = Loop(by_order(24.0, 28.2)).run(128)
    (event,) = loop.events
    assert event["training_iterations"] <= 128
    decided_at = event["training_iterations"]
    measured = loop.orders[:decided_at]
    # settles in today's order, then three blocks of 16 in each
    assert measured[:16] == [ENV_OVERLAP] * 16
    assert measured.count(TRAIN_FIRST) == 48 and measured.count(ENV_OVERLAP) == decided_at - 48
    blocks = [measured[i : i + 16] for i in range(16, decided_at, 16)]
    assert [b[0] for b in blocks] == [TRAIN_FIRST, ENV_OVERLAP] * 3
    assert all(len(set(b)) == 1 for b in blocks)
    assert set(loop.orders[decided_at:]) == {TRAIN_FIRST}


@pytest.mark.parametrize("faster", ORDERS)
def test_one_outlier_a_block_does_not_flip_it(faster):
    """An episode end, a metric flush or a checkpoint inside a block: half a
    second in an iteration of the order that is faster, in every one of its blocks."""
    base = by_order(24.0, 28.2) if faster == TRAIN_FIRST else by_order(43.0, 30.0)

    def seconds(order, i):
        return base(order, i) + (0.5 if order == faster and i % 16 == 7 else 0.0)

    loop = Loop(seconds).run(200)
    assert loop.controller.kept == faster
    means = {o: np.mean([seconds(o, i) for i in range(16)]) for o in ORDERS}
    assert min(means, key=means.get) != faster  # a mean would have been flipped


def test_the_first_iterations_of_a_block_are_not_timed():
    """They still carry the other order's queue: here they cost the order that wins ten times its time."""
    state = {"previous": None, "since_change": 0}

    def seconds(order, i):
        state["since_change"] = 0 if order != state["previous"] else state["since_change"] + 1
        state["previous"] = order
        base = by_order(24.0, 28.2)(order, i)
        return base * 10 if order == TRAIN_FIRST and state["since_change"] < 2 else base

    loop = Loop(seconds).run(200)
    assert loop.controller.kept == TRAIN_FIRST
    assert loop.events[0]["train_first_median_ms"] == pytest.approx(24.0)


def test_measures_again_at_its_period_and_follows_a_simulator_that_slowed():
    def seconds(order, i):  # the env takes 1 ms, then, from iteration 5,000 on, 20 ms
        return by_order(24.0, 28.2)(order, i) if i < 5_000 else by_order(43.0, 30.0)(order, i)

    loop = Loop(seconds).run(10_400)
    first, second = loop.events
    assert first["kept"] == TRAIN_FIRST and second["kept"] == ENV_OVERLAP
    assert second["decision"] == 2
    assert second["training_iterations"] - first["training_iterations"] == 10_000 + 96
    assert loop.controller.kept == ENV_OVERLAP
    # between the two measurements the order that had won ran alone
    quiet = loop.orders[first["training_iterations"] : first["training_iterations"] + 10_000]
    assert set(quiet) == {TRAIN_FIRST}
    # at most about 0.5% of the iterations of a period ran in the order that lost
    lost = loop.orders[first["training_iterations"] : second["training_iterations"]].count(ENV_OVERLAP)
    assert lost == 48 and lost / 10_096 < 0.005


def test_every_period_brings_one_measurement_of_the_same_length():
    loop = Loop(by_order(24.0, 28.2)).run(16 + 96 + 3 * (10_000 + 96) + 1)
    assert [e["decision"] for e in loop.events] == [1, 2, 3, 4]
    assert np.diff([e["training_iterations"] for e in loop.events]).tolist() == [10_096] * 3
    assert loop.counts[ENV_OVERLAP] == 16 + 4 * 48 and {e["kept"] for e in loop.events} == {TRAIN_FIRST}


@pytest.mark.parametrize(
    "two_orders, player_acts, trains",
    [(False, True, True), (True, False, True), (True, False, False)],
    ids=["host-buffer-or-dry-run", "prefill-that-trains", "prefill"],
)
def test_never_measures_where_only_one_order_exists(two_orders, player_acts, trains):
    loop = Loop(by_order(1.0, 50.0), two_orders=two_orders).run(500, player_acts=player_acts, trains=trains)
    assert set(loop.orders) == {ENV_OVERLAP}
    assert loop.events == []
    assert loop.counts == {ENV_OVERLAP: 500, TRAIN_FIRST: 0}
    assert loop.controller.decisions == 0


def test_iterations_without_a_gradient_step_are_neither_timed_nor_counted():
    """``replay_ratio`` 0.5: every other iteration trains; the others run in
    the block's order, take a second each, and decide nothing."""
    loop = Loop(lambda order, i: 0.0)

    for i in range(600):
        order = loop.controller.begin(True, loop.trained)
        loop.orders.append(order)
        trains = i % 2 == 0
        loop.now += by_order(24.0, 28.2)(order, i) if trains else 1.0
        loop.trained += int(trains)
    (event,) = loop.events
    assert event["kept"] == TRAIN_FIRST and event["training_iterations"] == 112
    assert event["train_first_median_ms"] == pytest.approx(24.0)
    assert event["env_overlap_median_ms"] == pytest.approx(28.2)


def test_prefill_before_training_does_not_eat_the_settling_time():
    loop = Loop(by_order(24.0, 28.2)).run(1_024, player_acts=False, trains=False).run(130)
    (event,) = loop.events
    assert event["training_iterations"] == 112 and loop.controller.kept == TRAIN_FIRST


@pytest.mark.parametrize("order", ORDERS)
def test_a_forced_order_is_every_iteration_that_has_two_and_never_measures(order):
    loop = Loop(by_order(1.0, 50.0) if order == ENV_OVERLAP else by_order(50.0, 1.0), force=order)
    loop.run(8, player_acts=False).run(300)
    assert loop.orders == [ENV_OVERLAP] * 8 + [order] * 300
    assert loop.events == [] and loop.controller.kept == order
    with pytest.raises(ValueError, match="force"):
        LoopOrder(True, force="fetch_first")


def test_the_counter_and_the_journal_event_read_what_it_did():
    """Through the facade: ``sheeprl_loop_order_iterations_total{order}`` on
    ``/metrics`` and one ``loop_order`` event a decision."""
    from sheeprl_tpu.diagnostics import schema
    from sheeprl_tpu.diagnostics.metrics_server import render_prometheus
    from sheeprl_tpu.diagnostics.telemetry import Telemetry

    telemetry = Telemetry({})
    assert "sheeprl_loop_order_iterations_total" not in render_prometheus(telemetry.snapshot())
    clock = {"t": 0.0}
    events = []
    controller = LoopOrder(
        True, clock=lambda: clock["t"], count=telemetry.note_loop_order, journal=lambda **f: events.append(f)
    )
    for i in range(200):
        order = controller.begin(True, i)
        clock["t"] += by_order(24.0, 28.2)(order, i)
    page = render_prometheus(telemetry.snapshot())
    assert 'sheeprl_loop_order_iterations_total{order="train_first"} 136' in page  # 48 measured + 88 kept
    assert 'sheeprl_loop_order_iterations_total{order="env_overlap"} 64' in page
    assert page.count("# TYPE sheeprl_loop_order_iterations_total counter") == 1
    assert "sheeprl_loop_order_iterations_total" in schema.METRICS and "loop_order" in schema.EVENT_KINDS
    (event,) = events
    assert set(event) == {
        "kept", "decision", "training_iterations", "samples", "env_overlap_median_ms", "train_first_median_ms"
    }
    assert event["samples"] == 3 * 14


# --------------------------------------------------------------------------
# the equivalence that licenses the choice
# --------------------------------------------------------------------------
TINY = [
    "dry_run=False",
    "checkpoint.save_last=True",
    "checkpoint.every=0",
    "env=dummy",
    "env.id=discrete_dummy",
    "env.num_envs=2",
    "env.capture_video=False",
    "buffer.memmap=False",
    "buffer.size=64",
    "buffer.device=True",
    "buffer.checkpoint=True",
    "metric.log_level=1",
    "metric.log_every=4",
    "fabric.devices=1",
    "fabric.accelerator=cpu",
    "seed=7",
    "algo.total_steps=28",
    "algo.learning_starts=8",
    "algo.replay_ratio=0.5",
    "algo.per_rank_batch_size=2",
    "algo.per_rank_sequence_length=3",
    "algo.horizon=4",
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
    "algo.run_test=False",
]
EXPERIMENTS = {
    "dreamer_v3": [],
    "p2e_dv3_exploration": ["algo.ensembles.n=3", "algo.ensembles.dense_units=8", "algo.ensembles.mlp_layers=1"],
}


def _run_in_order(order, exp, run_cli, monkeypatch, tmp_path):
    """One run of ``_dreamer_main`` with the controller's order forced by
    argument; returns the checkpoint it left and what its telemetry counted."""
    from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as dv3

    seen = {}
    make_ring, get_diagnostics = dv3.make_dreamer_replay_buffer, dv3.get_diagnostics

    def seeded_ring(*args, **kwargs):
        rb, on_device = make_ring(*args, **kwargs)
        rb.seed(11)  # the ring draws its samples from an unseeded generator
        return rb, on_device

    def kept_diagnostics(*args, **kwargs):
        seen["diag"] = get_diagnostics(*args, **kwargs)
        return seen["diag"]

    monkeypatch.setattr(dv3, "LoopOrder", functools.partial(LoopOrder, force=order))
    monkeypatch.setattr(dv3, "make_dreamer_replay_buffer", seeded_ring)
    monkeypatch.setattr(dv3, "get_diagnostics", kept_diagnostics)
    run_dir = tmp_path / order
    run_dir.mkdir()
    monkeypatch.chdir(run_dir)
    run_cli(f"exp={exp}", *TINY, *EXPERIMENTS[exp])
    (ckpt,) = sorted(Path("logs").rglob("*.ckpt"))
    with open(ckpt, "rb") as fh:
        state = pickle.load(fh)
    return state, seen["diag"].telemetry.snapshot()


@pytest.mark.parametrize("exp", sorted(EXPERIMENTS))
def test_the_two_orders_leave_the_same_state_bit_for_bit(exp, run_cli, monkeypatch, tmp_path):
    import jax

    states, snapshots = {}, {}
    for order in ORDERS:
        states[order], snapshots[order] = _run_in_order(order, exp, run_cli, monkeypatch, tmp_path)

    # both ran, each in its own order wherever the player acted (learning_starts is 4 iterations of 2 envs)
    iterations = 14
    assert snapshots[TRAIN_FIRST]["loop_order_iterations_total"] == {ENV_OVERLAP: 4, TRAIN_FIRST: iterations - 4}
    assert snapshots[ENV_OVERLAP]["loop_order_iterations_total"] == {ENV_OVERLAP: iterations}
    for order in ORDERS:
        snap = snapshots[order]
        assert snap["phase_calls_total"]["rollout"] == iterations  # one `rollout` start an iteration
        assert snap["phase_calls_total"]["rollout/action-fetch"] == iterations - 4
        assert snap["phase_seconds_total"]["buffer-sample"] > 0 and snap["phase_seconds_total"]["train"] > 0
    a, b = snapshots[ENV_OVERLAP], snapshots[TRAIN_FIRST]
    assert a["calls_total"]["train_step"] == b["calls_total"]["train_step"] >= 5
    assert a["phase_calls_total"] == b["phase_calls_total"]

    # parameters (every module the experiment has), optimizer state, moments, ring contents
    first, second = states[ENV_OVERLAP], states[TRAIN_FIRST]
    assert first.keys() == second.keys() and {"world_model", "opt_states", "moments", "rb"} <= set(first)
    leaves_a, tree_a = jax.tree_util.tree_flatten(first)
    leaves_b, tree_b = jax.tree_util.tree_flatten(second)
    assert tree_a == tree_b
    arrays = 0
    for x, y in zip(leaves_a, leaves_b):
        if isinstance(x, np.ndarray):
            arrays += 1
            assert x.dtype == y.dtype and x.shape == y.shape and x.tobytes() == y.tobytes()
        else:
            assert x == y
    assert arrays > 40
