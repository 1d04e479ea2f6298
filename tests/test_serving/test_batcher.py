"""Dynamic-batcher units + the padding-parity golden: a padded batched apply
must return, row for row, exactly what the unbatched apply returns — padded
rows never leak into responses."""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from sheeprl_tpu.serving.batcher import DynamicBatcher, ServeError, pick_bucket
from sheeprl_tpu.serving.server import PolicyService


def test_pick_bucket():
    assert pick_bucket(1, [8, 16, 32]) == 8
    assert pick_bucket(8, [8, 16, 32]) == 8
    assert pick_bucket(9, [8, 16, 32]) == 16
    assert pick_bucket(32, [8, 16, 32]) == 32
    with pytest.raises(ValueError):
        pick_bucket(33, [8, 16, 32])


def test_bucket_validation():
    with pytest.raises(ValueError):
        DynamicBatcher(lambda rows, greedy: ([], {}), buckets=[])
    with pytest.raises(ValueError):
        DynamicBatcher(lambda rows, greedy: ([], {}), buckets=[0, 4])


def _service(handle, **cfg):
    base = {"batch_buckets": [2, 4], "max_delay_ms": 20.0}
    base.update(cfg)
    return PolicyService(handle, base, aot=False).start()


def test_single_request_round_trip(fake_handle):
    svc = _service(fake_handle)
    try:
        result = svc.act({"state": [1, 2, 3, 4]})
        assert result["action"].tolist() == [1.0, 10.0]
        assert result["batch_width"] == 2  # padded to the smallest bucket
        assert result["batch_rows"] == 1
    finally:
        svc.close()


def test_rows_fan_back_to_their_own_requests(fake_handle):
    """Concurrent distinct rows: each response carries ITS row's sum, not a
    neighbor's and not a padding row's."""
    svc = _service(fake_handle, max_delay_ms=150.0)
    results = {}
    barrier = threading.Barrier(3)

    def client(i):
        barrier.wait()
        results[i] = svc.act({"state": np.full(4, i + 1, np.float32)})

    try:
        threads = [threading.Thread(target=client, args=(i,)) for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        svc.close()
    for i in range(3):
        assert results[i]["action"][1] == pytest.approx(4.0 * (i + 1))
    # 3 requests -> one padded-width-4 dispatch
    assert {r["dispatch_id"] for r in results.values()} == {results[0]["dispatch_id"]}
    assert results[0]["batch_width"] == 4


def test_validation_errors_are_client_errors(fake_handle):
    svc = _service(fake_handle)
    try:
        with pytest.raises(ValueError):
            svc.act({"wrong": [1]})
        with pytest.raises(ValueError):
            svc.act({"state": [1, 2]})
        with pytest.raises(ValueError):
            svc.act([1, 2, 3, 4])
    finally:
        svc.close()


def test_dispatch_failure_wakes_every_waiter(fake_handle):
    calls = {"n": 0}

    def exploding(rows, greedy):
        calls["n"] += 1
        raise RuntimeError("boom")

    batcher = DynamicBatcher(exploding, buckets=[4], max_delay_ms=50.0).start()
    errors = []

    def client():
        try:
            batcher.submit({"state": np.zeros(4, np.float32)}, True, timeout_s=5.0)
        except ServeError as err:
            errors.append(err)

    try:
        threads = [threading.Thread(target=client) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        batcher.close()
    assert len(errors) == 2 and all(e.status == 500 for e in errors)
    assert calls["n"] == 1  # one dispatch failed once, not per waiter
    assert batcher.stats()["errors_total"] == 2


def test_queue_full_is_backpressure(fake_handle):
    slow = threading.Event()

    def blocked(rows, greedy):
        slow.wait(5.0)
        return np.zeros((len(rows), 2), np.float32), {}

    batcher = DynamicBatcher(blocked, buckets=[1], max_delay_ms=0.0, max_queue=1).start()
    try:
        first = threading.Thread(
            target=lambda: batcher.submit({"s": np.zeros(1)}, True, timeout_s=5.0)
        )
        first.start()
        # the first request is being dispatched (blocked); fill the queue...
        deadline = time.monotonic() + 2.0
        while batcher.stats()["dispatches_total"] == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        second = threading.Thread(
            target=lambda: batcher.submit({"s": np.zeros(1)}, True, timeout_s=5.0)
        )
        second.start()
        deadline = time.monotonic() + 2.0
        while batcher.queue_depth() < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        # ...and the next submit must bounce with 503, not pile up
        with pytest.raises(ServeError) as excinfo:
            batcher.submit({"s": np.zeros(1)}, True, timeout_s=1.0)
        assert excinfo.value.status == 503
        slow.set()
        first.join(timeout=5)
        second.join(timeout=5)
    finally:
        slow.set()
        batcher.close()


def test_timed_out_request_is_dropped_from_queue_and_stats(fake_handle):
    """A client that gives up (504) must not waste a future batch slot, and
    an abandoned in-flight row must not poison the latency percentiles or
    break the requests = responses + errors invariant."""
    gate = threading.Event()

    def gated(rows, greedy):
        gate.wait(10.0)
        return np.zeros((len(rows), 2), np.float32), {}

    batcher = DynamicBatcher(gated, buckets=[1], max_delay_ms=0.0).start()
    try:
        # first request goes in flight (gated); second waits in the queue
        inflight = threading.Thread(
            target=lambda: batcher.submit({"s": np.zeros(1)}, True, timeout_s=10.0)
        )
        inflight.start()
        deadline = time.monotonic() + 2.0
        while batcher.stats()["dispatches_total"] == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        with pytest.raises(ServeError) as excinfo:
            batcher.submit({"s": np.zeros(1)}, True, timeout_s=0.2)
        assert excinfo.value.status == 504
        assert batcher.queue_depth() == 0, "timed-out request left in the queue"
        gate.set()
        inflight.join(timeout=5)
    finally:
        gate.set()
        batcher.close()
    stats = batcher.stats()
    assert stats["requests_total"] == 2
    assert stats["responses_total"] == 1 and stats["errors_total"] == 1
    assert stats["dispatches_total"] == 1  # the abandoned row never dispatched


def test_shutdown_fails_pending_requests(fake_handle):
    never = threading.Event()

    def blocked(rows, greedy):
        never.wait(10.0)
        return np.zeros((len(rows), 2), np.float32), {}

    batcher = DynamicBatcher(blocked, buckets=[1], max_delay_ms=0.0).start()
    outcome = {}

    def client(i):
        try:
            outcome[i] = batcher.submit({"s": np.zeros(1)}, True, timeout_s=10.0)
        except ServeError as err:
            outcome[i] = err

    threads = [threading.Thread(target=client, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 2.0
    while batcher.queue_depth() < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    never.set()
    batcher.close()
    for t in threads:
        t.join(timeout=5)
    # every client got an answer (a result or a 503) — nothing hangs
    assert len(outcome) == 3


# ---------------------------------------------------------------------------
# padding-parity golden: padded batched apply vs unbatched apply, real agent
# ---------------------------------------------------------------------------


def _tiny_ppo_handle(env_id: str):
    import gymnasium as gym

    from sheeprl_tpu.config import compose
    from sheeprl_tpu.serving.loader import build_policy

    cfg = compose(
        [
            "exp=ppo",
            "env=dummy",
            f"env.id={env_id}",
            "algo.mlp_keys.encoder=[state]",
            "algo.cnn_keys.encoder=[]",
            "algo.dense_units=8",
            "algo.mlp_layers=1",
            "fabric.accelerator=cpu",
            "fabric.devices=1",
        ]
    )
    obs_space = gym.spaces.Dict({"state": gym.spaces.Box(-20, 20, (10,), np.float32)})
    if env_id == "continuous_dummy":
        action_space = gym.spaces.Box(-1.0, 1.0, (2,), np.float32)
    else:
        action_space = gym.spaces.Discrete(4)
    return build_policy(cfg, obs_space, action_space)


@pytest.mark.parametrize("env_id", ["discrete_dummy", "continuous_dummy"])
def test_padding_parity_golden_vs_unbatched_apply(env_id):
    """Row 0 of a zero-padded width-4 greedy apply == the width-1 apply of
    the same observation — padding rows cannot bleed into valid rows through
    any batch-dependent op.  Equal to a few float32 ulp, not bit for bit: the
    two widths are different XLA executables and the backend may vectorize
    their reductions differently (XLA:CPU in jax 0.9 does, by 6e-8)."""
    import jax

    handle = _tiny_ppo_handle(env_id)
    step = handle.make_step(True)
    rng = np.random.default_rng(7)
    key = jax.random.PRNGKey(0)
    for _ in range(3):
        row = {"state": rng.normal(size=10).astype(np.float32)}
        padded = handle.assemble([row], 4)
        batched = np.asarray(step(handle.params, padded, key))
        single = np.asarray(step(handle.params, {"state": row["state"][None]}, key))
        np.testing.assert_allclose(batched[0], single[0], rtol=1e-5, atol=1e-7)


def test_padding_parity_through_the_service(fake_handle_factory):
    """The service slices exactly the valid rows: a width-2 dispatch of one
    request returns one action, computed from the real row."""
    svc = _service(fake_handle_factory(obs_dim=3))
    try:
        result = svc.act({"state": [5, 5, 5]})
        assert result["action"].shape == (2,)
        assert result["action"][1] == pytest.approx(15.0)
        assert result["batch_width"] == 2 and result["batch_rows"] == 1
    finally:
        svc.close()
