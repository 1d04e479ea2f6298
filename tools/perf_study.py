"""DV3 train-step performance study on the real chip (VERDICT r3 items 2-3).

Prints one JSON line per experiment:
- host-device latencies: async dispatch overhead + one blocking value fetch
  (what each hot-loop iteration pays the device link)
- DV3-S compute/MFU at batch 16/32/64 (weight-streaming amortization study)
- DV3-XL compute/MFU at batch 16 (the north-star config)

Usage: python tools/perf_study.py [--sizes S,XL] [--batches 16,32,64]
       python tools/perf_study.py --unroll-ab   # interleaved unroll 1-vs-8 pair
       python tools/perf_study.py --xl-levers   # unroll vs base at XL
       python tools/perf_study.py --decoupled-ab  # coupled-vs-decoupled PPO pair
                                                  # on the virtual 8-device mesh
"""

from __future__ import annotations

import json
import sys
import time

sys.path.insert(0, ".")

from bench import _require_tpu, measure_compute, measure_fetch_rtt  # noqa: E402


def measure_dispatch_and_fetch():
    import jax
    import jax.numpy as jnp
    import numpy as np

    f = jax.jit(lambda x: x + 1.0)
    x = f(jnp.zeros((256,)))
    np.asarray(x)
    t0 = time.perf_counter()
    y = x
    for _ in range(100):
        y = f(y)
    np.asarray(y)
    dispatch_ms = (time.perf_counter() - t0) * 10.0
    return {
        "experiment": "dispatch_fetch_latency",
        "dispatch_ms": round(dispatch_ms, 3),
        "fetch_rtt_ms": measure_fetch_rtt(),
    }


def measure_env_host(sleep_ms: float = 50.0, iters: int = 20, host_work_ms: float = 30.0):
    """Host-time split of the env pipeline: what ``envs.step`` used to cost on
    the hot thread vs what the split-phase layer leaves on it
    (``step_async`` issuance + the residual ``env_wait`` after ``host_work_ms``
    of overlapped work).  Pure host measurement on ``sleep_ms`` dummies.
    ``hidden_ms`` is the per-iteration env time the pipeline takes off the
    critical path (≈ min(sleep_ms, host_work_ms))."""
    import numpy as np

    from sheeprl_tpu.diagnostics.telemetry import Telemetry
    from sheeprl_tpu.envs.dummy import DiscreteDummyEnv
    from sheeprl_tpu.envs.env import vectorized_env
    from sheeprl_tpu.envs.pipeline import PipelinedVectorEnv

    def mk():
        return DiscreteDummyEnv(n_steps=1_000_000, image_size=(3, 8, 8), sleep_ms=sleep_ms)

    envs = PipelinedVectorEnv(vectorized_env([mk], sync=True))
    envs.reset(seed=0)
    actions = np.zeros(1, np.int64)
    # the live layer's own phase accounting (same Telemetry/phase_pct/* field
    # names a run journals), so this offline line diffs against live rows
    tele = Telemetry({})
    tele.open()
    step_s = async_s = wait_s = 0.0
    for _ in range(iters):  # serialized: the whole env latency is host time
        t0 = time.perf_counter()
        envs.step(actions)
        step_s += time.perf_counter() - t0
    tele.interval_metrics(None)  # phase window covers the pipelined loop only
    for _ in range(iters):  # pipelined: issue, overlap host work, collect
        t0 = time.perf_counter()
        with tele.span("env_step_async"):
            envs.step_async(actions)
        async_s += time.perf_counter() - t0
        with tele.span("train"):
            time.sleep(host_work_ms / 1e3)  # stand-in for train dispatch + fetch
        t0 = time.perf_counter()
        with tele.span("env_wait"):
            envs.step_wait()
        wait_s += time.perf_counter() - t0
    phases = tele.interval_metrics(None)
    tele.close()  # detach from the process-global compile-listener registry
    envs.close()
    env_step_ms = step_s / iters * 1e3
    env_wait_ms = wait_s / iters * 1e3
    return {
        "experiment": "env_overlap_host",
        "sleep_ms": sleep_ms,
        "host_work_ms": host_work_ms,
        "env_step_ms": round(env_step_ms, 2),
        "env_step_async_ms": round(async_s / iters * 1e3, 3),
        "env_wait_ms": round(env_wait_ms, 2),
        "hidden_ms": round(env_step_ms - env_wait_ms, 2),
        **{k: round(v, 2) for k, v in phases.items() if k.startswith("Telemetry/phase_pct/")},
    }


def measure_env_scale_host(num_envs_list=(4, 16, 64), sleep_ms: float = 0.5, iters: int = 15):
    """Host-only many-env scaling line (ISSUE 7): the sharded shm executor's
    ``env_steps_per_sec`` across env counts, no accelerator needed — isolates
    the worker-sharding win (one command/ack per WORKER + batched copy-out)
    from the device-link effects ``bench.py``'s ``env_scale`` stage adds.
    The signal: steps/s grows with ``num_envs`` while the auto heuristic can
    still add workers (one per core), then plateaus at cores/sleep_ms — the
    plateau, not a collapse, is the point: the old one-process-per-env layout
    degrades past the core count (scheduler thrash + per-env acks) instead of
    plateauing."""
    import numpy as np

    from sheeprl_tpu.envs.dummy import DiscreteDummyEnv
    from sheeprl_tpu.envs.executor import SharedMemoryVectorEnv

    out = {
        "experiment": "env_scale_host",
        "sleep_ms": sleep_ms,
        "iters": iters,
        "num_envs": [],
        "env_steps_per_sec": [],
        "envs_per_worker": [],
        "num_workers": [],
    }
    for n in num_envs_list:
        fns = [
            (lambda: DiscreteDummyEnv(n_steps=1_000_000, image_size=(3, 8, 8), vector_shape=(8,), sleep_ms=sleep_ms))
            for _ in range(n)
        ]
        envs = SharedMemoryVectorEnv(fns)  # auto envs_per_worker heuristic
        try:
            envs.reset(seed=0)
            actions = np.zeros(n, np.int64)
            for _ in range(3):
                envs.step(actions)
            t0 = time.perf_counter()
            for _ in range(iters):
                envs.step(actions)
            elapsed = time.perf_counter() - t0
        finally:
            envs.close()
        out["num_envs"].append(int(n))
        out["env_steps_per_sec"].append(round(n * iters / elapsed, 1))
        out["envs_per_worker"].append(int(envs.envs_per_worker))
        out["num_workers"].append(int(envs.num_workers))
    return out


PHASE_EXPERIMENTS = {
    # Phase isolation by config deltas vs the base (T=64, H=15, pixel obs):
    # the difference between base and each variant prices one phase.
    "horizon_1": ["algo.horizon=1"],  # base - this = imagination+actor/critic scan
    "seq_8": ["algo.per_rank_sequence_length=8"],  # (base - this)/56*64 ~ RSSM scan
    "vector_obs": [  # base - this = conv encoder+decoder stack
        "algo.cnn_keys.encoder=[]",
        "algo.cnn_keys.decoder=[]",
        "algo.mlp_keys.encoder=[state]",
        "algo.mlp_keys.decoder=[state]",
    ],
}


def _measure_interleaved_variants(
    precision: str,
    variants: dict,
    *,
    base_name: str,
    batch_size: int,
    rounds: int,
    block_steps: int,
    size: str,
    seq_len: int,
    experiment: str,
):
    """Shared interleaved A/B harness: each variant's train step is built and
    compiled once; timing then alternates between variants in short blocks
    (value-fetch barrier per block) so machine drift hits all variants
    equally.  Reports medians of per-block step times + per-block raw arrays.

    HBM note: interleaving is not free — every variant's params + optimizer
    state (+ one compiled executable each) stay resident simultaneously, so
    expect roughly len(variants) x the model-state HBM of a single run; size
    the batch accordingly before pointing this at a real chip.  The input
    batch itself is built once and shared across variants (the levers change
    compilation, not shapes), so it does not multiply.
    """
    import statistics

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import build_train_step_and_batch

    built = {}
    shared_batch = None
    for name, extra in variants.items():
        _, train_step, state, batch = build_train_step_and_batch(
            precision,
            size=size,
            batch_size=batch_size,
            sequence_length=seq_len,
            extra_overrides=extra,
        )
        if shared_batch is None:
            shared_batch = batch  # identical shapes across variants: keep ONE copy in HBM
        else:
            # drop this variant's freshly built duplicate immediately instead
            # of waiting for GC — at XL shapes the batch is HBM that the
            # next variant's compile may need
            for leaf in jax.tree_util.tree_leaves(batch):
                leaf.delete()
        del batch
        state["key"] = jax.random.PRNGKey(0)
        built[name] = (train_step, state)

    def block(name) -> float:
        train_step, state = built[name]
        batch = shared_batch
        t0 = time.perf_counter()
        for _ in range(block_steps):
            state["key"], sub = jax.random.split(state["key"])
            state["params"], state["opt_states"], state["moments_state"], metrics = train_step(
                state["params"], state["opt_states"], state["moments_state"], batch, sub, jnp.float32(0.02)
            )[:4]
        np.asarray(metrics)  # value barrier: forces the whole block's chain
        return (time.perf_counter() - t0) / block_steps

    for name in variants:  # compile + warm
        block(name)
    times = {name: [] for name in variants}
    for _ in range(rounds):
        for name in variants:  # interleave: drift hits all variants equally
            times[name].append(block(name))
    base_med = statistics.median(times[base_name])
    return {
        "experiment": experiment,
        "rounds": rounds,
        "block_steps": block_steps,
        **{
            f"{name}_step_ms": round(statistics.median(ts) * 1e3, 2) for name, ts in times.items()
        },
        **{
            f"{name}_vs_base": round(base_med / statistics.median(ts), 4)
            for name, ts in times.items()
            if name != base_name
        },
        **{f"{name}_blocks_ms": [round(t * 1e3, 1) for t in ts] for name, ts in times.items()},
    }


def measure_xl_levers(
    precision: str,
    batch_size: int = 16,
    rounds: int = 6,
    block_steps: int = 8,
    size: str = "XL",
    seq_len: int = 64,
):
    """The unresolved XL MFU lever (VERDICT r4 weak #3) on the interleaved
    harness above: ``unroll8`` — ``algo.scan_unroll=8`` on the
    RSSM/imagination scans; a single r4 sweep showed ~6%, unconfirmed beyond
    noise (the dedicated two-arm pair is ``measure_unroll_ab``).  The Pallas
    fused LayerNorm-GRU is not an arm: its weight block does not fit VMEM at
    the XL width (``ops/pallas_gru.py``), so ``algo.rssm_pallas=True`` raises
    there."""
    return _measure_interleaved_variants(
        precision,
        {
            "base": [],
            "unroll8": ["algo.scan_unroll=8"],
        },
        base_name="base",
        batch_size=batch_size,
        rounds=rounds,
        block_steps=block_steps,
        size=size,
        seq_len=seq_len,
        experiment=f"dreamer_v3_{size}_b{batch_size}_levers_interleaved",
    )


def measure_unroll_ab(
    precision: str,
    batch_size: int = 16,
    rounds: int = 8,
    block_steps: int = 8,
    size: str = "S",
    seq_len: int = 64,
):
    """Close the scan_unroll question (PERF.md §5): a dedicated TWO-arm
    interleaved pair — unroll 1 vs unroll 8 on the identical batch,
    alternating blocks so drift hits both arms equally — reporting
    ``step_ms`` medians and the speedup ratio.

    Deliberately **step_ms, not MFU**: XLA's ``cost_analysis()`` FLOP count
    inflates under unrolling (the unrolled graph repeats the body's ops), so
    an MFU comparison would flatter the unrolled arm.  Live runs with
    ``algo.scan_unroll > 1`` journal the same caveat as a ``telemetry_cost``
    ``note`` field so the gauge is never silently over-read.  The verdict
    rule of thumb: a median ratio inside ±2% of 1.0 across rounds is noise —
    keep ``scan_unroll=1``; a stable >2% win justifies the ~unroll x compile
    cost for long production runs.
    """
    return _measure_interleaved_variants(
        precision,
        {"unroll1": [], "unroll8": ["algo.scan_unroll=8"]},
        base_name="unroll1",
        batch_size=batch_size,
        rounds=rounds,
        block_steps=block_steps,
        size=size,
        seq_len=seq_len,
        experiment=f"dreamer_v3_{size}_b{batch_size}_unroll_ab_interleaved",
    )


def main() -> None:
    import os

    sizes = os.environ.get("PERF_SIZES", "S,XL").split(",")
    batches = [int(b) for b in os.environ.get("PERF_BATCHES", "16,32,64").split(",")]
    precision = os.environ.get("BENCH_PRECISION", "bf16-mixed")
    phases = os.environ.get("PERF_PHASES", "0") == "1"
    # the chip-study tool: a non-TPU platform is a non-zero exit, and every
    # section raises through (no stage swallows a failure)
    _require_tpu()
    from sheeprl_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    # decoupled-topology overhead pair (ISSUE 14 / VERDICT item 7): coupled@7
    # vs decoupled@1+7 dryrun-style PPO on the virtual 8-device CPU mesh, in
    # CPU subprocesses
    if os.environ.get("PERF_DECOUPLED_AB", "0") == "1" or "--decoupled-ab" in sys.argv:
        from bench import measure_decoupled

        print(
            json.dumps({"experiment": "ppo_decoupled_ab_virtual8", **measure_decoupled()}),
            flush=True,
        )
        return

    # env pipeline host-time split + many-env scaling (host-only sections)
    print(json.dumps(measure_env_host()), flush=True)
    print(json.dumps(measure_env_scale_host()), flush=True)

    print(json.dumps(measure_dispatch_and_fetch()), flush=True)
    if os.environ.get("PERF_UNROLL_AB", "0") == "1" or "--unroll-ab" in sys.argv:
        print(
            json.dumps(
                measure_unroll_ab(
                    precision,
                    batch_size=int(os.environ.get("PERF_LEVER_BATCH", "16")),
                    rounds=int(os.environ.get("PERF_LEVER_ROUNDS", "8")),
                    block_steps=int(os.environ.get("PERF_LEVER_BLOCK", "8")),
                    size=os.environ.get("PERF_LEVER_SIZE", "S"),
                    seq_len=int(os.environ.get("PERF_LEVER_SEQ", "64")),
                )
            ),
            flush=True,
        )
        return
    if os.environ.get("PERF_XL_LEVERS", "0") == "1" or "--xl-levers" in sys.argv:
        lever_size = os.environ.get("PERF_LEVER_SIZE", "XL")
        lever_rounds = int(os.environ.get("PERF_LEVER_ROUNDS", "6"))
        lever_block = int(os.environ.get("PERF_LEVER_BLOCK", "8"))
        lever_batch = int(os.environ.get("PERF_LEVER_BATCH", "16"))
        lever_seq = int(os.environ.get("PERF_LEVER_SEQ", "64"))
        print(
            json.dumps(
                measure_xl_levers(
                    precision,
                    batch_size=lever_batch,
                    rounds=lever_rounds,
                    block_steps=lever_block,
                    size=lever_size,
                    seq_len=lever_seq,
                )
            ),
            flush=True,
        )
        return

    for size in sizes:
        for b in batches if size == "S" else [16]:
            res = measure_compute(precision, size=size, batch_size=b, measure_steps=60)
            res = {
                "experiment": f"dreamer_v3_{size}_b{b}",
                "grad_steps_per_sec": res.pop("grad_steps_per_sec_compute"),
                **res,
                "samples_per_sec": round(res["step_ms"] and b / (res["step_ms"] / 1e3), 1),
            }
            print(json.dumps(res), flush=True)
        if phases:
            for name, overrides in PHASE_EXPERIMENTS.items():
                res = measure_compute(
                    precision, size=size, batch_size=16, measure_steps=60, extra_overrides=overrides
                )
                res = {
                    "experiment": f"dreamer_v3_{size}_b16_{name}",
                    "grad_steps_per_sec": res.pop("grad_steps_per_sec_compute"),
                    **res,
                }
                print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
