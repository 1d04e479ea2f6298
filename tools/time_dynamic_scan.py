"""The DV3 family's dynamic-learning scan alone, at a preset's widths: the
value and gradient of a scalar of `dynamic_learning_scan`'s four outputs with
respect to the world model's parameters, the embedded observations and the
actions (`sheeprl_tpu/algos/dreamer_v3/utils.py`).

    python tools/time_dynamic_scan.py --algo dreamer_v3_XL            # on the chip: milliseconds a call
    python tools/time_dynamic_scan.py --algo dreamer_v3_S --describe  # no chip: the compiled loops' counts

It imports `sheeprl_tpu` from the directory it is run in, so the same file
times another checkout (the parent's) when run from there.  Without
``--describe`` it refuses to run off an accelerator: a CPU gives no time.
``--describe`` compiles for a described TPU v5e and prints, for each `while`
of the program (the scan's forward, then its backward), its body's
instructions but parameters, tuples, constants and bitcasts, how many of them
are fusions, how many hold a product, and the two-dimensional float32 shapes
the loop carries.  Counts, never a time.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import sys
import time

sys.path.insert(0, os.getcwd())

import gymnasium as gym  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ACTIONS_DIM = (9,)
OBS_SPACE = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (3, 64, 64), np.uint8)})


def build(algo: str, overrides):
    """``(make(seed) -> (world model, arguments), value_and_grad(world model), what was built)``
    at ``algo``'s widths and batch."""
    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.algos.dreamer_v3.utils import dynamic_learning_scan
    from sheeprl_tpu.config import compose

    cfg = compose(
        [
            "exp=dreamer_v3",
            f"algo={algo}",
            "env=dummy",
            "env.id=discrete_dummy",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.cnn_keys.decoder=[rgb]",
            "algo.mlp_keys.encoder=[]",
            "algo.mlp_keys.decoder=[]",
            "metric.log_level=0",
            *overrides,
        ]
    )
    wm_cfg = cfg.algo.world_model
    T, B = int(cfg.algo.per_rank_sequence_length), int(cfg.algo.per_rank_batch_size)
    stoch_flat = wm_cfg.stochastic_size * wm_cfg.discrete_size

    def make(seed: int):
        wm_def, _, _, params = build_agent(None, ACTIONS_DIM, False, cfg, OBS_SPACE)
        keys = jax.random.split(jax.random.PRNGKey(seed), 4)
        rgb = jnp.zeros((1, 1, 3, 64, 64), jnp.float32)
        embed = wm_def.apply(params["world_model"], {"rgb": rgb}, method="encode").shape[-1]
        return wm_def, (
            params["world_model"],
            jax.random.normal(keys[0], (T, B, embed), jnp.float32),
            jax.nn.one_hot(jax.random.randint(keys[1], (T, B), 0, ACTIONS_DIM[0]), ACTIONS_DIM[0], dtype=jnp.float32),
            jnp.zeros((T, B, 1), jnp.float32).at[0].set(1.0).at[T // 3, 1].set(1.0),
            keys[2],
        )

    def value_and_grad(wm_def):
        def scalar(wm_params, embedded, actions, is_first, key):
            outputs = dynamic_learning_scan(
                wm_def,
                wm_params,
                actions,
                embedded,
                is_first,
                key,
                stoch_flat=stoch_flat,
                recurrent_size=wm_cfg.recurrent_model.recurrent_state_size,
                cdt=jnp.float32,
            )
            return sum(jnp.mean(jnp.square(out)) for out in outputs)

        return jax.jit(jax.value_and_grad(scalar, argnums=(0, 1, 2)))

    return make, value_and_grad, {"algo": algo, "T": T, "B": B, "stoch_flat": stoch_flat}


# ---------------------------------------------------------------------------
# --describe: the compiled program's loops, from its text

_SKIPPED = {"parameter", "get-tuple-element", "tuple", "constant", "bitcast"}


def _computations(hlo: str):
    """name -> its instruction lines."""
    out, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
        if head and not line.startswith(" "):
            name = head.group(1)
            out[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None and " = " in line:
            out[name].append(line.strip())
    return out


def _opcode(line: str) -> str:
    match = re.search(r"\)?\s([a-z][\w\-]*)\(", line.split(" = ", 1)[1])
    return match.group(1) if match else "?"


def loops_of(hlo: str):
    comps = _computations(hlo)

    def holds_product(line):
        called = re.search(r"calls=%?([\w.\-]+)", line)
        body = comps.get(called.group(1), []) if called else [line]
        return any(_opcode(inner) in ("convolution", "dot") for inner in body) or _opcode(line) in ("convolution", "dot")

    loops = []
    for lines in comps.values():
        for line in lines:
            if _opcode(line) != "while":
                continue
            body = comps[re.search(r"body=%?([\w.\-]+)", line).group(1)]
            ran = [inner for inner in body if _opcode(inner) not in _SKIPPED]
            loops.append(
                {
                    "operations": len(ran),
                    "fusions": sum(_opcode(inner) == "fusion" for inner in ran),
                    "products": sum(holds_product(inner) for inner in ran),
                    # the two-dimensional float32 shapes among what the loop carries: a kernel's, if it carries one
                    "carried_2d": sorted(set(re.findall(r"f32\[(\d+,\d+)\]", line.split(" while(", 1)[0]))),
                }
            )
    return loops


def describe(algo, overrides):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    make, value_and_grad, spec = build(algo, overrides)
    chip = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    box = {}

    def abstract():
        box["wm_def"], args = make(0)
        return args

    shapes = jax.eval_shape(abstract)
    shapes = jax.tree_util.tree_map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), shapes)
    compiled = value_and_grad(box["wm_def"]).lower(*shapes).compile()
    memory = compiled.memory_analysis()
    print(json.dumps({**spec, "loops": loops_of(compiled.as_text()), "temp_bytes": memory.temp_size_in_bytes}))


def measure(algo, overrides, calls, seed):
    device = jax.devices()[0]
    if device.platform == "cpu":
        raise SystemExit("no accelerator: a CPU gives counts (--describe), never a time")
    make, value_and_grad, spec = build(algo, overrides)
    wm_def, args = make(seed)
    step = value_and_grad(wm_def)
    start = time.perf_counter()
    value, grads = jax.block_until_ready(step(*args))
    compile_s = time.perf_counter() - start
    grad_norm = float(jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree_util.tree_leaves(grads))))
    del grads
    rounds = []
    for _ in range(3):
        start = time.perf_counter()
        for _ in range(calls):
            out = step(*args)
        jax.block_until_ready(out)
        rounds.append((time.perf_counter() - start) / calls * 1e3)
    print(
        json.dumps(
            {
                **spec,
                "device": f"{device.device_kind} x {jax.device_count()}",
                "value": float(value),
                "grad_norm": grad_norm,  # two checkouts on one seed agree to float32 re-association
                "compile_s": compile_s,
                "ms_a_call": rounds,
                "ms_a_call_median": statistics.median(rounds),
                "peak_gb": device.memory_stats()["peak_bytes_in_use"] / 1e9,
            }
        )
    )


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--algo", default="dreamer_v3_XL")
    parser.add_argument("--describe", action="store_true")
    parser.add_argument("--calls", type=int, default=50)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("overrides", nargs="*")
    options = parser.parse_args()
    if options.describe:
        describe(options.algo, options.overrides)
    else:
        measure(options.algo, options.overrides, options.calls, options.seed)
