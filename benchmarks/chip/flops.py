"""FLOPs one DreamerV3 gradient step needs, from shapes alone.

Matrix multiplications and convolutions only (2 FLOPs a multiply-add); norms,
activations, losses and the optimizers' elementwise updates are left out, and
so is anything the program computes twice: a part that is differentiated
counts forward + backward = 3x its forward, a part that only feeds values
counts once.  Nothing here reads ``cost_analysis()``.

``shapes`` is the ``shapes`` object of a configuration's file.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Mapping

HERE = os.path.dirname(os.path.abspath(__file__))


def _dense_stack(d_in: int, units: int, layers: int) -> int:
    """Multiply-adds of one row through ``layers`` dense layers of ``units``."""
    return d_in * units + max(layers - 1, 0) * units * units


def forward_macs(shapes: Mapping[str, int]) -> Dict[str, float]:
    """Multiply-adds of ONE row through each part's forward pass."""
    m, stages = shapes["cnn_channels_multiplier"], shapes["cnn_stages"]
    size, chans = shapes["image_size"], shapes["image_channels"]
    units, layers = shapes["dense_units"], shapes["mlp_layers"]
    rec, hidden = shapes["recurrent_state_size"], shapes["hidden_size"]
    stoch = shapes["stochastic_size"] * shapes["discrete_size"]
    actions, bins = shapes["n_actions"], shapes["bins"]
    latent = stoch + rec

    enc, c_in = 0, chans
    for i in range(stages):
        out = size // 2 ** (i + 1)
        c_out = 2**i * m
        enc += out * out * 16 * c_in * c_out
        c_in = c_out
    start = size // 2**stages
    embed = c_in * start * start
    mlp_obs = shapes.get("mlp_obs_dim", 0)
    if mlp_obs:
        enc += _dense_stack(mlp_obs, units, layers)
        embed += units

    dec = latent * start * start * c_in
    side = start
    for i in range(stages):
        c_out = chans if i == stages - 1 else 2 ** (stages - i - 2) * m
        dec += side * side * 16 * c_in * c_out  # each input pixel reaches 16 outputs
        c_in, side = c_out, side * 2

    recurrent = (stoch + actions) * units + (rec + units) * 3 * rec
    transition = rec * hidden + hidden * stoch
    representation = (rec + embed) * hidden + hidden * stoch
    return {
        "encoder": enc,
        "decoder": dec,
        "recurrent": recurrent,
        "transition": transition,
        "representation": representation,
        "reward": _dense_stack(latent, units, layers) + units * bins,
        "continue": _dense_stack(latent, units, layers) + units,
        "actor": _dense_stack(latent, units, layers) + units * actions,
        "critic": _dense_stack(latent, units, layers) + units * bins,
    }


def train_step_flops(shapes: Mapping[str, int]) -> Dict[str, float]:
    """FLOPs of one gradient step by part, and their ``total``."""
    T, B, H = shapes["sequence_length"], shapes["batch_size"], shapes["horizon"]
    f = forward_macs(shapes)
    rows = T * B
    world_model = rows * 3 * (
        f["encoder"] + f["decoder"] + f["recurrent"] + f["transition"]
        + f["representation"] + f["reward"] + f["continue"]
    ) + B * 3 * f["transition"]  # the learned initial state, once a step
    # imagination: the discrete actor's objective stops the gradient at the
    # advantage, so the H-step rollout and its value, reward and continue
    # read-outs are forward only; the actor is differentiated on its H+1 rows
    imagination = rows * (
        H * (f["recurrent"] + f["transition"])
        + (H + 1) * (f["critic"] + f["reward"] + f["continue"])
    )
    actor = rows * (H + 1) * 3 * f["actor"]
    critic = rows * H * (3 * f["critic"] + f["critic"])  # + the target critic's forward
    parts = {
        "world_model": 2.0 * world_model,
        "imagination": 2.0 * imagination,
        "actor": 2.0 * actor,
        "critic": 2.0 * critic,
    }
    parts["total"] = sum(parts.values())
    return parts


def peak_flops_per_s(device_kind: str) -> float:
    """bf16 peak of one chip from ``peaks.json``; an unknown kind is an error."""
    with open(os.path.join(HERE, "peaks.json")) as fh:
        peaks = json.load(fh)
    if device_kind not in peaks or device_kind.startswith("_"):
        raise KeyError(f"device kind {device_kind!r} is not in benchmarks/chip/peaks.json")
    return float(peaks[device_kind]["bf16_flops_per_s"])
