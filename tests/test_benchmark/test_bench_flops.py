"""``flops.py`` against a count made by hand at a tiny configuration, and its
scaling in T, B and H."""

import pytest

from benchmarks.chip.flops import forward_macs, peak_flops_per_s, train_step_flops

TINY = dict(
    image_channels=3, image_size=16, cnn_channels_multiplier=2, cnn_stages=2,
    dense_units=8, mlp_layers=2, recurrent_state_size=6, hidden_size=5,
    stochastic_size=2, discrete_size=3, bins=7, n_actions=4,
    sequence_length=3, batch_size=2, horizon=2,
)


def test_forward_macs_by_hand():
    f = forward_macs(TINY)
    # encoder: 16->8 (3->2 channels), 8->4 (2->4): out_h*out_w*4*4*cin*cout
    assert f["encoder"] == 8 * 8 * 16 * 3 * 2 + 4 * 4 * 16 * 2 * 4
    # decoder: latent (6 + 6) -> 4*4*4, then 4x4x4 -> 8x8x2 -> 16x16x3
    assert f["decoder"] == 12 * 4 * 4 * 4 + 4 * 4 * 16 * 4 * 2 + 8 * 8 * 16 * 2 * 3
    assert f["recurrent"] == (6 + 4) * 8 + (6 + 8) * 3 * 6
    assert f["transition"] == 6 * 5 + 5 * 6
    assert f["representation"] == (6 + 4 * 4 * 4) * 5 + 5 * 6
    assert f["reward"] == 12 * 8 + 8 * 8 + 8 * 7
    assert f["continue"] == 12 * 8 + 8 * 8 + 8 * 1
    assert f["actor"] == 12 * 8 + 8 * 8 + 8 * 4
    assert f["critic"] == f["reward"]


def test_train_step_total_by_hand():
    f = forward_macs(TINY)
    rows, B, H = 6, 2, 2
    wm = rows * 3 * (f["encoder"] + f["decoder"] + f["recurrent"] + f["transition"] + f["representation"]
                     + f["reward"] + f["continue"]) + B * 3 * f["transition"]
    img = rows * (H * (f["recurrent"] + f["transition"]) + (H + 1) * (f["critic"] + f["reward"] + f["continue"]))
    actor = rows * (H + 1) * 3 * f["actor"]
    critic = rows * H * 4 * f["critic"]
    parts = train_step_flops(TINY)
    assert parts["world_model"] == 2 * wm and parts["imagination"] == 2 * img
    assert parts["actor"] == 2 * actor and parts["critic"] == 2 * critic
    assert parts["total"] == 2 * (wm + img + actor + critic)


@pytest.mark.parametrize("key", ["sequence_length", "batch_size"])
def test_rows_scale_everything_but_the_initial_state(key):
    base, double = train_step_flops(TINY), train_step_flops({**TINY, key: 2 * TINY[key]})
    for part in ("imagination", "actor", "critic"):
        assert double[part] == 2 * base[part]
    assert double["world_model"] == pytest.approx(2 * base["world_model"], rel=0.01)


def test_horizon_scales_the_behaviour_only():
    base, longer = train_step_flops(TINY), train_step_flops({**TINY, "horizon": 4})
    assert longer["world_model"] == base["world_model"]
    assert longer["critic"] == 2 * base["critic"]
    assert longer["actor"] == base["actor"] * 5 / 3


def test_a_vector_observation_adds_its_encoder():
    assert forward_macs({**TINY, "mlp_obs_dim": 1})["encoder"] == forward_macs(TINY)["encoder"] + 1 * 8 + 8 * 8


def test_peaks_table():
    assert peak_flops_per_s("TPU v5 lite") == 197e12
    with pytest.raises(KeyError):
        peak_flops_per_s("cpu")
    with pytest.raises(KeyError):
        peak_flops_per_s("_source")
