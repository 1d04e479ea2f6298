"""The seam between the recurrent on-policy loop and its players (ISSUE 33).

The loop names no backbone; each backbone is a player of one surface
(``algos/ppo_recurrent/players.py``); what the loop hands ``train_step``
after a rollout is, per backbone, what it was before the seam."""

import gc
import inspect
from unittest import mock

import jax
import pytest

from test_ppo_recurrent_olmo import LSTM, TINY, _run_cli

SEQUENCE = {"actions", "advantages", "dones", "logprobs", "resets", "returns", "rewards", "values"}
F32, I32 = "float32", "int32"
# ``data`` of the first update at the tiny sizes: [L, S, ...] a step, [1, S, ...] what a sequence starts from
PINNED = {
    "lstm": {
        **{k: ((4, 4, 1), F32) for k in SEQUENCE},
        "state": ((4, 4, 10), F32),
        "prev_actions": ((4, 4, 2), F32),
        "hx0": ((1, 4, 64), F32),
        "cx0": ((1, 4, 64), F32),
    },
    "olmo_hybrid": {
        **{k: ((8, 4, 1), F32) for k in SEQUENCE},
        "actions": ((8, 4, 1), I32),
        "token": ((8, 4, 1), I32),
        "state0": {
            "layers": 3 * [{"S": ((1, 4, 2, 12, 6), F32), "conv": ((1, 4, 3, 48), F32)}]
            + [{"k": ((1, 4, 2, 24, 8), F32), "v": ((1, 4, 2, 24, 8), F32)}],
            "pos": ((1, 4), I32),
        },
    },
}


@pytest.mark.parametrize("overrides, backbone", [(LSTM, "lstm"), (TINY, "olmo_hybrid")], ids=["lstm", "olmo_hybrid"])
def test_train_step_gets_the_pinned_data(overrides, backbone):
    from sheeprl_tpu.diagnostics import Diagnostics

    seen = []
    instrument = Diagnostics.instrument

    def recording(self, name, fn, **kwargs):
        step = instrument(self, name, fn, **kwargs)

        def wrapped(params, opt_state, data, key, coefs):
            seen.append(jax.tree_util.tree_map(lambda x: (tuple(x.shape), str(x.dtype)), data))
            return step(params, opt_state, data, key, coefs)

        return wrapped

    with mock.patch.object(Diagnostics, "instrument", recording):
        _run_cli(*overrides, "dry_run=True", "algo.run_test=False")
    assert seen == [PINNED[backbone]]
    # and what the rollout's player carried ends with the loop: on the chip it is a gigabyte of the device's memory,
    # which the benchmark's reference needs (the update holds a player of its own, never started: ``main``)
    from sheeprl_tpu.algos.ppo_recurrent.players import LSTMPlayer, TokenPlayer

    gc.collect()
    assert not [p for p in gc.get_objects() if isinstance(p, (LSTMPlayer, TokenPlayer)) and hasattr(p, "num_envs")]


def test_the_two_players_answer_one_surface_and_nothing_beside_it():
    from sheeprl_tpu.algos.ppo_recurrent.players import LSTMPlayer, TokenPlayer

    surface = {"evaluate", "start", "begin_step", "stage", "act", "fetch", "end_rollout", "initial_state", "after_update", "test"}
    for player in (LSTMPlayer, TokenPlayer):
        assert {name for name, member in vars(player).items() if callable(member) and name != "__init__"} == surface
    for name in surface:  # and with the same arguments: nothing is asked of one only
        assert inspect.signature(getattr(LSTMPlayer, name)) == inspect.signature(getattr(TokenPlayer, name)), name


@pytest.mark.parametrize("name", ["main", "make_train_step"])
def test_the_loop_names_no_backbone(name):
    """ISSUE 33's grep, a function a case: what knows which backbone runs sits in ``players.py``, chosen in ``agent.py``."""
    from sheeprl_tpu.algos.ppo_recurrent import ppo_recurrent

    text = inspect.getsource(getattr(ppo_recurrent, name)).lower()
    for word in ("token_policy", "backbone", "olmo", "lstm", "hybrid", "hidden", "hx", "state0", "lstmplayer", "tokenplayer"):
        assert word not in text, f"{name} names {word!r}"
    assert "player." in text  # and it does go through the seam
