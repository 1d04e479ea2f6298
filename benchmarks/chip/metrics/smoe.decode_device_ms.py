"""Device-busy milliseconds inside one execution of the player's decode step
(the family's ``player`` executable, ``jit_policy_step``: one token an env
through the index cache, the selection and the gathered rows), from the device trace."""

from benchmarks.chip.lm_reduce import module_ms


def read(run):
    return module_ms(run, "player")
