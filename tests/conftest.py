"""Test configuration.

Multi-device simulation: the reference simulates multi-node with 2-process
Gloo DDP on CPU (reference tests/test_algos/test_algos.py:16-53); the JAX
equivalent is a virtual 8-device CPU platform via
``--xla_force_host_platform_device_count`` (SURVEY §4), set *before* jax
initializes its backends.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# JAX's persistent compilation cache, per session: with the variable set the
# program never places the cache itself (utils/compile_cache.py), so the
# suite neither reads nor warms <checkout>/.jax_cache, and the tests that
# count backend compiles (recompile watchdog, Telemetry compile counters,
# warm-restart drills) see the same cold directory on every run.
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    import atexit
    import shutil
    import tempfile

    os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(prefix="sheeprl_jax_cache_")
    atexit.register(shutil.rmtree, os.environ["JAX_COMPILATION_CACHE_DIR"], ignore_errors=True)

import pytest  # noqa: E402

# Each multi-device run these tests exercise compiles a real sharded graph
# for tens of seconds.  The tier-1 smoke (-m 'not slow', hard wall-clock
# budget) keeps one representative per algo family / sharding surface —
# test_ppo[2-discrete], test_a2c[2-discrete],
# test_sac[2], test_ppo_recurrent[2-discrete], the decoupled tests, the DV3
# sharded-step + quantile HLO checks, and the sharded-buffer unit trio — and
# defers these redundant siblings.  tests/run_tests.py's CI suites run
# without the marker filter, so they stay fully covered there.
_TIER1_DEFERRED_TO_CI = {
    "tests/test_algos/test_algos.py::test_ppo[2-multidiscrete_dummy]",
    "tests/test_algos/test_algos.py::test_ppo[2-continuous_dummy]",
    "tests/test_algos/test_algos.py::test_ppo_resume[2]",
    "tests/test_algos/test_algos.py::test_a2c[2-multidiscrete_dummy]",
    "tests/test_algos/test_algos.py::test_a2c[2-continuous_dummy]",
    "tests/test_algos/test_algos.py::test_sac_sample_next_obs[2]",
    "tests/test_algos/test_algos.py::test_ppo_recurrent[2-continuous_dummy]",
    "tests/test_data/test_device_buffer.py::test_dreamer_v3_e2e_with_sharded_device_buffer",
    "tests/test_parallel/test_dp_sharding.py::test_offpolicy_step_is_sharded_with_collectives[droq]",
    "tests/test_parallel/test_dp_sharding.py::test_offpolicy_step_is_sharded_with_collectives[sac_ae]",
    # The four longest single tests of the suite (40-65 s each, measured with
    # --durations): fitting the newly-unblocked 2-device proofs inside the
    # tier-1 wall-clock budget means deferring these to the CI suites.  Their
    # tier-1 surfaces stay covered by cheaper siblings — bf16 correctness by
    # test_dreamer_v3_bf16_e2e / test_ppo_bf16_e2e, P2E by the exploration
    # tests, journal crash-safety by the truncation-recovery unit tests.
    "tests/test_parallel/test_precision.py::test_dv3_bf16_mixed_loss_parity_and_dtypes",
    "tests/test_parallel/test_precision.py::test_dv3_bf16_true_param_dtype",
    "tests/test_algos/test_algos.py::test_p2e_dv3_finetuning_from_exploration_checkpoint[1]",
    "tests/test_diagnostics/test_cli_e2e.py::test_sigkilled_run_leaves_recoverable_journal",
    # PR 6 (many-env scaling) added ~40s of tier-1 tests (sharded-shm goldens,
    # slab-crash recovery, slab-add equivalence, env-telemetry asserts) and
    # the uncapped suite measured 819s — defer another ~80s of redundant
    # heavy SIBLINGS (measured with --durations=40): each deferred node's
    # surface keeps a cheaper tier-1 representative — P2E dv1/dv2 via [1-1],
    # P2E dv3 + dv3 action-space breadth via their discrete variants (dv3
    # continuous imagination-gradients stay via test_dreamer_v3
    # [1-continuous_dummy]), the dv1/dv2 device-buffer e2e via [dreamer_v1].
    "tests/test_algos/test_algos.py::test_p2e_dv1_dv2_exploration_and_finetuning[1-2]",
    "tests/test_algos/test_algos.py::test_p2e_dv3_exploration[1-continuous_dummy]",
    "tests/test_algos/test_algos.py::test_dreamer_v3[1-multidiscrete_dummy]",
    "tests/test_data/test_device_buffer.py::test_dv1_dv2_e2e_with_device_buffer[dreamer_v2]",
    # PR 7 (goodput observability) added ~60s of tier-1 tests (state-machine/
    # watchdog units + the two CLI acceptance e2es: the injected-stall drill
    # and the SIGKILL-then-resume killed-segment run) and the uncapped suite
    # measured 867s — defer another ~117s of redundant heavy siblings
    # (--durations=40): bf16-true e2e keeps the bf16-mixed e2e + the
    # bf16-compute HLO check as tier-1 representatives; the jepa training e2e
    # keeps test_jepa_evaluate_roundtrip (tiny jepa trained through the real
    # entrypoint, then evaluated); dv3 long-sequences keeps the episode-buffer
    # boundary units + the async-pipeline autoreset goldens + dv3[1-discrete];
    # dv2 use_continues and the dv1/dv2 continuous variants keep their
    # discrete siblings (continuous imagination stays via
    # test_dreamer_v3[1-continuous_dummy]).
    "tests/test_parallel/test_precision.py::test_dreamer_v3_bf16_e2e[bf16-true]",
    "tests/test_algos/test_algos.py::test_dreamer_v3_jepa[1]",
    "tests/test_algos/test_algos.py::test_dreamer_v3_long_sequences_with_mid_episode_dones[1]",
    "tests/test_algos/test_algos.py::test_dreamer_v2_use_continues[1]",
    "tests/test_algos/test_algos.py::test_dreamer_v2[1-continuous_dummy]",
    "tests/test_algos/test_algos.py::test_dreamer_v1[1-continuous_dummy]",
    # ... and the dv3 resume e2e (30s): checkpoint-resume through the real
    # CLI stays tier-1 via test_goodput's SIGKILL-then-resume killed-segment
    # e2e (which also asserts the resumed segment trains and completes);
    # dreamer-specific resume-state restoration stays covered in the CI e2e
    # suite.
    "tests/test_algos/test_algos.py::test_dreamer_v3_resume[1]",
}


def pytest_collection_modifyitems(config, items):
    nodeids = set()
    for item in items:
        nodeids.add(item.nodeid)
        if item.nodeid in _TIER1_DEFERRED_TO_CI:
            item.add_marker(pytest.mark.slow)
    # A renamed/re-parametrized test would silently fall out of the deferral
    # list and back into the tier-1 wall-clock budget; flag stale entries
    # whenever their file was collected (a warning, not an assert, so
    # single-test invocations of a listed file still work).
    collected_files = {n.split("::", 1)[0] for n in nodeids}
    stale = {
        n for n in _TIER1_DEFERRED_TO_CI if n.split("::", 1)[0] in collected_files and n not in nodeids
    }
    if stale and len(items) > len(_TIER1_DEFERRED_TO_CI):
        import warnings

        warnings.warn(
            f"_TIER1_DEFERRED_TO_CI entries matched no collected test (renamed?): {sorted(stale)}",
            stacklevel=1,
        )


@pytest.fixture(autouse=True)
def _tmp_logs(tmp_path, monkeypatch):
    """Keep run artifacts (logs/, checkpoints) inside pytest tmp dirs."""
    monkeypatch.chdir(tmp_path)
    yield


@pytest.fixture
def run_cli():
    """Drive the real CLI the way `python sheeprl.py ...` does.  New tests
    should use this instead of re-rolling the argv mock (two pre-existing
    module-local `_run_cli` helpers in test_algos/test_precision remain to be
    migrated)."""
    import sys
    from unittest import mock

    def _run(*args: str) -> None:
        from sheeprl_tpu.cli import run

        argv = ["sheeprl_tpu", *args]
        with mock.patch.object(sys, "argv", argv):
            run(argv[1:])

    return _run
