"""Where JAX's persistent compilation cache lives — the one place that decides.

Every entry point that compiles (the CLI, ``chip_smoke.py``, ``bench.py``,
``tools/serve.py`` through the CLI) calls :func:`enable_compile_cache` before
its first compile.  The rule:

1. ``JAX_COMPILATION_CACHE_DIR`` in the environment places the cache and this
   program never calls ``jax.config.update("jax_compilation_cache_dir", ...)``,
   whatever the run config says — a harness that measures two checkouts back
   to back, or the test suite's per-session directory, stays in charge.
2. Otherwise ``diagnostics.compilation_cache_dir``, when a run sets it (a
   deployment path), made absolute.
3. Otherwise ``<checkout>/.jax_cache``, resolved from this package's
   ``__file__`` — never from the cwd (run directories are timestamped and the
   tests ``chdir`` into tmp dirs), a pid or the clock, so every restart of
   the same checkout finds what the last one compiled.  A process restricted
   to the CPU platform (``JAX_PLATFORMS=cpu`` / ``fabric.accelerator=cpu``)
   skips this default: a checkout travels between hosts (the chip tool copies
   it), XLA:CPU executables are built for the compiling host's instruction
   set, and XLA logs two multi-kilobyte ``cpu_aot_loader`` errors on every
   hit; CPU compiles are what the tests and dry runs pay, not what a chip
   run waits for.

This is JAX's own cache (keyed on the HLO, so a source edit misses).  The AOT
*executable* cache (``diagnostics/telemetry.py``) skips lowering and stays
opt-in through ``diagnostics.compilation_cache_dir`` alone.
"""

from __future__ import annotations

import os
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def compile_cache_dir_to_set(configured: Optional[str] = None) -> Optional[str]:
    """The absolute directory this program must hand to JAX, or ``None`` when
    the environment already placed the cache (nothing to set)."""
    if os.environ.get(ENV_VAR):
        return None
    if configured:
        return os.path.abspath(str(configured))
    return os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache(configured: Optional[str] = None) -> Optional[str]:
    """Apply the rule above before the first compile; returns the directory
    in force (``None``: a CPU-only process with nothing asking for a cache)."""
    import jax

    target = compile_cache_dir_to_set(configured)
    if target is None:
        return os.environ[ENV_VAR]
    if not configured and jax.config.jax_platforms == "cpu":
        return None
    os.makedirs(target, exist_ok=True)
    if jax.config.jax_compilation_cache_dir != target:
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_compilation_cache_dir", target)
        # JAX opens its cache once, at the first compile: one opened for
        # another directory (an earlier run in this process) would keep
        # writing there
        compilation_cache.reset_cache()
    # default min compile time is 1s — restarts should also skip the many
    # sub-second helper jits, not just the train step
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return target
