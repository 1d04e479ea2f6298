"""Where JAX's persistent compilation cache lives — the one place that decides.

Every entry point that compiles (the CLI, ``chip_smoke.py``,
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

This is JAX's own cache (keyed on the HLO, so a source edit misses).  Wherever
it lies, a process that may use an accelerator keys it on the HLO *with* its
metadata (``jax_compilation_cache_include_metadata_in_key``): JAX leaves the
``op_name`` and source line of every operation out of the key by default, so
an executable loaded from the cache carries the names of whoever compiled it
first, and a ``jax.named_scope`` added since does not show in a profile (seen
on the v5e, PERF.md §6 PR 27: the train step of a checkout with scopes ran
with the scopeless names of the checkout before it).  The locations then hold
one frame, the operation's own source line (``jax_traceback_in_locations_limit``
1): with the ten frames JAX writes by default the key would hold the entry
script and whatever wraps the loop, and two entry points of one checkout
would share no executable (seen too: the benchmark's command and a script
around the same harness each compiled everything).  Not
``jax_include_full_tracebacks_in_locations=False``, which in this JAX also
drops the scope path from every ``op_name`` (seen on the v5e as well).  The
price is a compile where an edit only moved the lines of traced code, and a
profile's ``source_stack`` of one frame.  The AOT
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
    cpu_only = jax.config.jax_platforms == "cpu"
    if not cpu_only:
        # a profile of the device must show this checkout's names, not a cached
        # build's; the key then holds each operation's own line, not its callers
        jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
        jax.config.update("jax_traceback_in_locations_limit", 1)
    if target is None:
        return os.environ[ENV_VAR]
    if not configured and cpu_only:
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
