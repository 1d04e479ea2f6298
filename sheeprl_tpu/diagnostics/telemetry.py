"""Performance telemetry: recompilation watchdog + MFU/goodput accounting.

The run-health subsystem (journal/sentinel/tracing, ISSUE 1) answers "is the
run *healthy*?"; this module answers "is the run *fast*?" — continuously, from
inside the run itself (the benchmark's numbers are ``benchmarks/chip/``'s,
from a profiler trace: PERF.md).  Three mechanisms, all behind the ``Diagnostics`` facade:

* **Recompilation watchdog** — the training loops wrap their jitted train /
  rollout steps with :meth:`Telemetry.instrument`.  Every dispatch computes
  the argument *signature* (pytree structure + per-leaf shape/dtype/weak-type);
  a signature never seen before is exactly the condition under which
  ``jax.jit`` compiles, so each new one is journaled as a ``recompile`` event
  carrying a leaf-level diff against the previous signature.  A global
  ``jax.monitoring`` listener independently counts every backend compile in
  the process (including un-instrumented helpers), and where monitoring is
  unavailable the wrapper falls back to probing the jitted function's
  ``_cache_size()`` around the dispatch.  Too many recompiles inside a sliding
  window journals a ``recompile_storm`` warning — the silent perf killer this
  watchdog exists for.

* **MFU / goodput accounting** — for ``kind="train"`` instrumented steps the
  first dispatch goes through the AOT path (``fn.lower(*args).compile()``):
  the exact compiled executable's ``cost_analysis()`` FLOPs are captured once
  at first compile *and* the executable is kept for dispatch, so instrumenting
  costs zero extra compiles.  Per log interval the dispatched train FLOPs over
  wall-clock give ``Telemetry/tflops_per_sec`` and — against the device-kind
  peak table (or ``telemetry.mfu.peak_tflops_per_device``) —
  ``Telemetry/mfu``; the policy-step counter gives ``Telemetry/sps``.

* **Persistent AOT executable cache** — with
  ``diagnostics.compilation_cache_dir`` set, every executable the AOT path
  compiles is also serialized to disk
  (``jax.experimental.serialize_executable``) keyed by (fn name, dispatch
  signature, config hash) and stamped with a jax/jaxlib/platform
  fingerprint.  A restarted process loads the executable instead of
  recompiling — production restarts and recompile storms cost seconds, not
  minutes — journaling ``aot_cache_hit`` per loaded signature;
  ``aot_cache_miss`` records why a fresh compile ran (``absent`` /
  ``corrupt`` / ``fingerprint_mismatch`` / ``store_failed``), and a corrupt
  or stale entry always falls back to a fresh compile that overwrites it.
  This complements JAX's own on-disk compilation cache (enabled from the
  same directory at CLI startup): that one caches *compilation*, this one
  caches the loaded executable, skipping even the lowering/cache-probe work
  on the hot restart path and surviving backends where the XLA cache is
  unavailable.

* **Phase attribution** — the facade's existing ``span`` hooks (rollout /
  env_step_async / env_wait / buffer-sample / train / checkpoint) feed a
  nesting-aware self-time accumulator (a child span's time is subtracted from
  its parent), so each interval also reports where the wall-clock went:
  ``Telemetry/phase_pct/{train,env,fetch,other,unspanned}``.

Emission rides the rank-0 logger proxy: ``JournalingLogger`` asks the facade
to augment each aggregated-metrics interval with the ``Telemetry/*`` gauges
before the TensorBoard/W&B backend and the journal see it, so every algorithm
inherits live perf telemetry without loop changes.
"""

from __future__ import annotations

import os
import threading
import time
import warnings
from collections import deque
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

from sheeprl_tpu.diagnostics.tracing import is_part, profiler_annotation

TELEMETRY_PREFIX = "Telemetry/"

# Peak dense-matmul FLOP/s per chip by device kind (the live gauge's own table;
# the benchmark's is ``benchmarks/chip/peaks.json``).
# Unknown kinds (CPU, forced-host platforms) resolve to None: MFU is then
# only reported when `telemetry.mfu.peak_tflops_per_device` is set — an
# unknown denominator would make the gauge silently wrong, not conservative.
_PEAKS: Dict[str, Dict[str, float]] = {
    "v5e": {"bf16": 197e12, "f32": 98.5e12},
    "v4": {"bf16": 275e12, "f32": 137.5e12},
    "v5p": {"bf16": 459e12, "f32": 229.5e12},
}


def resolve_peak_flops(device_kind: str, precision: str) -> Optional[float]:
    """Per-device peak FLOP/s for a device kind + fabric precision, or None
    when the kind is unrecognized (no guessing: see `_PEAKS` note)."""
    kind = (device_kind or "").lower()
    table = None
    if "v5p" in kind:
        table = _PEAKS["v5p"]
    elif "v4" in kind:
        table = _PEAKS["v4"]
    elif any(t in kind for t in ("v5 lite", "v5e", "v5lite")):
        table = _PEAKS["v5e"]
    if table is None:
        return None
    return table["bf16"] if ("bf16" in precision or "16" in precision) else table["f32"]


# ---------------------------------------------------------------------------
# signatures


def tree_signature(args: Tuple[Any, ...], kwargs: Mapping[str, Any]) -> Tuple[str, Tuple]:
    """Hashable dispatch signature of a call: pytree structure + per-leaf
    (shape, dtype, weak_type).  Non-array leaves (Python scalars that become
    jit constants / static args) contribute their type and repr, so a static
    argument flip also registers as a new signature — which is exactly when
    jit recompiles."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, dict(kwargs)))
    sig: List[Tuple] = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            sig.append((tuple(shape), str(dtype), bool(getattr(leaf, "weak_type", False))))
        else:
            sig.append(("pyleaf", type(leaf).__name__, repr(leaf)[:48]))
    return (str(treedef), tuple(sig))


def signature_diff(
    old: Optional[Tuple[str, Tuple]], new: Tuple[str, Tuple], paths: List[str]
) -> List[str]:
    """Human-readable leaf-level diff between two signatures (what the
    ``recompile`` journal event carries)."""
    if old is None:
        return ["first compile"]
    changes: List[str] = []
    if old[0] != new[0]:
        changes.append("pytree structure changed")
    old_leaves, new_leaves = old[1], new[1]
    n = max(len(old_leaves), len(new_leaves))
    for i in range(n):
        o = old_leaves[i] if i < len(old_leaves) else None
        nw = new_leaves[i] if i < len(new_leaves) else None
        if o == nw:
            continue
        label = paths[i] if i < len(paths) else f"leaf[{i}]"
        changes.append(f"{label}: {_fmt_leaf(o)} -> {_fmt_leaf(nw)}")
        if len(changes) >= 16:  # a storm of changed leaves needs no full list
            changes.append(f"... ({n - i - 1} more leaves)")
            break
    return changes or ["signature changed"]


def _fmt_leaf(leaf_sig: Optional[Tuple]) -> str:
    if leaf_sig is None:
        return "<absent>"
    if leaf_sig[0] == "pyleaf":
        return f"{leaf_sig[1]}({leaf_sig[2]})"
    shape, dtype, weak = leaf_sig
    return f"{dtype}{list(shape)}" + ("~" if weak else "")


def _leaf_paths(args: Tuple[Any, ...], kwargs: Mapping[str, Any]) -> List[str]:
    import jax

    try:
        flat, _ = jax.tree_util.tree_flatten_with_path((args, dict(kwargs)))
        return [jax.tree_util.keystr(path) for path, _ in flat]
    except Exception:  # pragma: no cover - keystr availability
        return []


# ---------------------------------------------------------------------------
# global compile monitor (jax.monitoring)

_monitor_lock = threading.Lock()
_monitor_state = {"installed": False, "available": None}
_active_collectors: List["Telemetry"] = []


def _on_event_duration(name: str, secs: float, **kw: Any) -> None:
    if "backend_compile" not in name:
        return
    for collector in list(_active_collectors):
        collector._note_backend_compile(float(secs))


def monitoring_available() -> bool:
    """Install the process-wide ``jax.monitoring`` compile listener (once) and
    report whether the events API exists in this jax."""
    with _monitor_lock:
        if _monitor_state["installed"]:
            return bool(_monitor_state["available"])
        _monitor_state["installed"] = True
        try:
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(_on_event_duration)
            _monitor_state["available"] = True
        except Exception:
            _monitor_state["available"] = False
        return bool(_monitor_state["available"])


def _attach_collector(telemetry: "Telemetry") -> None:
    with _monitor_lock:
        if telemetry not in _active_collectors:
            _active_collectors.append(telemetry)


def _detach_collector(telemetry: "Telemetry") -> None:
    with _monitor_lock:
        if telemetry in _active_collectors:
            _active_collectors.remove(telemetry)


# ---------------------------------------------------------------------------
# instrumented dispatch


class _Instrumented:
    """Wrapper around one jitted callable: signature watch + cost capture.

    ``kind="train"`` goes through the AOT path (lower → compile → keep the
    executable): the FLOPs come from the *exact* executable that runs, and no
    second backend compile ever happens.  Executables are cached per
    signature, mirroring jit's own cache, so bouncing between two shapes
    (e.g. the shape-change fault injection) compiles each once, like jit.
    Any failure in the AOT path — lowering, compiling, or a dispatch
    rejection — permanently falls back to the native jit call and is
    journaled, so telemetry can never take training down.
    """

    def __init__(
        self,
        telemetry: "Telemetry",
        name: str,
        fn: Callable,
        kind: str,
        donate_argnums: Tuple[int, ...] = (),
        cost_note: Optional[str] = None,
    ):
        self._telemetry = telemetry
        self._fn = fn
        self.name = name
        self.kind = kind
        # what the call site DECLARED it donates — the memory monitor verifies
        # the buffers were actually consumed at first dispatch
        self.donate_argnums = tuple(donate_argnums or ())
        # caller-supplied caveat on the cost_analysis FLOPs (e.g. unrolled
        # scans inflate them) — journaled with every telemetry_cost event so
        # MFU is never silently over-reported on such graphs
        self.cost_note = cost_note
        self._use_aot = kind == "train" and telemetry.cost_analysis_enabled
        self._signature: Optional[Tuple[str, Tuple]] = None
        self._seen: set = set()
        self._compiled: Dict[Tuple[str, Tuple], Any] = {}
        # FLOPs are per signature: e.g. SAC's scan-over-gradient-steps train
        # step legitimately runs at several batch-count signatures (pretrain
        # burst vs steady state) with proportionally different FLOPs
        self._flops_by_sig: Dict[Tuple[str, Tuple], float] = {}
        self._cache_size_probe = getattr(fn, "_cache_size", None)
        self._last_cache_size = 0

    def __call__(self, *args: Any, **kwargs: Any):
        tele = self._telemetry
        sig = tree_signature(args, kwargs)
        # mirror jit's cache semantics: only a NEVER-seen signature compiles;
        # bouncing back to a previous signature is a cache hit, not a recompile
        new_sig = sig not in self._seen
        if new_sig:
            if self._seen:
                tele._watchdog_observe(self, sig, args, kwargs)
            self._seen.add(sig)
            if tele._goodput is not None:
                # a never-seen signature is exactly the condition under which
                # jit compiles: flip the run state to `compiling` before the
                # (potentially minutes-long) compile starts
                tele._goodput.note_compile_start(self.name)
        if self._use_aot:
            compiled = self._compiled.get(sig)
            if compiled is None:
                # a compile (or a cache load) inside a profiled window is
                # named on the profile's host plane
                with profiler_annotation(f"compile/{self.name}"):
                    compiled = self._aot_compile(sig, args, kwargs)
            if compiled is not None:
                self._signature = sig
                try:
                    out = self._invoke(compiled, args, kwargs)
                except Exception as err:
                    if getattr(err, "_sheeprl_diag_handled", False):
                        # the memory monitor already journaled this (blocked
                        # host transfer / OOM forensics): it is a run problem,
                        # not an AOT-path problem — do NOT fall back
                        raise
                    # sharding/committed-ness corner the AOT call rejects:
                    # permanently revert to the native dispatch path
                    self._use_aot = False
                    self._compiled.clear()
                    tele._journal(
                        "telemetry_fallback",
                        fn=self.name,
                        stage="aot_dispatch",
                        error=repr(err)[:200],
                    )
                    out = self._invoke(self._fn, args, kwargs, retry=True)
                tele._record_call(self)
                return out
        self._signature = sig
        out = self._invoke(self._fn, args, kwargs)
        if new_sig and self._cache_size_probe is not None:
            # compile-cache-size probe (the no-jax.monitoring fallback): a
            # grown cache confirms the signature change was a real compile —
            # counted only when the monitoring listener can't (no double count)
            try:
                size = int(self._cache_size_probe())
                if size > self._last_cache_size:
                    self._last_cache_size = size
                    if not getattr(tele, "_monitoring_ok", False):
                        tele._note_backend_compile(0.0)
            except Exception:  # pragma: no cover - private API drift
                self._cache_size_probe = None
        tele._record_call(self)
        return out

    def _invoke(self, fn: Callable, args: Tuple[Any, ...], kwargs: Mapping[str, Any], retry: bool = False):
        """The actual dispatch, routed through the memory monitor's guarded
        scope (transfer guard / audits / OOM forensics) when one is attached.
        ``retry`` marks the AOT-fallback re-dispatch of the same logical step
        (the monitor must not count it twice)."""
        mem = self._telemetry._memory
        if mem is None:
            return fn(*args, **kwargs)
        return mem.guarded_call(self, lambda: fn(*args, **kwargs), args, kwargs, count_call=not retry)

    def _fresh_compile(self, args, kwargs):
        """The one place a new executable is built — the warm-restart tests
        monkeypatch/count this to prove a cached restart compiles nothing."""
        return self._fn.lower(*args, **kwargs).compile()

    def _aot_compile(self, sig, args, kwargs):
        tele = self._telemetry
        cache_path = fingerprint = None
        if tele.aot_cache_dir:
            cache_path = aot_cache_path(tele.aot_cache_dir, self.name, sig, tele._aot_cache_salt)
            fingerprint = aot_cache_fingerprint()
            hit, miss_reason = _aot_cache_read(cache_path, fingerprint)
            if hit is not None:
                compiled, flops = hit
                if flops:
                    self._flops_by_sig[sig] = flops
                self._compiled[sig] = compiled
                if tele._memory is not None:
                    tele._memory.note_executable(self.name, compiled)
                hit_fields = dict(fn=self.name, path=cache_path, flops_per_call=flops)
                if self.cost_note:
                    # the warm restart never journals a telemetry_cost event,
                    # so the FLOPs-inflation caveat must ride the hit itself —
                    # the loaded FLOPs feed Telemetry/mfu exactly like fresh
                    # ones would
                    hit_fields["note"] = self.cost_note
                tele._journal("aot_cache_hit", **hit_fields)
                return compiled
            tele._journal(
                "aot_cache_miss", fn=self.name, stage="load", reason=miss_reason, path=cache_path
            )
        try:
            t0 = time.perf_counter()
            compiled = self._fresh_compile(args, kwargs)
            compile_s = time.perf_counter() - t0
            flops = _cost_flops(compiled)
            if flops:
                self._flops_by_sig[sig] = flops
                cost_fields = dict(
                    fn=self.name, flops_per_call=flops, compile_s=round(compile_s, 3)
                )
                if self.cost_note:
                    cost_fields["note"] = self.cost_note
                tele._journal("telemetry_cost", **cost_fields)
            self._compiled[sig] = compiled
            if tele._memory is not None:
                # the executable's memory_analysis (activation temps etc.)
                # feeds the memory_breakdown event — zero extra compiles
                tele._memory.note_executable(self.name, compiled)
            if cache_path is not None:
                store_err = _aot_cache_write(cache_path, fingerprint, compiled, flops)
                if store_err is not None:
                    # backends without executable serialization: the run is
                    # unaffected, but the next restart will compile again —
                    # journal it so "why was the restart cold?" has an answer
                    tele._journal(
                        "aot_cache_miss",
                        fn=self.name,
                        stage="store",
                        reason=f"store_failed: {store_err}",
                        path=cache_path,
                    )
            return compiled
        except Exception as err:
            self._use_aot = False
            self._compiled.clear()
            tele._journal(
                "telemetry_fallback", fn=self.name, stage="aot_compile", error=repr(err)[:200]
            )
            return None

    @property
    def flops_per_call(self) -> Optional[float]:
        """FLOPs of the signature dispatched last (None until captured)."""
        if self._signature is not None and self._signature in self._flops_by_sig:
            return self._flops_by_sig[self._signature]
        # fallback for signatures whose AOT capture failed: any known one
        return next(iter(self._flops_by_sig.values()), None)


def _cost_flops(compiled: Any) -> Optional[float]:
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        return float(cost.get("flops", 0.0)) or None
    except Exception:
        return None


# ---------------------------------------------------------------------------
# persistent AOT executable cache (diagnostics.compilation_cache_dir)

#: Bumped when the on-disk entry layout changes; part of the fingerprint so
#: old entries invalidate cleanly instead of failing to unpickle.
AOT_CACHE_FORMAT = 1


def _code_fingerprint() -> str:
    """Version component of the cache fingerprint: package version plus — in
    a git checkout — the HEAD revision (read from ``.git`` directly, no
    subprocess).  The executable cache skips lowering entirely, so unlike
    JAX's own compilation cache it can never notice a source edit via the
    HLO hash; this component invalidates on version bumps and commits
    instead.  (Uncommitted source edits in a dev checkout still hit stale
    entries — clear the cache dir when iterating on graph code.)"""
    try:
        import sheeprl_tpu

        version = str(getattr(sheeprl_tpu, "__version__", "?"))
        root = os.path.dirname(os.path.dirname(os.path.abspath(sheeprl_tpu.__file__)))
        head_path = os.path.join(root, ".git", "HEAD")
        rev = ""
        if os.path.exists(head_path):
            with open(head_path) as fh:
                head = fh.read().strip()
            if head.startswith("ref:"):
                ref_path = os.path.join(root, ".git", *head.split(" ", 1)[1].split("/"))
                if os.path.exists(ref_path):
                    with open(ref_path) as fh:
                        rev = fh.read().strip()[:12]
            else:
                rev = head[:12]
        return f"{version}@{rev}" if rev else version
    except Exception:  # pragma: no cover - never block the cache on this
        return "?"


def aot_cache_fingerprint() -> str:
    """Environment stamp an executable is only valid under: code version
    (package version + git HEAD when available), jax + jaxlib versions,
    backend platform, device kind and device count (a serialized executable
    is compiled FOR a specific code revision, runtime and topology)."""
    import jax

    try:
        import jaxlib

        jaxlib_version = getattr(jaxlib, "__version__", "?")
    except Exception:  # pragma: no cover - jaxlib always ships with jax
        jaxlib_version = "?"
    try:
        devices = jax.devices()
        kind = devices[0].device_kind if devices else ""
        count = len(devices)
    except Exception:  # pragma: no cover - pre-init probes
        kind, count = "", 0
    return "|".join(
        [
            f"fmt{AOT_CACHE_FORMAT}",
            _code_fingerprint(),
            jax.__version__,
            str(jaxlib_version),
            jax.default_backend(),
            str(kind),
            str(count),
        ]
    )


def aot_cache_path(cache_dir: str, name: str, sig: Tuple[str, Tuple], salt: str) -> str:
    """Entry file for one (fn, dispatch signature, config) triple.  The
    fingerprint is deliberately NOT part of the key: a jax upgrade then reads
    the old entry and journals ``fingerprint_mismatch`` (observable
    invalidation) instead of silently orphaning files."""
    import hashlib

    digest = hashlib.sha256(repr((name, sig, salt)).encode()).hexdigest()[:32]
    safe = "".join(c if c.isalnum() or c in "-_" else "_" for c in name)[:48]
    return os.path.join(str(cache_dir), f"{safe}-{digest}.aotx")


def _aot_cache_read(path: str, fingerprint: str):
    """(compiled, flops) from one cache entry, or (None, reason) on any miss.
    Every failure mode — missing file, truncated/corrupt pickle, wrong
    fingerprint, deserialize rejection — is a *reason string*, never an
    exception: the caller always has the fresh-compile fallback."""
    import pickle

    if not os.path.exists(path):
        return None, "absent"
    try:
        with open(path, "rb") as fh:
            entry = pickle.load(fh)
        if not isinstance(entry, dict):
            return None, "corrupt"
    except Exception:
        return None, "corrupt"
    if entry.get("fingerprint") != fingerprint:
        return None, "fingerprint_mismatch"
    try:
        from jax.experimental import serialize_executable

        compiled = serialize_executable.deserialize_and_load(
            entry["payload"], entry["in_tree"], entry["out_tree"]
        )
        return (compiled, entry.get("flops")), None
    except Exception:
        return None, "corrupt"


def _aot_cache_write(path: str, fingerprint: str, compiled: Any, flops: Optional[float]) -> Optional[str]:
    """Serialize ``compiled`` to ``path`` (atomic tmp+rename so a crashed
    writer can only ever leave a *missing* entry, not a half one).  Returns an
    error string on failure (backends without executable serialization),
    None on success."""
    import pickle

    try:
        from jax.experimental import serialize_executable

        payload, in_tree, out_tree = serialize_executable.serialize(compiled)
        entry = {
            "fingerprint": fingerprint,
            "flops": flops,
            "payload": payload,
            "in_tree": in_tree,
            "out_tree": out_tree,
        }
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as fh:
            pickle.dump(entry, fh)
        os.replace(tmp, path)
        return None
    except Exception as err:
        return repr(err)[:200]


# ---------------------------------------------------------------------------
# telemetry core


class Telemetry:
    """Per-run perf accounting: watchdog state, FLOPs/phase/step counters and
    the interval math behind the ``Telemetry/*`` gauges.

    Thread-safe (spans may close on whatever thread runs the loop; the
    metrics server snapshots from its own thread).  ``clock`` is injectable
    for deterministic tests.
    """

    def __init__(self, cfg: Optional[Mapping[str, Any]], clock: Callable[[], float] = time.perf_counter):
        cfg = cfg or {}
        diag_cfg = (cfg.get("diagnostics") or {}) if cfg else {}
        tele_cfg = diag_cfg.get("telemetry") or {}
        self.enabled = bool(tele_cfg.get("enabled", True))
        wd_cfg = tele_cfg.get("watchdog") or {}
        self.watchdog_enabled = bool(wd_cfg.get("enabled", True))
        # clamped: threshold 0 would turn EVERY recompile into a storm
        self.storm_threshold = max(1, int(wd_cfg.get("storm_threshold", 5)))
        self.storm_window_s = float(wd_cfg.get("storm_window_s", 60.0))
        inject = wd_cfg.get("inject_shape_change_iter")
        self.inject_shape_change_iter = None if inject is None else int(inject)
        mfu_cfg = tele_cfg.get("mfu") or {}
        self.mfu_enabled = bool(mfu_cfg.get("enabled", True))
        self.cost_analysis_enabled = self.mfu_enabled and bool(mfu_cfg.get("cost_analysis", True))
        self._peak_override = mfu_cfg.get("peak_tflops_per_device")
        http_cfg = tele_cfg.get("http") or {}
        self.http_enabled = bool(http_cfg.get("enabled", False))
        self.http_host = str(http_cfg.get("host", "127.0.0.1"))
        self.http_port = int(http_cfg.get("port", 0))
        # persistent AOT executable cache: same directory as JAX's on-disk
        # compilation cache (diagnostics.compilation_cache_dir — both are
        # restart accelerators and both are off when it is null).  The salt
        # folds the config identity into every cache key: two runs with
        # identical dispatch signatures but different graphs (e.g.
        # scan_unroll / rssm_chunks flips) must never share an executable.
        self.aot_cache_dir = str(diag_cfg.get("compilation_cache_dir") or "") or None
        self._aot_cache_salt = ""
        if self.aot_cache_dir:
            try:
                from sheeprl_tpu.diagnostics import config_hash
                from sheeprl_tpu.utils.utils import dotdict

                # hash only the GRAPH-shaping config sections: restarts and
                # resumes legitimately differ in run identity (run_name,
                # checkpoint.resume_from, seed, logging) and must still hit;
                # anything that changes the compiled graph without changing
                # the dispatch signature (scan_unroll, rssm_chunk_burn_in,
                # horizon, sentinel/health toggles, precision) must MISS.
                # Sections are deep-converted to plain dicts first: the CLI
                # hands dotdict sections, which yaml.safe_dump rejects.
                graph_cfg = {}
                for k in ("algo", "env", "fabric", "distribution", "diagnostics", "buffer"):
                    v = (cfg or {}).get(k)
                    if v is None:
                        continue
                    graph_cfg[k] = dotdict(v).as_dict() if isinstance(v, dict) else v
                self._aot_cache_salt = config_hash(graph_cfg)
            except Exception as err:
                # an un-hashable config must DISABLE the cache, not fall back
                # to an empty salt: an empty salt would let two different
                # graphs with identical dispatch signatures share an
                # executable
                self.aot_cache_dir = None
                warnings.warn(
                    "diagnostics.compilation_cache_dir is set but the config could not "
                    f"be hashed for the AOT executable cache key ({err!r}); the "
                    "executable cache is DISABLED for this run (JAX's own on-disk "
                    "compilation cache is unaffected).",
                    RuntimeWarning,
                )

        self._precision = str((cfg.get("fabric") or {}).get("precision", "32-true")) if cfg else "32-true"
        self._clock = clock
        # the facade attaches the MemoryMonitor here so instrumented
        # dispatches pick up the transfer guard / audits / OOM forensics
        self._memory = None
        # ... and the (rank-0, opened) GoodputMonitor so compiles/dispatches
        # drive the run-state machine and feed the stall watchdog
        self._goodput = None
        self._lock = threading.Lock()
        self._journal_fn: Optional[Callable[..., None]] = None
        self._span_stack = threading.local()

        # phase self-times (seconds): cumulative + current interval; a part
        # (slash name, tracing.KNOWN_PHASES) is counted inclusive, under its
        # full name, in the cumulative totals only
        self._phase_total: Dict[str, float] = {}
        self._phase_interval: Dict[str, float] = {}
        self._phase_calls_total: Dict[str, int] = {}
        # instrumented-call accounting
        self._instrumented: Dict[str, _Instrumented] = {}
        self._calls_total: Dict[str, int] = {}
        self._calls_interval: Dict[str, int] = {}
        self._train_flops_interval = 0.0
        self._train_flops_total = 0.0
        # env throughput: vector env steps (note_env_steps) over wall-clock,
        # and how many of them each blocking rollout fetch amortizes (one
        # kind="rollout" dispatch == one obs->action->fetch round trip)
        self._env_steps_interval = 0
        self._env_steps_total = 0
        self._policy_state: Dict[str, int] = {}
        self._policy_more: Dict[str, Any] = {}  # what a sparse-attention policy adds to it (note_policy_gauges / _selection / _update)
        self._loop_order_iterations: Dict[str, int] = {}
        self._rollout_calls_interval = 0
        # offline dataset feed: rows streamed from the loader (the env-free
        # mode's throughput axis) and the loader's epoch counter
        self._dataset_rows_interval = 0
        self._dataset_rows_total = 0
        self._dataset_epoch: Optional[float] = None
        # watchdog
        self._recompiles_total = 0
        self._recompile_times: deque = deque()
        self._storms_total = 0
        # global compile monitor
        self._backend_compiles = 0
        self._backend_compile_s = 0.0
        # sentinel mirror (the /metrics counter)
        self._sentinel_events = 0
        self._monitoring_ok = False
        # interval bookkeeping
        self._tick_t: Optional[float] = None
        self._tick_step: Optional[float] = None
        self._peak_flops_total: Optional[float] = None
        self._device_count = 1
        self._latest: Dict[str, float] = {}
        self._info: Dict[str, Any] = {}

    # -- lifecycle ---------------------------------------------------------
    def open(self, journal_fn: Optional[Callable[..., None]] = None, info: Optional[Mapping[str, Any]] = None) -> None:
        self._journal_fn = journal_fn
        self._info = dict(info or {})
        self._tick_t = self._clock()
        self._monitoring_ok = monitoring_available()
        _attach_collector(self)
        self._resolve_peak()

    def close(self) -> None:
        _detach_collector(self)

    def _resolve_peak(self) -> None:
        try:
            import jax

            devices = jax.devices()
            self._device_count = max(1, len(devices))
            kind = devices[0].device_kind if devices else ""
        except Exception:  # pragma: no cover - pre-init probes
            kind = ""
        if self._peak_override is not None:
            per_device = float(self._peak_override) * 1e12
        else:
            per_device = resolve_peak_flops(kind, self._precision)
        if per_device:
            self._peak_flops_total = per_device * self._device_count
        self._info.setdefault("device_kind", kind)

    def _journal(self, event: str, **fields: Any) -> None:
        if self._journal_fn is not None:
            self._journal_fn(event, **fields)

    # -- instrumentation ---------------------------------------------------
    def instrument(
        self,
        name: str,
        fn: Callable,
        kind: str = "train",
        donate_argnums: Tuple[int, ...] = (),
        cost_note: Optional[str] = None,
    ) -> Callable:
        if not self.enabled:
            return fn
        wrapped = _Instrumented(
            self, name, fn, kind, donate_argnums=donate_argnums, cost_note=cost_note
        )
        self._instrumented[name] = wrapped
        return wrapped

    def _record_call(self, inst: _Instrumented) -> None:
        with self._lock:
            self._calls_total[inst.name] = self._calls_total.get(inst.name, 0) + 1
            self._calls_interval[inst.name] = self._calls_interval.get(inst.name, 0) + 1
            if inst.kind == "train" and inst.flops_per_call:
                self._train_flops_interval += inst.flops_per_call
                self._train_flops_total += inst.flops_per_call
            if inst.kind == "rollout":
                self._rollout_calls_interval += 1
        if self._goodput is not None:
            # outside the lock on purpose: the stall fault injection sleeps
            # in this notification, and the watchdog thread must be able to
            # take its own lock (and read counters here) meanwhile
            self._goodput.note_dispatch(inst.name, inst.kind)

    def note_env_steps(self, n: int) -> None:
        """Count ``n`` environment steps (loops call it once per vector step
        with ``num_envs``) — feeds ``Telemetry/env_steps_per_sec`` and the
        fetch-amortization gauge."""
        with self._lock:
            self._env_steps_interval += int(n)
            self._env_steps_total += int(n)

    def note_policy_state(self, resets: int, cache_positions: int, carry_bytes: int, view_bytes: int = 0) -> None:
        """A sequence policy's carried state (``ppo_recurrent`` with a
        language-model backbone): the resets counted, the others as they stand."""
        with self._lock:
            self._policy_state = {
                "state_resets_total": self._policy_state.get("state_resets_total", 0) + int(resets),
                "cache_positions": int(cache_positions),
                "carry_bytes": int(carry_bytes),
                "view_bytes": int(view_bytes),
            }

    def note_policy_gauges(self, **gauges: Any) -> None:
        """More of a sequence policy's state as it stands (``carry_bytes_by_kind``);
        kept beside what :meth:`note_policy_state` replaces every vector step."""
        with self._lock:
            self._policy_more.update(gauges)

    def note_policy_selection(self, visible: int, attended: int) -> None:
        """A sparse-attention policy's vector step: the positions its queries
        see and those they attend, summed over the envs; as they stand and
        summed over the steps."""
        with self._lock:
            state = self._policy_more
            state["attended_positions"] = int(attended)
            state["visible_positions_total"] = state.get("visible_positions_total", 0) + int(visible)
            state["attended_positions_total"] = state.get("attended_positions_total", 0) + int(attended)

    def note_policy_update(self, **reports: float) -> None:
        """What one update of a sequence policy reported beside its losses,
        summed over the updates (``<name>_sum`` over ``updates_total`` is the mean)."""
        with self._lock:
            state = self._policy_more
            state["updates_total"] = state.get("updates_total", 0) + 1
            for name, value in reports.items():
                state[name + "_sum"] = state.get(name + "_sum", 0.0) + float(value)

    def note_loop_order(self, order: str) -> None:
        """One iteration of a loop that has two orders, under the order it ran
        in (``algos/dreamer_v3/loop_order.py``)."""
        with self._lock:
            self._loop_order_iterations[order] = self._loop_order_iterations.get(order, 0) + 1

    def note_fetch(self, n: int = 1) -> None:
        """Count a blocking obs→action fetch that did NOT go through an
        instrumented ``kind="rollout"`` dispatch (the Dreamer player fetches
        its action values directly)."""
        with self._lock:
            self._rollout_calls_interval += int(n)

    def note_dataset_rows(self, n: int) -> None:
        """Count ``n`` transitions streamed from an offline dataset loader —
        feeds ``Telemetry/dataset_read_sps`` (howto/offline_rl.md)."""
        with self._lock:
            self._dataset_rows_interval += int(n)
            self._dataset_rows_total += int(n)

    def note_dataset_epoch(self, epoch: float) -> None:
        """Record the offline loader's epoch counter — the
        ``Telemetry/dataset_epoch`` gauge."""
        with self._lock:
            self._dataset_epoch = float(epoch)

    def _watchdog_observe(self, inst: _Instrumented, sig, args, kwargs) -> None:
        """One *new* dispatch signature on an already-compiled fn == one
        recompile (the caller filters the expected first compile)."""
        if not self.watchdog_enabled:
            return
        diff = signature_diff(inst._signature, sig, _leaf_paths(args, kwargs))
        now = self._clock()
        with self._lock:
            self._recompiles_total += 1
            total = self._recompiles_total
            self._recompile_times.append(now)
            while self._recompile_times and now - self._recompile_times[0] > self.storm_window_s:
                self._recompile_times.popleft()
            storm = len(self._recompile_times) >= self.storm_threshold
            if storm:
                self._storms_total += 1
                self._recompile_times.clear()  # cooldown: re-arm the window
        self._journal("recompile", fn=inst.name, count=total, diff=diff)
        if storm:
            self._journal(
                "recompile_storm",
                recompiles_in_window=self.storm_threshold,
                window_s=self.storm_window_s,
                total=total,
            )
            warnings.warn(
                f"Recompile storm: >= {self.storm_threshold} recompiles within "
                f"{self.storm_window_s:g}s (total {total}). Something is feeding the "
                "jitted steps varying shapes/dtypes — check the `recompile` journal "
                "events for the leaf diff.",
                RuntimeWarning,
            )

    def _note_backend_compile(self, secs: float) -> None:
        with self._lock:
            self._backend_compiles += 1
            self._backend_compile_s += secs

    def count_sentinel_event(self, n: int = 1) -> None:
        with self._lock:
            self._sentinel_events += int(n)

    def train_seconds(self) -> float:
        """Cumulative self-time of the ``train`` spans — the exact numerator
        of the goodput gauge (includes any compile that ran inside a train
        span; the state machine's ``state_seconds`` splits `compiling` out)."""
        with self._lock:
            return self._phase_total.get("train", 0.0)

    # -- phase spans -------------------------------------------------------
    def span_enter(self, name: str) -> List:
        rec = [name, self._clock(), 0.0]  # [name, t0, child seconds]
        if is_part(name):  # off the self-time stack: its phase reads as without it
            return rec
        stack = getattr(self._span_stack, "stack", None)
        if stack is None:
            stack = self._span_stack.stack = []
        stack.append(rec)
        return rec

    def span_exit(self, rec: List) -> None:
        name = rec[0]
        seconds = self._clock() - rec[1]
        part = is_part(name)  # inclusive, and not in the interval's buckets
        if not part:
            stack = getattr(self._span_stack, "stack", None)
            if stack and stack[-1] is rec:
                stack.pop()
            if stack:
                stack[-1][2] += seconds
            seconds = max(0.0, seconds - rec[2])  # self time
        with self._lock:
            self._phase_total[name] = self._phase_total.get(name, 0.0) + seconds
            self._phase_calls_total[name] = self._phase_calls_total.get(name, 0) + 1
            if not part:
                self._phase_interval[name] = self._phase_interval.get(name, 0.0) + seconds

    # -- interval math -----------------------------------------------------
    # The phase -> bucket map behind Telemetry/phase_pct/*: `env` is host
    # work spent driving the envs/policy (rollout bookkeeping + async issue),
    # `fetch` is blocking waits on env results and batch staging, `train` is
    # the train-step dispatch+fetch, everything else (checkpoint, custom
    # spans) lands in `other`, and `unspanned` is host wall-clock under no
    # span (not device idleness: a compute-bound loop reads 98% here).
    _PHASE_BUCKETS = {
        "rollout": "env",
        "env_step_async": "env",
        "env_wait": "fetch",
        "buffer-sample": "fetch",
        "train": "train",
    }

    def interval_metrics(self, step: Optional[float]) -> Dict[str, float]:
        """Close the current accounting interval and return its Telemetry/*
        gauges (called by the facade once per aggregated-metrics interval)."""
        if not self.enabled:
            return {}
        now = self._clock()
        out: Dict[str, float] = {}
        with self._lock:
            dt = (now - self._tick_t) if self._tick_t is not None else 0.0
            if dt > 0:
                if step is not None and self._tick_step is not None and step >= self._tick_step:
                    out[TELEMETRY_PREFIX + "sps"] = (float(step) - self._tick_step) / dt
                if self._train_flops_interval > 0 and self.mfu_enabled:
                    flops_per_s = self._train_flops_interval / dt
                    out[TELEMETRY_PREFIX + "tflops_per_sec"] = flops_per_s / 1e12
                    if self._peak_flops_total:
                        out[TELEMETRY_PREFIX + "mfu"] = flops_per_s / self._peak_flops_total
                if self._env_steps_interval > 0:
                    out[TELEMETRY_PREFIX + "env_steps_per_sec"] = self._env_steps_interval / dt
                    if self._rollout_calls_interval > 0:
                        # env steps per blocking obs->action fetch: num_envs
                        # when the player batches all envs behind one d2h
                        out[TELEMETRY_PREFIX + "fetch_amortization"] = (
                            self._env_steps_interval / self._rollout_calls_interval
                        )
                if self._dataset_rows_interval > 0:
                    out[TELEMETRY_PREFIX + "dataset_read_sps"] = self._dataset_rows_interval / dt
                if self._phase_interval:
                    buckets: Dict[str, float] = {}
                    for name, secs in self._phase_interval.items():
                        bucket = self._PHASE_BUCKETS.get(name, "other")
                        buckets[bucket] = buckets.get(bucket, 0.0) + secs
                    accounted = sum(buckets.values())
                    buckets["unspanned"] = max(0.0, dt - accounted)
                    for bucket, secs in sorted(buckets.items()):
                        out[TELEMETRY_PREFIX + f"phase_pct/{bucket}"] = 100.0 * secs / dt
            if self._dataset_epoch is not None:
                out[TELEMETRY_PREFIX + "dataset_epoch"] = self._dataset_epoch
            out[TELEMETRY_PREFIX + "recompiles"] = float(self._recompiles_total)
            out[TELEMETRY_PREFIX + "compile_count"] = float(self._backend_compiles)
            out[TELEMETRY_PREFIX + "compile_time_s"] = round(self._backend_compile_s, 3)
            # reset the interval accumulators
            self._phase_interval = {}
            self._calls_interval = {}
            self._train_flops_interval = 0.0
            self._env_steps_interval = 0
            self._rollout_calls_interval = 0
            self._dataset_rows_interval = 0
            self._tick_t = now
            if step is not None:
                self._tick_step = float(step)
            self._latest = dict(out)
        return out

    # -- snapshots (metrics server / run summary) --------------------------
    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "info": dict(self._info),
                "gauges": dict(self._latest),
                "counters": {
                    "recompiles_total": self._recompiles_total,
                    "recompile_storms_total": self._storms_total,
                    "backend_compiles_total": self._backend_compiles,
                    "compile_seconds_total": round(self._backend_compile_s, 3),
                    "sentinel_events_total": self._sentinel_events,
                    "train_flops_total": self._train_flops_total,
                    "env_steps_total": self._env_steps_total,
                    "dataset_rows_read_total": self._dataset_rows_total,
                },
                "policy_steps": self._tick_step,
                "phase_seconds_total": dict(self._phase_total),
                "phase_calls_total": dict(self._phase_calls_total),
                "calls_total": dict(self._calls_total),
                "policy_state": {**self._policy_state, **self._policy_more},
                "loop_order_iterations_total": dict(self._loop_order_iterations),
                "flops_per_call": {
                    name: inst.flops_per_call
                    for name, inst in self._instrumented.items()
                    if inst.flops_per_call
                },
            }

    def summary(self) -> Dict[str, Any]:
        """Cumulative run totals for the closing ``telemetry_summary`` event."""
        snap = self.snapshot()
        return {
            "recompiles": snap["counters"]["recompiles_total"],
            "recompile_storms": snap["counters"]["recompile_storms_total"],
            "backend_compiles": snap["counters"]["backend_compiles_total"],
            "compile_time_s": snap["counters"]["compile_seconds_total"],
            "train_flops_total": snap["counters"]["train_flops_total"],
            "phase_seconds": {k: round(v, 3) for k, v in snap["phase_seconds_total"].items()},
            "instrumented_calls": snap["calls_total"],
        }
