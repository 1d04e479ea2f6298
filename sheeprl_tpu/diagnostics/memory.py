"""Memory & data-movement telemetry: the other half of TPU performance.

The telemetry layer (ISSUE 3) answers "is the run *fast*?" in compute terms —
MFU, recompiles, phase breakdown.  This module makes the *memory* side of the
same question observable, because on a TPU the second way a run dies or slows
down is invisible by default: HBM fills up until ``RESOURCE_EXHAUSTED``, a
stray host sync serializes the pipeline, a buffer you meant to donate gets a
second allocation, or a large array is silently replicated across every chip.
Four pillars, all journal-backed and surfaced on ``/metrics``:

* **HBM telemetry** — per-device ``memory_stats()`` (bytes in use, peak,
  largest allocation) sampled once per metric interval as the
  ``Telemetry/hbm_*`` gauges.  Backends without the API (CPU, some forced-host
  platforms) fall back to summing the bytes of all live ``jax.Array``s — a
  real measure of framework-held memory, journaled with its ``source`` so the
  two are never confused — plus the process RSS as ``Telemetry/host_rss_bytes``.
  A one-shot ``memory_breakdown`` event decomposes the static footprint:
  per-component tree bytes (params / optimizer state / replay buffers,
  registered by the training loops) and the compiled train step's own
  ``memory_analysis()`` (argument / output / activation-temp bytes) taken from
  the AOT executable the telemetry layer already builds — zero extra compiles.

* **Host-transfer guard** — ``diagnostics.transfers`` = ``off | log |
  disallow`` wraps every instrumented train/rollout dispatch in
  ``jax.transfer_guard``.  ``log`` makes the runtime print every implicit
  transfer (aval + destination sharding) to stderr; ``disallow`` turns one
  into an error, which is caught at the dispatch boundary, journaled as a
  ``host_transfer`` event with provenance (fn, dispatch index) and re-raised.
  ``diagnostics.memory.inject_transfer_iter`` drills the detector end-to-end:
  under ``log`` it forces a real device→host sync inside the guarded scope
  (journaled, exactly once); under ``disallow`` it forces an implicit
  host→device transfer the guard rejects on every backend.

* **Donation & sharding audit** — at the first train dispatch the declared
  ``donate_argnums`` buffers are verified to have actually been consumed
  (``is_deleted``): XLA silently keeps both copies when it cannot alias, which
  doubles the params+optimizer footprint.  Misses are journaled as
  ``donation_miss`` with the offending leaf paths.  The same first dispatch
  emits a ``sharding_audit`` event: a per-leaf bytes/sharding table of the
  dispatch arguments that flags large fully-replicated arrays on multi-device
  meshes (``tools/memory_report.py`` renders it).

* **OOM forensics** — ``RESOURCE_EXHAUSTED`` (or any allocator out-of-memory)
  escaping an instrumented dispatch is intercepted to journal an ``oom`` event
  carrying a final memory snapshot (device stats, component footprints,
  largest live arrays), fsync'd before the exception is re-raised — so the
  post-mortem survives even when the process is killed moments later.
  ``diagnostics.memory.inject_oom_iter`` simulates the failure for drills.

Everything here is rank-0-journal-backed, costs a few host-side counters per
dispatch plus one ``memory_stats``/``live_arrays`` walk per metric interval,
and rides the same ``Diagnostics`` facade / ``JournalingLogger`` proxy /
``/metrics`` endpoint as the rest of the diagnostics subsystem.
"""

from __future__ import annotations

import os
import threading
from contextlib import nullcontext
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

# journal event types this module emits (declared centrally in the schema
# registry; re-exported here for the existing import surface)
from sheeprl_tpu.diagnostics.schema import MEMORY_EVENTS  # noqa: E402

_TRANSFER_MODES = ("off", "log", "disallow")

# a replicated leaf at/above this many bytes on a >1-device mesh is flagged
# in the sharding audit (overridable: diagnostics.memory.replicated_warn_bytes)
DEFAULT_REPLICATED_WARN_BYTES = 16 * 1024 * 1024


def normalize_transfer_mode(value: Any) -> str:
    """``diagnostics.transfers`` arrives as a string from the CLI but YAML 1.1
    resolves bare ``off``/``on`` to booleans — accept both spellings."""
    if value is None or value is False:
        return "off"
    if value is True:
        return "log"
    mode = str(value).strip().lower()
    if mode in ("", "none", "null", "0", "false"):
        return "off"
    if mode not in _TRANSFER_MODES:
        raise ValueError(f"diagnostics.transfers must be one of {_TRANSFER_MODES}, got {value!r}")
    return mode


# ---------------------------------------------------------------------------
# byte accounting primitives


def _leaf_nbytes(leaf: Any) -> int:
    nbytes = getattr(leaf, "nbytes", None)
    if isinstance(nbytes, (int, float)):
        return int(nbytes)
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        try:
            import numpy as np

            size = 1
            for dim in shape:
                size *= int(dim)
            return size * np.dtype(dtype).itemsize
        except Exception:
            return 0
    return 0


def tree_bytes(tree: Any) -> int:
    """Total bytes of every array leaf in a pytree (non-arrays contribute 0)."""
    import jax

    return sum(_leaf_nbytes(leaf) for leaf in jax.tree_util.tree_leaves(tree))


def tree_leaf_sizes(tree: Any) -> List[Tuple[str, Any]]:
    """``[(path, leaf), ...]`` over a pytree's array leaves, with readable
    key paths (the sharding/donation audits label their findings with these)."""
    import jax

    try:
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        return [(jax.tree_util.keystr(path), leaf) for path, leaf in flat]
    except Exception:  # pragma: no cover - keystr availability
        return [(f"leaf[{i}]", leaf) for i, leaf in enumerate(jax.tree_util.tree_leaves(tree))]


def device_memory_stats() -> List[Dict[str, Any]]:
    """Per-device ``memory_stats()`` where the backend provides it.

    Returns one dict per device with at least ``device``/``kind`` plus the
    backend's counters (TPU/GPU: ``bytes_in_use``, ``peak_bytes_in_use``,
    ``largest_alloc_size``...).  Backends without the API (CPU) return ``[]``
    — the caller falls back to live-array accounting, never to a guess.
    """
    import jax

    out: List[Dict[str, Any]] = []
    try:
        devices = jax.local_devices()
    except Exception:  # pragma: no cover - pre-init probes
        return out
    for dev in devices:
        stats_fn = getattr(dev, "memory_stats", None)
        if stats_fn is None:
            continue
        try:
            stats = stats_fn()
        except Exception:
            stats = None
        if not stats:
            continue
        row = {"device": str(dev.id), "kind": str(dev.device_kind)}
        row.update({str(k): v for k, v in stats.items()})
        out.append(row)
    return out


def live_array_bytes() -> Dict[str, Any]:
    """Framework-held memory from ``jax.live_arrays()``: total bytes, array
    count, and the largest single allocation.  This is the CPU-testable
    fallback for ``memory_stats()`` — it counts what *jax* holds (not raw
    allocator pages), which is exactly the number the training loop controls.
    """
    import jax

    total = 0
    largest = 0
    count = 0
    try:
        arrays = jax.live_arrays()
    except Exception:  # pragma: no cover - API drift
        return {"bytes_in_use": 0, "largest_alloc_bytes": 0, "n_arrays": 0}
    for arr in arrays:
        n = _leaf_nbytes(arr)
        total += n
        count += 1
        if n > largest:
            largest = n
    return {"bytes_in_use": total, "largest_alloc_bytes": largest, "n_arrays": count}


def host_rss_bytes() -> Optional[int]:
    """Resident set size of this process (Linux ``/proc/self/statm``), or None
    where unreadable — replay buffers in host RAM show up here."""
    try:
        with open("/proc/self/statm") as fp:
            pages = int(fp.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE")
    except Exception:
        return None


def executable_memory_analysis(compiled: Any) -> Optional[Dict[str, int]]:
    """Byte breakdown of a compiled executable (``memory_analysis()``), or
    None where the backend/API doesn't provide one.  ``temp_bytes`` is the
    activation/scratch high-water mark — the part of the footprint no tree
    walk can see."""
    try:
        analysis = compiled.memory_analysis()
    except Exception:
        return None
    if analysis is None:
        return None
    fields = {
        "argument_bytes": "argument_size_in_bytes",
        "output_bytes": "output_size_in_bytes",
        "temp_bytes": "temp_size_in_bytes",
        "alias_bytes": "alias_size_in_bytes",
        "generated_code_bytes": "generated_code_size_in_bytes",
    }
    out: Dict[str, int] = {}
    for name, attr in fields.items():
        value = getattr(analysis, attr, None)
        if isinstance(value, (int, float)):
            out[name] = int(value)
    return out or None


def buffer_footprint(buffer: Any) -> Dict[str, int]:
    """Host/disk/device byte footprint of a replay buffer (any of the
    ``sheeprl_tpu.data`` classes exposing ``footprint()``)."""
    fp = getattr(buffer, "footprint", None)
    if callable(fp):
        try:
            out = fp()
            return {str(k): int(v) for k, v in out.items() if isinstance(v, (int, float))}
        except Exception:
            return {}
    return {}


# ---------------------------------------------------------------------------
# sharding / donation inspection


def _sharding_row(path: str, leaf: Any) -> Optional[Dict[str, Any]]:
    nbytes = _leaf_nbytes(leaf)
    if nbytes <= 0 or not hasattr(leaf, "shape"):
        return None
    row: Dict[str, Any] = {
        "path": path,
        "shape": list(getattr(leaf, "shape", ())),
        "dtype": str(getattr(leaf, "dtype", "?")),
        "bytes": nbytes,
    }
    sharding = getattr(leaf, "sharding", None)
    n_devices = 1
    replicated = False
    if sharding is not None:
        try:
            n_devices = max(1, len(sharding.device_set))
        except Exception:
            n_devices = 1
        try:
            replicated = bool(sharding.is_fully_replicated) and n_devices > 1
        except Exception:
            replicated = False
        row["sharding"] = str(sharding)[:120]
    row["n_devices"] = n_devices
    row["replicated"] = replicated
    # a replicated array costs its FULL size on every device; a sharded one
    # costs its shard — shard_shape is exact for partially-replicated 2-D
    # layouts (replicated over "data", sharded over "model")
    per_device = nbytes if replicated else max(1, nbytes) // n_devices
    if sharding is not None and not replicated:
        try:
            import numpy as np

            shard_shape = sharding.shard_shape(tuple(leaf.shape))
            itemsize = np.dtype(leaf.dtype).itemsize
            per_device = int(np.prod(shard_shape, dtype=np.int64)) * itemsize
        except Exception:
            pass
    row["bytes_per_device"] = per_device
    return row


def sharding_table(
    args: Tuple[Any, ...],
    kwargs: Mapping[str, Any],
    top_n: int = 20,
    replicated_warn_bytes: Optional[int] = None,
    fsdp_axis_size: Optional[int] = None,
    fsdp_min_shard_bytes: Optional[int] = None,
) -> Dict[str, Any]:
    """Per-leaf bytes/sharding rows of a dispatch's arguments, largest
    per-device cost first, plus totals (the ``sharding_audit`` payload).

    ``flagged_replicated`` is computed over ALL leaves before the table is
    truncated to ``top_n`` rows — a large replicated array must be flagged
    even when many sharded leaves outrank it.  Under FSDP
    (``fsdp_axis_size > 1``) leaves below ``fsdp_min_shard_bytes`` are exempt
    — the partition rule replicates them *on purpose* — and the flag comes
    with an actionable ``hint`` naming the knob instead of a bare list."""
    rows: List[Dict[str, Any]] = []
    for path, leaf in tree_leaf_sizes((args, dict(kwargs))):
        row = _sharding_row(path, leaf)
        if row is not None:
            rows.append(row)
    rows.sort(key=lambda r: r["bytes_per_device"], reverse=True)
    total = sum(r["bytes"] for r in rows)
    total_per_device = sum(r["bytes_per_device"] for r in rows)
    out: Dict[str, Any] = {
        "n_leaves": len(rows),
        "total_bytes": total,
        "total_bytes_per_device": total_per_device,
        "rows": rows[: max(1, int(top_n))],
    }
    if replicated_warn_bytes is not None:
        fsdp_on = fsdp_axis_size is not None and int(fsdp_axis_size) > 1
        exempt_below = int(fsdp_min_shard_bytes or 0) if fsdp_on else 0
        out["flagged_replicated"] = [
            r["path"]
            for r in rows
            if r["replicated"] and r["bytes"] >= max(replicated_warn_bytes, exempt_below)
        ]
        if out["flagged_replicated"]:
            if fsdp_on:
                out["hint"] = (
                    f"replicated leaves >= distribution.fsdp_min_shard_bytes under "
                    f"fsdp_axis_size={int(fsdp_axis_size)}: no dimension is divisible by "
                    "the model axis — consider padding the layer width or lowering the "
                    "axis size (howto/sharding.md)"
                )
            else:
                out["hint"] = (
                    "large replicated leaves on a multi-device mesh: set "
                    "distribution.fsdp_axis_size > 1 (fabric.fsdp) to shard them over "
                    "a second 'model' mesh axis (howto/sharding.md)"
                )
    return out


def donation_misses(args: Tuple[Any, ...], donate_argnums: Tuple[int, ...]) -> List[Dict[str, Any]]:
    """After a dispatch, the leaves of every donated argument should be
    consumed (``is_deleted``).  A live leaf means XLA kept both copies — the
    donation silently failed (dtype/layout mismatch, an extra reference, or a
    jit wrapper that dropped ``donate_argnums``)."""
    misses: List[Dict[str, Any]] = []
    for argnum in donate_argnums:
        if argnum >= len(args):
            continue
        for path, leaf in tree_leaf_sizes(args[argnum]):
            deleted = getattr(leaf, "is_deleted", None)
            if deleted is None or not hasattr(leaf, "shape"):
                # host numpy leaves can never be donated: that IS a miss
                if hasattr(leaf, "shape") and _leaf_nbytes(leaf) > 0:
                    misses.append({"argnum": argnum, "path": path, "bytes": _leaf_nbytes(leaf), "reason": "host array"})
                continue
            try:
                if not deleted():
                    misses.append({"argnum": argnum, "path": path, "bytes": _leaf_nbytes(leaf), "reason": "not donated"})
            except Exception:  # pragma: no cover - API drift
                continue
    return misses


# ---------------------------------------------------------------------------
# error classification


def is_resource_exhausted(err: BaseException) -> bool:
    text = f"{type(err).__name__}: {err}"
    return "RESOURCE_EXHAUSTED" in text or "Out of memory" in text


def is_transfer_guard_error(err: BaseException) -> bool:
    text = str(err)
    return "Disallowed" in text and "transfer" in text


# ---------------------------------------------------------------------------
# the monitor


class MemoryMonitor:
    """Per-run memory/data-movement accounting behind the facade.

    Thread-safe counters (decoupled loops dispatch from worker threads; the
    metrics server snapshots from its own).  All journal writes go through the
    facade's ``journal_fn`` so rank gating stays in one place.
    """

    def __init__(self, cfg: Optional[Mapping[str, Any]] = None):
        cfg = cfg or {}
        diag_cfg = (cfg.get("diagnostics") or {}) if cfg else {}
        mem_cfg = diag_cfg.get("memory") or {}
        self.enabled = bool(mem_cfg.get("enabled", True))
        self.transfer_mode = normalize_transfer_mode(diag_cfg.get("transfers"))
        self.hbm_enabled = bool(mem_cfg.get("hbm", True))
        self.replicated_warn_bytes = int(
            mem_cfg.get("replicated_warn_bytes", DEFAULT_REPLICATED_WARN_BYTES)
        )
        self.audit_top_n = int(mem_cfg.get("audit_top_n", 20))
        inject_transfer = mem_cfg.get("inject_transfer_iter")
        self._inject_transfer_iter = None if inject_transfer is None else int(inject_transfer)
        inject_oom = mem_cfg.get("inject_oom_iter")
        self._inject_oom_iter = None if inject_oom is None else int(inject_oom)

        self._lock = threading.Lock()
        self._journal_fn: Optional[Callable[..., None]] = None
        self._sync_fn: Optional[Callable[[], None]] = None
        self._footprints: Dict[str, int] = {}
        self._footprints_per_device: Dict[str, int] = {}
        # armed by the facade's on_fsdp_shard_map: {"axis_size", "min_shard_bytes"}
        self._fsdp: Optional[Dict[str, int]] = None
        self._buffers: Dict[str, Any] = {}
        self._executables: Dict[str, Dict[str, int]] = {}
        self._train_calls = 0
        self._audited = False
        self._post_audit_done = False
        self._breakdown_emitted = False
        self._hbm_source: Optional[str] = None
        self._live_peak = 0
        self._latest: Dict[str, float] = {}
        # counters mirrored to /metrics
        self._host_transfers = 0
        self._donation_miss_leaves = 0
        self._oom_events = 0

    # -- lifecycle ---------------------------------------------------------
    def open(self, journal_fn: Optional[Callable[..., None]] = None, sync_fn: Optional[Callable[[], None]] = None) -> None:
        self._journal_fn = journal_fn
        self._sync_fn = sync_fn

    def _journal(self, event: str, **fields: Any) -> None:
        if self._journal_fn is not None:
            self._journal_fn(event, **fields)

    def _journal_synced(self, event: str, **fields: Any) -> None:
        """Journal + force the bytes to disk — for events whose whole point is
        surviving the process dying right afterwards (oom)."""
        self._journal(event, **fields)
        if self._sync_fn is not None:
            try:
                self._sync_fn()
            except Exception:  # pragma: no cover
                pass

    # -- component registration (called by the training loops) -------------
    def register_footprint(self, name: str, tree_or_bytes: Any) -> None:
        """Record a static component's byte size (params, optimizer state...)
        for the ``memory_breakdown`` event.  Accepts a pytree or raw bytes."""
        if not self.enabled:
            return
        size = int(tree_or_bytes) if isinstance(tree_or_bytes, (int, float)) else tree_bytes(tree_or_bytes)
        per_device = None
        if not isinstance(tree_or_bytes, (int, float)):
            try:
                from sheeprl_tpu.parallel.fsdp import tree_bytes_per_device

                per_device = tree_bytes_per_device(tree_or_bytes)
            except Exception:  # pragma: no cover - never block registration
                per_device = None
        with self._lock:
            self._footprints[str(name)] = size
            if per_device is not None and per_device != size:
                # only genuinely sharded components get a per-device entry —
                # replicated/host trees cost their full size everywhere
                self._footprints_per_device[str(name)] = per_device

    def note_fsdp(self, summary: Mapping[str, Any]) -> None:
        """Arm FSDP-aware accounting (called via the facade's
        ``on_fsdp_shard_map``): the axis-size gauge, the sharding audit's
        ``min_shard_bytes`` exemption, and the per-device breakdown column."""
        if not self.enabled:
            return
        with self._lock:
            self._fsdp = {
                "axis_size": int(summary.get("axis_size", 1) or 1),
                "min_shard_bytes": int(summary.get("min_shard_bytes", 0) or 0),
            }

    def track_buffer(self, name: str, buffer: Any) -> None:
        """Track a replay buffer's live footprint (re-queried every metric
        interval: memmap growth and host-RAM growth both show up)."""
        if not self.enabled:
            return
        with self._lock:
            self._buffers[str(name)] = buffer

    def note_executable(self, name: str, compiled: Any) -> None:
        """Capture the compiled step's memory analysis (called by the
        telemetry AOT path at first compile — zero extra compiles)."""
        if not self.enabled:
            return
        analysis = executable_memory_analysis(compiled)
        if analysis:
            with self._lock:
                self._executables[str(name)] = analysis

    # -- guarded dispatch ---------------------------------------------------
    def guarded_call(
        self,
        inst: Any,
        call: Callable[[], Any],
        args: Tuple[Any, ...],
        kwargs: Mapping[str, Any],
        count_call: bool = True,
    ):
        """Run one instrumented dispatch under the transfer guard with fault
        injection, first-dispatch audits and OOM forensics.

        ``count_call=False`` marks a RETRY of the same logical step (the
        telemetry AOT-fallback re-dispatch) so one train iteration never
        advances the dispatch counter — and hence the injection targets and
        the journaled ``call`` provenance — twice.

        Errors this layer has already journaled are tagged
        ``_sheeprl_diag_handled`` so the telemetry AOT-fallback handler
        re-raises them instead of mistaking them for an AOT dispatch problem.
        """
        is_train = getattr(inst, "kind", "train") == "train"
        call_idx = 0
        first_train = False
        if is_train:
            with self._lock:
                if count_call:
                    self._train_calls += 1
                call_idx = self._train_calls
                first_train = not self._audited
                if first_train:
                    self._audited = True
        if first_train:
            self._sharding_audit(inst, args, kwargs)

        guard = self._guard_context()
        try:
            with guard:
                if is_train and self._inject_oom_iter is not None and call_idx == self._inject_oom_iter:
                    self._inject_oom_iter = None
                    raise RuntimeError(
                        "RESOURCE_EXHAUSTED: injected out-of-memory "
                        "(diagnostics.memory.inject_oom_iter) — OOM-forensics drill"
                    )
                out = call()
                if (
                    is_train
                    and self.transfer_mode != "off"  # the drill drills the GUARD: no guard, nothing to drill
                    and self._inject_transfer_iter is not None
                    and call_idx == self._inject_transfer_iter
                ):
                    self._inject_transfer_iter = None
                    self._fire_transfer_injection(inst, call_idx, out)
        except Exception as err:
            handled = self._handle_dispatch_error(inst, call_idx, err)
            if handled:
                err._sheeprl_diag_handled = True  # type: ignore[attr-defined]
            raise
        if is_train and not self._post_audit_done:
            # tracked separately from the pre-call audit: if the first
            # dispatch died mid-call (AOT fallback retry), the donation check
            # and breakdown still run on the first call that completes
            self._post_audit_done = True
            self._donation_audit(inst, args)
            self._emit_breakdown(inst)
        return out

    def _guard_context(self):
        if self.transfer_mode == "off":
            return nullcontext()
        import jax

        return jax.transfer_guard(self.transfer_mode)

    def _fire_transfer_injection(self, inst: Any, call_idx: int, out: Any) -> None:
        """The end-to-end drill.  ``log`` mode: force a REAL device→host sync
        on an output leaf inside the guarded scope (the runtime logs it, the
        journal records it, the run continues).  ``disallow`` mode: force an
        *implicit* host→device transfer — the one direction every backend's
        guard rejects — so the blocked-transfer path is exercised too."""
        import numpy as np

        if self.transfer_mode == "disallow":
            import jax.numpy as jnp

            # numpy operand entering a jitted computation = implicit h2d;
            # raises inside the surrounding guard and is journaled by the
            # dispatch error handler
            jnp.add(jnp.zeros((4,), jnp.float32), np.ones((4,), np.float32)).block_until_ready()
            return
        import jax

        leaves = [l for l in jax.tree_util.tree_leaves(out) if hasattr(l, "shape")]
        if not leaves:  # nothing to sync on: still record that the drill ran
            synced_bytes = 0
        else:
            fetched = np.asarray(leaves[0])  # device->host sync
            synced_bytes = int(fetched.nbytes)
        with self._lock:
            self._host_transfers += 1
        self._journal(
            "host_transfer",
            fn=getattr(inst, "name", "?"),
            call=call_idx,
            direction="device_to_host",
            injected=True,
            policy=self.transfer_mode,
            bytes=synced_bytes,
        )

    def _handle_dispatch_error(self, inst: Any, call_idx: int, err: BaseException) -> bool:
        if getattr(err, "_sheeprl_diag_handled", False):
            return True
        if is_transfer_guard_error(err):
            with self._lock:
                self._host_transfers += 1
            self._journal_synced(
                "host_transfer",
                fn=getattr(inst, "name", "?"),
                call=call_idx,
                blocked=True,
                policy=self.transfer_mode,
                error=str(err)[:300],
            )
            return True
        if is_resource_exhausted(err):
            with self._lock:
                self._oom_events += 1
            self._journal_synced(
                "oom",
                fn=getattr(inst, "name", "?"),
                call=call_idx,
                error=str(err)[:500],
                **self._forensics_snapshot(),
            )
            return True
        return False

    def _forensics_snapshot(self) -> Dict[str, Any]:
        """What a post-mortem needs, gathered defensively (the process may be
        in a bad state — never let forensics raise over the real error)."""
        snap: Dict[str, Any] = {}
        try:
            stats = device_memory_stats()
            if stats:
                snap["device_memory"] = stats
            else:
                snap["live_arrays"] = live_array_bytes()
        except Exception:  # pragma: no cover
            pass
        try:
            rss = host_rss_bytes()
            if rss is not None:
                snap["host_rss_bytes"] = rss
        except Exception:  # pragma: no cover
            pass
        with self._lock:
            if self._footprints:
                snap["components"] = dict(self._footprints)
            if self._executables:
                snap["executables"] = {k: dict(v) for k, v in self._executables.items()}
        try:
            buffers = {name: buffer_footprint(buf) for name, buf in list(self._buffers.items())}
            buffers = {k: v for k, v in buffers.items() if v}
            if buffers:
                snap["buffers"] = buffers
        except Exception:  # pragma: no cover
            pass
        return snap

    # -- first-dispatch audits ----------------------------------------------
    def _sharding_audit(self, inst: Any, args: Tuple[Any, ...], kwargs: Mapping[str, Any]) -> None:
        with self._lock:
            fsdp = dict(self._fsdp) if self._fsdp else {}
        try:
            table = sharding_table(
                args,
                kwargs,
                top_n=self.audit_top_n,
                replicated_warn_bytes=self.replicated_warn_bytes,
                fsdp_axis_size=fsdp.get("axis_size"),
                fsdp_min_shard_bytes=fsdp.get("min_shard_bytes"),
            )
        except Exception:  # pragma: no cover - never block the dispatch
            return
        self._journal("sharding_audit", fn=getattr(inst, "name", "?"), **table)

    def _donation_audit(self, inst: Any, args: Tuple[Any, ...]) -> None:
        donate = tuple(getattr(inst, "donate_argnums", ()) or ())
        if not donate:
            return
        try:
            misses = donation_misses(args, donate)
        except Exception:  # pragma: no cover
            return
        if not misses:
            return
        with self._lock:
            self._donation_miss_leaves += len(misses)
        self._journal(
            "donation_miss",
            fn=getattr(inst, "name", "?"),
            n_leaves=len(misses),
            bytes=sum(m["bytes"] for m in misses),
            leaves=misses[: self.audit_top_n],
        )

    def _emit_breakdown(self, inst: Any) -> None:
        with self._lock:
            if self._breakdown_emitted:
                return
            self._breakdown_emitted = True
        self._journal("memory_breakdown", fn=getattr(inst, "name", "?"), **self.breakdown())

    def breakdown(self) -> Dict[str, Any]:
        """The static footprint decomposition (``memory_breakdown`` payload
        and the ``tools/memory_report.py`` table)."""
        out: Dict[str, Any] = {}
        with self._lock:
            components = dict(self._footprints)
            per_device = dict(self._footprints_per_device)
            fsdp = dict(self._fsdp) if self._fsdp else None
            executables = {k: dict(v) for k, v in self._executables.items()}
            buffers = dict(self._buffers)
        for name, buf in buffers.items():
            fp = buffer_footprint(buf)
            for kind, size in fp.items():
                components[f"{name}_{kind}"] = size
        out["components"] = components
        if per_device:
            # present only when something is genuinely sharded (FSDP runs):
            # the per-device cost of each component, report.py renders the
            # extra column
            out["components_per_device"] = per_device
        if fsdp:
            out["fsdp_axis_size"] = fsdp["axis_size"]
        if executables:
            out["executables"] = executables
        stats = device_memory_stats()
        if stats:
            out["device_memory"] = stats
            out["source"] = "memory_stats"
        else:
            out["live_arrays"] = live_array_bytes()
            out["source"] = "live_arrays"
        rss = host_rss_bytes()
        if rss is not None:
            out["host_rss_bytes"] = rss
        return out

    # -- interval gauges -----------------------------------------------------
    def interval_metrics(self) -> Dict[str, float]:
        """``Telemetry/hbm_*`` + buffer/host gauges for one metric interval
        (merged by the facade next to the compute telemetry gauges)."""
        if not (self.enabled and self.hbm_enabled):
            return {}
        out: Dict[str, float] = {}
        stats = device_memory_stats()
        if stats:
            self._hbm_source = "memory_stats"
            in_use = max((s.get("bytes_in_use", 0) or 0) for s in stats)
            peak = max((s.get("peak_bytes_in_use", 0) or 0) for s in stats)
            largest = max((s.get("largest_alloc_size", 0) or 0) for s in stats)
            out["Telemetry/hbm_bytes_in_use"] = float(in_use)
            if peak:
                out["Telemetry/hbm_peak_bytes"] = float(peak)
            if largest:
                out["Telemetry/hbm_largest_alloc_bytes"] = float(largest)
        else:
            self._hbm_source = "live_arrays"
            live = live_array_bytes()
            with self._lock:
                self._live_peak = max(self._live_peak, live["bytes_in_use"])
                peak = self._live_peak
            out["Telemetry/hbm_bytes_in_use"] = float(live["bytes_in_use"])
            out["Telemetry/hbm_peak_bytes"] = float(peak)
            out["Telemetry/hbm_largest_alloc_bytes"] = float(live["largest_alloc_bytes"])
        rss = host_rss_bytes()
        if rss is not None:
            out["Telemetry/host_rss_bytes"] = float(rss)
        with self._lock:
            fsdp = dict(self._fsdp) if self._fsdp else None
            params_per_device = self._footprints_per_device.get("params")
        if fsdp is not None:
            out["Telemetry/fsdp_axis_size"] = float(fsdp["axis_size"])
            if params_per_device is not None:
                out["Telemetry/params_bytes_per_device"] = float(params_per_device)
        with self._lock:
            buffers = dict(self._buffers)
        for name, buf in buffers.items():
            for kind, size in buffer_footprint(buf).items():
                out[f"Telemetry/{name}_{kind}"] = float(size)
        with self._lock:
            self._latest = dict(out)
        return out

    # -- snapshots (metrics server / run summary) ---------------------------
    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "gauges": dict(self._latest),
                "counters": {
                    "host_transfers_total": self._host_transfers,
                    "donation_miss_leaves_total": self._donation_miss_leaves,
                    "oom_events_total": self._oom_events,
                },
                "info": {"hbm_source": self._hbm_source, "transfer_guard": self.transfer_mode},
            }

    def summary(self) -> Dict[str, Any]:
        """Cumulative totals for the closing ``memory_summary`` event."""
        snap = self.snapshot()
        with self._lock:
            components = dict(self._footprints)
            per_device = dict(self._footprints_per_device)
        out = {
            "host_transfers": snap["counters"]["host_transfers_total"],
            "donation_miss_leaves": snap["counters"]["donation_miss_leaves_total"],
            "oom_events": snap["counters"]["oom_events_total"],
            "hbm_source": self._hbm_source,
            "transfer_guard": self.transfer_mode,
            "components": components,
        }
        if per_device:
            out["components_per_device"] = per_device
        return out
