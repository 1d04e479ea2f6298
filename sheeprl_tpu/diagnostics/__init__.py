"""Run-health & observability subsystem.

Seven pillars behind one facade (ISSUE 1 tentpole + ISSUE 3 telemetry layer +
ISSUE 4 memory layer + ISSUE 8 run-lifecycle layer + ISSUE 9 learning-dynamics
layer):

* :mod:`~sheeprl_tpu.diagnostics.journal` — crash-safe JSONL run journal
  (write-ahead metric/event log; makes TensorBoard archaeology and the
  reward-recovery toolchain unnecessary for new runs);
* :mod:`~sheeprl_tpu.diagnostics.sentinel` — jit-compatible NaN/divergence
  sentinel (``warn`` / ``skip_update`` / ``halt``) + host-side rolling
  divergence detector;
* :mod:`~sheeprl_tpu.diagnostics.tracing` — step-phase Chrome-trace spans
  (rollout / buffer-sample / train / checkpoint) viewable in Perfetto,
  complementing the device-side ``jax.profiler`` gate, with run-id/rank/role
  clock anchors so multi-process traces merge (``tools/trace_report.py``);
* :mod:`~sheeprl_tpu.diagnostics.telemetry` — performance telemetry: a
  recompilation watchdog over the instrumented jitted steps, MFU/goodput
  accounting from compiled-step ``cost_analysis()`` FLOPs, phase-level
  wall-clock attribution, and (opt-in) a live rank-0 ``/metrics`` +
  ``/healthz`` HTTP endpoint (:mod:`~sheeprl_tpu.diagnostics.metrics_server`);
* :mod:`~sheeprl_tpu.diagnostics.memory` — memory & data-movement telemetry
  (ISSUE 4): per-interval HBM gauges + a static footprint breakdown, the
  ``diagnostics.transfers`` host-transfer guard around the instrumented
  dispatches, a first-dispatch donation/sharding audit, and OOM forensics
  journaled before a ``RESOURCE_EXHAUSTED`` takes the process down
  (``tools/memory_report.py`` renders the tables);
* :mod:`~sheeprl_tpu.diagnostics.goodput` — run lifecycle & goodput
  (ISSUE 8): a run-state machine (``starting → compiling → training /
  env_wait / checkpointing / stalled → ended``) driven by the hooks above, a
  heartbeat stall watchdog journaling fsync'd ``stall`` forensics
  (all-thread stacks, optional ``jax.profiler`` auto-capture), and the live
  ``Telemetry/run_state`` / ``Telemetry/goodput`` /
  ``Telemetry/time_to_first_step`` gauges (``tools/goodput_report.py``
  groups a resumed run's ``version_N`` segments post-mortem);
* :mod:`~sheeprl_tpu.diagnostics.health` — learning-dynamics observability
  (ISSUE 9): jit-compatible per-module grad/update/param statistics riding
  the guarded train steps' existing output fetch (zero extra device syncs),
  rolling-window anomaly detectors (entropy collapse, value-EV floor,
  update/weight-ratio band, loss plateau, dead gradients) journaling
  flood-controlled ``anomaly``/``anomaly_end`` events, and the live
  ``Telemetry/health/*`` gauges (``tools/health_report.py`` renders the
  post-mortem; ``tools/health_diff.py`` gates cross-run regressions).

The facade is constructed once in ``cli.run_algorithm`` from the
``configs/diagnostics/`` group and attached to the :class:`Runtime`; training
loops pick it up through ``sheeprl_tpu.utils.utils.get_diagnostics`` and the
rank-0 logger proxy journals every aggregated metric automatically — augmented
with the live ``Telemetry/*`` gauges — so non-flagship algorithms inherit
journaling *and* perf telemetry without loop changes.
"""

from __future__ import annotations

import hashlib
import os
import warnings
from contextlib import contextmanager, nullcontext
from typing import Any, Dict, Mapping, Optional

from sheeprl_tpu.diagnostics.goodput import GoodputMonitor
from sheeprl_tpu.diagnostics.health import HealthMonitor, HealthSpec, health_spec, health_stats
from sheeprl_tpu.diagnostics.journal import (
    JOURNAL_NAME,
    RunJournal,
    collect_journals,
    find_journal,
    iter_journal,
    read_journal,
)
from sheeprl_tpu.diagnostics.memory import MEMORY_EVENTS, MemoryMonitor, tree_bytes
from sheeprl_tpu.diagnostics.sentinel import (
    DivergenceDetector,
    SentinelHalt,
    SentinelSpec,
    poison_tree,
    sentinel_spec,
)
from sheeprl_tpu.diagnostics.telemetry import TELEMETRY_PREFIX, Telemetry, monitoring_available
from sheeprl_tpu.diagnostics.tracing import TRACE_NAME, NullTracer, PhaseTracer, profiler_annotation

__all__ = [
    "Diagnostics",
    "DivergenceDetector",
    "GoodputMonitor",
    "HealthMonitor",
    "HealthSpec",
    "JOURNAL_NAME",
    "MEMORY_EVENTS",
    "MemoryMonitor",
    "NullTracer",
    "PhaseTracer",
    "RunJournal",
    "SentinelHalt",
    "SentinelSpec",
    "TELEMETRY_PREFIX",
    "TRACE_NAME",
    "Telemetry",
    "build_diagnostics",
    "collect_journals",
    "config_hash",
    "find_journal",
    "health_spec",
    "health_stats",
    "iter_journal",
    "read_journal",
    "sentinel_spec",
    "tree_bytes",
]


def config_hash(cfg: Mapping[str, Any]) -> str:
    """Stable short hash of the composed run config (journaled at run_start,
    so any journal can be matched to the exact configuration that made it)."""
    import yaml

    plain = cfg.as_dict() if hasattr(cfg, "as_dict") else dict(cfg)
    return hashlib.sha256(yaml.safe_dump(plain, sort_keys=True).encode()).hexdigest()[:16]


def run_id_of(log_dir: str) -> str:
    """Correlation id shared by every process of a run: the tail of the
    (broadcast) log dir — ``<root_dir>/<run_name>/version_N`` — which is the
    one string all ranks already agree on without extra rendezvous."""
    parts = [p for p in os.path.normpath(str(log_dir)).split(os.sep) if p not in ("", ".")]
    return "/".join(parts[-3:]) if parts else str(log_dir)


class Diagnostics:
    """Facade over journal + sentinel + tracer + telemetry with rank-0 gating.

    Construct via :func:`build_diagnostics`; call :meth:`open` once the run's
    log dir exists (``get_diagnostics`` does both).  Every method is a no-op
    until opened — and stays one on non-rank-0 hosts or when
    ``diagnostics.enabled=False`` — so hook calls in the training loops are
    unconditional.
    """

    def __init__(self, cfg: Optional[Mapping[str, Any]] = None):
        self._cfg = cfg
        diag_cfg = (cfg or {}).get("diagnostics") or {}
        self.enabled = bool(diag_cfg.get("enabled", False))
        self._journal_cfg = diag_cfg.get("journal") or {}
        self._trace_cfg = diag_cfg.get("trace") or {}
        self.role = str(diag_cfg.get("role") or "main")
        self.sentinel: SentinelSpec = sentinel_spec(cfg or {})
        div_cfg = (diag_cfg.get("sentinel") or {}).get("divergence") or {}
        self._detector: Optional[DivergenceDetector] = None
        if self.enabled and div_cfg.get("enabled", True):
            self._detector = DivergenceDetector(
                window=int(div_cfg.get("window", 20)),
                min_points=int(div_cfg.get("min_points", 5)),
                loss_explosion_ratio=float(div_cfg.get("loss_explosion_ratio", 10.0) or 0.0),
                entropy_key=div_cfg.get("entropy_key"),
                entropy_floor=div_cfg.get("entropy_floor"),
            )
        self.telemetry: Optional[Telemetry] = None
        if self.enabled:
            telemetry = Telemetry(cfg or {})
            if telemetry.enabled:
                self.telemetry = telemetry
        self.memory: Optional[MemoryMonitor] = None
        if self.enabled:
            memory = MemoryMonitor(cfg or {})
            if memory.enabled:
                self.memory = memory
                if self.telemetry is not None:
                    # instrumented dispatches route through the monitor's
                    # guarded scope (transfer guard / audits / OOM forensics)
                    self.telemetry._memory = memory
                elif memory.transfer_mode != "off" or memory._inject_transfer_iter is not None or memory._inject_oom_iter is not None:
                    # the guard/audits/forensics live at the instrumented
                    # dispatch boundary, which telemetry provides — a config
                    # that asks for enforcement without it must not be
                    # silently inert
                    warnings.warn(
                        f"diagnostics.transfers={memory.transfer_mode!r} (or a memory fault injection) "
                        "is set but diagnostics.telemetry.enabled=False: the transfer guard, "
                        "donation audit and OOM forensics attach to instrumented dispatches and "
                        "will NOT run. Only the passive Telemetry/hbm_* gauges remain active.",
                        RuntimeWarning,
                    )
        self.goodput: Optional[GoodputMonitor] = None
        if self.enabled:
            goodput = GoodputMonitor(cfg or {})
            if goodput.enabled:
                self.goodput = goodput
        self.health: Optional[HealthMonitor] = None
        if self.enabled:
            health = HealthMonitor(cfg or {})
            if health.enabled:
                self.health = health
        self.resilience = None
        if self.enabled:
            from sheeprl_tpu.resilience.monitor import ResilienceMonitor

            resilience = ResilienceMonitor(cfg or {})
            if resilience.enabled:
                self.resilience = resilience
        self.journal: Optional[RunJournal] = None
        self.tracer = NullTracer()
        self.metrics_server = None
        self.log_dir: Optional[str] = None
        self.run_id: Optional[str] = None
        self._rank_zero = True
        self._closed = False

    # -- lifecycle ---------------------------------------------------------
    def open(
        self, log_dir: str, rank_zero: bool = True, device: Optional[Mapping[str, Any]] = None
    ) -> "Diagnostics":
        """Open journal/tracer/telemetry inside ``log_dir`` (idempotent;
        journal + endpoint are rank-0 only, the tracer — when
        ``trace.all_ranks`` — and the telemetry accounting run everywhere).
        ``device`` is ``Runtime.device_info`` — the platform, device kind and
        count the mesh resolved to, journaled with ``run_start``."""
        if not self.enabled or self.log_dir is not None:
            return self
        self.log_dir = str(log_dir)
        self.run_id = run_id_of(self.log_dir)
        self._rank_zero = bool(rank_zero)
        if self._trace_cfg.get("enabled", False) and (
            self._rank_zero or self._trace_cfg.get("all_ranks", True)
        ):
            import jax

            rank = jax.process_index()
            if self._rank_zero:
                trace_path = self._trace_cfg.get("path") or os.path.join(self.log_dir, TRACE_NAME)
            else:
                # an explicit trace.path must NOT be honored here: every rank
                # would open the same file in 'w' mode and clobber the others
                trace_path = os.path.join(self.log_dir, f"trace_rank{rank}.json")
            self.tracer = PhaseTracer(
                trace_path,
                pid=rank,
                max_events=self._trace_cfg.get("max_events"),
                rotate_keep=int(self._trace_cfg.get("rotate_keep", 2)),
                run_id=self.run_id,
                role=self.role,
            )
        if self._rank_zero and self._journal_cfg.get("enabled", True):
            self.journal = RunJournal(
                os.path.join(self.log_dir, JOURNAL_NAME),
                fsync_every=int(self._journal_cfg.get("fsync_every", 1)),
            )
        cfg = self._cfg or {}
        if self.journal is not None:
            self.journal.write(
                "run_start",
                config_hash=config_hash(cfg),
                algo=(cfg.get("algo") or {}).get("name"),
                env=(cfg.get("env") or {}).get("id"),
                seed=cfg.get("seed"),
                exp_name=cfg.get("exp_name"),
                run_name=cfg.get("run_name"),
                log_dir=self.log_dir,
                run_id=self.run_id,
                sentinel_policy=self.sentinel.policy if self.sentinel.enabled else None,
                **(device or {}),
            )
            import jax

            cache_dir = jax.config.jax_compilation_cache_dir
            if cache_dir:
                # the cache itself was placed at startup, before any compile
                # (utils/compile_cache.py); the journal records the directory
                # in force so restarts/post-mortems can account for compile
                # time that never shows up
                self.journal.write("compilation_cache", dir=str(cache_dir))
        if self.resilience is not None:
            # opened on every rank: each process of a decoupled topology must
            # honor its own preemption signal; journal writes (ckpt_begin/
            # ckpt_end, drained ckpt_skipped records) no-op off rank 0
            self.resilience.open(
                self._journal_event, self._journal_sync, rank_zero=self._rank_zero
            )
        if self.memory is not None:
            # opened on every rank: the transfer guard must protect every
            # process; journal writes no-op off rank 0 (journal is None there)
            self.memory.open(self._journal_event, self._journal_sync)
        if self.health is not None and self._rank_zero:
            # rank-0 only, like the journal: the detectors describe THE run,
            # and their output is the journal + the Telemetry/health gauges
            self.health.open(self._journal_event, self._journal_sync)
        if self.goodput is not None and self._rank_zero:
            # rank-0 only, like the journal: the state machine / watchdog
            # describe THE run, and their output is journal + gauges
            self.goodput.open(
                self._goodput_event,
                self._journal_sync,
                telemetry=self.telemetry,
                log_dir=self.log_dir,
            )
            if self.telemetry is None:
                # warned HERE (rank-0, at open) rather than in the ctor: the
                # gauges the warning is about only ever exist on this rank.
                # The state machine still runs on span/interval hooks, but
                # Telemetry/goodput + time_to_first_step need telemetry's
                # train-span seconds and dispatch notifications — they will
                # be OMITTED (never a false 0.0), which must not be a silent
                # surprise
                warnings.warn(
                    "diagnostics.goodput.enabled=True but diagnostics.telemetry.enabled=False: "
                    "Telemetry/goodput and Telemetry/time_to_first_step will be omitted "
                    "(the run-state machine and stall watchdog still run on span/interval hooks).",
                    RuntimeWarning,
                )
        if self.telemetry is not None:
            self.telemetry.open(
                self._journal_event,
                {
                    "run_id": self.run_id,
                    "algo": (cfg.get("algo") or {}).get("name"),
                    "env": (cfg.get("env") or {}).get("id"),
                    "role": self.role,
                },
            )
            if self.goodput is not None and self.goodput._opened:
                # telemetry drives the compile/dispatch notifications (and
                # hosts the stall-injection sleep) for the state machine
                self.telemetry._goodput = self.goodput
            if self._rank_zero and self.telemetry.http_enabled:
                self._start_metrics_server()
        return self

    def _start_metrics_server(self) -> None:
        from sheeprl_tpu.diagnostics.metrics_server import MetricsServer

        profile_fn = None
        if self.goodput is not None and self.goodput._opened and self.goodput.profile_enabled:
            profile_fn = self.goodput.capture_profile
        try:
            self.metrics_server = MetricsServer(
                self._server_snapshot,
                host=self.telemetry.http_host,
                port=self.telemetry.http_port,
                profile_fn=profile_fn,
            )
            host, port = self.metrics_server.start()
        except OSError as err:
            # a taken port must not take the run down with it
            self.metrics_server = None
            warnings.warn(f"diagnostics metrics endpoint failed to bind: {err}", RuntimeWarning)
            self._journal_event("metrics_server", status="bind_failed", error=str(err))
            return
        self._journal_event("metrics_server", status="serving", host=host, port=port)
        print(f"Telemetry endpoint: http://{host}:{port}/metrics (and /healthz)", flush=True)

    def _server_snapshot(self) -> Dict[str, Any]:
        snap = self.telemetry.snapshot() if self.telemetry is not None else {}
        if self.memory is not None:
            mem = self.memory.snapshot()
            snap.setdefault("gauges", {}).update(mem["gauges"])
            snap.setdefault("counters", {}).update(mem["counters"])
            info = snap.setdefault("info", {})
            for k, v in mem["info"].items():
                if v is not None:
                    info.setdefault(k, v)
        if self.goodput is not None and self.goodput._opened:
            good = self.goodput.snapshot()
            snap.setdefault("gauges", {}).update(good["gauges"])
            snap.setdefault("counters", {}).update(good["counters"])
            info = snap.setdefault("info", {})
            for k, v in good["info"].items():
                if v is not None:
                    info.setdefault(k, v)
        if self.health is not None and self.health._opened:
            health = self.health.snapshot()
            snap.setdefault("gauges", {}).update(health["gauges"])
            snap.setdefault("counters", {}).update(health["counters"])
            info = snap.setdefault("info", {})
            for k, v in health["info"].items():
                if v is not None:
                    info.setdefault(k, v)
        if self.resilience is not None and self.resilience._opened:
            res = self.resilience.snapshot()
            snap.setdefault("gauges", {}).update(res["gauges"])
            snap.setdefault("counters", {}).update(res["counters"])
            info = snap.setdefault("info", {})
            for k, v in res["info"].items():
                if v is not None:
                    info.setdefault(k, v)
        if self.journal is not None and self.journal.last_write_t is not None:
            import time

            snap["journal_lag_seconds"] = round(time.time() - self.journal.last_write_t, 3)
        return snap

    def _journal_event(self, event: str, **fields: Any) -> None:
        if self.journal is not None:
            self.journal.write(event, **fields)

    def _goodput_event(self, event: str, **fields: Any) -> None:
        """Goodput emissions mirror into the journal AND (as instants) the
        trace, so a Perfetto timeline shows state changes/stalls in place."""
        self._journal_event(event, **fields)
        if event == "state_change":
            self.tracer.instant(f"state:{fields.get('state')}", prev=fields.get("prev"))
        elif event in ("stall", "stall_end"):
            self.tracer.instant(event)

    def _journal_sync(self) -> None:
        """Force journal bytes to disk NOW (OOM forensics: the record must
        survive the process dying right after it is written)."""
        if self.journal is not None:
            self.journal.sync()

    def close(self, status: str = "completed") -> None:
        if self._closed:
            return
        self._closed = True
        if self.metrics_server is not None:
            self.metrics_server.close()
            self.metrics_server = None
        if self.resilience is not None:
            # FIRST: drain the async checkpoint writer so a pending (possibly
            # emergency) snapshot lands — and journals its ckpt_end — before
            # run_end is written
            self.resilience.close()
        goodput_open = self.goodput is not None and self.goodput._opened
        if goodput_open:
            # close BEFORE summarizing: the ended-transition folds the live
            # state tail (and any open stall) into the state_seconds totals
            self.goodput.close()
        if self.telemetry is not None or goodput_open:
            # one closing summary event whether either (or both) layers ran —
            # telemetry-off + goodput-on must not discard the state/stall
            # accounting
            if self.journal is not None:
                summary = self.telemetry.summary() if self.telemetry is not None else {}
                if goodput_open:
                    summary.update(self.goodput.summary())
                if self.health is not None and self.health._opened:
                    summary.update(self.health.summary())
                if self.resilience is not None:
                    summary.update(self.resilience.summary())
                self.journal.write("telemetry_summary", **summary)
            if self.telemetry is not None:
                self.telemetry.close()
        if self.memory is not None and self.journal is not None:
            self.journal.write("memory_summary", **self.memory.summary())
        if self.journal is not None:
            self.journal.write("run_end", status=status)
            self.journal.close()
        self.tracer.close()

    # -- tracing + phase accounting ----------------------------------------
    def span(self, name: str, **args: Any):
        """Phase span context manager: feeds the telemetry phase-attribution
        accumulator, the run-state machine, the ``jax.profiler`` session if
        one is running (``sheeprl/<name>`` on its host plane) and (when
        tracing is open) the Chrome trace.  A slash name is a part of the
        phase before the slash (``tracing.KNOWN_PHASES``)."""
        tracing = not isinstance(self.tracer, NullTracer)
        # `_opened` (not just `is not None`): goodput is rank-0 only, and
        # telemetry-off workers must not pay a generator per span for a no-op
        goodput = self.goodput if (self.goodput is not None and self.goodput._opened) else None
        if self.telemetry is None and not tracing and goodput is None:
            return nullcontext()
        return self._span(name, args, tracing, goodput)

    @contextmanager
    def _span(self, name: str, args: Dict[str, Any], tracing: bool, goodput=None):
        if goodput is not None:
            goodput.note_span(name)
        token = self.telemetry.span_enter(name) if self.telemetry is not None else None
        try:
            with profiler_annotation(name, **args):
                if tracing:
                    with self.tracer.span(name, **args):
                        yield
                else:
                    yield
        finally:
            if token is not None:
                self.telemetry.span_exit(token)

    # -- telemetry hooks ---------------------------------------------------
    def instrument(self, name: str, fn, kind: str = "train", donate_argnums=(), cost_note=None):
        """Wrap a jitted step for the recompile watchdog + FLOPs accounting
        (``kind="train"``) or signature-watch only (``kind="rollout"``).
        ``donate_argnums`` declares which arguments the wrapped jit donates —
        the memory monitor verifies the donation actually happened at first
        dispatch.  ``cost_note`` is a caveat journaled with the step's
        ``telemetry_cost`` FLOPs (e.g. unrolled scans inflate
        ``cost_analysis()``, so MFU must not be read at face value).
        Identity when telemetry is disabled."""
        if self.telemetry is None:
            return fn
        return self.telemetry.instrument(
            name, fn, kind=kind, donate_argnums=donate_argnums, cost_note=cost_note
        )

    def note_env_steps(self, n: int) -> None:
        """Count ``n`` env steps toward ``Telemetry/env_steps_per_sec`` and
        fetch amortization (loops call it once per vector step with
        ``num_envs``).  No-op when telemetry is disabled."""
        if self.telemetry is not None:
            self.telemetry.note_env_steps(n)

    def note_policy_state(self, resets: int, cache_positions: int, carry_bytes: int, view_bytes: int = 0) -> None:
        """A sequence policy's carried state, once a vector step: episode
        resets applied to it, the positions its caches hold over all envs, its
        bytes on the device, and those of the player's view of the parameters
        beside them (``sheeprl_policy_*`` on ``/metrics``).  No-op when
        telemetry is disabled."""
        if self.telemetry is not None:
            self.telemetry.note_policy_state(resets, cache_positions, carry_bytes, view_bytes)

    def note_policy_gauges(self, **gauges: Any) -> None:
        """More of a sequence policy's state as it stands
        (``sheeprl_policy_carry_bytes{kind}``).  No-op when telemetry is disabled."""
        if self.telemetry is not None:
            self.telemetry.note_policy_gauges(**gauges)

    def note_policy_selection(self, visible: int, attended: int) -> None:
        """A sparse-attention policy's vector step: positions seen and attended
        (``sheeprl_policy_attended_positions`` and the two ``*_positions_total``).
        No-op when telemetry is disabled."""
        if self.telemetry is not None:
            self.telemetry.note_policy_selection(visible, attended)

    def note_policy_update(self, **reports: float) -> None:
        """What one update of a sequence policy reported beside its losses
        (``sheeprl_policy_updates_total``, ``sheeprl_policy_<name>_sum``).
        No-op when telemetry is disabled."""
        if self.telemetry is not None:
            self.telemetry.note_policy_update(**reports)

    def note_loop_order(self, order: str) -> None:
        """Count one iteration under the order it ran in
        (``sheeprl_loop_order_iterations_total{order}`` on ``/metrics``).
        No-op when telemetry is disabled."""
        if self.telemetry is not None:
            self.telemetry.note_loop_order(order)

    def on_loop_order(self, **decision: Any) -> None:
        """Journal one decision of the loop's order controller
        (``algos/dreamer_v3/loop_order.py``): the ``loop_order`` event."""
        self._journal_event("loop_order", **decision)

    def note_fetch(self, n: int = 1) -> None:
        """Count a blocking obs→action fetch outside the instrumented rollout
        dispatch path (Dreamer's direct action fetch).  No-op when disabled."""
        if self.telemetry is not None:
            self.telemetry.note_fetch(n)

    def note_dataset_read(self, n: int) -> None:
        """Count ``n`` transitions streamed from the offline dataset loader
        toward ``Telemetry/dataset_read_sps``.  No-op when disabled."""
        if self.telemetry is not None:
            self.telemetry.note_dataset_rows(n)

    def note_dataset_epoch(self, epoch: float) -> None:
        """Record the offline loader's epoch counter
        (``Telemetry/dataset_epoch``).  No-op when disabled."""
        if self.telemetry is not None:
            self.telemetry.note_dataset_epoch(epoch)

    def augment_metrics(self, step: Optional[int], metrics: Mapping[str, Any]) -> Mapping[str, Any]:
        """Merge the interval's ``Telemetry/*`` gauges (compute + memory) into
        an aggregated metrics dict (called by the logger proxy before the
        backend logs)."""
        extra: Dict[str, Any] = {}
        if self.telemetry is not None:
            extra.update(self.telemetry.interval_metrics(step))
        if self.memory is not None and self._rank_zero and self.log_dir is not None:
            extra.update(self.memory.interval_metrics())
        if self.goodput is not None:
            extra.update(self.goodput.interval_metrics())
        if self.health is not None:
            extra.update(self.health.interval_metrics())
        if self.resilience is not None and self._rank_zero:
            extra.update(self.resilience.interval_metrics())
        if not extra:
            return metrics
        merged = dict(metrics)
        merged.update(extra)
        return merged

    # -- learning-health hooks ---------------------------------------------
    def on_health(self, step: Optional[int], stats: Mapping[str, Any]) -> None:
        """Digest one train step's fetched ``health_stats`` dict: updates the
        live ``Telemetry/health/*`` gauges and runs the stats-fed anomaly
        detectors (update/weight-ratio band, dead-gradient, value-EV floor).
        No-op until opened, off rank 0, or with an empty dict (the train
        steps return ``{}`` when ``diagnostics.health`` is disabled, so call
        sites stay unconditional)."""
        if self.health is not None and self._rank_zero and stats:
            self.health.on_stats(step, stats)

    # -- memory hooks ------------------------------------------------------
    def register_footprint(self, name: str, tree_or_bytes: Any) -> None:
        """Record a static component's byte size (params / optimizer state /
        ...) for the ``memory_breakdown`` event.  No-op when disabled."""
        if self.memory is not None:
            self.memory.register_footprint(name, tree_or_bytes)

    def track_buffer(self, name: str, buffer: Any) -> None:
        """Track a replay buffer's live footprint per metric interval
        (host RAM, memmap on-disk, or HBM-resident bytes)."""
        if self.memory is not None:
            self.memory.track_buffer(name, buffer)

    def on_fsdp_shard_map(self, summary: Mapping[str, Any]) -> None:
        """Record how the FSDP partition rule laid out the train state
        (``parallel/fsdp.py::shard_map_summary``): journals the
        ``fsdp_shard_map`` event and arms the memory monitor's per-device
        accounting (``Telemetry/fsdp_axis_size`` gauge + the ``min_shard_bytes``
        exemption in the sharding audit).  No-op when disabled."""
        if not self.enabled:
            return
        if self.memory is not None:
            self.memory.note_fsdp(summary)
        self._journal_event("fsdp_shard_map", **dict(summary))

    # -- journal hooks -----------------------------------------------------
    def log_metrics(self, step: Optional[int], metrics: Mapping[str, Any]) -> None:
        """Journal one aggregated-metrics interval + run divergence checks.

        Called by the rank-0 logger proxy right after the metrics went to
        TensorBoard/W&B, so the journal mirrors exactly what was logged.
        """
        if not metrics:
            return
        if self.journal is not None:
            self.journal.write("metrics", step=step, metrics=dict(metrics))
        if self._detector is not None and self._rank_zero:
            for event in self._detector.observe(step, metrics):
                self._journal_divergence(event)
        if self.health is not None and self._rank_zero:
            # entropy-collapse / loss-plateau windows feed on the same
            # aggregated stream the divergence detector watches
            self.health.observe_metrics(step, metrics)

    def on_checkpoint(self, step: Optional[int], path: str) -> None:
        if self.journal is not None:
            self.journal.write("checkpoint", step=step, path=str(path))
        self.tracer.instant("checkpoint", step=step)

    # -- resilience hooks (ISSUE 13) ----------------------------------------
    def save_checkpoint(self, path: str, state: Mapping[str, Any], group: Optional[Mapping[str, Any]] = None) -> bool:
        """Route one checkpoint save through the resilience layer (async
        writer or blocking-with-journaling, manifest sidecar either way).
        Returns False when the layer is off/unopened — the caller
        (``Runtime.save``) then performs the plain synchronous save itself.
        ``group`` threads the coordinated multi-host record into the
        manifest (``resilience/coordination.py``)."""
        if self.resilience is None or not self.resilience._opened:
            return False
        self.resilience.save(path, state, group=group)
        return True

    # -- fault isolation hooks (ISSUE 14: decoupled fencing & rollback) ------
    def gate_promotion(
        self,
        iter_num: int,
        step: Optional[int],
        stats: Optional[Mapping[str, Any]] = None,
        nonfinite: float = 0.0,
    ) -> bool:
        """Promotion gate for the trainer→player params hop: True = hand the
        freshly trained params to the player.  Judges the signals the loop
        ALREADY fetched (in-graph nonfinite count, ``health_stats`` norms)
        plus any open learning-health anomaly — zero extra device syncs.  A
        rejection journals ``params_reject`` and the player keeps its
        last-good params.  Always True when isolation is off (today's
        unconditional hand-off)."""
        res = self.resilience
        if res is None or res.isolation is None or not res._opened:
            return True
        anomalies = ()
        if self.health is not None and self.health._opened:
            anomalies = self.health.open_anomaly_kinds()
        return res.isolation.judge(iter_num, step, stats or {}, float(nonfinite), anomalies)

    def refresh_last_good(self, iter_num: int, params: Any, opt_state: Any) -> None:
        """Refresh the in-memory last-good snapshot after a healthy
        promotion (one batched device→host fetch, double-buffered)."""
        res = self.resilience
        if res is not None and res.isolation is not None and res._opened:
            res.isolation.refresh(iter_num, params, opt_state)

    def quarantine(
        self, err: BaseException, iter_num: int, step: Optional[int]
    ) -> Optional[Dict[str, Any]]:
        """Absorb one quarantined train-step failure: journal ``rollback``
        and return the last-good ``{params, opt_state, iter_num}`` snapshot
        for the loop to restore, or None (no snapshot / isolation off /
        retry budget spent) — the caller then re-raises."""
        res = self.resilience
        if res is None or res.isolation is None or not res._opened:
            return None
        return res.isolation.rollback(err, iter_num, step)

    def last_good_state(self) -> Optional[Dict[str, Any]]:
        """The in-memory last-good ``{params, opt_state, iter_num}`` host
        snapshot, or None.  The fence-halt checkpoint branch saves THIS, not
        the live trainer trees — under ``sentinel.policy=warn`` the live
        params are exactly the corrupted state the fence escalated about."""
        res = self.resilience
        if res is None or res.isolation is None or not res._opened:
            return None
        return res.isolation.last_good

    def fence_halt_due(self) -> bool:
        """True once the staleness budget is exhausted: the loop forces its
        checkpoint branch (emergency snapshot of the last-good state) and
        then calls :meth:`on_fence_halt`."""
        res = self.resilience
        return res is not None and res.isolation is not None and res._opened and res.isolation.halt_due

    def on_fence_halt(self, step: Optional[int], iter_num: int, ckpt_path: str) -> None:
        """Finish a staleness escalation: journal the structured finding
        (fsync'd), close the run with status ``halted`` and raise
        :class:`~sheeprl_tpu.resilience.isolation.IsolationHalt`."""
        from sheeprl_tpu.resilience.isolation import IsolationHalt

        iso = self.resilience.isolation
        self._journal_divergence(
            {
                "kind": "param_staleness_exhausted",
                "step": step,
                "iter_num": int(iter_num),
                "staleness": iso.staleness,
                "budget": iso.max_staleness,
                "path": str(ckpt_path),
            }
        )
        self._journal_sync()
        self.close("halted")
        raise IsolationHalt(
            f"player param staleness exhausted its budget ({iso.staleness} > "
            f"{iso.max_staleness} consecutive rejected promotions) at iteration {iter_num}; "
            f"emergency checkpoint {ckpt_path} "
            "(diagnostics.resilience.isolation.max_staleness)"
        )

    def maybe_chaos_trainer_fault(self, iter_num: int) -> None:
        """Raise the scheduled :class:`ChaosTrainerError` at the train
        dispatch boundary (chaos fault ``trainer_exception``); no-op
        otherwise."""
        res = self.resilience
        if res is None or res.chaos is None or not res._opened:
            return
        if res.chaos.take(iter_num, "trainer_exception"):
            from sheeprl_tpu.resilience.chaos import ChaosTrainerError

            raise ChaosTrainerError(
                f"chaos: injected trainer exception at iteration {iter_num}"
            )

    def preempt_due(self, iter_num: int) -> bool:
        """True once a preemption (SIGTERM/SIGINT, or the
        ``diagnostics.resilience.inject_preempt_iter`` drill) is pending.
        The loop then forces its checkpoint branch — the emergency snapshot —
        and calls :meth:`on_preempted` with the written path."""
        return self.resilience is not None and self.resilience.preempt_due(iter_num)

    def on_preempted(self, step: Optional[int], iter_num: int, ckpt_path: str) -> None:
        """Finish a graceful preemption: drain the async writer FIRST (the
        ``preempted`` record must not claim a snapshot that never landed),
        journal the fsync'd ``preempted`` record with the observed durability,
        close the run with status ``preempted`` and exit with the distinct
        preemption code by raising :class:`PreemptedExit`."""
        from sheeprl_tpu.resilience.preemption import PreemptedExit

        reason = "preempt"
        durable = True
        if self.resilience is not None:
            reason = self.resilience.preempt_reason
            # bounded: a write slower than the flush timeout is abandoned at
            # exit, and the record says so — resume selection only ever picks
            # VERIFIED checkpoints, so a lost snapshot costs progress, not
            # correctness
            durable = self.resilience.flush()
        self._journal_event(
            "preempted",
            step=step,
            iter_num=int(iter_num),
            path=str(ckpt_path),
            reason=reason,
            snapshot_durable=durable,
        )
        self._journal_sync()
        self.close("preempted")
        raise PreemptedExit(
            f"preempted ({reason}) at iteration {iter_num}: emergency checkpoint {ckpt_path}"
        )

    def _journal_divergence(self, event: Dict[str, Any]) -> None:
        if self.telemetry is not None:
            self.telemetry.count_sentinel_event()
        if self.journal is not None:
            kind = event.pop("kind", "unknown")
            step = event.pop("step", None)
            self.journal.write("divergence", kind=kind, step=step, **event)
            self.tracer.instant(f"divergence:{kind}", step=step)

    # -- sentinel host side ------------------------------------------------
    def on_update(self, step: Optional[int], stats: Mapping[str, Any], nonfinite: float = 0.0) -> None:
        """Digest one (fetched) train-step metric bundle.

        ``nonfinite`` is the in-graph count of optimizer steps whose
        loss/grad-norm finiteness flag tripped.  Journals a structured
        ``divergence`` event and applies the configured policy: ``warn``
        warns, ``skip_update`` already discarded the bad update in-graph (so
        this only records it), ``halt`` raises :class:`SentinelHalt`.
        """
        if not (self.enabled and self.sentinel.enabled):
            return
        nonfinite = float(nonfinite)
        if nonfinite <= 0:
            return
        self._journal_divergence(
            {
                "kind": "nonfinite_update",
                "step": step,
                "nonfinite_steps": nonfinite,
                "policy": self.sentinel.policy,
                **{k: v for k, v in stats.items()},
            }
        )
        if self.sentinel.policy == "halt":
            # a decoupled loop with the isolation layer armed catches this
            # halt and rolls back to the last-good snapshot — closing the
            # facade here would kill the journal under a run that survives
            absorbable = (
                self.resilience is not None
                and self.resilience.isolation is not None
                and self.resilience.isolation.can_absorb()
            )
            if not absorbable:
                self.close("halted")
            raise SentinelHalt(
                f"non-finite training update at step {step} "
                f"(nonfinite optimizer steps this interval: {nonfinite:g}); "
                "diagnostics.sentinel.policy=halt"
            )
        if self.sentinel.policy == "warn" and self._rank_zero:
            warnings.warn(
                f"Sentinel: non-finite training update at step {step} "
                f"({nonfinite:g} optimizer steps); params may be corrupted "
                "(diagnostics.sentinel.policy=warn)",
                RuntimeWarning,
            )

    def observe_rows(self, step: Optional[int], names, rows) -> None:
        """Sentinel digest for the Dreamer metric drain: ``rows`` is a list of
        per-gradient-step metric vectors (ordered as ``names``) fetched at the
        log boundary.  Counts rows with any non-finite entry; under
        ``skip_update`` those steps were already discarded in-graph."""
        if not (self.enabled and self.sentinel.enabled) or not rows:
            return
        import numpy as np

        arr = np.asarray(rows, dtype=np.float64)
        bad = ~np.isfinite(arr).all(axis=tuple(range(1, arr.ndim)))
        n_bad = int(bad.sum())
        if n_bad:
            first_bad = arr[bad][0]
            stats = {str(n): float(v) for n, v in zip(names, first_bad)}
            self.on_update(step, stats, nonfinite=n_bad)

    # -- fault injection (tests / chaos drills) ----------------------------
    def maybe_inject_nan(self, iter_num: int, tree):
        """Poison a train batch at the configured iteration
        (``diagnostics.sentinel.inject_nan_iter``, or a chaos schedule's
        ``nan_grads`` entry) — the documented way to drill the sentinel /
        fencing paths end-to-end without doctoring model code."""
        poison = False
        res = self.resilience
        if res is not None and res.chaos is not None and res._opened:
            # take() journals its own fault_injection (kind=nan_grads)
            poison = res.chaos.take(iter_num, "nan_grads")
        inject = self.sentinel.inject_nan_iter
        if inject is not None and int(iter_num) == inject:
            if self.journal is not None:
                self.journal.write("fault_injection", iter_num=int(iter_num))
            poison = True
        if not poison:
            return tree
        return poison_tree(tree)

    def maybe_inject_shape_change(self, iter_num: int, tree, pad: int = 1):
        """Shape-change fault injection for the recompile watchdog
        (``diagnostics.telemetry.watchdog.inject_shape_change_iter``): pad the
        leading axis of every array leaf by repeating its last row ``pad``
        times at the configured loop iteration.  Only wired into the
        PPO-family loops, whose minibatch indexing reads exactly
        ``num_minibatches * batch_size`` rows — the padding rows are never
        sampled, so training math is untouched while the dispatch signature
        (and hence the compiled graph) genuinely changes.  ``pad`` defaults to
        1; multi-device callers pass their data-axis divisor."""
        telemetry = self.telemetry
        if telemetry is None or telemetry.inject_shape_change_iter is None:
            return tree
        if int(iter_num) != telemetry.inject_shape_change_iter:
            return tree
        import jax
        import jax.numpy as jnp

        if self.journal is not None:
            self.journal.write("fault_injection", iter_num=int(iter_num), kind="shape_change", pad=int(pad))

        def pad_leaf(x):
            if not hasattr(x, "shape") or not getattr(x, "shape", ()):  # scalars
                return x
            tail = jnp.repeat(x[-1:], int(pad), axis=0)
            return jnp.concatenate([x, tail], axis=0)

        return jax.tree_util.tree_map(pad_leaf, tree)


def build_diagnostics(cfg: Optional[Mapping[str, Any]]) -> Diagnostics:
    """Construct the facade from a composed run config (never raises on a
    missing ``diagnostics`` section — direct callers with a partial config
    simply get a disabled facade).  Installs the process-wide compile-event
    listener early so compiles that happen before the run dir exists (agent
    build, warmup jits) are still counted."""
    diagnostics = Diagnostics(cfg)
    if diagnostics.telemetry is not None:
        monitoring_available()
    return diagnostics
