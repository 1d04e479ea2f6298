"""One run of one cell: the real CLI loop, a window bounded from outside.

``run_cell`` drives ``sheeprl_tpu.cli.run`` in this process on the main
thread, exactly as ``python sheeprl.py`` does, with overrides taken from the
cell's files.  Nothing of the program is edited; the harness stands around it:

- everything that knows which algorithm runs comes from the family module the
  configuration's file names (``families/<family>.py``): where the
  benchmark's own weights go in and the player's forward pass is copied, how
  the train step's arguments and result are read, which env the cells run
  against, the comparison that decides ``correct``, and the faults that
  comparison has to catch;
- the env is the benchmark's own, which keeps the client's clock (``steplog.py``);
- the compiled train step the loop built is wrapped where every loop gets it
  (``Diagnostics.instrument``): the :class:`Recorder` copies what goes into
  and comes out of its first steps, stamps every later call, and is otherwise
  a pass-through.  The object the window drives is the one those first steps
  went through;
- a watcher thread tails the run's journal, waits for the warm-up the cell's
  file states, scrapes ``/metrics`` at both ends of the window, traces a few
  seconds of it when asked, and ends the run the way a preemptible pool does:
  ``SIGTERM``, which the program turns into an emergency checkpoint and
  ``PreemptedExit``.

Everything before the window's start is ``setup_s``.
"""

from __future__ import annotations

import gc
import glob
import json
import os
import shutil
import signal
import sys
import threading
import time
import traceback
import urllib.request
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from benchmarks.chip.manifest import Manifest, ManifestError
from benchmarks.chip.steplog import read_step_log
from benchmarks.chip.window import window_metrics

RECORDED_STEPS = 3
_CALL_CAPACITY = 1 << 20
TOTAL_STEPS = 2_000_000_000  # a run length the window never reaches


class BenchFailure(RuntimeError):
    """The run is no measurement; the message says why."""


# --------------------------------------------------------------------------
# the train step, wrapped where the loop receives it
# --------------------------------------------------------------------------
def to_host(tree: Any) -> Any:
    """Host copies of a tree's leaves."""
    import jax

    return jax.tree_util.tree_map(np.asarray, tree)


class Recorder:
    """Pass-through around the loop's train step that keeps its first steps.

    Made before the loop starts, with the family's ``split_step`` as its only
    knowledge of the step's signature; :meth:`wrap` takes the compiled step
    when the loop hands it over.  Of the first ``RECORDED_STEPS`` calls it
    keeps host copies (the arguments are donated, so copied before the call):
    ``params_before`` the first, of each ``steps[n]`` the ``batch``, ``key``
    and ``aux`` that went in and the ``metrics`` that came out,
    ``opt_state_after_first``, and ``params_after`` the last.  ``player`` is
    the family's to fill (``install``)."""

    def __init__(self, split_step: Callable[[tuple, Optional[tuple]], Dict[str, Any]]):
        self._split = split_step
        self._step: Optional[Callable] = None
        self.calls = 0
        self.call_times = np.zeros(_CALL_CAPACITY, np.float64)
        self.params_before: Any = None
        self.steps: List[Dict[str, Any]] = []
        self.opt_state_after_first: Any = None
        self.params_after: Any = None
        self.player: Any = None

    def wrap(self, step: Callable) -> "Recorder":
        self._step = step
        return self

    def __getattr__(self, name: str) -> Any:  # whatever else the loop asks of the step
        return getattr(self._step, name)

    def release(self) -> None:
        """Let go of the compiled step once the loop has ended, so that the check has the device to itself."""
        self._step = None

    def __call__(self, *args):
        n = self.calls
        if n < _CALL_CAPACITY:
            self.call_times[n] = time.time()
        self.calls = n + 1
        if n >= RECORDED_STEPS:
            return self._step(*args)
        went_in = self._split(args, None)
        if n == 0:
            self.params_before = to_host(went_in["params"])
        record = {k: to_host(went_in[k]) for k in ("batch", "key", "aux")}
        out = self._step(*args)
        came_out = self._split(args, out)
        record["metrics"] = to_host(came_out["metrics"])
        self.steps.append(record)
        if n == 0:
            self.opt_state_after_first = to_host(came_out["opt_state"])
        if n == RECORDED_STEPS - 1:
            self.params_after = to_host(came_out["params"])
        return out


# --------------------------------------------------------------------------
# the watcher
# --------------------------------------------------------------------------
def read_journal(path: str) -> Dict[str, List[Dict[str, Any]]]:
    by_kind: Dict[str, List[Dict[str, Any]]] = {}
    if not os.path.isfile(path):
        return by_kind
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                event = json.loads(line)
            except ValueError:
                continue  # a line still being written
            by_kind.setdefault(event.get("event", "?"), []).append(event)
    return by_kind


def parse_metrics_text(text: str) -> Dict[str, float]:
    """Prometheus text -> ``{name or name{labels}: value}``."""
    out: Dict[str, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        try:
            out[name] = float(value)
        except ValueError:
            continue
    return out


def scrape(port: int) -> Dict[str, float]:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=5) as resp:
        return parse_metrics_text(resp.read().decode())


class Watcher(threading.Thread):
    """Bounds the window from outside the loop."""

    def __init__(self, journal_glob: str, recorder: Recorder, seconds: float,
                 warmup_steps: int, trace_dir: Optional[str], trace_seconds: float, startup_limit_s: float):
        super().__init__(name="bench-watcher", daemon=True)
        self._journal_glob = journal_glob
        self._recorder = recorder
        self.seconds = float(seconds)
        self._warmup_steps = int(warmup_steps)
        self._trace_dir = trace_dir
        self._trace_seconds = float(trace_seconds)
        self._startup_limit_s = float(startup_limit_s)
        self.t0: Optional[float] = None
        self.journal_path: Optional[str] = None
        self.scrapes: List[Dict[str, float]] = []
        self.error: Optional[str] = None
        self.trace_window: Optional[List[float]] = None
        self.tracer: Optional[threading.Thread] = None
        self.abort = threading.Event()

    def _wait_for(self, condition: Callable[[], bool], deadline: float) -> bool:
        while not self.abort.is_set():
            if condition():
                return True
            if time.time() > deadline:
                return False
            time.sleep(0.02)
        return False

    def _port(self) -> Optional[int]:
        found = glob.glob(self._journal_glob)
        if not found:
            return None
        self.journal_path = found[0]
        for event in read_journal(found[0]).get("metrics_server", []):
            if event.get("status") == "serving":
                return int(event["port"])
        return None

    def run(self) -> None:
        try:
            self._run()
        except Exception:  # the watcher must end the run whatever happens to it
            self.error = traceback.format_exc()
        finally:
            if not self.abort.is_set():
                end_run()

    def _run(self) -> None:
        deadline = time.time() + self._startup_limit_s

        if not self._wait_for(lambda: self._recorder.calls >= self._warmup_steps, deadline):
            self.error = f"the loop did not reach {self._warmup_steps} train steps in {self._startup_limit_s:.0f} s"
            return
        port = self._port()
        if port is None:
            self.error = "the journal has no serving metrics_server event"
            return
        self.t0 = time.time()
        self.scrapes.append(scrape(port))
        t1 = self.t0 + self.seconds
        if self._trace_dir:
            # in a thread of its own: stopping a trace takes seconds, and the
            # window's end and its second scrape must not wait for that
            self.tracer = threading.Thread(
                target=self._trace, args=(min(self.t0 + 1.0, t1), t1), name="bench-tracer", daemon=True
            )
            self.tracer.start()
        self._wait_for(lambda: time.time() >= t1, t1 + 1.0)
        self.scrapes.append(scrape(port))

    def _trace(self, start: float, t1: float) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the interpreter's own events are most of a trace and none of ours
        self._wait_for(lambda: time.time() >= start, t1)
        jax.profiler.start_trace(self._trace_dir, profiler_options=options)
        began = time.time()
        self._wait_for(lambda: time.time() >= min(began + self._trace_seconds, t1), t1)
        jax.profiler.stop_trace()
        self.trace_window = [began, time.time()]


def end_run() -> None:
    """What a preemptible pool sends.  Only when the program's guard listens:
    the default disposition would kill the process with nothing written."""
    if signal.getsignal(signal.SIGTERM) in (signal.SIG_DFL, signal.SIG_IGN, None):
        print("bench: no SIGTERM handler is installed; the run cannot be ended gracefully", file=sys.stderr)
        os._exit(3)
    os.kill(os.getpid(), signal.SIGTERM)


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------
def compose_overrides(family: Any, config: Dict[str, Any], cell: Dict[str, Any], seed: int, log_path: str,
                      extra: Optional[List[str]] = None) -> List[str]:
    overrides = list(config["overrides"]) + [f"env={family.env_group}"]
    overrides += list(family.env_overrides(cell, log_path))
    overrides += list(cell.get("overrides", []))
    overrides += [
        f"seed={seed}",
        f"algo.total_steps={TOTAL_STEPS}",
        "diagnostics.telemetry.http.enabled=True",
        "root_dir=bench",
        f"run_name={cell['name']}",
    ]
    return overrides + list(extra or [])


def program_seed(seed: int) -> int:
    """The driver's seeds pass 2**31; the program's seed arithmetic is kept inside 31 bits."""
    return int(seed) % 2147483647


def run_cell(
    manifest: Manifest,
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    t_start: float,
    work_dir: str,
    fault: Optional[str] = None,
    extra_overrides: Optional[List[str]] = None,
    controls: Optional[List[str]] = None,
    precision: Optional[str] = None,
) -> Dict[str, Any]:
    """Run one cell once; returns the result object of the contract plus, under
    ``_run``, what the readers saw.  ``fault`` names one of the family's
    ``faults``, planted under the loop's train step.  The caller has already
    looked for the chip."""
    import jax

    cell = manifest.workload(workload)
    config = manifest.config(cell["config"])
    family = manifest.family(config)
    if fault and fault not in family.faults:
        raise ManifestError(f"family {config['family']!r} has no fault {fault!r}: {sorted(family.faults)}")
    break_step = family.faults[fault] if fault else None
    if precision:  # the control: the program's own path in another precision
        config["precision"] = precision
        extra_overrides = list(extra_overrides or []) + [f"fabric.precision={precision}"]
    seed = program_seed(seed)
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": int(cell["chips"])}

    run_dir = os.path.join(work_dir, workload)
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    log_path = os.path.join(run_dir, "env_steps.npz")
    trace_dir = os.path.join(run_dir, "trace") if trace else None
    search_path = os.environ.get("SHEEPRL_TPU_SEARCH_PATH")
    os.environ["SHEEPRL_TPU_SEARCH_PATH"] = os.path.join(manifest.bench_dir, "hydra")

    from sheeprl_tpu import diagnostics as diag_module
    from sheeprl_tpu.cli import run as cli_run

    recorder = Recorder(family.split_step)
    original_instrument = diag_module.Diagnostics.instrument

    def instrument(self, name, fn, **kwargs):
        wrapped = original_instrument(self, name, fn, **kwargs)
        if name != "train_step":
            return wrapped
        if break_step is not None:
            wrapped = break_step(wrapped)
        return recorder.wrap(wrapped)

    watcher = Watcher(
        journal_glob=os.path.join(run_dir, "logs", "runs", "bench", cell["name"], "version_*", "journal.jsonl"),
        recorder=recorder,
        seconds=seconds,
        warmup_steps=int(cell["warmup_steps"]),
        trace_dir=trace_dir,
        trace_seconds=float(cell.get("trace_seconds", 3.0)),
        startup_limit_s=float(cell.get("startup_limit_s", 1000.0)),
    )
    overrides = compose_overrides(family, config, cell, seed, log_path, extra_overrides)
    cwd = os.getcwd()
    exit_code: Optional[int] = None
    restore_program: Callable[[], None] = lambda: None  # noqa: E731
    try:
        diag_module.Diagnostics.instrument = instrument
        restore_program = family.install(seed, recorder)
        os.chdir(run_dir)
        watcher.start()
        try:
            cli_run(overrides)
            raise BenchFailure("the run ended by itself before the window closed")
        except SystemExit as err:  # PreemptedExit: the emergency checkpoint landed
            exit_code = err.code if isinstance(err.code, int) else 1
    finally:
        watcher.abort.set()
        os.chdir(cwd)
        if search_path is None:
            os.environ.pop("SHEEPRL_TPU_SEARCH_PATH", None)
        else:
            os.environ["SHEEPRL_TPU_SEARCH_PATH"] = search_path
        diag_module.Diagnostics.instrument = original_instrument
        restore_program()
    watcher.join(timeout=30)
    if watcher.tracer is not None:
        watcher.tracer.join(timeout=300)

    # the peak is read before anything of the check touches the device
    stats = devices[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))

    if watcher.error or watcher.t0 is None or len(watcher.scrapes) < 2:
        raise BenchFailure(f"the window was never bounded: {watcher.error}")
    recorder.release()
    gc.collect()
    journal = read_journal(watcher.journal_path)
    t0, t1 = watcher.t0, watcher.t0 + watcher.seconds

    step_log = read_step_log(log_path)
    window = window_metrics(step_log["times"], t0, watcher.seconds, step_log["env"])
    call_times = recorder.call_times[: min(recorder.calls, _CALL_CAPACITY)]
    window["gradient_steps"] = int(((call_times >= t0) & (call_times <= t1)).sum())
    window["seconds"] = watcher.seconds
    s0, s1 = watcher.scrapes
    phase = 'sheeprl_phase_seconds_total{phase="'
    phase_delta = {
        k[len(phase):-2]: s1[k] - s0.get(k, 0.0) for k in s1 if k.startswith(phase)
    }
    first_cost = [e for e in journal.get("telemetry_cost", []) if e.get("fn") == "train_step"]
    run: Dict[str, Any] = {
        "t_start": t_start,
        "t0": t0,
        "config": config,
        "family": family,
        "cell": cell,
        "device": device,
        "journal": journal,
        "window": window,
        "phase_delta_s": phase_delta,
        "scrapes": [s0, s1],
        "first_train_step_t": first_cost[0]["t"] if first_cost else float(call_times[0]),
        "trace": None,
        "exit_code": exit_code,
    }

    for key in ("sheeprl_env_steps_total", "sheeprl_backend_compiles_total"):
        print(f"bench: scrape {key}: {s0.get(key)!r} -> {s1.get(key)!r}", file=sys.stderr)
    checks = run_validity(run)
    if trace:
        from benchmarks.chip.trace_reduce import find_xplane, load_xplane, reduce_trace

        run["trace"] = reduce_trace(load_xplane(find_xplane(trace_dir)), module_match=family.executables["train_step"])
        run["trace"]["host_window"] = watcher.trace_window
    checks.update(family.compare(recorder, recorder.player, step_log, config, cell, seed, controls))

    run["checks"] = checks
    result = assemble_result(manifest, workload, run, checks, trace)
    result["_run"] = run
    return result


def assemble_result(manifest: Manifest, workload: str, run: Dict[str, Any], checks: Dict[str, Dict[str, Any]],
                    trace: bool) -> Dict[str, Any]:
    """The result object of the contract from what a run left behind.  With
    ``trace`` the metrics are the cell's per-layer ones, each from its own
    reader (a reader that finds nothing is left out); without, the end-to-end
    ones, which the harness takes itself."""
    window, device = run["window"], dict(run["device"])
    metrics: Dict[str, Dict[str, Any]] = {}
    if trace:
        for spec in manifest.metrics_for(workload, "per_layer"):
            value = manifest.reader(spec["name"])(run)
            if value is not None:
                metrics[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
    else:
        values = {
            "setup_s": run["t0"] - run["t_start"],
            "env_steps_per_s": window["env_steps_per_s"],
            "action_gap_p95_ms": window["action_gap_p95_ms"],
        }
        for spec in manifest.metrics_for(workload, "end_to_end"):
            metrics[spec["name"]] = {"value": float(values[spec["name"]]), "unit": spec["unit"]}
    result: Dict[str, Any] = {
        "correct": all(c["ok"] for c in checks.values()),
        "attempted": int(window["steps"]),
        "failed": 0,
        "metrics": metrics,
        "device": device,
    }
    if trace:
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        result["breakdown"] = {
            "device_ops": run["trace"]["device_ops"],
            "idle_gaps": run["trace"]["idle_by_span"],
        }
    result["window"] = {k: window[k] for k in ("steps", "gaps", "gradient_steps", "action_gap_p50_ms", "steps_by_10s")}
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]} for k, c in checks.items()}
    return result


def run_validity(run: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """What makes a run a measurement at all, as numbers beside their limits."""
    journal, (s0, s1) = run["journal"], run["scrapes"]
    start = (journal.get("run_start") or [{}])[-1]
    platform = (start.get("device") or {}).get("platform", start.get("platform"))
    memory = (journal.get("memory_summary") or [{}])[-1]
    ckpt_ok = [e for e in journal.get("ckpt_end", []) if e.get("status") == "ok" and e.get("verified")]
    compiles = "sheeprl_backend_compiles_total"
    program_steps = s1.get("sheeprl_env_steps_total", 0.0) - s0.get("sheeprl_env_steps_total", 0.0)

    def exact(value: float, want: float) -> Dict[str, Any]:
        return {"value": value, "limit": want, "ok": value == want}

    return {
        "ran_on_tpu": exact(float(platform == run["device"]["platform"]), 1.0),
        "window_compiles": exact(s1.get(compiles, -1.0) - s0.get(compiles, 0.0), 0.0),
        "telemetry_fallbacks": exact(float(len(journal.get("telemetry_fallback", []))), 0.0),
        "donation_miss_leaves": exact(float(memory.get("donation_miss_leaves", -1)), 0.0),
        "verified_checkpoints": exact(float(min(len(ckpt_ok), 1)), 1.0),
        "preempted_exit": exact(float(run["exit_code"] == 75 and bool(journal.get("preempted"))), 1.0),
        # the program's own count of env steps against the env's clock: the
        # scrapes are not taken at the window's edges to the millisecond
        "env_steps_counter_gap": {
            "value": abs(program_steps - run["window"]["steps"]),
            "limit": 0.02 * run["window"]["steps"] + 2,
            "ok": abs(program_steps - run["window"]["steps"]) <= 0.02 * run["window"]["steps"] + 2,
        },
    }
