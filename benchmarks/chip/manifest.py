"""Finding a cell's files by name.

``BENCHMARK.json`` names cells, configurations and per-layer metrics; whatever
belongs to one of them sits in a file of its own that is found by that name:

- ``workloads/<cell>.json``: the traffic (env parameters, warm-up, extra overrides);
- the configuration's ``file`` (``configs/<config>.json``): source, overrides,
  shapes, the ``family`` of its algorithm and its plain ``reference``;
- ``families/<family>.py``: everything that knows which algorithm runs
  (:data:`FAMILY_ANSWERS`; PERF.md section 3 says what each is);
- ``metrics/<metric>.py``: one reader, ``read(run) -> float | None``.

Adding a cell, a configuration, a family or a per-layer metric adds files and
entries in ``BENCHMARK.json``; nothing here, in ``run.py``, ``harness.py`` or
``check.py`` names any of them.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
# what a family module answers: the whole of what a new algorithm family brings
FAMILY_ANSWERS = ("install", "split_step", "compare", "faults", "train_step_flops", "executables", "train_step_scopes",
                  "env_group", "env_overrides")


class ManifestError(RuntimeError):
    pass


def load_json(path: str) -> Dict[str, Any]:
    try:
        with open(path) as fh:
            return json.load(fh)
    except (OSError, ValueError) as err:
        raise ManifestError(f"cannot read {path}: {err}") from err


class Manifest:
    """``BENCHMARK.json`` plus the directory its files are found in."""

    def __init__(self, root: str = ROOT, bench_dir: Optional[str] = None):
        self.root = root
        self.bench_dir = bench_dir or os.path.join(root, "benchmarks", "chip")
        self.data = load_json(os.path.join(root, "BENCHMARK.json"))

    def _entry(self, section: str, name: str) -> Dict[str, Any]:
        for entry in self.data.get(section, []):
            if entry.get("name") == name:
                return entry
        known = [e.get("name") for e in self.data.get(section, [])]
        raise ManifestError(f"{name!r} is not among BENCHMARK.json's {section}: {known}")

    def workload(self, name: str) -> Dict[str, Any]:
        """The cell's entry merged over its data file (the entry wins)."""
        entry = self._entry("workloads", name)
        cell = load_json(os.path.join(self.bench_dir, "workloads", f"{name}.json"))
        return {**cell, **entry}

    def config(self, name: str) -> Dict[str, Any]:
        """The configuration's file, its ``reference`` made a path from the root."""
        entry = self._entry("configs", name)
        config = {**load_json(os.path.join(self.root, entry["file"])), "name": name}
        if "reference" in config:
            config["reference"] = os.path.join(self.root, config["reference"])
        return config

    def family(self, config: Dict[str, Any]) -> Any:
        """The module ``families/<family>.py`` that the configuration's file names."""
        name = config.get("family")
        if not name or not NAME_RE.match(str(name)):
            raise ManifestError(f"configuration {config.get('name')!r} names no family in its file")
        path = os.path.join(self.bench_dir, "families", f"{name}.py")
        if not os.path.isfile(path):
            raise ManifestError(f"family {name!r} of configuration {config.get('name')!r} has no file at {path}")
        module = load_file(path, "bench_family_" + name)
        missing = [answer for answer in FAMILY_ANSWERS if not hasattr(module, answer)]
        if missing:
            raise ManifestError(f"family file {path} does not answer {missing}")
        return module

    def metrics_for(self, workload: str, section: str) -> List[Dict[str, Any]]:
        """The metrics of ``end_to_end`` or ``per_layer`` that this cell reports."""
        return [
            m for m in self.data.get(section, []) if "workloads" not in m or workload in m["workloads"]
        ]

    def reader(self, metric: str) -> Callable[[Dict[str, Any]], Optional[float]]:
        path = os.path.join(self.bench_dir, "metrics", f"{metric}.py")
        if not os.path.isfile(path):
            raise ManifestError(f"per-layer metric {metric!r} has no reader at {path}")
        return load_file(path, "bench_metric_" + metric).read


def load_file(path: str, name: str) -> Any:
    """The module in the file at ``path``: found by where it is, not by a name on ``sys.path``."""
    spec = importlib.util.spec_from_file_location(re.sub(r"\W", "_", name), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def check_names(data: Dict[str, Any]) -> List[str]:
    """What the contract's naming rules would refuse, as a list of complaints."""
    bad = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e.get("name", "") for e in data.get(section, [])]
        bad += [f"{section}: bad name {n!r}" for n in names if not NAME_RE.match(n)]
        bad += [f"{section}: duplicate name {n!r}" for n in set(names) if names.count(n) > 1]
    for section in ("end_to_end", "per_layer"):
        for m in data.get(section, []):
            if not UNIT_RE.match(m.get("unit", "")):
                bad.append(f"{section}: bad unit {m.get('unit')!r} on {m.get('name')!r}")
            if m.get("better") not in ("lower", "higher"):
                bad.append(f"{section}: {m.get('name')!r} needs better = lower | higher")
    return bad
