#!/usr/bin/env python3
"""The benchmark's command: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Refuses to start (exit 1, one line on stderr, nothing on stdout) unless JAX
finds a TPU with as many chips as the cell asks for, or when the program is
not beside the benchmark.  The last line of stdout is the result object of
the contract; the numbers compared, each beside its limit, are its last key
and the last lines of stderr.  Three flags the driver never passes are for
showing that the comparison fails what it should: ``--precision`` puts the
program's own path in another precision in the cell's place (the control),
``--fault`` plants one of the family's ``faults`` under the loop's train step, and
``--control`` also reads the reference in a lower precision against itself
and prints the worst leaves on stderr.
"""

from __future__ import annotations

import time

T_START = time.time()  # before any import that costs: everything from here is set-up

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
WORK_DIR = os.path.join(ROOT, ".bench_runs")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--control", default="", help="comma-separated precisions to read the reference in as well")
    parser.add_argument("--precision", default="", help="run the program at this fabric.precision instead (a control)")
    parser.add_argument("--fault", default="", help="plant this fault of the configuration's family under the train step")
    args = parser.parse_args(argv)

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmarks.chip.manifest import Manifest, ManifestError

    try:
        manifest = Manifest(ROOT)
        cell = manifest.workload(args.workload)
        manifest.family(manifest.config(cell["config"]))
    except ManifestError as err:
        print(f"bench: {err}", file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(ROOT, "sheeprl_tpu")):
        print("bench: refusing to start: the program (sheeprl_tpu/) is not in this checkout", file=sys.stderr)
        return 1

    import jax

    try:
        devices = jax.devices()
    except RuntimeError as err:
        print(f"bench: refusing to start: JAX found no device ({err})", file=sys.stderr)
        return 1
    if devices[0].platform != "tpu" or len(devices) < int(cell["chips"]):
        print(
            f"bench: refusing to start: {args.workload} needs {cell['chips']} TPU chip(s), "
            f"JAX found {len(devices)} x {devices[0].platform}",
            file=sys.stderr,
        )
        return 1

    from benchmarks.chip.harness import BenchFailure, run_cell

    try:
        result = run_cell(
            manifest, args.workload, args.seed, args.seconds, bool(args.trace), T_START, WORK_DIR,
            fault=args.fault or None,
            controls=[c for c in args.control.split(",") if c], precision=args.precision or None,
        )
    except ManifestError as err:  # a fault the cell's family does not have
        print(f"bench: {err}", file=sys.stderr)
        return 2
    except BenchFailure as err:
        print(f"bench: no measurement: {err}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(os.path.join(WORK_DIR, args.workload), ignore_errors=True)
    result.pop("_run")  # which leaves the numbers compared last in the line
    for name, check in result["checks"].items():
        print(f"bench: check {name}: value {check['value']!r} limit {check['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
