"""Readings that set a cell's limits: the program's compared numbers and
the control's, on several seeds, at the cell's own size, in one process.

    python bench/controls.py --workload <cell> --seeds 11 12 13 [--seconds 2]
                             [--fault <stage>:<stale|half|altered>]

For each seed it runs the cell's set-up and a short window once and judges
the same kept windows twice: the program's outputs against the reference
(the lower reading), and the control in the program's place (the
reference with its hit-less epoch switch broken; the upper reading). With
``--fault`` the program runs with that fault planted in that stage's
program (``bench/faults.py``), so the program's readings are the fault's.
The benchmark's own runs never run this. Prints one JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import faults  # noqa: E402
import harness  # noqa: E402
import run  # noqa: E402


def readings(spec, workload, config, traffic, seed, seconds, require_tpu=True):
    """{"program": {name: value}, "control": {name: value}} for one seed."""
    args = argparse.Namespace(workload=workload["name"], seed=seed, seconds=seconds,
                              trace=0)
    kept = {}
    result, checks = run.run_cell(spec, workload, config, traffic, args,
                                  require_tpu=require_tpu, keep_state=kept)
    control, _ = kept["state"].check(kept["facts"]["kept"], control=True)
    return {"seed": seed, "windows_checked": len(kept["facts"]["kept"]),
            "program": {k: v for k, (v, _) in checks.items()},
            "control": {k: v for k, (v, _) in control.items()},
            "limits": {k: lim for k, (_, lim) in checks.items()}}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--fault", help="<stage>:<fault>, planted in the program")
    args = ap.parse_args(argv)
    spec = harness.load_json(harness.ROOT, "BENCHMARK.json")
    workload, config, traffic = harness.cell(spec, args.workload)
    if args.fault:
        stage, fault = args.fault.split(":")
        faults.plant(harness.load_module("paths", traffic["path"]), stage, fault)
    for seed in args.seeds:
        r = readings(spec, workload, config, traffic, seed, args.seconds)
        print(json.dumps(dict(r, fault=args.fault)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
