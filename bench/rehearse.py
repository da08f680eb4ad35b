"""Rehearse every cell without the chip: tiny sizes, the CPU backend and
the Pallas kernels in interpret mode.

    python bench/rehearse.py [--seed N]

It drives the same set-up, window, check and control as a real run and
prints, per cell, only whether the outputs matched the reference and the
control failed. It never prints a metric: CPU timings say nothing of the
chip.
"""
from __future__ import annotations

import argparse
import functools
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import controls  # noqa: E402
import run  # noqa: E402

TINY_CONFIG = {"members_per_instance": 8, "daqs": 4}
TINY_TRAFFIC = {"window_packets": 2048, "pool_windows": 2, "check_windows": 3,
                "warm_windows": 2, "mean_bundle_bytes": 20_000}


def tiny_cell(name):
    """(spec, workload, config, traffic) of a cell, cut to a CPU size."""
    spec = harness.load_json(harness.ROOT, "BENCHMARK.json")
    workload, config, traffic = harness.cell(spec, name)
    config = dict(config, **TINY_CONFIG)
    traffic = dict(traffic, **{k: v for k, v in TINY_TRAFFIC.items() if k in traffic})
    return spec, workload, config, traffic


def interpret_kernels():
    """Program the data plane with the Pallas kernels, interpreted."""
    dataplane = harness.load_module("paths", "dataplane")
    if not isinstance(dataplane.program_instances, functools.partial):
        dataplane.program_instances = functools.partial(
            dataplane.program_instances, backend="pallas")


def rehearse(name, seed=0, seconds=0.5):
    """(result, checks) of one tiny run of cell ``name`` on the CPU."""
    spec, workload, config, traffic = tiny_cell(name)
    args = argparse.Namespace(workload=name, seed=seed, seconds=seconds, trace=0)
    return run.run_cell(spec, workload, config, traffic, args, require_tpu=False)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    interpret_kernels()
    spec = harness.load_json(harness.ROOT, "BENCHMARK.json")
    ok = True
    for w in spec["workloads"]:
        r = controls.readings(*tiny_cell(w["name"]), args.seed, 0.5, require_tpu=False)
        sound = all(v <= r["limits"][k] for k, v in r["program"].items())
        caught = any(v > r["limits"][k] for k, v in r["control"].items())
        print(f"rehearsal {w['name']}: program {r['program']} control {r['control']} "
              f"limits {r['limits']}: correct={sound} control_fails={caught}")
        ok &= sound and caught
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
