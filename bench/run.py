"""Run one benchmark cell once on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's inputs and deployment from the seed, warms up every
program the cell uses (set-up), offers windows back to back for
``--seconds`` (at most ``in_flight`` outstanding), checks a seeded sample
of the windows' outputs against the plain reference, and prints one JSON
line: the cell's end-to-end metrics (``--trace 0``) or, from a profiler
trace of the window, its per-layer metrics (``--trace 1``).

Exits 2 without a result when JAX finds no TPU or fewer chips than the
cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

import harness  # noqa: E402
import tracereduce  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_compile_cache():
    """The program's persistent cache (``$JAX_COMPILATION_CACHE_DIR`` if
    set, else ``.jax_cache/`` at the checkout's root), with every program
    cached, however fast it compiled."""
    import jax

    from repro.launch.compile_cache import use_compile_cache as program_cache

    program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class NoChip(RuntimeError):
    pass


def run_cell(spec, workload, config, traffic, args, require_tpu=True, keep_state=None):
    """One run of one cell: (result line, compared numbers). A dict given
    as ``keep_state`` receives the path's state and the window's facts."""
    t_import = time.perf_counter()
    import jax

    devices = jax.devices()
    t_devices = time.perf_counter()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < workload["chips"]:
        raise NoChip(f"{workload['name']} needs {workload['chips']} chips, "
                     f"JAX found {len(devices)}")
    devices = devices[:workload["chips"]]
    if devices[0].platform == "tpu":
        use_compile_cache()
    seed = args.seed % 2**63
    path = harness.load_module("paths", traffic["path"])
    state = path.setup(config, traffic, seed, devices)
    t_state = time.perf_counter()
    warm = harness.measure(state, 0.0, traffic["in_flight"], 0, None,
                           warm=traffic["warm_windows"])
    t_warm = time.perf_counter()
    setup_s = t_warm - T_START
    phases = {"start": t_import - T_START, "devices": t_devices - t_import,
              **getattr(state, "setup_phases", {}), "warm": t_warm - t_state}
    print("bench: set-up " + " ".join(f"{k} {v:.3f}" for k, v in phases.items())
          + f" s; warm-up loaded or compiled {warm['compiles']} programs", file=sys.stderr)

    seconds, tracing, trace_dir = args.seconds, None, None
    if args.trace:
        # a traced window is the traffic's trace_seconds: a profile of a few
        # thousand windows takes minutes to write and read
        seconds = min(args.seconds, traffic["trace_seconds"])
        trace_dir = os.path.join(BENCH, "out", "trace", workload["name"])
        # host_tracer_level 1 keeps the benchmark's spans and drops the
        # runtime's own per-transfer events, which swell a 10 s trace of
        # the data plane to 170 MB and minutes of reading
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = 1
        options.python_tracer_level = 0
        # the first seconds of a process, and a profiler session that has
        # not yet held as many events, run slower than the steady state:
        # run through both before the traced window, so that its idle share
        # is not the profiler's start
        harness.measure(state, traffic["trace_warm_seconds"], traffic["in_flight"], 0, None)
        shutil.rmtree(trace_dir, ignore_errors=True)
        harness.measure(state, seconds, traffic["in_flight"], 0, None,
                        jax.profiler.trace(trace_dir, profiler_options=options))
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracing = jax.profiler.trace(trace_dir, profiler_options=options)
    facts = harness.measure(state, seconds, traffic["in_flight"],
                            traffic["check_windows"], np.random.default_rng((seed, 1)),
                            tracing)
    lat = np.asarray(facts["latencies"])
    print(f"bench: {facts['windows']} windows in {facts['window_s']:.3f} s; "
          f"compiles inside the window: {facts['compiles']}; slowest window "
          f"{1e3 * lat.max():.3f} ms, {int(np.sum(lat > 2 * np.median(lat)))} over twice "
          f"the median, {float(np.sum(lat[lat > 2 * np.median(lat)])):.3f} s in those",
          file=sys.stderr)
    peak = harness.memory_peak(devices)
    state.release()
    with harness.span("bench.check"):
        checks, failed = state.check(facts["kept"])
    if keep_state is not None:
        keep_state.update(state=state, facts=facts)

    result = {"correct": all(v <= lim for v, lim in checks.values()),
              "attempted": facts["windows"], "failed": failed, "metrics": {},
              "device": {"platform": devices[0].platform, "kind": devices[0].device_kind,
                         "count": len(devices), "memory_peak_bytes": peak}}
    name = workload["name"]
    if args.trace:
        xplane = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
        red = tracereduce.reduce(sorted(xplane)[-1], len(devices))
        ctx = {"trace": red, "traffic": traffic, "config": config,
               "kind": devices[0].device_kind}
        for m in harness.metrics_for(spec["per_layer"], name):
            value = harness.reader(m["name"]).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        result["device"].update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = tracereduce.breakdown(red)
    else:
        e2e = harness.end_to_end(facts, setup_s)
        for m in harness.metrics_for(spec["end_to_end"], name):
            result["metrics"][m["name"]] = {"value": e2e[harness.base(m["name"])],
                                            "unit": m["unit"]}
    return result, checks


def main(argv=None):
    args = parse(argv)
    spec = harness.load_json(harness.ROOT, "BENCHMARK.json")
    workload, config, traffic = harness.cell(spec, args.workload)
    try:
        result, checks = run_cell(spec, workload, config, traffic, args)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
