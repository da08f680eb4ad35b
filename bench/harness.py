"""What every cell shares: finding a cell's files by name, the measured
window (windows offered back to back, at most ``in_flight`` outstanding),
the end-to-end metrics, and the result line.

A path module (``bench/paths/<path>.py``) gives ``setup(config, traffic,
seed, devices) -> state``. The state offers ``packets_per_window``,
``submit(i)`` (hand window ``i`` to the LB; returns at once),
``collect(handle)`` (wait until its outputs are on the host),
``check(kept, control=False)`` (compare kept outputs with the reference;
returns ``{name: (value, limit)}`` and the number of windows that
differed) and ``release()`` (free device state before the
reference runs).
"""
from __future__ import annotations

import collections
import contextlib
import importlib.util
import json
import os
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """``bench/<kind>/<name>.py`` as a module (loaded once per process)."""
    key = f"bench_{kind}_{name}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            key, os.path.join(BENCH, kind, name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


def cell(spec, name):
    """(workload entry, config dict, traffic dict) for a cell name."""
    for w in spec["workloads"]:
        if w["name"] == name:
            conf = next(c for c in spec["configs"] if c["name"] == w["config"])
            return (w, load_json(ROOT, conf["file"]),
                    load_json(BENCH, "cells", w["traffic"] + ".json"))
    raise KeyError(f"no workload named {name!r} in BENCHMARK.json")


def metrics_for(entries, workload):
    return [m for m in entries if workload in m.get("workloads", [workload])]


def base(name):
    """A metric named ``<base>.<tag>`` is ``<base>`` measured in the cells
    it lists, under a name (and a bound) of their own."""
    return name.split(".")[0]


def reader(name):
    """``bench/metrics/<name>.py``, else the reader of its base metric."""
    own = os.path.exists(os.path.join(BENCH, "metrics", name + ".py"))
    return load_module("metrics", name if own else base(name))


def span(name):
    """A host span in the profiler's trace (free when not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class CompileCounter:
    """Counts XLA compiles and persistent-cache loads while active."""

    def __init__(self):
        self.events = []
        self.on = False

    def __call__(self, event, secs, **kw):
        if self.on and ("backend_compile" in event or "cache_retrieval" in event):
            self.events.append(event)

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        self.on = True
        return self

    def __exit__(self, *exc):
        import jax

        self.on = False
        jax.monitoring.unregister_event_duration_listener(self)


def measure(state, seconds, in_flight, keep, rng, tracing=None, warm=0):
    """Offer windows back to back for ``seconds`` (or ``warm`` windows, as
    set-up), then drain.

    Returns the window's facts: windows completed, packets, the host-clock
    window (first hand-off to last output on the host), per-window
    latencies and ``keep`` completed windows sampled uniformly (reservoir,
    drawn from ``rng``) for the check. Every window handed off is waited
    for, so the rate counts all the work and all the time.
    """
    lat, kept = [], []
    pending = collections.deque()
    i = 0
    with CompileCounter() as cc, (tracing or contextlib.nullcontext()), \
            span("bench.window"):
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while True:
            now = time.perf_counter()
            if now < deadline or i < warm:
                pending.append((i, now, state.submit(i)))
                i += 1
                if len(pending) < in_flight:
                    continue
            if not pending:
                break
            j, t, handle = pending.popleft()
            out = state.collect(handle)
            lat.append(time.perf_counter() - t)
            n_done = len(lat)
            if n_done <= keep:
                kept.append((j, out))
            elif keep:
                r = int(rng.integers(0, n_done))
                if r < keep:
                    kept[r] = (j, out)
        t1 = time.perf_counter()
    return dict(windows=len(lat), packets=len(lat) * state.packets_per_window,
                window_s=t1 - t0, latencies=lat, kept=kept,
                compiles=len(cc.events))


def end_to_end(facts, setup_s):
    lat = np.asarray(facts["latencies"]) * 1e3
    return {"pkt_rate": facts["packets"] / facts["window_s"],
            "window_p50_ms": float(np.percentile(lat, 50)),
            "window_p99_ms": float(np.percentile(lat, 99)),
            "setup_s": setup_s}


def memory_peak(devices):
    peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
             for d in devices if d.memory_stats()]
    return max(peaks) if peaks else 0


def emit(result, checks):
    """Compared numbers as the last lines on stderr, then the result line."""
    for name, (value, limit) in checks.items():
        print(f"check {name} {value} limit {limit}", file=sys.stderr)
    sys.stderr.flush()
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    print(json.dumps(result), flush=True)
