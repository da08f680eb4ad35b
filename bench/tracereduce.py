"""Reduce a profiler trace (``*.xplane.pb``) of one measured window to
what the per-layer metrics read.

* The window is the host span ``bench.window``.
* Device planes are ``/device:TPU:<k>``; on each, the line ``XLA Ops``
  gives the operations and ``XLA Modules`` the programs (``jit_<name>(id)``
  -> ``<name>``).
* Busy time is the union of operation intervals inside the window;
  idle gaps are its complement there, each attributed to the host spans
  (``bench.*``, the window excluded) that overlap it, the rest to
  ``host.other``.
* Collective time is the sum of operations whose name marks a collective.

Numbers are averaged over the chips the cell uses.
"""
from __future__ import annotations

import collections
import re

COLLECTIVES = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter",
               "collective-permute")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_MODULE = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")
_OP = re.compile(r"^(%\S+) = .*?\b([a-z][a-z0-9-]*)\(")


def _union(intervals):
    """Sorted, merged intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(a0, a1, b0, b1):
    return max(0.0, min(a1, b1) - max(a0, b0))


def _op_name(text):
    """``%sort.0 sort`` from an operation's HLO text."""
    m = _OP.match(text)
    return f"{m.group(1)} {m.group(2)}" if m else text[:80]


def reduce(path, n_chips):
    """Window, busy and idle seconds, per-program and per-op device time,
    collective time and named idle gaps, from the trace at ``path``."""
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, n_chips)


def reduce_planes(planes, n_chips):
    """``reduce`` over planes with ``name`` and ``lines``, lines with
    ``name`` and ``events``, events with ``name``, ``start_ns`` and
    ``duration_ns``."""
    spans, devices = [], {}
    for plane in planes:
        m = _DEVICE.match(plane.name)
        if m:
            devices[int(m.group(1))] = plane
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("bench."):
                    spans.append((ev.name, ev.start_ns * 1e-9,
                                  (ev.start_ns + ev.duration_ns) * 1e-9))
    windows = [(s, e) for n, s, e in spans if n == "bench.window"]
    if not windows or not devices:
        raise ValueError("trace has no bench.window span or no TPU plane")
    w0, w1 = windows[0]
    host = [(n, s, e) for n, s, e in spans if n != "bench.window"]
    chips = [devices[k] for k in sorted(devices)[:n_chips]]
    busy = []
    modules = collections.defaultdict(lambda: [0.0, 0])
    ops = collections.Counter()
    collective = 0.0
    gaps = collections.Counter()
    for c, plane in enumerate(chips):
        lines = {line.name: line for line in plane.lines}
        intervals = []
        for ev in lines["XLA Ops"].events:
            s, e = ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9
            if e <= w0 or s >= w1:
                continue
            intervals.append((max(s, w0), min(e, w1)))
            ops[_op_name(ev.name)] += (e - s) / len(chips)
            if any(k in ev.name for k in COLLECTIVES):
                collective += (e - s) / len(chips)
        for ev in lines["XLA Modules"].events:
            s = ev.start_ns * 1e-9
            if w0 <= s < w1:
                mod = modules[_MODULE.match(ev.name).group(1)]
                mod[0] += ev.duration_ns * 1e-9 / len(chips)
                mod[1] += 1
        merged = _union(intervals)
        busy.append(sum(e - s for s, e in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            named = 0.0
            for n, s, e in host:
                o = _overlap(g0, g1, s, e)
                if o:
                    gaps[n] += o / len(chips)
                    named += o
            if g1 - g0 > named:
                gaps["host.other"] += (g1 - g0 - named) / len(chips)
    return dict(
        window_s=w1 - w0, busy_s=sum(busy) / len(busy),
        modules={k: (v[0], v[1] // len(chips)) for k, v in modules.items()},
        ops=ops, collective_s=collective, gaps=gaps, chips=len(chips))


def breakdown(red):
    top = lambda c: [[k, v] for k, v in c.most_common(10)]
    return {"device_ops": top(red["ops"]), "idle_gaps": top(red["gaps"])}
