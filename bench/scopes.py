"""Device time of a named scope inside one program, from the profiler trace
of the measured window.

* Program: each ``XLA Ops`` event of a device plane belongs to the
  ``XLA Modules`` event (``jit_<name>(id)`` -> ``<name>``) that contains it
  on the same plane, so same-named instructions of two programs stay apart.
* Scope: the program's optimized HLO, taken from the executables this
  process holds (``client.live_executables()``), gives each instruction's
  ``op_name`` (``jit(bench_reassembly)/jit(reassembly_plan)/reassembly.count/
  scatter-add``); an op counts under every scope named in its path. A
  fusion carries the ``op_name`` XLA gave it and is never split.
* Window and chips as in ``tracereduce``: program calls that start inside
  the span ``bench.window`` count, whole; seconds are averaged over the
  cell's chips.

The result is device milliseconds of the scope per call of the program: per
window, like ``reassembly_ms``. ``None`` where the program made no call in
the window or none of its ops carries the scope.
"""
from __future__ import annotations

import bisect
import collections
import functools
import glob
import os
import re

from tracereduce import _DEVICE, _MODULE, _OP

TRACES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "out", "trace")
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*?\bop_name="([^"]*)"')


def newest_trace(root):
    """The ``*.xplane.pb`` written last under ``root``, or ``None``."""
    paths = glob.glob(os.path.join(root, "**", "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def op_names(text):
    """``{instruction: op_name}`` over every computation of an HLO text."""
    out = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def live_op_names():
    """``{program: {instruction: op_name}}`` of the executables this process
    holds, programs named as the trace's ``XLA Modules`` line names them."""
    import jax

    out = collections.defaultdict(dict)
    for exe in jax.devices()[0].client.live_executables():
        for module in exe.hlo_modules():
            out[_MODULE.match(module.name).group(1)].update(op_names(module.to_string()))
    return dict(out)


def program_ops(planes, n_chips):
    """``{program: (calls, {instruction: seconds})}`` in the window, over
    planes shaped as ``tracereduce.reduce_planes`` reads them."""
    devices, windows = {}, []
    for plane in planes:
        m = _DEVICE.match(plane.name)
        if m:
            devices[int(m.group(1))] = plane
            continue
        windows += [(ev.start_ns, ev.start_ns + ev.duration_ns)
                    for line in plane.lines for ev in line.events
                    if ev.name == "bench.window"]
    if not windows or not devices:
        raise ValueError("trace has no bench.window span or no TPU plane")
    w0, w1 = windows[0]
    chips = [devices[k] for k in sorted(devices)[:n_chips]]
    calls = collections.Counter()
    seconds = collections.defaultdict(collections.Counter)
    for plane in chips:
        lines = {line.name: line for line in plane.lines}
        mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                       _MODULE.match(ev.name).group(1))
                      for ev in lines["XLA Modules"].events)
        starts = [s for s, _, _ in mods]
        for s, _, name in mods:
            calls[name] += w0 <= s < w1
        for ev in lines["XLA Ops"].events:
            k = bisect.bisect_right(starts, ev.start_ns) - 1
            if k < 0 or ev.start_ns >= mods[k][1] or not w0 <= mods[k][0] < w1:
                continue
            m = _OP.match(ev.name)
            op = (m.group(1) if m else ev.name).lstrip("%")
            seconds[mods[k][2]][op] += ev.duration_ns * 1e-9 / len(chips)
    return {p: (calls[p] // len(chips), seconds[p]) for p in calls}


@functools.lru_cache(maxsize=None)
def _trace_ops(path, n_chips):
    from jax.profiler import ProfileData

    return program_ops(ProfileData.from_file(path).planes, n_chips)


def scope_ms(ops, names, program, scope):
    """Milliseconds of ``scope`` per call of ``program``, from
    ``program_ops`` and ``{program: op_names}``; ``None`` if absent."""
    calls, seconds = ops.get(program, (0, {}))
    named = names.get(program, {})
    hit = [s for op, s in seconds.items() if scope in named.get(op, "").split("/")]
    return 1e3 * sum(hit) / calls if calls and hit else None


def read(ctx, program, scope):
    """A per-layer reader's value: ``scope`` of ``program`` in the trace
    ``bench/run.py`` has just written."""
    path = newest_trace(TRACES)
    if path is None:
        return None
    return scope_ms(_trace_ops(path, ctx["trace"]["chips"]), live_op_names(),
                    program, scope)
