"""Faults planted in a path's timed programs, where their outputs are
produced, so that a check can be shown to catch each:

* ``stale``: each call hands back the previous call's outputs (a state
  returned unchanged);
* ``half``: the second half of every per-row output is dropped (the rows
  left out read -1, or false);
* ``altered``: one answer of the first output is changed.

``bench/test_bench.py`` plants each in every stage a cell runs, at tiny
sizes on the CPU; ``bench/controls.py --fault <stage>:<fault>`` reads one
on the chip at the cell's own size.
"""
from __future__ import annotations


def stale(f):
    last = []

    def g(*a):
        last.append(f(*a))
        return last.pop(0) if len(last) > 1 else last[0]
    return g


def half(f):
    import jax

    def drop(x):
        if x.ndim == 0:
            return x
        rows = x.shape[0] // 2
        return x.at[rows:].set(False if x.dtype == bool else -1)

    return lambda *a: jax.tree_util.tree_map(drop, f(*a))


def altered(f):
    import jax

    def g(*a):
        leaves, tree = jax.tree_util.tree_flatten(f(*a))
        x = leaves[0]
        leaves[0] = x.at[0].set(~x[0]) if x.dtype == bool else x.at[0].add(1)
        return jax.tree_util.tree_unflatten(tree, leaves)
    return g


FAULTS = {"stale": stale, "half": half, "altered": altered}


def plant(path_module, stage, fault):
    """Wrap program ``stage`` of ``path_module`` in ``FAULTS[fault]``;
    returns the module's own ``programs``, to put back."""
    real = path_module.programs

    def programs(*a, **kw):
        prog = real(*a, **kw)
        prog[stage] = FAULTS[fault](prog[stage])
        return prog

    path_module.programs = programs
    return real
