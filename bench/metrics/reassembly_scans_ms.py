"""Device time of the ``bench_reassembly`` program per window less its
``reassembly.sort`` and ``reassembly.groups`` scopes: the cumulative scans
that settle each group's completion, which the TPU compiler splits into
``reduce-window`` levels that carry no scope, with the few unscoped ops
around them (profiler trace, read by the three readers named here)."""
import harness


def read(ctx):
    parts = [harness.reader(name).read(ctx) for name in
             ("reassembly_ms", "reassembly_sort_ms", "reassembly_groups_ms")]
    if None in parts:
        return None
    total, sort, groups = parts
    return total - sort - groups
