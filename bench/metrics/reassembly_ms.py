"""Device time of the ``bench_reassembly`` stage program per window
(profiler trace, ``XLA Modules`` line)."""


def read(ctx):
    total, calls = ctx["trace"]["modules"].get("bench_reassembly", (0.0, 0))
    return 1e3 * total / calls if calls else None
