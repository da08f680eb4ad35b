"""Device time of the ``bench_route`` stage program per window
(profiler trace, ``XLA Modules`` line)."""


def read(ctx):
    total, calls = ctx["trace"]["modules"].get("bench_route", (0.0, 0))
    return 1e3 * total / calls if calls else None
