"""Device time of the ``reassembly.count`` scope of the ``bench_reassembly``
program per window (profiler trace, attributed by ``bench/scopes.py``)."""
import scopes


def read(ctx):
    return scopes.read(ctx, "bench_reassembly", "reassembly.count")
