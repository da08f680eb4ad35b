"""Per-scope device time (``scopes.py``) on hand-made traces and HLO text
with known answers, on a small recorded TPU v5e trace, and on the HLO of a
scoped function compiled for the CPU."""
import os
import shutil
from types import SimpleNamespace as NS

import pytest

import harness
import scopes
import tracereduce
from test_tracereduce import RECORDED, chip, ev

HLO = """\
HloModule jit_bench_reassembly, is_scheduled=true

%fused_computation (param_0: u32[8]) -> s32[9] {
  %param_0 = u32[8]{0} parameter(0)
  ROOT %scatter.1 = s32[9]{0} scatter(%param_0), metadata={op_name="jit(bench_reassembly)/jit(reassembly_plan)/reassembly.count/scatter-add" stack_frame_id=3}
}

ENTRY %main.5 (p: u32[8]) -> s32[9] {
  %p = u32[8]{0} parameter(0)
  %sort.0 = u32[8]{0} sort(%p), dimensions={0}, metadata={op_name="jit(bench_reassembly)/jit(reassembly_plan)/reassembly.sort/sort"}
  %fusion.1 = s32[9]{0} fusion(%sort.0), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(bench_reassembly)/jit(reassembly_plan)/reassembly.count/scatter-add" stack_frame_id=3}
  ROOT %fusion.2 = s32[9]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(bench_reassembly)/decode"}
}
"""


def op(name, start_ms, dur_ms):
    return ev(f"%{name} = s32[9]{{0}} {name.split('.')[0]}(u32[8]{{0}} %p)", start_ms, dur_ms)


def host(w0=0, w1=100):
    return NS(name="/host:CPU", lines=[NS(name="python", events=[ev("bench.window", w0, w1 - w0)])])


def test_op_names_of_hlo_text():
    names = scopes.op_names(HLO)
    assert names["sort.0"].endswith("/reassembly.sort/sort")
    assert names["fusion.1"].endswith("/reassembly.count/scatter-add")
    assert names["scatter.1"].endswith("/reassembly.count/scatter-add")
    assert names["fusion.2"] == "jit(bench_reassembly)/decode"
    assert "p" not in names  # no metadata, no scope


def traced():
    """Two chips; on each, two calls of bench_reassembly and one of
    bench_route, whose ``%fusion.1`` is another program's instruction."""
    c0 = chip(0, [op("sort.0", 10, 2), op("fusion.1", 12, 6), op("fusion.2", 18, 1),
                  op("fusion.1", 25, 3),
                  op("sort.0", 40, 4), op("fusion.1", 44, 8), op("fusion.2", 52, 1)],
              [ev("jit_bench_reassembly(3)", 10, 10), ev("jit_bench_route(4)", 25, 4),
               ev("jit_bench_reassembly(3)", 40, 14)])
    c1 = chip(1, [op("sort.0", 10, 4), op("fusion.1", 14, 10), op("fusion.2", 24, 1),
                  op("fusion.1", 30, 5),
                  op("sort.0", 40, 2), op("fusion.1", 42, 4), op("fusion.2", 46, 1)],
              [ev("jit_bench_reassembly(3)", 10, 15), ev("jit_bench_route(4)", 30, 5),
               ev("jit_bench_reassembly(3)", 40, 7)])
    return [host(), c1, c0]


NAMES = {"bench_reassembly": scopes.op_names(HLO), "bench_route": {}}


def test_scope_time_per_call_averaged_over_chips():
    ops = scopes.program_ops(traced(), n_chips=2)
    calls, seconds = ops["bench_reassembly"]
    assert calls == 2 and ops["bench_route"][0] == 1
    # reassembly.count is fusion.1 of bench_reassembly alone: chip 0 6 + 8 ms,
    # chip 1 10 + 4 ms; averaged over the chips, over 2 calls
    assert scopes.scope_ms(ops, NAMES, "bench_reassembly", "reassembly.count") == \
        pytest.approx((14 + 14) / 2 / 2)
    assert scopes.scope_ms(ops, NAMES, "bench_reassembly", "reassembly.sort") == \
        pytest.approx((6 + 6) / 2 / 2)
    # the same %fusion.1 in bench_route is kept apart
    assert seconds["fusion.1"] == pytest.approx((0.014 + 0.014) / 2)
    assert ops["bench_route"][1]["fusion.1"] == pytest.approx((0.003 + 0.005) / 2)


def test_window_clips_whole_calls():
    # the window opens after the first calls: only the calls at 40 ms count
    planes = traced()
    planes[0] = host(30, 100)
    ops = scopes.program_ops(planes, n_chips=2)
    assert ops["bench_reassembly"][0] == 1
    assert scopes.scope_ms(ops, NAMES, "bench_reassembly", "reassembly.count") == \
        pytest.approx((8 + 4) / 2)
    assert ops["bench_route"][0] == 0
    assert scopes.scope_ms(ops, NAMES, "bench_route", "reassembly.count") is None


def test_one_chip_of_two():
    ops = scopes.program_ops(traced(), n_chips=1)
    assert scopes.scope_ms(ops, NAMES, "bench_reassembly", "reassembly.count") == \
        pytest.approx((6 + 8) / 2)


def test_absent_program_or_scope_is_none():
    ops = scopes.program_ops(traced(), n_chips=2)
    assert scopes.scope_ms(ops, NAMES, "bench_plan", "reassembly.count") is None
    assert scopes.scope_ms(ops, NAMES, "bench_reassembly", "reassembly.groups") is None
    # a program without its HLO (the parent: no scopes) reads None too
    assert scopes.scope_ms(ops, {}, "bench_reassembly", "reassembly.count") is None


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        scopes.program_ops([traced()[1]], 1)


def test_recorded_trace_agrees_with_tracereduce():
    from jax.profiler import ProfileData

    ops = scopes.program_ops(ProfileData.from_file(RECORDED).planes, 1)
    calls, seconds = ops["bench_route"]
    total, want_calls = tracereduce.reduce(RECORDED, 1)["modules"]["bench_route"]
    assert calls == want_calls > 0
    assert 0.5 * total < sum(seconds.values()) <= total


def test_reader_without_the_scope_reads_none(tmp_path, monkeypatch):
    """The newest trace of the directory is read; a trace of a program
    without the scope gives no number."""
    older = tmp_path / "a" / "t.xplane.pb"
    older.parent.mkdir()
    older.write_bytes(b"not a trace")
    os.utime(older, (1, 1))
    shutil.copy(RECORDED, tmp_path / "route.xplane.pb")
    monkeypatch.setattr(scopes, "TRACES", str(tmp_path))
    assert scopes.newest_trace(str(tmp_path)).endswith("route.xplane.pb")
    ctx = {"trace": {"chips": 1}}
    assert scopes.read(ctx, "bench_route", "reassembly.count") is None
    assert scopes.read(ctx, "bench_reassembly", "reassembly.count") is None
    monkeypatch.setattr(scopes, "TRACES", str(tmp_path / "none"))
    assert scopes.read(ctx, "bench_reassembly", "reassembly.count") is None


def scans(monkeypatch, names, planes=None, n_chips=2):
    """``reassembly_scans_ms`` read from the two-chip trace, with
    ``names`` as the live executables' op names."""
    planes = planes or traced()
    monkeypatch.setattr(scopes, "newest_trace", lambda root: "traced")
    monkeypatch.setattr(scopes, "_trace_ops", lambda path, n: scopes.program_ops(planes, n))
    monkeypatch.setattr(scopes, "live_op_names", lambda: names)
    ctx = {"trace": tracereduce.reduce_planes(planes, n_chips)}
    return harness.reader("reassembly_scans_ms").read(ctx)


GROUPED = dict(NAMES, bench_reassembly=dict(
    NAMES["bench_reassembly"],
    **{"fusion.2": "jit(bench_reassembly)/jit(reassembly_plan)/reassembly.groups/cumsum"}))


def test_scans_are_the_program_less_sort_and_groups(monkeypatch):
    # bench_reassembly runs 10 + 14 ms on chip 0 and 15 + 7 ms on chip 1:
    # 11.5 ms per call; sort 3 ms and groups (fusion.2) 1 ms per call
    assert scans(monkeypatch, GROUPED) == pytest.approx(11.5 - 3 - 1)


def test_scans_without_a_scope_read_none(monkeypatch):
    assert scans(monkeypatch, NAMES) is None  # no reassembly.groups
    assert scans(monkeypatch, {}) is None  # the program's HLO not held


def test_scans_of_a_route_trace_read_none(tmp_path, monkeypatch):
    shutil.copy(RECORDED, tmp_path / "route.xplane.pb")
    monkeypatch.setattr(scopes, "TRACES", str(tmp_path))
    ctx = {"trace": tracereduce.reduce(RECORDED, 1)}
    assert harness.reader("reassembly_scans_ms").read(ctx) is None


def test_live_executables_carry_the_scopes():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def bench_scoped(x):
        with jax.named_scope("reassembly.sort"):
            y = jnp.sort(x)
        with jax.named_scope("reassembly.count"):
            return jnp.zeros((9,), jnp.int32).at[y % 9].add(1)

    bench_scoped(jnp.arange(16, dtype=jnp.uint32)[::-1]).block_until_ready()
    names = scopes.live_op_names()["bench_scoped"]
    found = {s for n in names.values() for s in n.split("/")}
    assert {"reassembly.sort", "reassembly.count"} <= found
    assert all(n.startswith("jit(bench_scoped)") for n in names.values() if "reassembly." in n)
