"""The trace reduction on a hand-made trace with known answers, and on a
small trace recorded on a TPU v5e (``testdata/route-64b.xplane.pb``)."""
import os
from types import SimpleNamespace as NS

import pytest

import tracereduce

MS = 1_000_000  # ns


def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS)


def chip(k, ops, modules):
    return NS(name=f"/device:TPU:{k}", lines=[
        NS(name="XLA Ops", events=ops), NS(name="XLA Modules", events=modules)])


def test_hand_made_trace():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 0, 100), ev("bench.h2d", 0, 10), ev("bench.fetch", 60, 30),
        ev("unrelated", 0, 100)])])
    c0 = chip(0, [ev("fusion.1", 10, 20), ev("all-to-all.3", 20, 20), ev("fusion.2", 95, 10)],
              [ev("jit_bench_route(7)", 10, 30), ev("jit_bench_route(7)", 95, 10)])
    c1 = chip(1, [ev("fusion.1", 10, 40)], [ev("jit_bench_route(9)", 10, 40)])
    c2 = chip(2, [ev("fusion.1", 0, 100)], [])  # beyond the cell's two chips
    r = tracereduce.reduce_planes([host, c1, c2, c0], n_chips=2)
    assert r["window_s"] == pytest.approx(0.1)
    # chip 0 busy 10..40 and 95..100 = 35 ms; chip 1 busy 10..50 = 40 ms
    assert r["busy_s"] == pytest.approx(0.0375)
    total, calls = r["modules"]["bench_route"]
    assert calls == 1 and total == pytest.approx((0.040 + 0.040) / 2)
    assert r["collective_s"] == pytest.approx(0.020 / 2)
    assert r["ops"]["fusion.1"] == pytest.approx((0.020 + 0.040) / 2)
    # idle: chip 0 [0,10) [40,95), chip 1 [0,10) [50,100)
    g = r["gaps"]
    assert g["bench.h2d"] == pytest.approx(0.010)
    assert g["bench.fetch"] == pytest.approx((0.030 + 0.030) / 2)
    assert sum(g.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert "unrelated" not in g
    bd = tracereduce.breakdown(r)
    assert bd["idle_gaps"][0][0] == "bench.fetch" and len(bd["device_ops"]) <= 10


def test_no_device_plane_is_an_error():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[ev("bench.window", 0, 1)])])
    with pytest.raises(ValueError):
        tracereduce.reduce_planes([host], 1)


RECORDED = os.path.join(os.path.dirname(__file__), "testdata", "route-64b.xplane.pb")


def test_recorded_trace():
    from jax.profiler import ProfileData

    r = tracereduce.reduce(RECORDED, 1)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert sum(r["gaps"].values()) == pytest.approx(r["window_s"] - r["busy_s"])
    total, calls = r["modules"]["bench_route"]
    assert calls > 0
    # the same sum taken straight from the trace
    planes = list(ProfileData.from_file(RECORDED).planes)
    (w0, w1), = [(e.start_ns, e.start_ns + e.duration_ns) for p in planes
                 for ln in p.lines for e in ln.events if e.name == "bench.window"]
    dev = next(p for p in planes if p.name == "/device:TPU:0")
    mods = next(ln for ln in dev.lines if ln.name == "XLA Modules")
    want = sum(e.duration_ns for e in mods.events
               if e.name.startswith("jit_bench_route(") and w0 <= e.start_ns < w1)
    assert total == pytest.approx(want * 1e-9)


def test_readers_on_recorded_trace():
    import harness

    spec = harness.load_json(harness.ROOT, "BENCHMARK.json")
    _, config, traffic = harness.cell(spec, "route-64b")
    ctx = {"trace": tracereduce.reduce(RECORDED, 1), "traffic": traffic,
           "config": config, "kind": "TPU v5 lite"}
    got = {m["name"]: harness.reader(m["name"]).read(ctx)
           for m in harness.metrics_for(spec["per_layer"], "route-64b")}
    assert set(got) == {"device_idle_share.fwd", "route_ms.fwd", "route_roofline.fwd"}
    assert 0 < got["device_idle_share.fwd"] < 100
    assert 0 < got["route_roofline.fwd"] <= 100
    least_ms = 1e3 * 36 * traffic["window_packets"] / 819e9
    assert got["route_roofline.fwd"] == pytest.approx(100 * least_ms / got["route_ms.fwd"])
    with pytest.raises(KeyError):
        harness.reader("route_roofline").read(dict(ctx, kind="TPU v9 imaginary"))
