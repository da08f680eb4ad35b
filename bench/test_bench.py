"""The harness on the CPU at tiny sizes: a sound run is correct, the control
and every fault a cell can have come out as not correct, and a machine
without a TPU, or a checkout without the program, gets no result."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import controls
import faults
import harness
import rehearse

CELLS = [w["name"] for w in harness.load_json(harness.ROOT, "BENCHMARK.json")["workloads"]]


@pytest.fixture(autouse=True)
def kernels_interpreted():
    rehearse.interpret_kernels()


def judged(name, seed=7):
    r = controls.readings(*rehearse.tiny_cell(name), seed, 0.3, require_tpu=False)
    over = lambda side: any(v > r["limits"][k] for k, v in r[side].items())
    return over("program"), over("control")


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct_and_control_is_not(name):
    program_fails, control_fails = judged(name)
    assert not program_fails
    assert control_fails


FAULTS = [(w["name"], w["traffic"], stage, fault)
          for w in harness.load_json(harness.ROOT, "BENCHMARK.json")["workloads"]
          for stage in harness.load_json(harness.BENCH, "cells", w["traffic"] + ".json")["stages"]
          for fault in faults.FAULTS]


@pytest.mark.parametrize("name,traffic,stage,fault", FAULTS,
                         ids=[f"{c}-{s}-{f}" for c, _, s, f in FAULTS])
def test_fault_is_not_correct(monkeypatch, name, traffic, stage, fault):
    path = harness.load_module(
        "paths", harness.load_json(harness.BENCH, "cells", traffic + ".json")["path"])
    monkeypatch.setattr(path, "programs", path.programs)
    faults.plant(path, stage, fault)
    r = controls.readings(*rehearse.tiny_cell(name), 7, 0.3, require_tpu=False)
    assert r["program"][f"{stage}_mismatch"] > r["limits"][f"{stage}_mismatch"]


def _bench(args, cwd, **env):
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          capture_output=True, text=True, timeout=300,
                          env=dict(os.environ, **env))


def test_no_tpu_exits_without_a_result():
    p = _bench(["--workload", "route-64b", "--seed", "1", "--seconds", "1",
                "--trace", "0"], harness.ROOT, JAX_PLATFORMS="cpu")
    assert p.returncode == 2
    assert p.stdout.strip() == ""


def test_bench_files_alone_give_no_result(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    code = ("import sys; sys.path.insert(0, 'bench'); import rehearse; "
            "rehearse.rehearse('route-64b')")
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert "ModuleNotFoundError" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_result_line_keys(capsys):
    result, checks = rehearse.rehearse("route-64b", seed=2**31 + 5, seconds=0.2)
    harness.emit(result, checks)
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert out.err.strip().splitlines()[-1].startswith("check route_mismatch 0 limit 0")


def test_benchmark_json_is_whole():
    spec = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cells = {w["name"] for w in spec["workloads"]}
    metrics = spec["end_to_end"] + spec["per_layer"]
    for m in spec["per_layer"]:
        own, base = (os.path.join(harness.BENCH, "metrics", n + ".py")
                     for n in (m["name"], harness.base(m["name"])))
        assert os.path.exists(own) or os.path.exists(base), m["name"]
    for m in metrics:
        assert set(m.get("workloads", cells)) <= cells, m["name"]
    for m in spec["end_to_end"]:
        assert 0 < m["bound"] < 1, m["name"]
