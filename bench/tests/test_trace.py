"""The trace reduction on hand-made intervals, and on a trace recorded
on a TPU v5e (``bench/tests/data/fit_n64.xplane.pb.gz``: the fused
training cell at 64 envs, one checkpointed segment of two intervals
resumed from a checkpoint)."""
import gzip
import shutil
from pathlib import Path

import pytest

from bench import trace

FIXTURE = Path(__file__).parent / "data" / "fit_n64.xplane.pb.gz"


def test_union_total_and_gaps():
    iv = [(0, 10), (5, 15), (20, 30), (30, 31), (40, 41)]
    assert trace.union(iv) == [(0, 15), (20, 31), (40, 41)]
    assert trace.total(trace.union(iv)) == 27
    assert trace.gaps(iv, -5, 50) == [(-5, 0), (15, 20), (31, 40),
                                      (41, 50)]
    assert trace.clip(iv, 8, 22) == [(8, 10), (8, 15), (20, 22)]


def test_subtract_is_the_exposed_part():
    coll = [(0, 10), (20, 30)]
    compute = [(5, 12), (18, 22), (25, 26)]
    # exposed: 0-5, 22-25, 26-30
    assert trace.subtract(coll, compute) == 12
    assert trace.subtract(coll, []) == 20
    assert trace.subtract([], compute) == 0


def test_self_time_removes_nested_ops():
    ev = [(0, 100, "while"), (10, 30, "fusion"), (40, 50, "fusion"),
          (60, 70, "conv"), (200, 210, "fusion")]
    st = trace.self_times(ev)
    assert st == {"while": 60, "fusion": 40, "conv": 10}


def test_op_name_and_collectives():
    assert trace.op_name("%fusion.12 = f32[8]{0} fusion(%a)") == "fusion.12"
    assert trace.is_collective("all-gather-start.3")
    assert not trace.is_collective("fusion.2")


def test_reduce_reads_window_busy_and_breakdown():
    planes = {
        "devices": {"/device:TPU:0": {
            "ops": [(100, 200, "%a = f32[] fusion()"),
                    (150, 180, "%b = f32[] convolution()"),
                    (400, 450, "%c = f32[] all-gather-start()"),
                    (420, 430, "%d = f32[] fusion()")],
            "modules": [(90, 210, "jit_step(1)"), (390, 460, "jit_drain(2)")],
        }},
        "host": {"python": [(0, 1000, "bench.window"),
                            (210, 380, "bench.save")]},
    }
    r = trace.reduce(planes)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(150e-9)
    dev = r["devices"]["/device:TPU:0"]
    assert dev["modules_s"]["jit_step"] == pytest.approx(120e-9)
    assert dev["collective_exposed_s"] == pytest.approx(40e-9)
    assert r["device_ops"][0][0] == "a"
    assert r["idle_gaps"][0] == ["host:no span", pytest.approx(550e-9)]
    assert ["bench.save", pytest.approx(200e-9)] in r["idle_gaps"]


def test_reduce_a_recorded_tpu_trace(tmp_path):
    path = tmp_path / "t" / "fit_n64.xplane.pb"
    path.parent.mkdir()
    with gzip.open(FIXTURE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    assert trace.find_xplane(str(tmp_path)) == str(path)
    planes = trace.load(str(path))
    assert list(planes["devices"]) == ["/device:TPU:0"]
    r = trace.reduce(planes)
    assert 0 < r["busy_s"] < r["window_s"]
    dev = r["devices"]["/device:TPU:0"]
    assert dev["modules_s"] and dev["collective_exposed_s"] == 0
    assert all(t > 0 for _, t in r["device_ops"])
    ops = r["device_ops"]
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    assert sum(t for _, t in r["idle_gaps"]) <= r["window_s"] - r["busy_s"] \
        + 1e-9
