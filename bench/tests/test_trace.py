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
            "ops": [(100, 200, "%a = f32[] fusion()", "jit(step)/a"),
                    (150, 180, "%b = f32[] convolution()", "jit(step)/b"),
                    (400, 450, "%c = f32[] all-gather-start()", ""),
                    (420, 430, "%d = f32[] fusion()", "")],
            "modules": [(90, 210, "jit_step(1)"), (390, 460, "jit_drain(2)")],
        }},
        "host": [(210, 380, "fit.save"), (0, 1000, "bench.window"),
                 (205, 390, "PjitFunction(step)"),
                 (200, 385, "fit.segment")],
    }
    r = trace.reduce(planes)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(150e-9)
    dev = r["devices"]["/device:TPU:0"]
    assert dev["modules_s"]["jit_step"] == pytest.approx(120e-9)
    assert dev["collective_exposed_s"] == pytest.approx(40e-9)
    assert r["device_ops"][0][0] == "a"
    assert r["idle_gaps"][0] == [trace.NO_SPAN, pytest.approx(550e-9)]
    # the innermost program span on any thread's line, not a JAX span
    assert ["fit.save", pytest.approx(200e-9)] in r["idle_gaps"]


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


def test_recorded_gaps_are_named_by_the_programs_spans(tmp_path):
    """On the trace of the instrumented program, whose spans and window
    lie on a line named ``python3`` among the host's lines, the window is
    the ``bench.window`` span and the longest idle gaps are named by the
    ``fit.`` spans around them."""
    path = tmp_path / "fit_n64_scoped.xplane.pb"
    with gzip.open(FIXTURE.parent / "fit_n64_scoped.xplane.pb.gz") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    planes = trace.load(str(path))
    span = [(s, e) for s, e, n in planes["host"] if n == trace.WINDOW_SPAN]
    assert len(span) == 1
    assert trace.window(planes) == span[0]
    gaps = trace.reduce(planes)["idle_gaps"]
    names = [n for n, _ in gaps]
    assert names[:2] == ["fit.save", "fit.resume"]
    assert all(n.startswith("fit.") for n in names)


def test_load_keeps_lines_that_share_a_name(tmp_path):
    """Python threads' lines may all be named ``python3``: the loader
    keeps the events of each, so the window and the program's spans are
    found whichever thread's line holds them."""
    space = trace._messages()()
    host = space.planes.add(name="/host:CPU")
    for key, name in enumerate(("bench.window", "fit.save", "worker")):
        host.event_metadata.add(key=key + 1).value.name = name
    main, worker = host.lines.add(name="python3"), host.lines.add(
        name="python3")
    main.timestamp_ns = worker.timestamp_ns = 1000
    main.events.add(metadata_id=1, offset_ps=0, duration_ps=9_000_000)
    main.events.add(metadata_id=2, offset_ps=2_000_000,
                    duration_ps=3_000_000)
    worker.events.add(metadata_id=3, offset_ps=1_000_000,
                      duration_ps=1_000_000)
    path = tmp_path / "two_lines.xplane.pb"
    path.write_bytes(space.SerializeToString())
    planes = trace.load(str(path))
    assert sorted(planes["host"]) == [(1000.0, 10000.0, "bench.window"),
                                      (2000.0, 3000.0, "worker"),
                                      (3000.0, 6000.0, "fit.save")]
    assert trace.window(planes) == (1000.0, 10000.0)
