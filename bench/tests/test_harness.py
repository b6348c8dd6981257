"""The harness finds every piece of a cell by name, a new cell or metric
needs only new files and entries, and a run refuses anything but a
TPU."""
import json
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import harness
from bench.drivers import fit

ROOT = harness.ROOT
DOC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in DOC["workloads"]])
def test_every_cell_resolves(cell):
    c = harness.Benchmark(ROOT).cell(cell)
    assert callable(c.driver)
    assert c.config["spec"]["policy"]["name"] == "cnn"
    assert c.limits["limits"], "a cell needs the limits of its check"
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert m["moves"] in e2e
        assert callable(c.readers[m["name"]])


def test_benchmark_file_keeps_its_contract():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in DOC["configs"]] + \
        [w["name"] for w in DOC["workloads"]] + \
        [m["name"] for m in DOC["end_to_end"] + DOC["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    used = {w["config"] for w in DOC["workloads"]}
    assert used == {c["name"] for c in DOC["configs"]}
    for c in DOC["configs"]:
        assert (ROOT / c["file"]).is_file()
    for m in DOC["end_to_end"]:
        assert 0 < m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    fours = sum(w["chips"] == 4 for w in DOC["workloads"])
    assert fours <= max(1, len(DOC["workloads"]) // 2)


def test_reader_returns_nothing_without_its_input():
    c = harness.Benchmark(ROOT).cell(DOC["workloads"][0]["name"])
    for name, read in c.readers.items():
        assert read({}) is None, name


def test_new_cell_and_metric_are_files_and_entries_only(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    doc = json.loads(json.dumps(DOC))
    first = doc["workloads"][0]
    traffic = json.loads((ROOT / "bench" / "traffic"
                          / f"{first['traffic']}.json").read_text())
    traffic["intervals_per_s"] = 1
    (tmp_path / "bench" / "traffic" / "added-mix.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench" / "limits" / "added.cell.json").write_text(
        json.dumps({"limits": {"x": 1}}))
    (tmp_path / "bench" / "metrics" / "added.metric.py").write_text(
        "def read(record):\n    return record.get('added')\n")
    doc["workloads"].append({"name": "added.cell",
                             "config": first["config"],
                             "traffic": "added-mix", "chips": 1,
                             "why": "a cell added by files alone"})
    moves = next(m["name"] for m in doc["end_to_end"]
                 if m["name"] != "setup_s")
    for m in doc["end_to_end"]:
        if m["name"] == moves:
            m.setdefault("workloads", []).append("added.cell")
    doc["per_layer"].append({"name": "added.metric", "unit": "%",
                             "better": "lower", "source": "host_clock",
                             "layer": "device", "moves": moves,
                             "workloads": ["added.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    cell = harness.Benchmark(tmp_path).cell("added.cell")
    assert cell.traffic["intervals_per_s"] == 1
    assert [m["name"] for m in cell.per_layer] == ["added.metric"]
    got = harness.read_per_layer(cell, {"added": 2.5})
    assert got == {"added.metric": {"value": 2.5, "unit": "%"}}
    assert harness.read_per_layer(cell, {}) == {}


def test_run_refuses_a_device_that_is_not_a_tpu():
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         DOC["workloads"][0]["name"], "--seed", "2147483700",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr and "cpu" in p.stderr


@pytest.mark.parametrize("traffic", sorted(
    p.stem for p in (ROOT / "bench" / "traffic").glob("*.json")))
def test_training_traffic_is_whole_segments(traffic):
    mix = json.loads((ROOT / "bench" / "traffic" / f"{traffic}.json")
                     .read_text())
    every = mix["ckpt_every"]
    for seconds in (1, 10, 51):
        n = fit.window_intervals(mix, seconds)
        assert n % every == 0 and n >= every
        assert n >= seconds * mix["intervals_per_s"]
        assert n < seconds * mix["intervals_per_s"] + every
