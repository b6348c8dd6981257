"""Finding a cell's pieces by name, and the record they share.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix:

* ``bench/configs/<config>.json``  -- the model, env and update rule as
  an ExperimentSpec fragment (``spec``), plus its source, cuts and
  assumed sizes;
* ``bench/traffic/<traffic>.json`` -- the job or request mix: its
  ``kind`` (the driver in ``bench/drivers/<kind>.py`` that reads it),
  the runtime and sizes (``spec``), and the kind's parameters;
* ``bench/limits/<cell>.json``     -- the limit of each number the
  correctness check compares, with the readings it was set from;
* ``bench/metrics/<metric>.py``    -- one reader per per-layer metric,
  ``read(record) -> float | None``;
* ``bench/reference/env_<env>.py`` -- the plain reference of the env
  the configuration names, ``make(**kwargs) -> RefEnv``;
* ``bench/reference/net_<policy>.py`` -- the plain reference of the
  policy it names, ``make(policy, obs_shape, n_actions)``, and its FLOP
  count by layer, ``layers(obs_shape, arch, n_actions)``.

Nothing here names a cell, configuration, env, policy or metric: a later
change adds files and ``BENCHMARK.json`` entries, and edits none of
these.
"""
from __future__ import annotations

import copy
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def merge(base: dict, over: dict) -> dict:
    """Deep merge of two JSON objects; ``over`` wins."""
    out = copy.deepcopy(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = merge(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


def load_file(folder: str, name: str, bench_dir: Path = BENCH_DIR,
              prefix: str = ""):
    """The module ``<bench_dir>/<folder>/<prefix><name>.py``; a name with
    no such file is an error that lists the names found there."""
    where = Path(bench_dir) / folder
    path = where / f"{prefix}{name}.py"
    if not path.is_file():
        found = sorted(p.stem[len(prefix):] for p in where.glob(
            f"{prefix}*.py") if not p.stem.startswith("_"))
        raise LookupError(f"no {folder}/{prefix}{name}.py under "
                          f"{bench_dir}; found {found}")
    return _load_module(
        path, f"bench_{folder}_{prefix}{name}".replace(".", "_"))


def load_reader(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    """``read`` of ``bench/metrics/<name>.py``."""
    return load_file("metrics", name, bench_dir).read


@dataclass
class Cell:
    name: str
    config: dict            # bench/configs/<config>.json
    traffic: dict           # bench/traffic/<traffic>.json
    limits: dict            # bench/limits/<cell>.json
    chips: int
    end_to_end: List[dict]  # BENCHMARK.json entries this cell reports
    per_layer: List[dict]
    driver: Callable        # bench/drivers/<kind>.py: run(ctx) -> record
    readers: Dict[str, Callable]   # per-layer metric name -> read()
    bench_dir: Path = BENCH_DIR    # where its references are found

    def spec_dict(self) -> dict:
        """The ExperimentSpec this cell runs: configuration, then the
        traffic's runtime and sizes on top."""
        return merge(self.config["spec"], self.traffic.get("spec", {}))


class Benchmark:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.bench_dir = self.root / "bench"
        self.doc = _load_json(self.root / "BENCHMARK.json")

    def cell_names(self) -> List[str]:
        return [w["name"] for w in self.doc["workloads"]]

    def _reports(self, metric: dict, cell: str, e2e_of_cell) -> bool:
        if "workloads" in metric:
            return cell in metric["workloads"]
        if e2e_of_cell is None:          # an end-to-end metric
            return True
        return metric["moves"] in e2e_of_cell

    def reader(self, name: str) -> Callable:
        return load_reader(name, self.bench_dir)

    def driver(self, kind: str) -> Callable:
        return load_file("drivers", kind, self.bench_dir).run

    def cell(self, name: str) -> Cell:
        by_name = {w["name"]: w for w in self.doc["workloads"]}
        if name not in by_name:
            raise KeyError(f"unknown workload {name!r}; BENCHMARK.json "
                           f"has {sorted(by_name)}")
        w = by_name[name]
        return self.make_cell(name, w["config"], w["traffic"], w["chips"])

    def make_cell(self, name: str, config: str, traffic: str,
                  chips: int) -> Cell:
        """A cell from its parts by name; ``cell`` takes them from
        BENCHMARK.json, a test may name a mix no entry uses yet."""
        files = {c["name"]: c["file"] for c in self.doc["configs"]}
        config_path = (self.root / files[config] if config in files
                       else self.bench_dir / "configs" / f"{config}.json")
        config = _load_json(config_path)
        traffic = _load_json(self.bench_dir / "traffic" / f"{traffic}.json")
        limits_path = self.bench_dir / "limits" / f"{name}.json"
        limits = _load_json(limits_path) if limits_path.exists() else {}
        e2e = [m for m in self.doc["end_to_end"]
               if self._reports(m, name, None)]
        names = {m["name"] for m in e2e}
        per = [m for m in self.doc["per_layer"]
               if self._reports(m, name, names)]
        return Cell(name=name, config=config, traffic=traffic,
                    limits=limits, chips=int(chips), end_to_end=e2e,
                    per_layer=per, driver=self.driver(traffic["kind"]),
                    readers={m["name"]: self.reader(m["name"])
                             for m in per},
                    bench_dir=self.bench_dir)


def read_per_layer(cell: Cell, record: dict) -> Dict[str, Optional[float]]:
    """Every per-layer metric of the cell that its reader finds; a
    reader that finds nothing to read returns None and the metric is
    left out."""
    out = {}
    for m in cell.per_layer:
        value = cell.readers[m["name"]](record)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
