"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration and a traffic mix.
Each lives in a file of its own, so that a later cell, configuration or
metric is added as files and entries, with no edit here:

- ``configs/<config>.json``: the model and training spec (``spec``, the
  ``ExperimentSpec`` fields), the architecture's shapes (``arch``, read by
  the reference and the FLOP count), the federation's scale (``clients``,
  ``samples_per_client``), ``source``, ``reduced`` and ``assumed``;
- ``traffic/<traffic>.json``: how the clients take part and where they run:
  the execution plane (``exec``), the data split (``iid``) and the share of
  clients sampled each round (``participation``);
- ``limits/<cell>.json``: the limit of each number that decides ``correct``;
- ``metrics/<metric>.py``: the reader of one per-layer metric.
"""
from __future__ import annotations

import copy
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass
class Cell:
    name: str
    config_name: str
    config: dict
    traffic: dict
    chips: int
    limits: dict
    end_to_end: list
    per_layer: list

    @property
    def arch(self) -> dict:
        return self.config["arch"]

    def spec_dict(self, seed: int) -> dict:
        """The ``ExperimentSpec`` of this cell, as a dict, for ``seed``.

        Rounds are unbounded: the harness ends the run itself."""
        spec = copy.deepcopy(self.config["spec"])
        n, per = self.config["clients"], self.config["samples_per_client"]
        spec.setdefault("data", {}).update(
            clients=n, samples=n * per, iid=self.traffic["iid"])
        spec["exec"] = dict(self.traffic["exec"])
        spec["participation"] = self.traffic["participation"]
        spec["rounds"] = 10 ** 9
        spec["seed"] = seed
        return spec


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


def load(name: str, root: Path = ROOT, bench: dict | None = None) -> Cell:
    bench = bench or benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {', '.join(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def applies(metric):
        return "workloads" not in metric or name in metric["workloads"]

    return Cell(
        name=name, config_name=w["config"],
        config=_json(root / conf["file"]),
        traffic=_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        chips=int(w["chips"]),
        limits=_json(BENCH / "limits" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)])


def reader(metric: str):
    """The ``read(record)`` function of ``metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> dict:
    table = _json(BENCH / "peaks.json")
    if device_kind not in table["devices"]:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in peaks.json; "
                         f"known: {', '.join(table['devices'])}")
    return table["devices"][device_kind]
