"""Finds a cell's pieces by name: BENCHMARK.json, its configuration,
traffic mix, correctness limits and per-layer metric readers; the
configuration's reference module and each layer kind's counts."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _load_json(*parts) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict  # check name -> limit
    end_to_end: list  # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(workload: str, benchmark: dict | None = None) -> Cell:
    bench = benchmark or json.load(
        open(os.path.join(ROOT, "BENCHMARK.json")))
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(by_name)}")
    w = by_name[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.load(open(os.path.join(ROOT, cfg_entry["file"])))
    traffic = _load_json("traffic", w["traffic"] + ".json")
    limits = _load_json("cells", workload + ".json")["limits"]
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, workload, names)]
    return Cell(workload, int(w["chips"]), config, traffic, limits, e2e,
                per_layer)


def driver(kind: str):
    """The driver module for a traffic kind: ``drivers/<kind>.py``."""
    return importlib.import_module(f"bench.drivers.{kind}")


def reference(config: dict):
    """The plain reference a configuration names in its ``reference``
    key: ``reference/<name>.py`` (interface in ``reference/__init__``)."""
    return importlib.import_module(f"bench.reference.{config['reference']}")


def counts(kind: str):
    """Operations and state bytes of one layer of a ``LayerSpec`` kind:
    ``counts/<kind>.py`` (interface in ``counts/__init__``)."""
    return importlib.import_module(f"bench.counts.{kind}")


def metric_reader(name: str):
    """The per-layer reader ``metrics/<name>.py``'s ``read``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    return _load_module(path, "bench_metric_" + name.replace(".", "_")
                        ).read


def peaks(device_kind: str) -> dict:
    table = _load_json("peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json ({sorted(table)})")
    return table[device_kind]
