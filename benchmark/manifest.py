"""BENCHMARK.json and the files its names resolve to.

A workload names a configuration and a traffic mix; a configuration names an
operator family and a system under test; a traffic mix names a loop; a metric
names a reducer. Each of those is a file of its own under this directory,
found by name, so that a later PR adds a cell by adding files and entries."""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """The module ``<kind>/<name>.py`` of the benchmark, by file path."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind}/{name}.py in the benchmark")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(name: str, rehearse: bool = False) -> dict:
    """Everything one workload resolves to. ``rehearse`` swaps in the small
    sizes the configuration and the traffic mix keep for CPU rehearsals."""
    bm = benchmark()
    wl = next((w for w in bm["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in bm['workloads']]}")
    entry = next(c for c in bm["configs"] if c["name"] == wl["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    traffic = load_json("traffic", wl["traffic"] + ".json")
    if rehearse:
        for group, small in cfg.get("rehearse", {}).items():
            cfg[group] = {**cfg[group], **small}
        traffic = {**traffic, **traffic.get("rehearse", {})}

    def mine(metric):
        return name in metric.get("workloads", [name])

    return {
        "workload": wl, "config": cfg, "traffic": traffic,
        "end_to_end": [m for m in bm["end_to_end"] if mine(m)],
        "per_layer": [m for m in bm["per_layer"] if mine(m)],
    }


def metric_reader(group: str, name: str):
    """(read function, parameters) of the metric ``<group>/<name>.json``."""
    spec = load_json(group, name + ".json")
    return load_module("reducers", spec["reducer"]).read, spec.get("params", {})


def peaks(device_kind: str) -> dict:
    table = load_json("peaks.json")
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json")
    return table[device_kind]
