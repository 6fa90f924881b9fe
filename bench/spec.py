"""``BENCHMARK.json`` and the files its names lead to.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric sits in a file of its own, found by name:

    bench/configs/<config>.json     sizes as run, source, reduced, assumed
    bench/traffic/<traffic>.json    serve flags, length distributions, waves
    bench/limits/<cell>.json        limits of the numbers ``correct`` compares
    bench/metrics/<metric>.py       reader of one per-layer metric
    bench/kits/<kit>.py             weights, reference and operation counts
                                    of one kind of layer (``kit`` key of a
                                    configuration file; ``dense`` if none)
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_entry(bm: dict, name: str) -> dict:
    for c in bm["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def resolve(bm: dict, name: str, root: str = ROOT) -> dict:
    """The cell's entry, configuration, its kit, traffic mix and limits."""
    w = cell(bm, name)
    c = config_entry(bm, w["config"])
    conf = load_json(os.path.join(root, c["file"]))
    return {
        "cell": w,
        "config": conf,
        "kit": kit(conf, root),
        "traffic": load_json(os.path.join(root, "bench", "traffic",
                                          w["traffic"] + ".json")),
        "limits": load_json(os.path.join(root, "bench", "limits",
                                         name + ".json")),
    }


def applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def end_to_end(bm: dict, cell_name: str) -> list:
    return [m for m in bm["end_to_end"] if applies(m, cell_name)]


def per_layer(bm: dict, cell_name: str) -> list:
    return [m for m in bm["per_layer"] if applies(m, cell_name)]


def _module(kind: str, name: str, root: str):
    """``bench/<kind>/<name>.py`` under ``root``, loaded by its path."""
    if not NAME.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    path = os.path.join(root, "bench", kind, name + ".py")
    sp = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


def reader(metric_name: str, root: str = ROOT):
    """The module that reads one per-layer metric: ``read(ctx)`` returns
    its value, or None where the trace holds nothing to read."""
    return _module("metrics", metric_name, root)


def kit(conf: dict, root: str = ROOT):
    """The kit of a configuration file: the module that holds the weight
    generator, the reference and the operation counts of its kind of layer
    (``bench/kits/dense.py`` describes what a kit exposes)."""
    return _module("kits", conf.get("kit", "dense"), root)
