"""Finds every piece of the benchmark by the name `BENCHMARK.json` gives it. A later
PR adds a configuration, a mix, a query family, a corpus generator, a metric or a
trace reduction as files and entries, and edits nothing that is there."""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)


def _json(*parts: str) -> dict:
    path = os.path.join(*parts)
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(CHECKOUT, "BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return _json(CHECKOUT, c["file"])
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def mix(name: str) -> dict:
    return _json(BENCH_DIR, "traffic", name + ".json")


def settings() -> dict:
    """What every cell shares: index name, samples, warm-up and tracing parameters."""
    return _json(BENCH_DIR, "settings.json")


def peaks(device_kind: str) -> dict:
    table = _json(BENCH_DIR, "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in benchmark/peaks.json: "
                       "add it with its source, do not guess a peak")
    return table["devices"][device_kind]


def layer_metric(name: str) -> dict:
    """The definition file of the per-layer metric `name`."""
    return _json(BENCH_DIR, "layer_metrics", name + ".json")


def metrics_of(bench: dict, cell_name: str, group: str, directory: str) -> list:
    """The metrics of `group` (`end_to_end` or `per_layer`) that this cell reports,
    each as (entry in BENCHMARK.json, its definition file)."""
    out = []
    for m in bench[group]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        out.append((m, _json(BENCH_DIR, directory, m["name"] + ".json")))
    return out


def module(directory: str, name: str):
    """The Python file benchmark/<directory>/<name>.py, loaded by its path."""
    path = os.path.join(BENCH_DIR, directory, name + ".py")
    spec = importlib.util.spec_from_file_location(f"benchmark.{directory}.{name}", path)
    if spec is None or not os.path.exists(path):
        raise KeyError(f"no {directory} named {name!r}: {path} is missing")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
