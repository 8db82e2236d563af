"""The rest of a run, driven on the CPU at a tiny size with the harness's look for a
chip skipped: a sound server comes out `correct: true`, and a server whose timed path
is broken underneath (every BM25 weight one part in a thousand off, where the score is
produced; a bucket of an aggregation one document short) comes out `correct: false`,
on the number that names the fault. The CPU rehearsal as a user runs it never says
true. The sound runs' server writes down every request it is handed: each `_search`
carries the URL parameters its configuration states (`search.params`), late writes'
searches included, and no other. A cell of several chips gets as many virtual CPU
devices. Each case starts a server: about a minute."""

import argparse
import json
import os
import time

import pytest

from benchmark.harness import cell, registry

HERE = os.path.dirname(os.path.abspath(__file__))
SHORT_WARMUP = {"warmup": {"pool_pass_max_seconds": 20, "rehearsals": 1}}


def _run(capsys, monkeypatch, workload, **options):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    args = argparse.Namespace(workload=workload, seed=2**31 + 17, seconds=3.0,
                              trace=0, docs=1500)
    rc = cell.run(args, time.perf_counter(), settings=SHORT_WARMUP, **options)
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    return rc, lines


def _paths(*extra):
    return os.pathsep.join(list(extra) + [registry.CHECKOUT] +
                           [p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep)
                            if p])


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      registry.benchmark()["workloads"]])
def test_a_sound_run_is_correct_and_reports_its_metrics(capsys, monkeypatch, tmp_path,
                                                        workload):
    requests = tmp_path / "requests.jsonl"
    rc, lines = _run(capsys, monkeypatch, workload, assume_chip=True, server_env={
        "PYTHONPATH": _paths(os.path.join(HERE, "logging_server")),
        "BENCH_TEST_REQUESTS": str(requests)})
    result = lines[-1]
    assert rc == 0 and result["correct"] is True, lines[-3:]
    assert set(result) >= {"correct", "attempted", "failed", "metrics", "device"}
    bench = registry.benchmark()
    want = {m["name"] for m, _ in registry.metrics_of(
        bench, workload, "end_to_end", "end_to_end")}
    assert set(result["metrics"]) == want
    assert all(v["value"] > 0 for v in result["metrics"].values())
    compared = [l for l in lines if l.get("phase") == "compare"]
    assert len(compared) == 3
    for l in compared:
        assert all(n["value"] <= n["limit"] for n in l["numbers"].values())
    # every number compared, beside its limit, comes last in the result's line
    assert list(result)[-1] == "compared"
    assert all(value <= limit for value, limit in result["compared"].values())
    assert {"rose." + p for p in registry.settings()["must_not_rise"]} | \
        {"before.rel_dev", "window.rel_dev", "late_writes.rel_dev"} <= \
        set(result["compared"])
    # what the server was handed: every search with the configuration's parameters
    cell_ = registry.cell(bench, workload)
    config = registry.config(bench, cell_["config"])
    stated = (config.get("search") or {}).get("params") or {}
    searches = [r for r in map(json.loads, requests.read_text().splitlines())
                if r["path"].endswith("/_search")]
    shared = registry.settings()
    # the first answers, the warm-up (a rehearsal at least), the window, late writes
    assert len(searches) >= shared["sample"] + 2 * result["attempted"] * 0.8 + \
        shared["late_writes"]
    assert all(r["method"] == "POST" and r["params"] == stated for r in searches)
    device = [l for l in lines if l.get("phase") == "device"][0]
    assert device["count"] == cell_["chips"] or cell_["chips"] == 1


def _first_cell(own_comparison: bool) -> str:
    """The first cell whose mix has (or has not) a query family that brings a
    comparison of its own."""
    for w in registry.benchmark()["workloads"]:
        mods = [registry.module("queries", f["family"])
                for f in registry.mix(w["traffic"])["families"]]
        if any(hasattr(m, "compare") for m in mods) == own_comparison:
            return w["name"]
    raise KeyError(own_comparison)


@pytest.mark.parametrize("fault, own_comparison, number", [
    ("broken_server", False, "rel_dev"),
    ("lost_count_server", True, "agg_counts_off")])
def test_a_broken_timed_path_is_not_correct(capsys, monkeypatch, fault, own_comparison,
                                            number):
    workload = _first_cell(own_comparison)
    rc, lines = _run(capsys, monkeypatch, workload, assume_chip=True, server_env={
        "PYTHONPATH": _paths(os.path.join(HERE, fault))})
    result = lines[-1]
    assert rc == 1 and result["correct"] is False
    window = [l for l in lines if l.get("phase") == "compare"
              and l["sample"].startswith("the window")][0]
    got = window["numbers"][number]
    assert got["value"] > got["limit"]
    assert result["compared"]["window." + number] == [got["value"], got["limit"]]
    if number != "rel_dev":
        # the hits of the same responses are sound: only the family's number bites
        assert window["numbers"]["rel_dev"]["value"] <= window["numbers"]["rel_dev"]["limit"]
        assert window["numbers"]["ids_off"]["value"] == 0
    else:
        assert got["limit"] == 1e-5


def test_the_rehearsal_never_says_correct(capsys, monkeypatch):
    workload = registry.benchmark()["workloads"][0]["name"]
    rc, lines = _run(capsys, monkeypatch, workload)
    assert rc == 2 and lines[-1]["correct"] is False
    assert all(l.get("rehearsal") for l in lines[:-1])
