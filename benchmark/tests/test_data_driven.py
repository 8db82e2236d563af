"""The harness finds a configuration (with URL parameters for its searches and
counters of its own that may not rise), a mix, a query family (with a comparison, numbers
and limits of its own, and what the window keeps for it), a layer metric and a trace
reduction that were added as files and entries alone, and names none of them in its
code."""

import argparse
import json
import os
import re
import shutil

import numpy as np
import pytest

from benchmark.harness import readers, registry


@pytest.fixture()
def copy(tmp_path, monkeypatch):
    """A copy of the benchmark in which a later PR's files can be added."""
    bench_dir = tmp_path / "benchmark"
    shutil.copytree(registry.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(registry.CHECKOUT, "BENCHMARK.json"), tmp_path)
    monkeypatch.setattr(registry, "BENCH_DIR", str(bench_dir))
    monkeypatch.setattr(registry, "CHECKOUT", str(tmp_path))
    return tmp_path


def test_pieces_added_as_files_alone_are_found(copy):
    bench = json.loads((copy / "BENCHMARK.json").read_text())
    general = ["run.py", "settings.json"] + [
        "harness/" + f for f in os.listdir(copy / "benchmark/harness")
        if f.endswith(".py")]
    before = {p: (copy / "benchmark" / p).read_bytes() for p in general}
    # a later PR: one configuration, one mix with a new family, one metric, one cell
    config = json.loads((copy / "benchmark/configs" /
                         (bench["configs"][0]["name"] + ".json")).read_text())
    config["name"] = "tiny-logs"
    config["documents"] = 300
    config["search"] = {"params": {"search_type": "dfs_query_then_fetch",
                                   "preference": "_local"}}
    config["must_not_rise"] = ["search_serving.mesh_fallbacks"]
    (copy / "benchmark/configs/tiny-logs.json").write_text(json.dumps(config))
    (copy / "benchmark/queries/one_term.py").write_text(
        "from benchmark.harness.reference import word\n"
        "def plan(params, rng, n):\n"
        "    return [float(rng.random()) for _ in range(n)]\n"
        "def build(params, ref, plans):\n"
        "    out = []\n"
        "    for u in plans:\n"
        "        t = int(ref.by_df[int(u * ref.n_present)])\n"
        "        out.append({'terms': [t], 'must_all': False, 'size': 5, 'allowed': None,\n"
        "                    'body': {'query': {'match': {params['field']: word(t)}},\n"
        "                             'size': 5}})\n"
        "    return out\n"
        "def expected(ref, q):\n"
        "    return ref.score_all(q['terms'], False)\n")
    (copy / "benchmark/traffic/single.json").write_text(json.dumps({
        "name": "single", "loop": "closed", "clients": 1, "keep_alive": True,
        "pool": 32, "plan_seed": 1, "who": "one caller", "why": "latency floor",
        "families": [{"family": "one_term", "weight": 1,
                      "params": {"field": "body"}}]}))
    (copy / "benchmark/layer_metrics/full_flush_share.json").write_text(json.dumps({
        "layer": "batcher", "reader": "counter_ratio", "scale": 100.0,
        "numerator": ["search.batcher.full_flushes"],
        "denominator": ["search.batcher.launches"]}))
    (copy / "benchmark/reductions/longest_op.py").write_text(
        "def reduce(trace):\n"
        "    longest = [float(line['dur_ns'].max()) for p in trace['planes'].values()\n"
        "               for n, line in p['lines'].items() if n == 'XLA Ops']\n"
        "    return {'longest_us': max(longest) / 1e3} if longest else {}\n")
    (copy / "benchmark/layer_metrics/longest_op_us.json").write_text(json.dumps({
        "layer": "kernels", "reader": "reduction", "reduction": "longest_op",
        "field": "longest_us"}))
    bench["per_layer"].append({"name": "longest_op_us", "unit": "us",
                               "better": "lower", "source": "device_trace",
                               "layer": "kernels", "moves": "searches_per_s",
                               "workloads": ["logs.single"]})
    bench["end_to_end"][2]["workloads"].append("logs.single")
    assert bench["end_to_end"][2]["name"] == "searches_per_s"
    bench["configs"].append({"name": "tiny-logs", "source": "a test",
                             "file": "benchmark/configs/tiny-logs.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "logs.single", "config": "tiny-logs",
                               "traffic": "single", "chips": 1, "why": "a test"})
    bench["per_layer"].append({"name": "full_flush_share", "unit": "%",
                               "better": "higher", "source": "program_counter",
                               "layer": "batcher", "moves": "searches_per_s",
                               "workloads": ["logs.single"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))

    from benchmark.harness.cell import Run

    bench = registry.benchmark()
    run = Run(argparse.Namespace(workload="logs.single", seed=5, seconds=1.0, trace=1,
                                 docs=None), 0.0, assume_chip=True)
    assert run.config["documents"] == 300 and run.n_docs == 300
    run.make_corpus()
    run.make_reference()
    pool = run.pool
    assert len(pool.queries) == 32 and pool.queries[0]["size"] == 5
    # every search of the run goes to the configuration's path
    assert pool.path == \
        "/bench/_search?search_type=dfs_query_then_fetch&preference=_local"
    shared = registry.settings()
    assert run.must_not_rise() == shared["must_not_rise"] + \
        ["search_serving.mesh_fallbacks"]
    # the reduction that the new metric names runs beside those of settings.json
    assert run.reductions() == shared["trace"]["reductions"] + ["longest_op"]
    line = {"names": ["a", "b"], "start_ns": np.array([0.0, 9e3]),
            "dur_ns": np.array([2e3, 5e3])}
    trace = {"planes": {"/device:TPU:0": {"lines": {"XLA Ops": line}}}, "window_s": 1.0}
    traced = readers.Observations("idx")
    for name in run.reductions():
        traced.reduced[name] = registry.module("reductions", name).reduce(trace)
    definition = [d for m, d in registry.metrics_of(
        bench, "logs.single", "per_layer", "layer_metrics")
        if m["name"] == "longest_op_us"][0]
    assert readers.read(definition, traced) == pytest.approx(5.0)
    names = [m["name"] for m, _ in
             registry.metrics_of(bench, "logs.single", "per_layer", "layer_metrics")]
    assert "full_flush_share" in names and "gen_late_p95_ms" not in names
    obs = readers.Observations("idx")
    obs.stats_before = {"search": {"batcher": {"full_flushes": 0, "launches": 0}}}
    obs.stats_after = {"search": {"batcher": {"full_flushes": 5, "launches": 20}}}
    _entry, definition = [(m, d) for m, d in registry.metrics_of(
        bench, "logs.single", "per_layer", "layer_metrics")
        if m["name"] == "full_flush_share"][0]
    assert readers.read(definition, obs) == pytest.approx(25.0)
    for p, content in before.items():
        assert (copy / "benchmark" / p).read_bytes() == content


FAMILY_WITH_ITS_OWN_NUMBER = (
    "from benchmark.harness.reference import check_hits, hits_answer, word\n"
    "LIMITS = {LIMITS}\n"
    "KEEP = {'response': ['max_of_hits'], 'hit': ['_type']}\n"
    "def plan(params, rng, n):\n"
    "    return [float(rng.random()) for _ in range(n)]\n"
    "def build(params, ref, plans):\n"
    "    out = []\n"
    "    for u in plans:\n"
    "        t = int(ref.by_df[int(u * ref.n_present)])\n"
    "        out.append({'terms': [t], 'must_all': False, 'size': 5, 'allowed': None,\n"
    "                    'body': {'query': {'match': {params['field']: word(t)}},\n"
    "                             'size': 5}})\n"
    "    return out\n"
    "def expected(ref, q):\n"
    "    return ref.score_all(q['terms'], False)\n"
    "def answer(ref, q):\n"
    "    resp = hits_answer(ref, *expected(ref, q), q['size'])\n"
    "    resp['max_of_hits'] = max(h['_score'] for h in resp['hits']['hits'])\n"
    "    return resp\n"
    "def compare(ref, q, resp, tol):\n"
    "    out = check_hits(ref, *expected(ref, q), q['size'], resp, tol)\n"
    "    top = max(h['_score'] for h in resp['hits']['hits'])\n"
    "    out['max_off'] = int(resp.get('max_of_hits') != top)\n"
    "    return out\n")


def _a_cell_of_a_family_with_its_own_number(copy, limits: str):
    bench = json.loads((copy / "BENCHMARK.json").read_text())
    (copy / "benchmark/queries/topped.py").write_text(
        FAMILY_WITH_ITS_OWN_NUMBER.replace("{LIMITS}", limits))
    (copy / "benchmark/traffic/topped_alone.json").write_text(json.dumps({
        "name": "topped_alone", "loop": "closed", "clients": 1, "keep_alive": True,
        "pool": 8, "plan_seed": 2, "who": "a test", "why": "a test",
        "families": [{"family": "topped", "weight": 1, "params": {"field": "body"}}]}))
    bench["workloads"].append({"name": "passage.topped", "config":
                               bench["configs"][0]["name"], "traffic": "topped_alone",
                               "chips": 1, "why": "a test"})
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))
    from benchmark.harness.cell import Run

    run = Run(argparse.Namespace(workload="passage.topped", seed=5, seconds=1.0,
                                 trace=0, docs=300), 0.0, assume_chip=True)
    run.make_corpus()
    return run


def test_a_family_brings_its_comparison_numbers_and_limits_as_files_alone(copy, capsys):
    from benchmark.harness.cell import Compared
    from benchmark.harness.loadgen import _digest, as_response

    general = ["run.py", "settings.json"] + [
        "harness/" + f for f in os.listdir(copy / "benchmark/harness")
        if f.endswith(".py")]
    before = {p: (copy / "benchmark" / p).read_bytes() for p in general}
    run = _a_cell_of_a_family_with_its_own_number(copy, "{'max_off': 0}")
    run.make_reference()
    assert run.pool.limits == {**run.limits, "max_off": 0}
    assert run.pool.keeps == [{"response": ["max_of_hits"], "hit": ["_type"]}] * 8
    # a response of the window: through the compact answer, then the family's compare
    sound = run.pool.answer(run.ref, 3)
    for h in sound["hits"]["hits"]:
        h.update(_type="doc", _source={"body": "w1"})
    _whole, answer, _spans = _digest(200, json.dumps(sound).encode(), run.pool.keeps[3])
    kept = as_response(answer)
    assert kept["max_of_hits"] == sound["max_of_hits"]
    assert all(set(h) == {"_id", "_score", "_type"} for h in kept["hits"]["hits"])
    got = Compared(run.pool.limits)
    got.add(run.pool.compare(run.ref, 3, kept, 1e-5))
    assert got.passed
    wrong = dict(kept, max_of_hits=kept["max_of_hits"] * 2)
    got.add(run.pool.compare(run.ref, 3, wrong, 1e-5))
    assert not got.passed
    # the family's number stands beside its limit wherever the shared ones do
    run.compare_line("window", "a test", got)
    assert run.compared["window.max_off"] == [1, 0]
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["numbers"]["max_off"] == {"value": 1, "limit": 0}
    assert list(line["numbers"])[:6] == list(run.limits)
    for p, content in before.items():
        assert (copy / "benchmark" / p).read_bytes() == content


@pytest.mark.parametrize("limits, message", [
    ("{}", "gives no limit"),                 # a number of its own without a limit
    ("{'max_off': 0, 'ids_off': 3}", "it has 0")])  # a shared limit is not loosened
def test_a_family_number_without_a_limit_is_refused_before_any_search(copy, limits,
                                                                      message):
    from benchmark.harness.server import BenchFailure

    run = _a_cell_of_a_family_with_its_own_number(copy, limits)
    with pytest.raises(BenchFailure, match=message):
        run.make_reference()


def test_a_cell_without_the_new_keys_is_held_to_what_all_cells_share():
    from benchmark.harness.cell import Run

    bench = registry.benchmark()
    shared = registry.settings()
    for w in bench["workloads"]:
        config = registry.config(bench, w["config"])
        run = Run(argparse.Namespace(workload=w["name"], seed=5, seconds=1.0, trace=1,
                                     docs=None), 0.0, assume_chip=True)
        assert run.must_not_rise() == \
            shared["must_not_rise"] + config.get("must_not_rise", [])
        assert run.reductions()[:3] == shared["trace"]["reductions"]
        if "search" not in config:
            assert "must_not_rise" not in config
            assert run.must_not_rise() == shared["must_not_rise"]


def test_harness_names_no_configuration_mix_family_or_metric():
    bench = registry.benchmark()
    names = {c["name"] for c in bench["configs"]} | \
        {w["name"] for w in bench["workloads"]} | \
        {w["traffic"] for w in bench["workloads"]} | \
        {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    for d in ("queries", "corpora", "reductions"):
        names |= {f[:-3] for f in os.listdir(os.path.join(registry.BENCH_DIR, d))
                  if f.endswith(".py")}
    code = [os.path.join(registry.BENCH_DIR, "run.py")]
    harness = os.path.join(registry.BENCH_DIR, "harness")
    code += [os.path.join(harness, f) for f in os.listdir(harness) if f.endswith(".py")]
    for path in code:
        text = open(path).read()
        for name in names:
            assert not re.search(r"(?<![\w.])" + re.escape(name) + r"(?![\w])", text), \
                f"{path} names {name!r}"


def test_an_unknown_device_is_an_error_not_a_default():
    assert registry.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        registry.peaks("TPU v9")


def test_benchmark_json_and_its_files_agree():
    bench = registry.benchmark()
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for w in bench["workloads"]:
        registry.config(bench, w["config"])
        mix = registry.mix(w["traffic"])
        for fam in mix["families"]:
            registry.module("queries", fam["family"])
        reported = [m for m, _ in registry.metrics_of(
            bench, w["name"], "end_to_end", "end_to_end")]
        assert len(reported) >= 2
        assert registry.metrics_of(bench, w["name"], "per_layer", "layer_metrics")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for c in m.get("workloads", cells):
            assert c in cells
            moved = e2e[m["moves"]]
            assert c in moved.get("workloads", cells), (m["name"], c)
    for c in bench["configs"]:
        assert registry.config(bench, c["name"])["reduced"] == c["reduced"]
