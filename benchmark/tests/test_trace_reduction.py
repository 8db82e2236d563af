"""The reduction from a profiler trace to busy time, idle share and time per module,
on a trace recorded on a v5e (PR 23, call 1: 4 s of `wiki.filtered`, the host's
Python-tracer plane cut away), and on intervals made by hand."""

import os

import numpy as np
import pytest

from benchmark.harness import registry, xplane

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "recorded",
                        "tpu_v5e_filtered_4s.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    planes = xplane.read_planes(RECORDED, "/device:TPU:")
    start, stop = xplane.profile_times(RECORDED)
    return {"planes": planes, "window_s": (stop - start) / 1e9}


def test_the_decoder_reads_what_jax_reads(trace):
    jax = pytest.importorskip("jax")
    theirs = {}
    for plane in jax.profiler.ProfileData.from_file(RECORDED).planes:
        for line in plane.lines:
            ev = list(line.events)
            theirs[(plane.name, line.name)] = (
                len(ev), sum(e.duration_ns for e in ev),
                [e.name for e in ev[:3]], [e.start_ns for e in ev[:3]])
    assert xplane.plane_names(RECORDED) == ["/device:TPU:0", "Task Environment"]
    for name, plane in trace["planes"].items():
        for line_name, line in plane["lines"].items():
            n, total, names, starts = theirs[(name, line_name)]
            assert len(line["names"]) == n
            assert line["dur_ns"].sum() == pytest.approx(total, rel=1e-5)  # theirs: whole ns
            assert line["names"][:3] == names
            assert line["start_ns"][:3].tolist() == pytest.approx(starts, abs=1.0)
    start, stop = xplane.profile_times(RECORDED)
    assert start == 1790518117822383187 and (stop - start) / 1e9 == pytest.approx(4.403443659)


def test_busy_union_idle_share_and_time_per_module(trace):
    busy = registry.module("reductions", "device_busy").reduce(trace)
    assert busy["busy_s"] == pytest.approx(0.11038631546800112, rel=1e-9)
    assert busy["window_s"] == trace["window_s"]
    assert busy["idle_share_pct"] == pytest.approx(
        100 * (1 - busy["busy_s"] / trace["window_s"]))
    assert 97.0 < busy["idle_share_pct"] < 97.5
    assert busy["busy_s_by_chip"] == [busy["busy_s"]] and busy["busy_spread_pct"] == 0.0
    mods = registry.module("reductions", "module_time").reduce(trace)
    assert mods["launches"] == 255
    assert mods["modules"][0][0] == "jit_wrapper"
    assert mods["modules"][0][1] == pytest.approx(0.109987363516, rel=1e-9)
    assert mods["total_ms"] == pytest.approx(110.411501874, rel=1e-9)
    assert mods["ops"][0][0] == "op %fusion.4"
    # operations run inside modules: the union of the one is within the sum of the other
    assert busy["busy_s"] <= mods["total_ms"] / 1000.0


def test_gaps_go_to_the_host_span_that_covers_them(trace):
    gaps = registry.module("reductions", "host_gaps")
    bare = gaps.reduce(dict(trace))
    total = sum(v for _, v in bare["gaps"])
    busy = registry.module("reductions", "device_busy").reduce(trace)
    assert total == pytest.approx(trace["window_s"] - busy["busy_s"], rel=1e-6)
    assert bare["gaps"][0][0] == "no search in flight"
    covered = gaps.reduce(dict(trace, host_spans=[("rest", 0.0, 5.0),
                                                  ("batcher.queue", 1.0, 2.0)]))
    by = dict(covered["gaps"])
    assert by["sampled search in batcher.queue"] == pytest.approx(1.0, abs=0.05)
    assert "no search in flight" not in by


def _four_planes(trace):
    """The recorded plane copied to four chips, each later by 1 ms a chip; chip c
    keeps only the first (4 - c) quarters of its operations, and every operation named
    `%fusion.4` stands for an `all-gather` there (chips 0 and 1 a started and finished
    one, as the TPU runs an asynchronous collective)."""
    one = trace["planes"]["/device:TPU:0"]["lines"]
    planes = {}
    for c in range(4):
        lines = {}
        for name, line in one.items():
            n = len(line["names"]) * (4 - c) // 4
            lines[name] = {
                "names": [x.replace("%fusion.4 ", "%all-gather-start.1 " if c < 2
                                    else "%all-gather.1 ") for x in line["names"][:n]],
                "start_ns": line["start_ns"][:n] + c * 1e6,
                "dur_ns": line["dur_ns"][:n]}
        planes[f"/device:TPU:{c}"] = {"lines": lines}
    return {"planes": planes, "window_s": trace["window_s"]}


def test_four_chips_busy_by_chip_their_spread_and_the_collectives_share(trace):
    four = _four_planes(trace)
    busy = registry.module("reductions", "device_busy").reduce(four)
    one = registry.module("reductions", "device_busy").reduce(trace)
    by_chip = busy["busy_s_by_chip"]
    assert len(by_chip) == 4 and by_chip[0] == pytest.approx(one["busy_s"], rel=1e-9)
    assert by_chip == sorted(by_chip, reverse=True) and by_chip[3] < 0.3 * by_chip[0]
    assert busy["busy_s"] == pytest.approx(sum(by_chip) / 4)
    assert busy["idle_share_pct"] == pytest.approx(
        100 * (1 - busy["busy_s"] / four["window_s"]))
    assert busy["busy_spread_pct"] == pytest.approx(
        100 * (by_chip[0] - by_chip[3]) / four["window_s"])
    coll = registry.module("reductions", "collective_time").reduce(four)
    ops = [line for p in four["planes"].values() for n, line in p["lines"].items()
           if n == "XLA Ops"]
    total = sum(float(l["dur_ns"].sum()) for l in ops) / 1e9
    gathered = sum(float(d) for l in ops for x, d in zip(l["names"], l["dur_ns"])
                   if x.startswith("%all-gather")) / 1e9
    assert 0 < gathered < total
    assert coll["ops_s"] == pytest.approx(total / 4, rel=1e-9)
    assert coll["collective_s"] == pytest.approx(gathered / 4, rel=1e-9)
    assert coll["share_pct"] == pytest.approx(100 * gathered / total, rel=1e-9)
    assert [k for k, _ in coll["by_op"]] == ["all-gather-start.1", "all-gather.1"]
    # one chip runs no collective: the share is a true 0, not nothing
    alone = registry.module("reductions", "collective_time").reduce(trace)
    assert alone["share_pct"] == 0.0 and alone["collective_s"] == 0.0
    mods = registry.module("reductions", "module_time").reduce(four)
    assert mods["total_ms"] < registry.module("reductions", "module_time").reduce(
        trace)["total_ms"]


@pytest.mark.parametrize("op", ["all-gather.2", "all-reduce", "all-to-all.1",
                                "collective-permute-start", "reduce-scatter.3"])
def test_every_collective_of_the_list_is_counted(op):
    line = {"names": [f"%{op} = f32[8]{{0}} {op.split('.')[0]}(%p)", "%fusion.1 = f32[8]"],
            "start_ns": np.array([0.0, 2e6]), "dur_ns": np.array([1e6, 3e6])}
    got = registry.module("reductions", "collective_time").reduce(
        {"planes": {"/device:TPU:0": {"lines": {"XLA Ops": line}}}, "window_s": 1.0})
    assert got["share_pct"] == pytest.approx(25.0)
    assert got["by_op"] == [[op, pytest.approx(1e-3)]]


def test_overlapping_intervals_count_once():
    busy = registry.module("reductions", "device_busy")
    s, e = busy.merged(np.array([0.0, 5.0, 8.0, 20.0]), np.array([10.0, 2.0, 4.0, 5.0]))
    assert s.tolist() == [0.0, 20.0] and e.tolist() == [12.0, 25.0]
    line = {"names": ["a"] * 4, "start_ns": np.array([0.0, 5.0, 8.0, 20.0]) * 1e9,
            "dur_ns": np.array([10.0, 2.0, 4.0, 5.0]) * 1e9}
    got = busy.reduce({"planes": {"/device:TPU:0": {"lines": {"XLA Ops": line}}},
                       "window_s": 34.0})
    assert got["busy_s"] == pytest.approx(17.0) and got["idle_share_pct"] == 50.0


def test_a_trace_without_device_operations_gives_nothing():
    for name in ("device_busy", "module_time", "host_gaps", "collective_time"):
        assert registry.module("reductions", name).reduce(
            {"planes": {}, "window_s": 4.0}) == {}
