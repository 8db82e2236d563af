"""`reductions/handoff_gaps.py` on the trace recorded on a v5e (`recorded/`, 4.4 s of
`wiki.filtered`) with host spans made by hand: idle gaps go to the hand-over that
covers their middle before any span of `host_gaps`'s list, and the share is taken of
the idle seconds inside sampled searches alone."""

import os

import pytest

from benchmark.harness import readers, registry, xplane

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "recorded",
                        "tpu_v5e_filtered_4s.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    planes = xplane.read_planes(RECORDED, "/device:TPU:")
    start, stop = xplane.profile_times(RECORDED)
    return {"planes": planes, "window_s": (stop - start) / 1e9}


def _share(reduced: dict):
    obs = readers.Observations("idx")
    obs.reduced["handoff_gaps"] = reduced
    return readers.read(registry.layer_metric("handoff_idle_share"), obs)


def test_idle_gaps_go_to_the_hand_over_that_covers_them(trace):
    handoff = registry.module("reductions", "handoff_gaps")
    host = registry.module("reductions", "host_gaps")
    # one sampled search over the first 4 s: a pool wait and a wake-up inside its
    # shard span, the codec inside the wake-up (the deeper name wins), the fetch
    spans = [("rest", 0.0, 4.0), ("shard", 0.5, 3.0), ("pool.wait", 0.5, 1.0),
             ("batcher.hold", 1.0, 1.5),
             ("thread.wake", 2.0, 3.0), ("transport.codec", 2.5, 3.5),
             ("shard.fetch", 3.5, 4.0)]
    got = handoff.reduce(dict(trace, host_spans=spans))
    by = dict(got["gaps"])
    assert by["sampled search in pool.wait"] == pytest.approx(0.5, abs=0.08)
    assert by["sampled search in thread.wake"] == pytest.approx(1.0, abs=0.08)
    assert by["sampled search in transport.codec"] == pytest.approx(0.5, abs=0.08)
    assert by["sampled search in shard.fetch"] == pytest.approx(0.5, abs=0.08)
    # a gap goes where its middle lies: one that straddles 1.5 s is the shard's
    assert 0.3 < by["sampled search in batcher.hold"] < 0.58
    assert by["sampled search in batcher.hold"] + by["sampled search in shard"] \
        == pytest.approx(1.0, abs=0.08)
    assert by["sampled search in rest"] == pytest.approx(0.5, abs=0.08)
    assert got["handoff_s"] == pytest.approx(
        by["sampled search in pool.wait"] + by["sampled search in thread.wake"]
        + by["sampled search in transport.codec"])
    assert got["sampled_s"] == pytest.approx(
        sum(v for k, v in by.items() if k.startswith("sampled search in ")))
    assert _share(got) == pytest.approx(100.0 * got["handoff_s"] / got["sampled_s"])
    assert 45.0 < _share(got) < 55.0  # the held batch is no hand-over between threads
    # the same seconds as host_gaps books, label by label where no hand-over is
    both = dict(host.reduce(dict(trace, host_spans=spans))["gaps"])
    assert sum(by.values()) == pytest.approx(sum(both.values()))
    for label in ("inside one launch (gaps under 20 us)", "no search in flight"):
        assert by[label] == pytest.approx(both[label])


def test_a_program_without_the_spans_reads_zero_and_no_span_reads_nothing(trace):
    handoff = registry.module("reductions", "handoff_gaps")
    old = handoff.reduce(dict(trace, host_spans=[("rest", 0.0, 4.0),
                                                 ("batcher.queue", 1.0, 2.0)]))
    assert old["handoff_s"] == 0.0 and _share(old) == 0.0
    assert dict(old["gaps"]) == dict(registry.module("reductions", "host_gaps").reduce(
        dict(trace, host_spans=[("rest", 0.0, 4.0), ("batcher.queue", 1.0, 2.0)]))["gaps"])
    bare = handoff.reduce(dict(trace))
    assert "handoff_share_pct" not in bare and _share(bare) is None
    assert handoff.reduce({"planes": {}, "window_s": 1.0}) == {}
    assert _share({}) is None


def test_the_metric_names_the_reduction_in_its_cells():
    bench = registry.benchmark()
    idle = next(m for m in bench["per_layer"] if m["name"] == "device_idle_share")
    entry = next(m for m in bench["per_layer"] if m["name"] == "handoff_idle_share")
    assert entry["workloads"] == idle["workloads"] and entry["moves"] == idle["moves"]
    for cell in entry["workloads"]:
        named = [d.get("reduction") for _m, d in registry.metrics_of(
            bench, cell, "per_layer", "layer_metrics")]
        assert "handoff_gaps" in named
