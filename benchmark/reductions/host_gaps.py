"""The device's idle time over the traced window, by what the host was doing: each
gap between operations goes to the deepest sampled host span that covers its middle
(spans come from `?trace=true` on every n-th search, moved onto the profiler's clock),
else to how many searches the client had in flight. Seconds are summed by label."""

from __future__ import annotations

import numpy as np

from benchmark.reductions.device_busy import OPS_LINE, merged

DEPTH = ("device_pull", "batcher.merge", "batcher.dispatch", "batcher.queue", "shard",
         "coordinator", "rest")  # deepest first


MIN_GAP_S = 20e-6  # shorter gaps are between the operations of one launch


def _covered(intervals: list, mids: np.ndarray) -> np.ndarray:
    """Which of `mids` lie inside at least one of the (t0, t1) intervals."""
    if not intervals:
        return np.zeros(len(mids), bool)
    t0 = np.sort([a for a, _ in intervals])
    t1 = np.sort([b for _, b in intervals])
    return np.searchsorted(t0, mids, "right") - np.searchsorted(t1, mids, "left") > 0


def reduce(trace: dict) -> dict:
    planes = list(trace["planes"].values())
    if not planes or OPS_LINE not in planes[0]["lines"]:
        return {}
    line = planes[0]["lines"][OPS_LINE]
    starts, ends = merged(line["start_ns"], line["dur_ns"])
    if not len(starts):
        return {}
    lo = np.concatenate([[0.0], ends]) / 1e9
    hi = np.concatenate([starts, [trace["window_s"] * 1e9]]) / 1e9
    length = np.maximum(hi - lo, 0.0)
    mid = (lo + hi) / 2
    totals = {"inside one launch (gaps under 20 us)": float(length[length < MIN_GAP_S].sum())}
    open_ = length >= MIN_GAP_S
    spans = trace.get("host_spans") or []      # (name, t0, t1) seconds on this clock
    for name in DEPTH:
        hit = open_ & _covered([(a, b) for n, a, b in spans if n == name], mid)
        if hit.any():
            totals[f"sampled search in {name}"] = float(length[hit].sum())
        open_ &= ~hit
    sent, done = trace.get("requests") or (np.zeros(0), np.zeros(0))
    busy = _covered(list(zip(sent, done)), mid)
    totals["searches in flight, none sampled"] = float(length[open_ & busy].sum())
    totals["no search in flight"] = float(length[open_ & ~busy].sum())
    ranked = sorted(((k, v) for k, v in totals.items() if v > 0), key=lambda kv: -kv[1])
    return {"gaps": [[k, v] for k, v in ranked], "longest_gap_s": float(length.max())}
