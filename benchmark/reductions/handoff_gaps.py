"""The device's idle time put down to the hand-overs between the host's threads:
`host_gaps`'s arithmetic with the spans of a hand-over ahead of its list, deepest
first: the wake-up of a thread that waited (`thread.wake`), a search's wait for a pool
thread (`pool.wait`), the local transport's codec round trip (`transport.codec`), then
the fetch handler (`shard.fetch`) and a finished batch held behind the next one's
dispatch (`batcher.hold`), then `host_gaps.DEPTH`. `handoff_share_pct` is the
share of the idle seconds that fall inside a sampled search whose gap's middle lies in
one of the first three. A program without those spans reads 0."""

from __future__ import annotations

import numpy as np

from benchmark.reductions.device_busy import OPS_LINE, merged
from benchmark.reductions.host_gaps import DEPTH as HOST_DEPTH
from benchmark.reductions.host_gaps import MIN_GAP_S, _covered

HANDOVERS = ("thread.wake", "pool.wait", "transport.codec")
DEPTH = HANDOVERS + ("shard.fetch", "batcher.hold") + HOST_DEPTH  # deepest first


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
    sampled = handed_over = 0.0
    for name in DEPTH:
        hit = open_ & _covered([(a, b) for n, a, b in spans if n == name], mid)
        seconds = float(length[hit].sum())
        if seconds:
            totals[f"sampled search in {name}"] = seconds
        sampled += seconds
        if name in HANDOVERS:
            handed_over += seconds
        open_ &= ~hit
    sent, done = trace.get("requests") or (np.zeros(0), np.zeros(0))
    busy = _covered(list(zip(sent, done)), mid)
    totals["searches in flight, none sampled"] = float(length[open_ & busy].sum())
    totals["no search in flight"] = float(length[open_ & ~busy].sum())
    ranked = sorted(((k, v) for k, v in totals.items() if v > 0), key=lambda kv: -kv[1])
    out = {"gaps": [[k, v] for k, v in ranked], "sampled_s": sampled,
           "handoff_s": handed_over}
    if sampled:
        out["handoff_share_pct"] = 100.0 * handed_over / sampled
    return out
