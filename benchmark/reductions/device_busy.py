"""Busy and idle time of the device over the traced window: busy is the union of the
intervals in which an operation ran (the `XLA Ops` line), averaged over the chips; the
seconds of each chip are kept beside the mean, and the distance between the busiest
and the idlest chip as a share of the window."""

from __future__ import annotations

import numpy as np

OPS_LINE = "XLA Ops"


def merged(start_ns: np.ndarray, dur_ns: np.ndarray):
    """Sorted, non-overlapping (starts, ends) covering the same time."""
    if not len(start_ns):
        return np.zeros(0), np.zeros(0)
    order = np.argsort(start_ns, kind="stable")
    s, e = start_ns[order], (start_ns + dur_ns)[order]
    e = np.maximum.accumulate(e)
    new = np.concatenate([[True], s[1:] > e[:-1]])
    starts = s[new]
    ends = np.concatenate([e[:-1][new[1:]], e[-1:]])
    return starts, ends


def reduce(trace: dict) -> dict:
    busy = []
    for _name, plane in sorted(trace["planes"].items()):
        line = plane["lines"].get(OPS_LINE)
        if line is None:
            continue
        s, e = merged(line["start_ns"], line["dur_ns"])
        busy.append(float((e - s).sum()) / 1e9)
    if not busy:
        return {}
    window_s = trace["window_s"]
    busy_s = float(np.mean(busy))
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share_pct": 100.0 * (1.0 - busy_s / window_s),
            "busy_s_by_chip": busy,
            "busy_spread_pct": 100.0 * (max(busy) - min(busy)) / window_s}
