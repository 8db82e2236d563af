"""The general readers that a metric's definition file names by `reader`. Each takes
the definition and the run's observations and returns a number, or None where it finds
nothing to read: the harness then leaves the metric out of the line."""

from __future__ import annotations

import numpy as np

from . import registry
from .loadgen import REQUEST_TIMEOUT_S


class Observations:
    """What one run saw: named facts of its phases, the window's load result, the
    server's counters at the window's start and end, sampled host spans, and the
    reduced device trace (traced runs only)."""

    def __init__(self, index: str):
        self.index = index
        self.facts: dict = {}
        self.window = None
        self.stats_before: dict = {}
        self.stats_after: dict = {}
        self.reduced: dict = {}   # reduction name -> its result
        self.until = None         # traced runs: where the profiler started (seconds)

    def searches(self):
        """(due, sent, done, ok) of the window's searches; in a traced run only of
        those due before the profiler started, whose Python tracer slows the host."""
        due, sent, done, ok = self.window.arrays()
        if self.until is None:
            return due, sent, done, ok
        keep = due < self.until
        return due[keep], sent[keep], done[keep], ok[keep]

    def counter(self, path: str, when: str = "after"):
        node = self.stats_after if when == "after" else self.stats_before
        for key in path.format(index=self.index).split("."):
            if not isinstance(node, dict) or key not in node:
                return None
            node = node[key]
        return node if isinstance(node, (int, float)) else None

    def delta(self, path: str):
        a, b = self.counter(path, "before"), self.counter(path, "after")
        return None if a is None or b is None else b - a


def _latencies_ms(obs, start: str) -> np.ndarray:
    due, sent, done, ok = obs.searches()
    lat = done - (due if start == "due" else sent)
    lat[~ok] = np.maximum(lat[~ok], REQUEST_TIMEOUT_S)  # a failure misses any limit
    return lat * 1000.0


def latency_percentile(d: dict, obs):
    lat = _latencies_ms(obs, d.get("from", "due"))
    return float(np.percentile(lat, d["q"])) if len(lat) else None


def lateness_percentile(d: dict, obs):
    due, sent, _done, _ok = obs.searches()
    return float(np.percentile((sent - due) * 1000.0, d["q"])) if len(due) else None


def share_slower_than(d: dict, obs):
    """Share of the window's searches slower than `factor` times its median."""
    lat = _latencies_ms(obs, d.get("from", "due"))
    if not len(lat):
        return None
    return float(100.0 * (lat > d["factor"] * np.median(lat)).mean())


def completed_per_second(d: dict, obs):
    _due, _sent, done, ok = obs.window.arrays()
    return float((ok & (done <= obs.window.seconds)).sum() / obs.window.seconds)


def fact(d: dict, obs):
    return obs.facts.get(d["fact"])


def counter_value(d: dict, obs):
    v = obs.counter(d["path"], d.get("when", "before"))
    return None if v is None else v * d.get("scale", 1.0)


def counter_delta(d: dict, obs):
    v = obs.delta(d["path"])
    return None if v is None else v * d.get("scale", 1.0)


def _paths(d: dict, key: str) -> list:
    """A definition's list of counter paths under `key`; where it gives a metric's
    name in the list's place, that metric's list, so that two shares of one sum keep
    it in one file."""
    v = d[key]
    return registry.layer_metric(v)[key] if isinstance(v, str) else v


def counter_ratio(d: dict, obs):
    num = [obs.delta(p) for p in _paths(d, "numerator")]
    den = [obs.delta(p) for p in _paths(d, "denominator")]
    if any(v is None for v in num + den) or not sum(den):
        return None
    return sum(num) / sum(den) * d.get("scale", 1.0)


def _union_ms(intervals: list) -> float:
    total, end = 0.0, None
    for t0, t1 in sorted(intervals):
        if end is None or t0 > end:
            total += t1 - t0
            end = t1
        elif t1 > end:
            total += t1 - end
            end = t1
    return total * 1000.0


def span_median(d: dict, obs):
    """Median over the sampled searches of the time their spans named `span` cover,
    less the part the spans named in `minus` cover (a nested span counts once)."""
    values = []
    for _sent, _done, spans in obs.window.spans:
        own = [(t0, t1) for name, t0, t1 in spans if name == d["span"]]
        if not own:
            continue
        lo, hi = min(t for t, _ in own), max(t for _, t in own)
        inner = [(max(t0, lo), min(t1, hi)) for name, t0, t1 in spans
                 if name in d.get("minus", ()) and t1 > lo and t0 < hi]
        values.append(_union_ms(own) - _union_ms(inner))
    return float(np.median(values)) if values else None


def reduction(d: dict, obs):
    value = (obs.reduced.get(d["reduction"]) or {}).get(d["field"])
    if value is None:
        return None
    if "per_fact" in d:
        n = obs.facts.get(d["per_fact"])
        if not n:
            return None
        value = value / n
    return value * d.get("scale", 1.0)


READERS = {f.__name__: f for f in (
    latency_percentile, lateness_percentile, share_slower_than, completed_per_second, fact,
    counter_value, counter_delta, counter_ratio, span_median, reduction)}


def read(definition: dict, obs):
    kind = definition["reader"]
    if kind not in READERS:
        raise KeyError(f"no reader {kind!r}; there are {sorted(READERS)}")
    return READERS[kind](definition, obs)
