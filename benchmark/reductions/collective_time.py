"""Device time in collectives: the seconds of the `XLA Ops` line spent in operations
whose HLO name begins with one of `COLLECTIVES` (an asynchronous one counts its
`-start` and `-done` halves), as a mean over the chips, and their share of all the
operations' seconds. A trace of one chip has none and reads 0."""

from __future__ import annotations

from benchmark.reductions.device_busy import OPS_LINE

COLLECTIVES = ("all-gather", "all-reduce", "all-to-all", "collective-permute",
               "reduce-scatter")


def reduce(trace: dict) -> dict:
    ops_s = collective_s = 0.0
    by_op: dict = {}
    chips = 0
    for plane in trace["planes"].values():
        line = plane["lines"].get(OPS_LINE)
        if line is None:
            continue
        chips += 1
        for name, dur in zip(line["names"], line["dur_ns"]):
            seconds = float(dur) / 1e9
            ops_s += seconds
            op = name.split(" = ", 1)[0].lstrip("%")
            if op.startswith(COLLECTIVES):
                collective_s += seconds
                by_op[op] = by_op.get(op, 0.0) + seconds
    if not ops_s:
        return {}
    return {"collective_s": collective_s / chips, "ops_s": ops_s / chips,
            "share_pct": 100.0 * collective_s / ops_s,
            "by_op": [[k, v / chips] for k, v in
                      sorted(by_op.items(), key=lambda kv: -kv[1])]}
