"""Device time per XLA module (the `XLA Modules` line) and per operation (the `XLA
Ops` line). No kernel carries a `jax.named_scope` yet, so a module's name
(`jit_<function>(<fingerprint>)`, the fingerprint dropped) and an operation's HLO name
(`%fusion.4`) are the finest stable groupings there are."""

from __future__ import annotations

import re

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
_FINGERPRINT = re.compile(r"\(\d+\)$")


def _sum_by(line, key) -> dict:
    seconds: dict = {}
    for name, dur in zip(line["names"], line["dur_ns"]):
        k = key(name)
        seconds[k] = seconds.get(k, 0.0) + float(dur) / 1e9
    return seconds


def _ranked(seconds: dict, chips: int) -> list:
    return [[k, v / chips] for k, v in sorted(seconds.items(), key=lambda kv: -kv[1])]


def reduce(trace: dict) -> dict:
    modules: dict = {}
    ops: dict = {}
    launches = 0
    for plane in trace["planes"].values():
        line = plane["lines"].get(MODULES_LINE)
        if line is not None:
            launches += len(line["names"])
            for k, v in _sum_by(line, lambda n: _FINGERPRINT.sub("", n)).items():
                modules[k] = modules.get(k, 0.0) + v
        line = plane["lines"].get(OPS_LINE)
        if line is not None:
            for k, v in _sum_by(line, lambda n: "op " + n.split(" = ", 1)[0]).items():
                ops[k] = ops.get(k, 0.0) + v
    if not modules:
        return {}
    chips = max(1, len(trace["planes"]))
    by_module, by_op = _ranked(modules, chips), _ranked(ops, chips)
    return {"modules": by_module, "ops": by_op,
            "top": by_module[:4] + by_op[:6],
            "total_ms": 1000.0 * sum(modules.values()) / chips,
            "launches": launches / chips}
