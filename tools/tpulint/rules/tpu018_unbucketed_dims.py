"""TPU018 — unbucketed request-derived dimension reaching a jit boundary.

XLA compiles one executable per distinct operand shape. Every dimension that
reaches a jit/shard_map/pallas_call boundary must therefore come from a
BOUNDED value space: a config/mapper constant, or a recognized bucket ladder
(`_pow2_bucket` / `_k_bucket` — the batcher's pow-2 Q padding rides the same
ladders). A dimension derived from raw request data (`len(hits)`, a helper
that returns one — resolved cross-module via the compile-surface
return-calls fixpoint) gives every distinct request size its own executable:
an unbounded compile family, which is precisely the serving-path compile
stall compile warming exists to kill (a first sighting stalls the one
drainer 0.3-1 s on the chip, PERF.md section 7).

Scope is the compile surface only (tools/tpulint/compilesurface.py's
`jit_scope`): functions that construct an executable, plus their direct
callers — the launch wrappers whose array allocations become traced operand
shapes. Flagged sinks are the shape-taking allocators/reshapers
(`zeros`/`ones`/`full`/`empty`/`arange`/`reshape`/`broadcast_to`) with an
`unbounded`-classified dimension. Host-side bookkeeping in functions nowhere
near a jit boundary stays silent, as do `unknown` dims (bare parameters,
`.shape[i]` reads — those are bucketed upstream or not provable; tpulint
never guesses).

Fix: round the dimension through `_pow2_bucket`/`_k_bucket` (or a fixed pad)
before it shapes an array. `min(len(x), CAP)` also bounds it.
"""

from __future__ import annotations

import ast

from .. import compilesurface as cs
from ..engine import Finding, SourceFile

RULE_ID = "TPU018"
DOC = ("unbucketed request-derived dimension reaching a jit boundary "
       "(one executable per distinct request size — unbounded compile "
       "families on the serving path)")

# shape-taking sinks: first arg is the shape for allocators, every positional
# arg is a dim for the reshapers
_ALLOC_SINKS = {"zeros", "ones", "full", "empty"}
_DIM_SINKS = {"arange", "reshape", "broadcast_to"}


class _V(cs.EnvScan):
    def __init__(self, sf: SourceFile, out: list, unb_fns: set,
                 bucket_fns: set):
        super().__init__(unb_fns, bucket_fns)
        self.sf = sf
        self.out = out

    def visit_Call(self, node: ast.Call):
        n = cs._last_name(node.func)
        if n in _ALLOC_SINKS or n in _DIM_SINKS:
            shape_args = node.args[:1] if n in _ALLOC_SINKS else node.args
            for a in shape_args:
                elts = a.elts if isinstance(a, (ast.Tuple, ast.List)) else [a]
                for el in elts:
                    cls, why = self.classify(el)
                    if cls == cs.UNBOUNDED:
                        self.out.append(Finding(
                            self.sf.relpath, node.lineno, RULE_ID,
                            f"shape dimension {why} is request-derived with "
                            "no bucket ladder at a jit boundary — every "
                            "distinct value traces and compiles a fresh "
                            "executable on the serving path; round it "
                            "through _pow2_bucket/_k_bucket (or a fixed "
                            "pad) before it shapes an array"))
        self.generic_visit(node)


def run(files: list[SourceFile], project=None) -> list[Finding]:
    out: list[Finding] = []
    if project is None:
        return out
    sa = cs.analysis(files, project)
    for sf in files:
        unb_fns = sa.unbounded_fn_names(sf)
        bucket_fns = sa.bucket_fn_names(sf)
        for fi in project.functions:
            if fi.sf is not sf or fi.fid not in sa.jit_scope:
                continue
            v = _V(sf, out, unb_fns, bucket_fns)
            for stmt in fi.node.body:
                v.visit(stmt)
    return out
