"""BASELINE config #5: 8-shard cross-shard top-k merge — SPMD mesh vs transport.

Measures the same 8-shard search served two ways on identical hardware:
  a) the shard_map SPMD program (DFS psum + all_gather top-k over the mesh axis —
     parallel/mesh_search.py), one launch per batch
  b) the transport scatter-gather (per-shard query phase + host-side sort_docs
     reduce), the reference's coordinator architecture

On a TPU host the mesh rides ICI over the chips jax reports (needs 8). Where the
caller sets JAX_PLATFORMS=cpu it runs on a virtual 8-device CPU mesh: the absolute
numbers are then CPU numbers, labelled so, and never a device measurement. With
JAX_PLATFORMS unset and no TPU it exits non-zero.

Run: python tools/bench_mesh.py                       (8 TPU chips)
     JAX_PLATFORMS=cpu python tools/bench_mesh.py     (virtual CPU mesh, labelled)
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from elasticsearch_tpu.common.jaxenv import (  # noqa: E402
    cpu_requested, force_cpu_platform, require_accelerator)

if cpu_requested():
    force_cpu_platform(n_devices=8)  # the caller asked for the CPU: 8 virtual devices

N_SHARDS = 8
DOCS_PER_SHARD = int(os.environ.get("BENCH_MESH_DOCS", 20_000))
VOCAB = 8_000
BATCH = int(os.environ.get("BENCH_MESH_BATCH", 64))
K = 100
ROUNDS = 6


def main():
    device = require_accelerator("bench_mesh")
    if device["device_count"] < N_SHARDS:
        raise SystemExit(f"bench_mesh: {N_SHARDS} shards need {N_SHARDS} devices, "
                         f"jax reports {device['device_count']}")

    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.index.engine import Engine
    from elasticsearch_tpu.mapper.core import MapperService
    from elasticsearch_tpu.parallel.mesh_search import (
        MeshSearchExecutor,
        build_sharded_index,
    )
    from elasticsearch_tpu.search import ShardContext, parse_query
    from elasticsearch_tpu.search.controller import sort_docs
    from elasticsearch_tpu.search.execute import lower_flat
    from elasticsearch_tpu.search.service import (
        ShardQueryResult,
        execute_query_phase,
        parse_search_body,
    )
    from elasticsearch_tpu.search.similarity import SimilarityService

    rng = np.random.default_rng(5)
    words = [f"tok{i}" for i in range(VOCAB)]
    settings = Settings.from_flat({"index.similarity.default.type": "BM25"})
    shards = []
    import tempfile

    tmp = tempfile.mkdtemp(prefix="bench_mesh_")
    t0 = time.time()
    zipf = (rng.zipf(1.3, DOCS_PER_SHARD * N_SHARDS * 40) - 1) % VOCAB
    pos = 0
    for si in range(N_SHARDS):
        svc = MapperService(settings)
        e = Engine(f"{tmp}/s{si}", svc)
        for i in range(DOCS_PER_SHARD):
            n = 40
            e.index("doc", f"{si}-{i}",
                    {"body": " ".join(words[t] for t in zipf[pos: pos + n])})
            pos += n
        e.refresh()
        ctx = ShardContext(e.acquire_searcher(), svc,
                           SimilarityService(settings, mapper_service=svc))
        shards.append((e, svc, ctx))
    print(f"# indexed {N_SHARDS}x{DOCS_PER_SHARD} docs in {time.time()-t0:.0f}s",
          file=sys.stderr)

    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:N_SHARDS]), ("shards",))
    sharded = build_sharded_index([ctx.searcher for (_e, _s, ctx) in shards],
                                  ["body"], mesh=mesh)
    executor = MeshSearchExecutor(sharded, mesh, similarity="BM25",
                                  use_global_stats=False)

    pool = [w for w in words[50:4000]]
    queries = [" ".join(rng.choice(pool, size=3)) for _ in range(BATCH)]

    def lower_batch():
        # parse + lower INSIDE the timed region — the mesh serving path does this
        # per search, so the comparison must charge it to both sides
        return [lower_flat(parse_query({"match": {"body": q}}), shards[0][2])
                for q in queries]

    req = parse_search_body({"size": K})

    # --- mesh path: one SPMD launch per batch -------------------------------
    executor.search(lower_batch(), K)  # compile
    t0 = time.perf_counter()
    for _ in range(ROUNDS):
        out = executor.search(lower_batch(), K)
    mesh_qps = BATCH * ROUNDS / (time.perf_counter() - t0)

    # --- transport-architecture path: per-shard query + coordinator reduce --
    def transport_search(q):
        results = []
        for si, (_e, _s, ctx) in enumerate(shards):
            r = execute_query_phase(ctx, parse_search_body(
                {"query": {"match": {"body": q}}, "size": K}), shard_id=si)
            r.shard_id = si
            results.append(r)
        return sort_docs(req, results)

    transport_search(queries[0])  # warm caches/compiles
    t0 = time.perf_counter()
    sub = queries[: max(8, BATCH // 8)]
    for q in sub:
        transport_search(q)
    transport_qps = len(sub) / (time.perf_counter() - t0)

    # ordering gate: mesh vs transport on a sample
    for qi in range(4):
        merged = transport_search(queries[qi])
        m_docs = [(int(out.shard[qi][j]), int(out.doc[qi][j]))
                  for j in range(K) if out.shard[qi][j] >= 0]
        t_docs = [(r[1], r[2]) for r in merged.hits[:len(m_docs)]]
        if m_docs[:10] != t_docs[:10]:
            print(json.dumps({"metric": "MESH ORDERING MISMATCH", "value": 0,
                              "unit": "error", "vs_baseline": 0}))
            sys.exit(1)

    platform = device["platform"]
    print(json.dumps({
        "metric": f"8-shard cross-shard top-{K} merge: SPMD mesh vs transport "
                  f"scatter-gather qps ({N_SHARDS}x{DOCS_PER_SHARD} docs, {platform})",
        "value": round(mesh_qps, 1),
        "unit": "queries/sec",
        "vs_baseline": round(mesh_qps / transport_qps, 2),
        **device,
    }))
    print(f"# mesh {mesh_qps:.0f} qps  transport {transport_qps:.0f} qps",
          file=sys.stderr)


if __name__ == "__main__":
    main()
