"""Multi-shard: SearchPhaseController merge + DFS aggregation + mesh executor on a
virtual 8-device CPU mesh.

Parity chain: mesh program (host-summed DFS stats + all_gather top-k) must agree with the host
reference (per-shard search with DFS-global stats, merged by sort_docs) — the same
agreement the reference guarantees between DfsQueryThenFetch and its controller."""

import numpy as np
import pytest

from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index import Engine
from elasticsearch_tpu.mapper import MapperService
from elasticsearch_tpu.search import ShardContext, parse_query
from elasticsearch_tpu.search.controller import (
    aggregate_dfs,
    collect_dfs,
    merge_responses,
    sort_docs,
)
from elasticsearch_tpu.search.execute import lower_flat, search_shard
from elasticsearch_tpu.search.service import (
    ShardQueryResult,
    execute_query_phase,
    parse_search_body,
)
from elasticsearch_tpu.search.similarity import SimilarityService

VOCAB = ("alpha beta gamma delta epsilon zeta eta theta iota kappa lamda mu nu xi "
         "omicron pi rho sigma tau upsilon phi chi psi omega").split()

N_SHARDS = 4
DOCS_PER_SHARD = 30


def make_shards(tmp_path, similarity="BM25", n_shards=N_SHARDS):
    rng = np.random.default_rng(123)
    settings = Settings.from_flat({"index.similarity.default.type": similarity})
    shards = []
    for si in range(n_shards):
        svc = MapperService(settings)
        e = Engine(str(tmp_path / f"shard{si}"), svc)
        for i in range(DOCS_PER_SHARD):
            body = " ".join(rng.choice(VOCAB, size=rng.integers(5, 20)))
            e.index("doc", f"{si}-{i}", {"body": body, "shard": si})
            if i == 15:
                e.refresh()
        e.refresh()
        ctx = ShardContext(e.acquire_searcher(), svc,
                           SimilarityService(settings, mapper_service=svc))
        shards.append((e, svc, ctx))
    return shards


def host_reference_search(shards, query_dict, k, similarity="BM25"):
    """DFS phase (host) + per-shard query with global stats + controller merge."""
    q = parse_query(query_dict)
    dfs = [collect_dfs(ctx, q, shard_id=si) for si, (_, _, ctx) in enumerate(shards)]
    global_stats = aggregate_dfs(dfs)
    results = []
    for si, (e, svc, ctx) in enumerate(shards):
        gctx = ShardContext(ctx.searcher, svc, ctx.similarity_service, global_stats)
        td = search_shard(gctx, q, k, use_device=False)
        results.append(ShardQueryResult(
            total=td.total, docs=[(s, d, None) for s, d in td.hits],
            max_score=td.max_score, shard_id=si))
    req = parse_search_body({"query": query_dict, "size": k})
    return sort_docs(req, results), results


class TestController:
    def test_dfs_aggregation(self, tmp_path):
        shards = make_shards(tmp_path)
        q = parse_query({"match": {"body": "alpha beta"}})
        dfs = [collect_dfs(ctx, q, si) for si, (_, _, ctx) in enumerate(shards)]
        agg = aggregate_dfs(dfs)
        assert agg["max_doc"] == sum(ctx.searcher.max_doc for _, _, ctx in shards)
        total_df = sum(ctx.searcher.doc_freq("body", "alpha") for _, _, ctx in shards)
        assert agg["df"][("body", "alpha")] == total_df

    def test_global_idf_changes_scores(self, tmp_path):
        """Without DFS, per-shard idf differs; with global stats all shards agree."""
        shards = make_shards(tmp_path)
        merged, results = host_reference_search(shards, {"match": {"body": "alpha"}}, 10)
        # same analysed term must produce CONSISTENT scores across shards for docs
        # with identical (freq, dl): verified indirectly — merge is strictly ordered
        scores = [h[0] for h in merged.hits]
        assert scores == sorted(scores, reverse=True)
        assert merged.total == sum(r.total for r in results)

    def test_merge_tie_break_by_shard_then_doc(self):
        req = parse_search_body({"size": 4})
        r0 = ShardQueryResult(total=2, docs=[(1.0, 5, None), (0.5, 9, None)],
                              max_score=1.0, shard_id=1)
        r1 = ShardQueryResult(total=2, docs=[(1.0, 3, None), (0.5, 1, None)],
                              max_score=1.0, shard_id=0)
        merged = sort_docs(req, [r0, r1])
        assert [(h[1], h[2]) for h in merged.hits] == [(0, 3), (1, 5), (0, 1), (1, 9)]

    def test_field_sort_merge(self):
        req = parse_search_body({"size": 4, "sort": [{"price": "asc"}]})
        r0 = ShardQueryResult(total=2, docs=[(float("nan"), 1, [10.0]),
                                             (float("nan"), 2, [30.0])],
                              max_score=float("nan"), shard_id=0)
        r1 = ShardQueryResult(total=2, docs=[(float("nan"), 1, [5.0]),
                                             (float("nan"), 2, [20.0])],
                              max_score=float("nan"), shard_id=1)
        merged = sort_docs(req, [r0, r1])
        assert [h[3][0] for h in merged.hits] == [5.0, 10.0, 20.0, 30.0]

    def test_agg_reduce_across_shards(self, tmp_path):
        shards = make_shards(tmp_path)
        body = {"size": 0, "aggs": {"by_shard": {"terms": {"field": "shard"}},
                                    "n": {"value_count": {"field": "shard"}}}}
        req = parse_search_body(body)
        results = []
        for si, (_, _, ctx) in enumerate(shards):
            r = execute_query_phase(ctx, req, shard_id=si)
            results.append(r)
        merged = sort_docs(req, results)
        resp = merge_responses(req, merged, results, [], took_ms=1,
                               total_shards=len(shards), successful=len(shards))
        assert resp["aggregations"]["n"]["value"] == N_SHARDS * DOCS_PER_SHARD
        buckets = {b["key"]: b["doc_count"] for b in
                   resp["aggregations"]["by_shard"]["buckets"]}
        assert buckets == {si: DOCS_PER_SHARD for si in range(N_SHARDS)}


@pytest.mark.parametrize("similarity", ["BM25", "default"])
class TestMeshExecutor:
    def test_mesh_matches_host_reference(self, tmp_path, similarity):
        import jax
        from jax.sharding import Mesh

        shards = make_shards(tmp_path, similarity=similarity)
        devices = np.array(jax.devices()[: N_SHARDS])
        mesh = Mesh(devices, ("shards",))
        from elasticsearch_tpu.parallel import MeshSearchExecutor, build_sharded_index

        sidx = build_sharded_index([ctx.searcher for _, _, ctx in shards],
                                   fields=["body"], mesh=mesh)
        ex = MeshSearchExecutor(sidx, mesh, similarity=similarity)
        queries = [
            {"match": {"body": "alpha beta gamma"}},
            {"match": {"body": {"query": "delta epsilon", "operator": "and"}}},
            {"term": {"body": "omega"}},
            {"bool": {"must": [{"term": {"body": "pi"}}],
                      "must_not": [{"term": {"body": "rho"}}]}},
        ]
        ctx0 = shards[0][2]
        plans = [lower_flat(parse_query(qd), ctx0) for qd in queries]
        assert all(p is not None for p in plans)
        k = 10
        out = ex.search(plans, k)
        for qi, qd in enumerate(queries):
            merged, _ = host_reference_search(shards, qd, k, similarity)
            assert out.totals[qi] == merged.total, qd
            # compare (shard, local_doc) hit lists; scores within a few ulps
            mesh_hits = [(int(out.shard[qi, j]), int(out.doc[qi, j]))
                         for j in range(k) if out.shard[qi, j] >= 0]
            ref_hits = [(h[1], h[2]) for h in merged.hits]
            ref_scores = [h[0] for h in merged.hits]
            assert len(mesh_hits) == len(ref_hits), qd
            for mh, ms, rh, rs in zip(mesh_hits, out.scores[qi], ref_hits, ref_scores):
                assert ms == pytest.approx(rs, rel=3e-6), qd
                if mh != rh:
                    # only near-tie swaps permitted
                    assert any(abs(ms - s2) <= 3e-6 * abs(ms) for s2 in ref_scores
                               if s2 != rs) or ms == pytest.approx(rs, rel=3e-6), qd
