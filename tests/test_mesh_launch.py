"""The mesh launch crosses the host-device boundary once each way (PR 30): one
packed operand plane down, the norm cache resident beside the index, one pull up.

Every test here runs under the conftest's transfer_guard("disallow") (the module
is in `_SANITIZED_MODULES`), on the virtual CPU devices the conftest provides.
The eleven-operand launch the parent made is kept HERE alone, as the yardstick
the packed launch must equal bit for bit: `_mesh_score_program` is the program
both run."""

import numpy as np
import pytest

from elasticsearch_tpu.parallel.mesh_search import (
    MeshSearchExecutor,
    _mesh_score_program,
    _pack_plane,
    _unpack_plane,
    build_sharded_index,
    ensure_mesh_agg_stack,
)
from elasticsearch_tpu.search import parse_query
from elasticsearch_tpu.search.execute import lower_flat

from .test_multishard import N_SHARDS, make_shards

pytestmark = pytest.mark.mesh


def _bits(a):
    a = np.ascontiguousarray(a)
    return a.view(np.int32) if a.dtype == np.float32 else a


def _same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == b.shape and a.dtype == b.dtype, (a.shape, b.shape)
    np.testing.assert_array_equal(_bits(a), _bits(b))


# ---------------------------------------------------------------------------
# (a) the plane round trip
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("S,M,C,W,Qp", [(4, 16, 3, 4, 1), (2, 64, 11, 9, 4)])
def test_plane_round_trip_is_bit_exact(S, M, C, W, Qp):
    import jax

    rng = np.random.default_rng(M)
    entries = [rng.integers(0, 1 << 30, (S, M)).astype(np.int32) for _ in range(6)]
    # floats whose bits a rounding or a value-preserving copy would lose:
    # subnormals, -0.0, infinities, a NaN with a payload
    odd = np.array([1e-45, -0.0, np.inf, -np.inf, 1.0000001, 3.4e38],
                   np.float32)
    weight_c = rng.standard_normal((S, C)).astype(np.float32)
    weight_c.reshape(-1)[: min(odd.size, weight_c.size)] = odd[: weight_c.size]
    coord = rng.random((Qp, W)).astype(np.float32)
    coord.view(np.int32)[0, 0] = 0x7FC00123  # NaN payload
    n_must = rng.integers(0, 9, Qp).astype(np.int32)
    msm = rng.integers(0, 9, Qp).astype(np.int32)
    fields = (*entries, weight_c, n_must, msm, coord)

    plane = _pack_plane(*fields)
    assert plane.dtype == np.int32
    assert plane.shape == (S, 6 * M + C + Qp * (2 + W))
    back = jax.device_get(jax.jit(
        lambda p: _unpack_plane(p, M, Qp, W))(jax.device_put(plane)))
    assert len(back) == len(fields)
    for sent, got in zip(fields, back):
        _same_bits(sent, got)


# ---------------------------------------------------------------------------
# (b) the packed launch against the parent's eleven-operand launch
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def executor(tmp_path_factory):
    import jax
    from jax.sharding import Mesh

    shards = make_shards(tmp_path_factory.mktemp("mesh_launch"))
    mesh = Mesh(np.array(jax.devices()[:N_SHARDS]), ("shards",))
    sidx = build_sharded_index([ctx.searcher for _, _, ctx in shards],
                               fields=["body"], mesh=mesh)
    return MeshSearchExecutor(sidx, mesh, similarity="BM25"), shards


def _plans(shards, queries):
    plans = [lower_flat(parse_query(q), shards[0][2]) for q in queries]
    assert all(p is not None for p in plans)
    return plans


def _eleven_operand_launch(ex, plans, k, *, filter_masks=None, agg_rows=None,
                           sort_keys=None, sort_desc=False):
    """What MeshSearchExecutor.search did before the plane: every operand of
    _assemble its own device_put with its own sharding, the norm cache sent
    down with them, the program's outputs as they come."""
    import jax
    from jax import shard_map
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    idx = ex.index
    operands = ex._assemble(plans)
    Qp = operands[7].shape[0]
    sh, rep = P("shards"), P()
    raw = [idx.blk_docs, idx.blk_tf, idx.norms, idx.live, *operands[:7],
           ex._norm_caches(), *operands[7:]]
    specs = [sh] * 12 + [rep] * 3
    for extra in (filter_masks, agg_rows, sort_keys):
        if extra is not None:
            raw.append(extra)
            specs.append(sh)
    program = _mesh_score_program(
        k, Qp, idx.doc_pad, ex.similarity_kind,
        use_filter=filter_masks is not None, use_stack=agg_rows is not None,
        use_aggs=agg_rows is not None, use_sort=sort_keys is not None,
        sort_desc=sort_desc)
    n_out = 4 + (sort_keys is not None) + 2 * (agg_rows is not None)
    fn = jax.jit(shard_map(program, mesh=ex.mesh, in_specs=tuple(specs),
                           out_specs=(rep,) * n_out, check_vma=False))
    args = [jax.device_put(a, NamedSharding(ex.mesh, s))
            for a, s in zip(raw, specs)]
    return [o[0] for o in jax.device_get(fn(*args))]


ONE = [{"match": {"body": "alpha beta gamma"}}]
SEVERAL = [{"match": {"body": "alpha beta gamma"}},
           {"match": {"body": {"query": "delta epsilon", "operator": "and"}}},
           {"bool": {"must": [{"term": {"body": "pi"}}],
                     "must_not": [{"term": {"body": "rho"}}]}}]


def _variant(kind, ex, n_queries):
    """The variant's own operands, as mesh_serving hands them to search()."""
    idx = ex.index
    rng = np.random.default_rng(5)
    if kind == "filtered":
        return {"filter_masks": rng.random(
            (idx.n_shards, n_queries, idx.doc_pad)) < 0.6}
    if kind == "sorted":
        return {"sort_keys": rng.permutation(
            idx.n_shards * idx.doc_pad).reshape(
                idx.n_shards, idx.doc_pad).astype(np.float32),
            "sort_desc": True}
    if kind == "aggregated":
        return {"agg_rows": ensure_mesh_agg_stack(idx, ("shard",))}
    return {}


@pytest.mark.parametrize("queries", [ONE, SEVERAL], ids=["one", "several"])
@pytest.mark.parametrize("kind", ["plain", "filtered", "sorted", "aggregated"])
def test_packed_launch_equals_the_eleven_operand_launch(executor, kind, queries):
    ex, shards = executor
    plans = _plans(shards, queries)
    Q, k = len(plans), 8
    Qp = ex._assemble(plans)[7].shape[0]
    variant = _variant(kind, ex, Qp)
    assert kind != "aggregated" or variant["agg_rows"] is not None
    old = _eleven_operand_launch(ex, plans, k, **variant)
    new = ex.search(plans, k, **variant)

    top_scores, top_ids, shard_totals, qmax = old[:4]
    _same_bits(new.scores, top_scores[:Q])
    _same_bits(new.shard_totals, shard_totals[:, :Q])
    _same_bits(new.qmax, qmax[:, :Q])
    assert new.totals.tolist() == shard_totals[:, :Q].sum(axis=0).tolist()
    assert (new.totals > 0).any()
    rest = old[4:]
    rank_ok = np.isfinite(rest[0][:Q] if kind == "sorted" else top_scores[:Q])
    hit = (top_ids[:Q] >= 0) & rank_ok
    assert hit.any()
    np.testing.assert_array_equal(new.shard >= 0, hit)
    np.testing.assert_array_equal(
        (new.shard * ex.index.doc_pad + new.doc)[hit], top_ids[:Q][hit])
    if kind == "sorted":
        _same_bits(new.sort_keys, rest.pop(0)[:Q])
    if kind == "aggregated":
        _same_bits(new.agg_counts, rest.pop(0)[:, :Q])
        _same_bits(new.agg_stats, rest.pop(0)[:, :Q])
        assert new.agg_counts.sum() > 0
    assert not rest


# ---------------------------------------------------------------------------
# (c) one put down, one pull up; the constants stay where they are
# ---------------------------------------------------------------------------


def test_warmed_plain_search_puts_one_array_and_pulls_once(executor, monkeypatch):
    import jax

    from elasticsearch_tpu.common.jaxenv import sanitize
    from elasticsearch_tpu.ops.scoring import LAUNCHES

    ex, shards = executor
    plans = _plans(shards, ONE)
    ex.search(plans, 8)  # first sighting compiles
    # the norm cache is on the mesh, sharded a shard a chip like the index
    assert isinstance(ex._norm_cache, jax.Array)
    assert ex._norm_cache.sharding == ex.index.norms.sharding
    _same_bits(jax.device_get(ex._norm_cache), ex._norm_caches())

    calls = {"put": [], "get": 0}
    real_put, real_get = jax.device_put, jax.device_get

    def counting_put(x, *a, **kw):
        calls["put"].append(jax.tree_util.tree_leaves(x))
        return real_put(x, *a, **kw)

    def counting_get(x):
        calls["get"] += 1
        return real_get(x)

    monkeypatch.setattr(jax, "device_put", counting_put)
    monkeypatch.setattr(jax, "device_get", counting_get)
    before = LAUNCHES.snapshot()["operand_puts"]
    with sanitize(max_compiles=0, transfers="disallow"):
        out = ex.search(plans, 8)
    assert LAUNCHES.snapshot()["operand_puts"] - before == 1
    assert calls["get"] == 1
    # one call, one leaf, and that leaf a host array: nothing resident is put again
    assert len(calls["put"]) == 1 and len(calls["put"][0]) == 1
    assert isinstance(calls["put"][0][0], np.ndarray)
    assert out.totals[0] > 0


def test_a_variant_s_operands_ride_the_same_put(executor, monkeypatch):
    import jax

    from elasticsearch_tpu.ops.scoring import LAUNCHES

    ex, shards = executor
    plans = _plans(shards, ONE)
    variant = {**_variant("filtered", ex, 1), **_variant("sorted", ex, 1),
               **_variant("aggregated", ex, 1)}
    puts = []
    real_put = jax.device_put
    monkeypatch.setattr(jax, "device_put", lambda x, *a, **kw: (
        puts.append(x), real_put(x, *a, **kw))[1])
    before = LAUNCHES.snapshot()["operand_puts"]
    ex.search(plans, 8, **variant)
    # plane + filter masks + sort keys are host arrays; the agg stack is resident
    assert LAUNCHES.snapshot()["operand_puts"] - before == 3
    assert len(puts) == 1


# ---------------------------------------------------------------------------
# (d) the dispatch clock under batcher.dispatch
# ---------------------------------------------------------------------------


def _find(node, name):
    out = [node] if node["name"] == name else []
    for c in node["children"]:
        out.extend(_find(c, name))
    return out


def test_sampled_mesh_search_splits_its_dispatch(tmp_path):
    from elasticsearch_tpu.node import Node
    from elasticsearch_tpu.rest.controller import RestRequest, build_rest_controller
    from elasticsearch_tpu.transport.local import LocalTransportRegistry

    n = Node(name="mesh_clock", registry=LocalTransportRegistry(),
             data_path=str(tmp_path))
    n.start([n.local_node.transport_address])
    try:
        n.wait_for_master()
        client = n.client()
        client.create_index("library", {"settings": {
            "number_of_shards": N_SHARDS, "number_of_replicas": 0}})
        client.cluster_health(wait_for_status="green")
        for i in range(40):
            client.index("library", "doc", {"body": f"alpha beta w{i % 7}"},
                         id=str(i))
        client.refresh("library")
        rc = build_rest_controller(n)
        ms = n.actions.mesh_serving

        def search():
            before = ms.mesh_queries
            resp = rc.dispatch(RestRequest(
                method="POST", path="/library/_search",
                params={"trace": "true"},
                body={"query": {"match": {"body": "alpha w3"}}, "size": 5}))
            assert resp.status == 200, resp.body
            assert ms.mesh_queries == before + 1
            return resp.body["trace"]["tree"]

        search()  # first sighting compiles
        tree = search()
        (dispatch,) = _find(tree, "batcher.dispatch")
        assert dispatch["tags"]["family"] == "mesh"
        kids = dispatch["children"]
        assert [c["name"] for c in kids] == [
            "dispatch.stage", "dispatch.launch", "device_pull"]
        # gap-free: each interval starts where the one before it ended, the
        # first where the dispatch clock started, all inside the dispatch
        for a, b in zip(kids, kids[1:]):
            assert a["t1"] == b["t0"]
        assert kids[0]["t0"] >= dispatch["t0"] - 1e-6
        assert kids[-1]["t1"] <= dispatch["t1"] + 1e-6
        assert "compiled" not in kids[1].get("tags", {})
        (merge,) = _find(tree, "batcher.merge")
        assert merge["children"] == []
        # the pull is booked to the drainer's pull state, not to its dispatch
        drainer = n.search_batcher.stats()["drainer"]
        assert drainer["pull_s"] > 0
    finally:
        n.close()
