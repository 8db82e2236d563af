"""Product-path benchmarks: BASELINE.md configs #1 and #2 through the REAL stack.

Unlike bench.py (which packs the device layout directly to time the serving kernel),
this indexes documents through MapperService analysis + Engine segment building, then
serves queries through execute_flat_batch — the exact path a REST _search takes on one
shard. Numbers land in BASELINE.md's measurement table.

  config #1: single-shard `match`, default TF-IDF, top-10, 100k-doc synthetic-enwiki
  config #2: BM25 via index similarity settings, 1k batched 4-term bool, top-100

CPU reference = the framework's vectorized numpy host scorer (search_shard
use_device=False), a stronger baseline than Lucene's per-doc scoring loops.
Correctness gate: device and host must produce identical hit ordering per query.

Run: python tools/bench_product.py          (needs a TPU; exits non-zero off one)
     JAX_PLATFORMS=cpu BENCH_PRODUCT_DOCS=20000 python tools/bench_product.py
     (a CPU run the caller asked for: labelled "cpu", never a device measurement)
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_DOCS = int(os.environ.get("BENCH_PRODUCT_DOCS", 100_000))
VOCAB = 50_000
AVG_LEN = 60
CACHE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     ".bench_cache")


def _words(n):
    """Pronounceable pseudo-words so the analysis chain does real tokenization."""
    cons = "bcdfghjklmnprstvwz"
    vow = "aeiou"
    out = []
    i = 0
    while len(out) < n:
        w = ""
        x = i
        for _ in range(3):
            w += cons[x % len(cons)] + vow[(x // len(cons)) % len(vow)]
            x //= len(cons) * len(vow)
        out.append(w + str(i % 10))
        i += 1
    return out


def build_index(path, similarity):
    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.index.engine import Engine
    from elasticsearch_tpu.mapper.core import MapperService

    settings = Settings.from_flat({"index.similarity.default.type": similarity})
    svc = MapperService(settings)
    eng = Engine(path, svc)
    meta_path = os.path.join(path, "bench_meta.json")
    if os.path.exists(meta_path):
        with open(meta_path) as f:
            meta = json.load(f)
        if meta == {"docs": N_DOCS, "vocab": VOCAB, "sim": similarity, "v": 2}:
            eng.recover_from_store()
            eng.refresh()
            return eng, svc, None
        shutil.rmtree(path)
        os.makedirs(path)
        eng = Engine(path, svc)

    rng = np.random.default_rng(1234)
    vocab = _words(VOCAB)
    lengths = np.clip(rng.poisson(AVG_LEN, N_DOCS), 5, 400)
    raw = rng.zipf(1.35, int(lengths.sum())).astype(np.int64) - 1
    term_of_tok = raw % VOCAB
    t0 = time.time()
    pos = 0
    for i in range(N_DOCS):
        n = int(lengths[i])
        body = " ".join(vocab[t] for t in term_of_tok[pos: pos + n])
        pos += n
        # pop: deterministic numeric column for config #4's script_score
        eng.index("doc", str(i), {"body": body, "pop": (i * 13) % 1000 + 1})
        if (i + 1) % 20_000 == 0:
            eng.refresh()
            print(f"# indexed {i+1}/{N_DOCS} ({(i+1)/(time.time()-t0):.0f} docs/s)",
                  file=sys.stderr)
    eng.refresh()
    eng.flush()
    with open(meta_path, "w") as f:
        json.dump({"docs": N_DOCS, "vocab": VOCAB, "sim": similarity, "v": 2}, f)
    ix_rate = N_DOCS / (time.time() - t0)
    return eng, svc, ix_rate


def pick_terms(ctx, rng, n_queries, terms_per_query):
    """Mid-frequency terms, like bench.py's pool (skip stopword-like heads)."""
    seg_terms: dict[str, int] = {}
    for seg in ctx.searcher.segments:
        for t in seg.term_dict.get("body", ()):
            seg_terms[t] = seg_terms.get(t, 0) + seg.doc_freq("body", t)
    ranked = sorted(seg_terms, key=lambda t: -seg_terms[t])
    pool = ranked[50:5000]
    return [list(rng.choice(pool, size=terms_per_query, replace=False))
            for _ in range(n_queries)]


def _ordering_gate(name, ctx, qdicts, k, tie_rel=0.0):
    """Device and host must produce identical hit ordering; with tie_rel > 0,
    adjacent swaps are forgiven when the scores are within that relative gap
    (f32 in-kernel script evaluation vs the host's f64-then-cast can flip exact
    near-ties — config #4 only)."""
    from elasticsearch_tpu.search import parse_query
    from elasticsearch_tpu.search.execute import search_shard

    for qd in qdicts:
        dev = search_shard(ctx, parse_query(qd), k, use_device=True)
        host = search_shard(ctx, parse_query(qd), k, use_device=False)
        d_ids = [d for _, d in dev.hits]
        h_ids = [d for _, d in host.hits]
        ok = d_ids == h_ids and dev.total == host.total
        if not ok and tie_rel > 0 and dev.total == host.total \
                and sorted(d_ids) == sorted(h_ids):
            pos = {d: i for i, d in enumerate(h_ids)}
            hs = {d: s for s, d in host.hits}
            ok = all(
                abs(pos[d] - i) <= 1
                and abs(hs[d] - s) <= tie_rel * max(abs(s), 1e-9)
                for i, (s, d) in enumerate(dev.hits))
        if not ok:
            print(json.dumps({"metric": f"{name} ORDERING MISMATCH", "value": 0,
                              "unit": "error", "vs_baseline": 0}))
            sys.exit(1)


def run_config(name, eng, svc, settings_sim, queries, k, batch, wrap=None,
               tie_rel=0.0):
    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.search import ShardContext, parse_query
    from elasticsearch_tpu.search.execute import execute_flat_batch, lower_flat, search_shard
    from elasticsearch_tpu.search.similarity import SimilarityService

    settings = Settings.from_flat({"index.similarity.default.type": settings_sim})
    ctx = ShardContext(eng.acquire_searcher(), svc,
                       SimilarityService(settings, mapper_service=svc))
    qdicts = [{"match": {"body": " ".join(terms)}} for terms in queries]
    if wrap is not None:
        qdicts = [wrap(qd) for qd in qdicts]
    plans = [lower_flat(parse_query(qd), ctx) for qd in qdicts]
    assert all(p is not None for p in plans), "bench queries must lower flat"

    # correctness gate: identical ordering device vs host on a sample
    _ordering_gate(name, ctx, qdicts[:8], k, tie_rel=tie_rel)

    # device timing: batched through the serving planner (one warmup for compiles)
    execute_flat_batch(plans[:batch], ctx, k)
    t0 = time.perf_counter()
    done = 0
    while done < len(plans):
        execute_flat_batch(plans[done: done + batch], ctx, k)
        done += batch
    device_qps = len(plans) / (time.perf_counter() - t0)

    # host baseline on a subset
    sub = min(64, len(plans))
    t0 = time.perf_counter()
    for qd in qdicts[:sub]:
        search_shard(ctx, parse_query(qd), k, use_device=False)
    cpu_qps = sub / (time.perf_counter() - t0)
    return device_qps, cpu_qps


def run_fused_paths(eng, svc, queries, platform):
    """Supplementary rows: the fused request-feature kernels (aggs / sort)
    through execute_query_phase, device vs the host mask path — per-query
    serving (Q=1), the latency shape these paths exist for."""
    import time

    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.search import ShardContext
    from elasticsearch_tpu.search.aggregations import reduce_aggs
    from elasticsearch_tpu.search.service import execute_query_phase, parse_search_body
    from elasticsearch_tpu.search.similarity import SimilarityService

    settings = Settings.from_flat({"index.similarity.default.type": "BM25"})
    ctx = ShardContext(eng.acquire_searcher(), svc,
                       SimilarityService(settings, mapper_service=svc))
    shapes = {
        "aggs (stats+terms)": lambda terms: {
            "query": {"match": {"body": " ".join(terms)}}, "size": 0,
            "aggs": {"s": {"stats": {"field": "pop"}},
                     "t": {"terms": {"field": "pop", "size": 50}}}},
        "sort (field asc)": lambda terms: {
            "query": {"match": {"body": " ".join(terms)}},
            "sort": [{"pop": "asc"}], "size": 10},
    }
    out = []
    for name, mk in shapes.items():
        reqs = [parse_search_body(mk(t)) for t in queries[:256]]
        # correctness gate on a sample: totals + docs + reduced aggs must agree
        def deep_close(a, b):
            if isinstance(a, dict) and isinstance(b, dict):
                return set(a) == set(b) and all(deep_close(a[x], b[x]) for x in a)
            if isinstance(a, list) and isinstance(b, list):
                return len(a) == len(b) and all(
                    deep_close(x, y) for x, y in zip(a, b))
            if isinstance(a, float) and isinstance(b, float):
                return a == b or abs(a - b) <= 1e-5 * max(abs(b), 1.0)
            return a == b

        for req in reqs[:5]:
            dev = execute_query_phase(ctx, req, use_device=True)
            host = execute_query_phase(ctx, req, use_device=False)
            assert dev.total == host.total
            assert [d for _s, d, _v in dev.docs] == [d for _s, d, _v in host.docs]
            if req.aggs:
                assert deep_close(reduce_aggs(req.aggs, dev.agg_partials),
                                  reduce_aggs(req.aggs, host.agg_partials))
        execute_query_phase(ctx, reqs[0], use_device=True)  # warm compile
        t0 = time.perf_counter()
        for req in reqs:
            execute_query_phase(ctx, req, use_device=True)
        dev_qps = len(reqs) / (time.perf_counter() - t0)
        t0 = time.perf_counter()
        for req in reqs[:64]:
            execute_query_phase(ctx, req, use_device=False)
        host_qps = 64 / (time.perf_counter() - t0)
        line = {"metric": f"fused {name} per-query qps ({platform})",
                "value": round(dev_qps, 1), "unit": "queries/sec",
                "vs_baseline": round(dev_qps / host_qps, 2)}
        out.append(line)
        print(json.dumps(line))
        print(f"# fused {name}: device {dev_qps:.0f} qps  host {host_qps:.0f} qps",
              file=sys.stderr)
    return out


def main():
    from elasticsearch_tpu.common.jaxenv import (
        enable_persistent_compile_cache, require_accelerator)

    device = require_accelerator("bench_product")
    platform = device["platform"]
    global N_DOCS
    if platform == "cpu":
        N_DOCS = min(N_DOCS, 20_000)
    enable_persistent_compile_cache()  # placed by jaxenv's one rule

    def wrap_script(qd):
        # config #4 (BASELINE.md): BM25 sub query + _score-reading script_score —
        # the script compiles to XLA and runs inside the dense kernel
        return {"function_score": {"query": qd,
                                   "script_score": {
                                       "script": "_score * log(2 + doc['pop'].value)"}}}

    rng = np.random.default_rng(99)
    results = []
    for (cfg, sim, tpq, k, n_q, batch, wrap, tie_rel) in (
        ("config#1 match top-10 TFIDF", "default", 2, 10, 512, 128, None, 0.0),
        ("config#2 bool top-100 BM25", "BM25", 4, 100, 1024, 1024, None, 0.0),
        ("config#4 function_score script BM25", "BM25", 3, 100, 512, 256,
         wrap_script, 1e-5),
    ):
        path = os.path.join(CACHE, f"product_idx_{sim}_{N_DOCS}")
        os.makedirs(path, exist_ok=True)
        eng, svc, ix_rate = build_index(path, sim)
        if ix_rate:
            print(f"# indexed at {ix_rate:.0f} docs/s through Engine+analysis",
                  file=sys.stderr)
        queries = pick_terms(
            __import__("elasticsearch_tpu.search", fromlist=["ShardContext"])
            .ShardContext(eng.acquire_searcher(), svc), rng, n_q, tpq)
        dev, cpu = run_config(cfg, eng, svc, sim, queries, k, batch, wrap=wrap,
                              tie_rel=tie_rel)
        line = {"metric": f"{cfg} product-path qps ({N_DOCS} docs, {platform})",
                "value": round(dev, 1), "unit": "queries/sec",
                "vs_baseline": round(dev / cpu, 2), **device}
        results.append(line)
        print(json.dumps(line))
        print(f"# {cfg}: device {dev:.0f} qps  host {cpu:.0f} qps", file=sys.stderr)
        if cfg.startswith("config#2"):
            results.extend(run_fused_paths(eng, svc, queries, platform))
        eng.close()
    return results


if __name__ == "__main__":
    main()
