"""Per-path serving latency profile: times each device kernel variant end to end
through execute_query_phase on a real Engine-built corpus (Q=1, the latency
shape), plus the host mask path for comparison.

Run on TPU:  python tools/serving_profile.py   (exits non-zero off a TPU)
CPU:         JAX_PLATFORMS=cpu python tools/serving_profile.py   (labelled "cpu")
Env:         SERVING_PROFILE_DOCS=50000 (default 20000)
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from elasticsearch_tpu.common.jaxenv import require_accelerator  # noqa: E402

# off a TPU this exits non-zero unless the caller set JAX_PLATFORMS=cpu
platform = require_accelerator("serving_profile")["platform"]

import numpy as np  # noqa: E402

from elasticsearch_tpu.common.settings import Settings  # noqa: E402
from elasticsearch_tpu.index.engine import Engine  # noqa: E402
from elasticsearch_tpu.mapper.core import MapperService  # noqa: E402
from elasticsearch_tpu.search import ShardContext  # noqa: E402
from elasticsearch_tpu.search.service import (  # noqa: E402
    SERVING_COUNTERS,
    execute_query_phase,
    parse_search_body,
)
from elasticsearch_tpu.search.similarity import SimilarityService  # noqa: E402

N_DOCS = int(os.environ.get("SERVING_PROFILE_DOCS", 20_000))

SHAPES = {
    "sparse top-k": {"query": {"match": {"body": "w3 w17 w40 w99"}}, "size": 10},
    "filtered": {"query": {"filtered": {
        "query": {"match": {"body": "w3 w17"}},
        "filter": {"range": {"pop": {"gte": 200}}}}}, "size": 10},
    "function_score rows": {"query": {"function_score": {
        "query": {"match": {"body": "w3 w17"}},
        "field_value_factor": {"field": "pop", "modifier": "log1p",
                               "missing": 1}}}, "size": 10},
    "function_score script": {"query": {"function_score": {
        "query": {"match": {"body": "w3 w17"}},
        "script_score": {"script": "_score * log(2 + doc['pop'].value)"}}},
        "size": 10},
    "metric aggs": {"query": {"match": {"body": "w3 w17"}}, "size": 0,
                    "aggs": {"s": {"stats": {"field": "pop"}}}},
    "terms agg": {"query": {"match": {"body": "w3 w17"}}, "size": 0,
                  "aggs": {"t": {"terms": {"field": "pop", "size": 50}}}},
    "terms + sub-avg": {"query": {"match": {"body": "w3 w17"}}, "size": 0,
                        "aggs": {"t": {"terms": {"field": "pop", "size": 50},
                                       "aggs": {"a": {"avg": {"field": "pop"}}}}}},
    "field sort": {"query": {"match": {"body": "w3 w17"}},
                   "sort": [{"pop": "asc"}], "size": 10},
}


def main():
    import tempfile

    svc = MapperService(Settings.from_flat({}))
    eng = Engine(tempfile.mkdtemp(prefix="serving_profile_"), svc)
    rng = np.random.default_rng(5)
    vocab = [f"w{i}" for i in range(2000)]
    t0 = time.time()
    for i in range(N_DOCS):
        eng.index("doc", str(i), {
            "body": " ".join(rng.choice(vocab, size=40)),
            "pop": int(rng.integers(1, 1000))})
    eng.refresh()
    print(f"# indexed {N_DOCS} docs in {time.time()-t0:.1f}s on {platform}",
          file=sys.stderr)
    ctx = ShardContext(eng.acquire_searcher(), svc,
                       SimilarityService(Settings.from_flat({}),
                                         mapper_service=svc))
    host_count_before = None
    for name, body in SHAPES.items():
        req = parse_search_body(body)
        host_count_before = SERVING_COUNTERS["host"]
        execute_query_phase(ctx, req, use_device=True)  # warm compile
        assert SERVING_COUNTERS["host"] == host_count_before, \
            f"{name} fell back to the host path"
        n = 30
        t0 = time.perf_counter()
        for _ in range(n):
            execute_query_phase(ctx, req, use_device=True)
        dev_ms = (time.perf_counter() - t0) / n * 1000
        t0 = time.perf_counter()
        for _ in range(10):
            execute_query_phase(ctx, req, use_device=False)
        host_ms = (time.perf_counter() - t0) / 10 * 1000
        print(f"{name:24s} device {dev_ms:8.2f} ms   host {host_ms:8.2f} ms   "
              f"({host_ms/dev_ms:5.2f}x)")
    eng.close()


if __name__ == "__main__":
    main()
