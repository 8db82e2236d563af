"""A fault for the server, put on the child's PYTHONPATH by test_run.py: every BM25
weight comes out one part in a thousand too large, as a weight held in too few bits
would. The served scores then differ from the reference's past the limit."""

import elasticsearch_tpu.search.similarity as similarity

_idf = similarity.BM25Similarity.idf


def _coarse_idf(df, max_docs):
    return _idf(df, max_docs) * 1.001


similarity.BM25Similarity.idf = staticmethod(_coarse_idf)
