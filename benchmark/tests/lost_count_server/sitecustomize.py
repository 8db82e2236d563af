"""A fault for the server, put on the child's PYTHONPATH by test_run.py: where the
device's bucket counts become an aggregation's partial, the fullest bucket loses one
document, as a scatter that dropped an update would. Hits, scores and totals stay
sound; only a comparison that reads the buckets can see it."""

import numpy as np

import elasticsearch_tpu.search.aggregations as aggregations

_partial = aggregations.device_bucket_partial


def _lossy_partial(agg, keys, counts, **more):
    counts = np.array(counts)
    if counts.size and counts.max() > 0:
        counts[int(counts.argmax())] -= 1
    return _partial(agg, keys, counts, **more)


aggregations.device_bucket_partial = _lossy_partial
