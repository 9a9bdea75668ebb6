"""Micro-batcher: 95th percentile of the submit-to-batch wait over the window,
at the resolution of reporter_microbatch_queue_wait_seconds's buckets."""

import math

from common import hist_delta, hist_quantile


def read(rec):
    h = hist_delta(rec["snap0"], rec["snap1"], "reporter_microbatch_queue_wait_seconds")
    if not h:
        return None
    q = hist_quantile(h[0], h[1], 0.95)
    return None if q is None or math.isinf(q) else q * 1e3
