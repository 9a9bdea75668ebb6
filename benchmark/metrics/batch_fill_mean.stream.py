"""Micro-batcher: traces per dispatched micro-batch over the window, from the
reporter_microbatch_batch_fill histogram (sum / count)."""

from common import hist_delta


def read(rec):
    h = hist_delta(rec["snap0"], rec["snap1"], "reporter_microbatch_batch_fill")
    return h[2] / h[3] if h and h[3] > 0 else None
