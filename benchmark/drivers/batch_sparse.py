"""The ``batch_sparse`` entry: the ``batch`` entry's backfill run (see
``batch.py``), with every tile row of the checked file judged against the
sparse-gap reference (``reference_sparse.SparseReference``) instead of the
dense one.

Every window of this entry's mix is sparse by the cohort rule, so one more
check holds the program to the configuration's guarantee that each window
took the sparse-model decode: Δ``reporter_sparse_points_total`` over the
window equals the points of the shard files finished.  A program without
that counter gets no such check.
"""

from __future__ import annotations

import os

import common
from reference_sparse import SparseReference

_batch = common.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "batch.py"), "batch_sparse_base")
# batch._check looks the reference class up in its module when it runs
_batch.Reference = SparseReference

SPARSE_POINTS = "reporter_sparse_points_total"


def run(ctx: dict) -> dict:
    rec = _batch.run(ctx)
    if SPARSE_POINTS in rec["snap1"]:
        got = (common.family_total(rec["snap1"], SPARSE_POINTS)
               - common.family_total(rec["snap0"], SPARSE_POINTS))
        rec["checks"]["sparse_points"] = {"value": got, "limit": rec["points"],
                                          "ok": got == rec["points"]}
    return rec
