"""Matcher host: the share of the points matched in the window that went
through the sparse-gap programs, Δ``reporter_sparse_points_total`` over the
points of the shard files finished.  Nothing where the program has no such
counter."""

from common import family_total

NAME = "reporter_sparse_points_total"


def read(rec):
    if rec["points"] <= 0 or NAME not in rec["snap1"]:
        return None
    return (family_total(rec["snap1"], NAME) - family_total(rec["snap0"], NAME)) / rec["points"]
