"""Matcher host: host segment association of fetched results per answered
request, from reporter_host_stage_seconds_total{associate}.  Nothing where the
program counts no such stage."""

from common import family_total

NAME = "reporter_host_stage_seconds_total"
STAGES = ("associate",)


def read(rec):
    n = rec["attempted"] - rec["failed"]
    fam = rec["snap1"].get(NAME) or {}
    if n <= 0 or not set(STAGES) <= {lv[0] for lv, _v in fam.get("samples", []) if lv}:
        return None
    d = family_total(rec["snap1"], NAME, STAGES) - family_total(rec["snap0"], NAME, STAGES)
    return d * 1e3 / n
