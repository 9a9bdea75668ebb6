"""Batch pipeline: phase-2 host work around the matcher (shard read and window
split, report(), tile-row fan-out and writes) per million points matched, from
reporter_host_stage_seconds_total{read,report,tile}.  Nothing where the
program counts no such stages."""

from common import family_total

NAME = "reporter_host_stage_seconds_total"
STAGES = ("read", "report", "tile")


def read(rec):
    fam = rec["snap1"].get(NAME) or {}
    if rec["points"] <= 0 or not set(STAGES) <= {lv[0] for lv, _v in fam.get("samples", []) if lv}:
        return None
    d = family_total(rec["snap1"], NAME, STAGES) - family_total(rec["snap0"], NAME, STAGES)
    return d / (rec["points"] / 1e6)
