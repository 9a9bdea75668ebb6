"""Matcher host: batch packing + device enqueue host seconds per million points
matched, from reporter_host_stage_seconds_total{pack,dispatch}.  ``collect``
is left out: it includes the wait for the device."""

from common import family_total

NAME = "reporter_host_stage_seconds_total"


def read(rec):
    if rec["points"] <= 0 or NAME not in rec["snap1"]:
        return None
    stages = ("pack", "dispatch")
    d = family_total(rec["snap1"], NAME, stages) - family_total(rec["snap0"], NAME, stages)
    return d / (rec["points"] / 1e6)
