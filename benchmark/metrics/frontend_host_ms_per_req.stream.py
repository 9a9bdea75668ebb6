"""HTTP front end: request decode + response encode host time per answered
request, from reporter_host_stage_seconds_total{parse,serialize}."""

from common import family_total

NAME = "reporter_host_stage_seconds_total"


def read(rec):
    n = rec["attempted"] - rec["failed"]
    if n <= 0 or NAME not in rec["snap1"]:
        return None
    stages = ("parse", "serialize")
    d = family_total(rec["snap1"], NAME, stages) - family_total(rec["snap0"], NAME, stages)
    return d * 1e3 / n
