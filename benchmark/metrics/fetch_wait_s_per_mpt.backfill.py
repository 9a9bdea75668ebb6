"""Device: the host's blocking wait for micro-batch device results and their
copy to the host, per million points matched, from
reporter_host_stage_seconds_total{fetch}.  Nothing where the program counts no
such stage."""

from common import family_total

NAME = "reporter_host_stage_seconds_total"
STAGES = ("fetch",)


def read(rec):
    fam = rec["snap1"].get(NAME) or {}
    if rec["points"] <= 0 or not set(STAGES) <= {lv[0] for lv, _v in fam.get("samples", []) if lv}:
        return None
    d = family_total(rec["snap1"], NAME, STAGES) - family_total(rec["snap0"], NAME, STAGES)
    return d / (rec["points"] / 1e6)
