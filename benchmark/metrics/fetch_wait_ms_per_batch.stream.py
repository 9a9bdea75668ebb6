"""Device: the host's blocking wait for a micro-batch's device result and its
copy to the host, per dispatched micro-batch, from
reporter_host_stage_seconds_total{fetch} over reporter_microbatch_batches_total.
Nothing where the program counts no such stage."""

from common import family_total

NAME = "reporter_host_stage_seconds_total"
BATCHES = "reporter_microbatch_batches_total"
STAGES = ("fetch",)


def read(rec):
    fam = rec["snap1"].get(NAME) or {}
    if not set(STAGES) <= {lv[0] for lv, _v in fam.get("samples", []) if lv}:
        return None
    n = family_total(rec["snap1"], BATCHES) - family_total(rec["snap0"], BATCHES)
    if n <= 0:
        return None
    d = family_total(rec["snap1"], NAME, STAGES) - family_total(rec["snap0"], NAME, STAGES)
    return d * 1e3 / n
