"""Kernels: the share of the window's HMM steps whose UBODT probe was
answered by the per-step compacted probe, Δ``reporter_ubodt_probe_steps_total``
{path="compact"} over Δ all its paths (compact, overflow, full).  Nothing
where the program has no such counter or ran no step."""

from common import family_total

NAME = "reporter_ubodt_probe_steps_total"


def read(rec):
    if NAME not in rec["snap1"]:
        return None
    s0, s1 = rec["snap0"], rec["snap1"]
    steps = family_total(s1, NAME) - family_total(s0, NAME)
    if steps <= 0:
        return None
    return (family_total(s1, NAME, ("compact",)) - family_total(s0, NAME, ("compact",))) / steps
