"""Kernels: device time of every named program stage (rs.*) in the traced
stretch per thousand points the stretch matched."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr["stages_s"] or not rec.get("traced_points"):
        return None
    return sum(tr["stages_s"].values()) * 1e3 / (rec["traced_points"] / 1e3)
