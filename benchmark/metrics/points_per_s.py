"""GPS points of the requests answered in time (stream) or of the shard files
matched (backfill), over the whole window."""


def read(rec):
    return rec["points"] / rec["window_s"] if rec["window_s"] > 0 else None
