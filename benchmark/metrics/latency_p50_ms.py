"""Median latency of every request of the window, from its due time (the
open-loop schedule) until its answer was read; a failed request (not 200,
degraded, past the 10 s timeout or never answered) reads above any limit."""

from common import percentile


def read(rec):
    lat = rec.get("latency_ms")
    return percentile(lat, 0.50) if lat else None
