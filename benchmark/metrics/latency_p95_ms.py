"""95th percentile of the latency of every request of the window, from its
due time until its answer was read; a failed request reads above any limit
(see latency_p50_ms)."""

from common import percentile


def read(rec):
    lat = rec.get("latency_ms")
    return percentile(lat, 0.95) if lat else None
