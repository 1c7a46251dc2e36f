"""Median request latency, due time to output ready, over every request
due in the window; a failed or refused request counts as the time the run
stopped waiting for it, beyond every served one."""
import math

from bench.harness import percentile


def read(rec):
    lat = rec.get("latencies_s")
    if not lat:
        return None
    v = percentile(lat, 0.5)
    return 1e3 * (v if math.isfinite(v) else rec["give_up_s"])
