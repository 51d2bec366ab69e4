"""95th percentile (nearest rank) of the time from a reconciliation's due
instant to its peer's result, over every reconciliation started in the
window (host clock).  A reconciliation that gave no result counts as beyond
any limit; when one lands on the percentile there is no number."""
import math


def read(run):
    lat = sorted(r.latency_ms for r in run.recons)
    p95 = lat[math.ceil(0.95 * len(lat)) - 1]
    return p95 if math.isfinite(p95) else None
