"""How late the load generator submitted: the 95th percentile (nearest
rank) of submit time minus due time over the window's requests, in ms."""
import math


def read(rec):
    xs = sorted(rec.get("lag_ms") or [])
    return xs[max(0, math.ceil(0.95 * len(xs)) - 1)] if xs else None
