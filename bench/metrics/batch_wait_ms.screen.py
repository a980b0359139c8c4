"""Time in the batcher: the median of ``flush_s - arrival_s`` (the
service's own stamps) over the window's completed requests, in ms."""
import statistics


def read(rec):
    xs = rec.get("batch_wait_ms")
    return statistics.median(xs) if xs else None
