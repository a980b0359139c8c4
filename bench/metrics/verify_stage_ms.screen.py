"""The verify stage: the median of ``ServingMetrics.verify_s`` (the
service's own timing of ``_verify_batch``, device work included) over the
window's batches, in ms."""
import statistics


def read(rec):
    xs = rec.get("verify_ms")
    return statistics.median(xs) if xs else None
