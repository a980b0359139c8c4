"""The probe stage: the median of ``ServingMetrics.probe_s`` (the
service's own timing of ``_probe_batch``, device work included) over the
window's batches, in ms."""
import statistics


def read(rec):
    xs = rec.get("probe_ms")
    return statistics.median(xs) if xs else None
