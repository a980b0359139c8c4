"""Device time of the streamed probe megakernel per document, in us:
the summed durations of its operations in the traced window over the
documents of the splits the window completed."""
from bench.trace import KernelNotFound, kernel_ns

#: the trace names the streamed probe kernel's operation after its
#: jitted wrapper (``%fused_probe_stream_pallas.1 = ... custom-call``)
KERNEL = "fused_probe_stream_pallas"


def read(rec):
    t = rec["trace"]
    if t is None:
        return None
    try:
        ns = kernel_ns(t["trace"], KERNEL, t["lo"], t["hi"])
    except KernelNotFound:
        return None
    return ns / 1e3 / rec["docs"]
