"""The streamed probe megakernel's share of its bytes roofline, in %.

The least time is the bytes any implementation must read, the window's
documents as int32 tokens once per plan side (D x T x 4 x sides), over
the chip's HBM bandwidth; the share is that over the kernel's device
time. It is a bound by bytes only: the kernel's compute has no op count
independent of the implementation yet."""
from bench.trace import KernelNotFound, kernel_ns

KERNEL = "fused_probe_stream_pallas"


def read(rec):
    t = rec["trace"]
    if t is None:
        return None
    try:
        ns = kernel_ns(t["trace"], KERNEL, t["lo"], t["hi"])
    except KernelNotFound:
        return None
    nbytes = rec["docs"] * rec["doc_len"] * 4 * rec["sides"]
    least_ns = nbytes / t["peaks"]["hbm_bytes_per_s"] * 1e9
    return 100.0 * least_ns / ns
