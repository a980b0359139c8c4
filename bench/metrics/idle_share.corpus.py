"""The device's idle share of the traced window, in %: 1 - busy / window,
busy being the union of the device's operation intervals."""
from bench.trace import busy_ns


def read(rec):
    t = rec["trace"]
    if t is None:
        return None
    return 100.0 * (1.0 - busy_ns(t["trace"], t["lo"], t["hi"])
                    / (t["hi"] - t["lo"]))
