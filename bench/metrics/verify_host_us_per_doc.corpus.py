"""Host time of the verify calls per document, in us: the summed
``eejoin.verify`` spans (each plan side's ``side_matches``: the scan's
host tracing, its program's cache load and its dispatch) inside the
traced window over the documents of the splits the window completed. A
program that opens no such span reads None."""

SPAN = "eejoin.verify"


def read(rec):
    t = rec["trace"]
    if t is None:
        return None
    lo, hi = t["lo"], t["hi"]
    parts = [min(s + d, hi) - max(s, lo)
             for n, s, d in t["trace"].spans if n == SPAN]
    parts = [p for p in parts if p > 0]
    return sum(parts) / 1e3 / rec["docs"] if parts else None
