"""Host time of the candidate window gather per document, in us: the
summed ``eejoin.windows.gather`` spans (the numpy row and window gather
of ``engine.candidates_from_flat_host``) inside the traced window over
the documents of the splits the window completed. A program that opens
no such span reads None."""

SPAN = "eejoin.windows.gather"


def read(rec):
    t = rec["trace"]
    if t is None:
        return None
    lo, hi = t["lo"], t["hi"]
    parts = [min(s + d, hi) - max(s, lo)
             for n, s, d in t["trace"].spans if n == SPAN]
    parts = [p for p in parts if p > 0]
    return sum(parts) / 1e3 / rec["docs"] if parts else None
