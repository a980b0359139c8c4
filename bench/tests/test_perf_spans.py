"""The readers of the program's ``eejoin.*`` host spans, on hand-built
traces: a span is summed only where it lies inside the window, and a
trace without the span reads None, never 0."""
import pytest

from bench import trace as bt
from bench.run import load_reader
from bench.tests.tiny import BENCH

MS = 1e6  # ns

READERS = [("window_gather_us_per_doc.corpus", "eejoin.windows.gather"),
           ("verify_host_us_per_doc.corpus", "eejoin.verify")]


def _record(spans, docs=100):
    tr = bt.Trace(devices=[[("%fusion.1 = x", 10 * MS, 1 * MS)]],
                  spans=[("bench.window", 10 * MS, 100 * MS)] + spans)
    lo, hi = tr.window()
    return {"trace": {"trace": tr, "lo": lo, "hi": hi, "peaks": {}},
            "docs": docs}


@pytest.mark.parametrize("metric,span", READERS)
def test_span_reader_sums_the_spans_inside_the_window(metric, span):
    read = load_reader(BENCH, metric)
    rec = _record([
        ("bench.split", 12 * MS, 90 * MS),
        (span, 20 * MS, 4 * MS),
        (span, 50 * MS, 6 * MS),
        (span, 105 * MS, 10 * MS),   # half past the window's end
        (span, 200 * MS, 5 * MS),    # after the window
        ("eejoin.other", 30 * MS, 7 * MS),
    ])
    # 4 + 6 + 5 ms over 100 documents
    assert read(rec) == pytest.approx(15 * MS / 1e3 / 100)


@pytest.mark.parametrize("metric,span", READERS)
def test_span_reader_reads_none_without_the_span(metric, span):
    read = load_reader(BENCH, metric)
    # the program opens other spans, or the span only outside the window
    assert read(_record([("bench.split", 12 * MS, 90 * MS)])) is None
    assert read(_record([(span, 200 * MS, 5 * MS)])) is None
    assert read({"trace": None, "docs": 100}) is None
