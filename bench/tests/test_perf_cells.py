"""Each cell's phases end to end at a tiny size on the CPU, through the
harness's own functions (its look for a chip skipped), and a cell that
exists only in a temporary directory."""
import json
import math
import time

import jax
import pytest

from bench import run
from bench import trace as bt
from bench.tests.tiny import write_tiny_root

CELLS = ["corpus-zipf16k.splits", "screen-watchlist16k.steady"]
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def fake_trace(_log_dir):
    """A device trace as a TPU would give it (the CPU has none): one
    streamed-kernel operation inside a one-second window."""
    ms = 1e6
    return bt.Trace(
        devices=[[("%fused_probe_stream_pallas.3 = (s32[16]{0}) custom-call()",
                   100 * ms, 50 * ms),
                  ("%fusion.7 = s32[8]{0} fusion()", 300 * ms, 200 * ms)]],
        modules=[[("jit_fused_probe_stream_pallas(1)", 100 * ms, 50 * ms),
                  ("jit_scan(2)", 300 * ms, 200 * ms)]],
        spans=[("bench.window", 0.0, 1000 * ms)])


@pytest.fixture()
def as_tpu(monkeypatch):
    """The fake trace and the v5e's peaks in place of the CPU's (none)."""
    monkeypatch.setattr(bt, "load", fake_trace)
    peaks = run.peaks_for
    monkeypatch.setattr(run, "peaks_for",
                        lambda kind, d: peaks("TPU v5 lite", d))


def run_tiny(root, cell_name, trace=False, seconds=1.0):
    cell = run.load_cell(root, cell_name)
    return cell, run.run_cell(cell, 2**31 + 77, seconds, trace,
                              jax.devices()[:1], time.monotonic())


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_end_to_end_with_its_last_line(tiny_root, name):
    cell, out = run_tiny(tiny_root, name)
    assert list(out)[:5] == KEYS and list(out)[-1] == "compared"
    json.dumps(out)  # one JSON line
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    want = {m["name"] for m in cell.end_to_end}
    assert set(out["metrics"]) == want and "setup_s" in want
    assert all(math.isfinite(m["value"]) and m["value"] > 0
               for m in out["metrics"].values())
    assert out["compared"]["set_diff"] == {"value": 0, "limit": 0}
    assert out["compared"]["score_gap"]["value"] < 1e-6
    assert out["compared"]["side_semantics_off"] == {"value": 0, "limit": 0}
    assert out["device"]["count"] == 1


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reads_every_per_layer_metric(tiny_root, name, as_tpu):
    cell, out = run_tiny(tiny_root, name, trace=True)
    want = {m["name"] for m in cell.per_layer}
    assert want and set(out["metrics"]) == want
    assert all(math.isfinite(m["value"]) for m in out["metrics"].values())
    assert out["device"]["busy_s"] == pytest.approx(0.25)
    assert out["device"]["window_s"] == pytest.approx(1.0)
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert out["correct"] is True


def test_a_missing_kernel_fails_the_traced_run(tiny_root, as_tpu,
                                               monkeypatch):
    def no_kernel(_log_dir):
        return bt.Trace(devices=[[("%fusion.7 = s32[8]{0} fusion()", 0.0,
                                   1e8)]],
                        spans=[("bench.window", 0.0, 1e9)])

    monkeypatch.setattr(bt, "load", no_kernel)
    with pytest.raises(LookupError, match="probe_us_per_doc"):
        run_tiny(tiny_root, "corpus-zipf16k.splits", trace=True)


def test_a_cell_defined_only_in_a_temporary_directory(tmp_path, as_tpu):
    """A new traffic mix, a new per-layer metric and a new cell, added as
    files and entries: no existing file of the harness changes."""
    root = write_tiny_root(tmp_path)
    bench = root / "bench"
    (bench / "traffic" / "closed2.json").write_text(json.dumps(
        {"loop": "closed", "why": "a second closed loop"}))
    (bench / "metrics" / "docs_traced.closed2.py").write_text(
        "def read(rec):\n    return rec['docs']\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append(
        {"name": "corpus-zipf16k.closed2", "config": "corpus-zipf16k",
         "traffic": "closed2", "chips": 1, "why": "a second closed loop"})
    spec["per_layer"].append(
        {"name": "docs_traced.closed2", "unit": "docs", "better": "higher",
         "source": "host_clock", "layer": "device", "moves": "docs_per_s",
         "workloads": ["corpus-zipf16k.closed2"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = run.load_cell(root, "corpus-zipf16k.closed2")
    assert cell.traffic["why"] == "a second closed loop"
    assert "docs_traced.closed2" in {m["name"] for m in cell.per_layer}
    _, out = run_tiny(root, "corpus-zipf16k.closed2", trace=True)
    assert out["correct"] is True
    assert out["metrics"]["docs_traced.closed2"]["value"] > 0
    # the shipped cell is untouched by the addition
    assert run.load_cell(root, "corpus-zipf16k.splits").per_layer == \
        run.load_cell(write_tiny_root(tmp_path / "fresh"),
                      "corpus-zipf16k.splits").per_layer
