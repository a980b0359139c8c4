"""The trace reduction, on a small synthesised trace file."""
import pytest

from bench import trace as bt
from bench.tests.tiny import BENCH

MS = 1e6  # ns

PROBE = "%fused_probe_stream_pallas.{} = (s32[16]{{0}}) custom-call(s32[16]{{0}} %p)"

#: one device; the window is [10, 110] ms; the kernel runs twice
TRACE = bt.Trace(
    devices=[[
        ("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %a)", 0 * MS, 15 * MS),
        (PROBE.format(1), 20 * MS, 10 * MS),
        ("%jaccard_verify_pallas.6 = f32[8,5]{1,0} custom-call()",
         25 * MS, 20 * MS),                       # overlaps the kernel
        (PROBE.format(2), 60 * MS, 10 * MS),
        # names the kernel as an operand only: not the kernel's time
        ("%copy.2 = s32[16]{0} copy(s32[16]{0} %fused_probe_stream_pallas.2)",
         100 * MS, 20 * MS),                      # runs past the window
    ]],
    modules=[[
        ("jit_scan(123)", 0 * MS, 15 * MS),
        ("jit_fused_probe_stream_pallas(9)", 20 * MS, 10 * MS),
        ("jit_scan(456)", 25 * MS, 20 * MS),
        ("jit_fused_probe_stream_pallas(9)", 60 * MS, 10 * MS),
    ]],
    spans=[
        ("bench.window", 10 * MS, 100 * MS),
        ("bench.split", 12 * MS, 50 * MS),
        ("eejoin.serve.probe", 48 * MS, 10 * MS),
    ],
)


@pytest.fixture()
def trace(tmp_path):
    path = tmp_path / "trace.json"
    path.write_text(TRACE.to_json())
    return bt.Trace.from_json(path.read_text())


def test_busy_is_the_union_of_intervals_inside_the_window(trace):
    lo, hi = trace.window()
    assert (lo, hi) == (10 * MS, 110 * MS)
    # [10,15] + [20,45] + [60,70] + [100,110] = 5 + 25 + 10 + 10
    assert bt.busy_ns(trace, lo, hi) == pytest.approx(50 * MS)


def test_busy_is_averaged_over_devices(trace):
    two = bt.Trace(devices=trace.devices + [[("x", 10 * MS, 10 * MS)]],
                   spans=trace.spans)
    assert bt.busy_ns(two, 10 * MS, 110 * MS) == pytest.approx(30 * MS)


def test_kernel_time_by_name(trace):
    lo, hi = trace.window()
    assert bt.kernel_ns(trace, "fused_probe_stream_pallas", lo,
                        hi) == pytest.approx(20 * MS)
    assert bt.kernel_ns(trace, "jaccard_verify_pallas", lo,
                        hi) == pytest.approx(20 * MS)


def test_an_absent_kernel_fails_and_never_reads_zero(trace):
    lo, hi = trace.window()
    with pytest.raises(bt.KernelNotFound):
        bt.kernel_ns(trace, "_kernel_not_there", lo, hi)
    with pytest.raises(bt.KernelNotFound):  # a prefix of a name is not it
        bt.kernel_ns(trace, "fused_probe", lo, hi)
    with pytest.raises(bt.KernelNotFound):  # present, but outside the window
        bt.kernel_ns(trace, "fusion", 20 * MS, 60 * MS)


def test_no_device_plane_fails(trace):
    with pytest.raises(LookupError):
        bt.busy_ns(bt.Trace(devices=[], spans=trace.spans), 0, 1)


def test_breakdown_programs_and_idle_gaps_by_host_span(trace):
    lo, hi = trace.window()
    progs = bt.top_programs(trace, lo, hi)
    assert progs[0][0] == "jit_scan"  # both scan programs, 5 + 20 ms
    assert dict(progs) == pytest.approx(
        {"jit_scan": 0.025, "jit_fused_probe_stream_pallas": 0.020})
    gaps = dict(bt.idle_gaps(trace, lo, hi))
    # gaps [15,20] and [45,60] fall in bench.split; [70,100] in none
    # ([45,60]'s midpoint 52.5 is inside eejoin.serve.probe, the innermost)
    assert gaps == pytest.approx({"bench.split": 0.005,
                                  "eejoin.serve.probe": 0.015,
                                  "idle": 0.030})


def test_the_window_span_must_be_unique(trace):
    dup = bt.Trace(devices=trace.devices,
                   spans=trace.spans + [("bench.window", 0, 1)])
    with pytest.raises(LookupError):
        dup.window()


def test_peaks_table_has_the_v5e_and_refuses_unknown_chips():
    from bench import run

    assert run.peaks_for("TPU v5 lite", BENCH)["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        run.peaks_for("TPU v9 imaginary", BENCH)
