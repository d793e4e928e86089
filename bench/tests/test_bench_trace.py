"""The trace reduction gives known answers on a synthetic event list."""
import pytest

from bench import tracereduce as tr

# device operations (name, start_ns, end_ns) on one chip
OPS = [
    ("interp_quant", 100, 200),
    ("interp_quant.3", 150, 260),       # overlaps the first
    ("copy.1", 400, 450),
    ("interp_recon", 900, 1000),
    ("interp_quantize_other", 1200, 1300),
    ("before", 0, 50),                   # outside the window
]
SPANS = [("window", 80, 1250), ("compress", 90, 500), ("read", 600, 1250)]


def test_busy_union_merges_overlaps_and_clips_to_the_window():
    assert tr.busy_intervals(OPS, 80, 1250) == [
        (100, 260), (400, 450), (900, 1000), (1200, 1250)]
    assert tr.busy_ns(OPS, 80, 1250) == 160 + 50 + 100 + 50


def test_kernel_time_by_name_takes_numeric_suffixes_only():
    assert tr.kernel_ns(OPS, ["interp_quant"]) == 100 + 110
    assert tr.kernel_ns(OPS, ["interp_recon"]) == 100
    assert tr.kernel_ns(OPS, ["interp_quant", "interp_recon"]) == 310
    assert tr.kernel_ns(OPS, ["decode_fused"]) == 0


def test_gaps_are_labelled_by_the_innermost_span():
    g = tr.gaps(OPS, 80, 1250)
    assert g == [(80, 100), (260, 400), (450, 900), (1000, 1200)]
    top = tr.top_gaps(OPS, SPANS, 80, 1250, k=3)
    assert top == [["read", 450e-9], ["read", 200e-9],
                   ["compress", 140e-9]]
    assert tr.label(1240, SPANS) == "read"
    assert tr.label(550, SPANS) == "window"
    assert tr.label(2000, SPANS) == "outside"


def test_top_ops_sum_by_name():
    top = tr.top_ops(tr.clip(OPS, 80, 1250), k=2)
    assert top == [["interp_quant", 210e-9], ["interp_recon", 100e-9]]


def test_op_names_come_from_the_hlo_instruction():
    hlo = ('%interp_quant.1 = (s32[6,62976,8]{2,1,0:T(8,128)}, f32[6,62976'
           ',8]) custom-call(f32[6,62976,11] %select_maximum_fusion.1), '
           'custom_call_target="tpu_custom_call"')
    assert tr.op_name(hlo) == "interp_quant.1"
    assert tr.kernel_ns([(tr.op_name(hlo), 0, 5)], ["interp_quant"]) == 5
    assert tr.op_name("%copy.2 = f32[6,8000,500] copy(%x.1)") == "copy.2"
    assert tr.op_name("fusion") == "fusion"


def test_summary_averages_kernel_time_over_chips():
    s = tr.Summary(ops={0: OPS, 1: [("interp_quant", 0, 30)]}, spans=SPANS,
                   lo=80, hi=1250, busy_s=360e-9, window_s=1170e-9,
                   top_ops=[], top_gaps=[])
    assert s.kernel_s(["interp_quant"]) == pytest.approx((210 + 30) / 2e9)
    assert s.idle_share() == pytest.approx(1 - 360 / 1170)
