"""The peaks table and the required work of the fold kernel, on shapes
whose answers are known by hand."""
import pytest

from bench import peaks, run

# the name a v5e trace gives one fold kernel call (operand text shortened)
FOLD_EVENT = (
    "%vmap_jit_fold_count_max_pallas__.26 = (s32[1,1024]{1,0:T(1,128)S(1)},"
    " s32[5,1024]{1,0:T(8,128)S(1)}) custom-call(s32[239872,1]{1,0:T(8,128)}"
    " %copy.203, s32[239872,1]{1,0:T(8,128)} %copy.204, s32[239872,5]"
    "{1,0:T(8,128)} %bitcast-convert_bitcast_fusion.2), custom_call_target="
    "\"tpu_custom_call\"")


def test_fold_work_counts_entries_and_table_once():
    # 256 entries of 5 key words: slot id, amount and 5 words read (7 words
    # each); a 1024-slot table of count + 5 words read and written
    ops, bytes_ = peaks.fold_count_max_work(batch=256, capacity=1024,
                                            width=5)
    assert ops == 256 * 6
    assert bytes_ == 4 * 256 * 7 + 2 * 4 * 1024 * 6


def test_roofline_share_takes_the_binding_bound():
    kind = "TPU v5 lite"
    # 819 GB in one second is the whole bandwidth
    assert peaks.roofline_share(0, 819e9, 1.0, kind) == pytest.approx(100)
    # 197 TOP in two seconds is half the compute peak
    assert peaks.roofline_share(197e12, 0, 2.0, kind) == pytest.approx(50)
    assert peaks.roofline_share(197e12, 819e9 / 4, 4.0, kind) == \
        pytest.approx(25)


def test_a_device_not_in_the_table_is_an_error():
    with pytest.raises(KeyError, match="no peak rates"):
        peaks.peaks("TPU v99")


def test_fold_shapes_are_read_from_the_trace_event():
    mod = run.reader("fold_count_max_roofline.survey").__globals__
    assert mod["shapes"](FOLD_EVENT) == dict(batch=239872, capacity=1024,
                                             width=5)
    with pytest.raises(ValueError):
        mod["shapes"]("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)")
