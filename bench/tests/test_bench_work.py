"""Peaks table and the logical work of one leaf-kernel call."""
import pytest

from bench import readers, work


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        work.peaks("TPU v0 imaginary")


def test_v5e_row_has_its_source():
    p = work.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]


def test_leaf_work_by_hand():
    # 64 rows x 256 bf16 candidates of d=3, k'=16:
    #   candidates 64*256*3*2 = 98,304; ids 64*256*4 = 65,536;
    #   queries 64*3*4 = 768; radii 64*4 = 256; out 64*16*12 = 12,288
    w = work.leaf_topk_l2(64, 256, 3, 2, 16)
    assert w["hbm_bytes"] == 98304 + 65536 + 768 + 256 + 12288
    assert w["flops"] == 3 * 64 * 256 * 3
    t, bound = work.least_seconds(w, work.peaks("TPU v5 lite"))
    assert bound == "hbm_bytes" and t == pytest.approx(177152 / 819e9)
    # an int8 call with its per-candidate scale row
    s = work.leaf_topk_l2(8, 128, 3, 1, 8, scaled=True)
    assert s["hbm_bytes"] == 8 * 128 * 3 + 8 * 128 * 4 * 2 + 8 * 3 * 4 + 8 * 4 + 8 * 8 * 12
    assert s["flops"] == 4 * 8 * 128 * 3


def test_call_work_reads_logical_shapes_not_padding():
    # results (R, k') x3, then q (R, 128), candidates (R, C, 128), ids, radii
    call = {"seconds": 1e-5, "shapes": [
        ("f32", [64, 16]), ("s32", [64, 16]), ("s32", [64, 16]),
        ("f32", [64, 128]), ("bf16", [64, 256, 128]), ("s32", [64, 256]),
        ("f32", [64, 1])]}
    assert readers.call_work(call, 3) == work.leaf_topk_l2(64, 256, 3, 2, 16)
    assert readers.call_work({"seconds": 1.0, "shapes": []}, 3) is None
