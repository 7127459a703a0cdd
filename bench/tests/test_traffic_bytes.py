"""The least bytes and operations of an edge call, against hand arithmetic."""
from bench import traffic_bytes as tb
from bench.spec import load_module

BANK = load_module("references", "sobel5").BANK


def test_sobel_hd_request_bytes():
    # One frame of 1920x1080: f32 in (4 B) + f32 magnitude out (4 B) per
    # pixel, plus one f32 peak.
    assert tb.frame_bytes(1, 1080, 1920, "float32") == 1080 * 1920 * 8 + 4
    assert tb.frame_bytes(1, 1080, 1920, "float32") == 16_588_804


def test_cam1080_frame_bytes():
    # u8 in (1 B) + f32 out (4 B) per pixel, one peak.
    assert tb.frame_bytes(1, 1080, 1920, "uint8") == 1080 * 1920 * 5 + 4


def test_bytes_do_not_depend_on_blocks():
    # The count takes no block shape: the same call is the same work.
    assert tb.frame_bytes(2, 100, 300, "float32") == 2 * 100 * 300 * 8 + 8


def test_edge_ops_count_the_nonzero_taps():
    # Each of the four 5x5 filters has 20 non-zero taps (a zero column or
    # a zero diagonal): 80 multiply-adds, then 8 for the magnitude.
    assert [int((k != 0).sum()) for k in BANK] == [20, 20, 20, 20]
    assert tb.edge_ops(1, 10, 10, BANK) == 100 * (2 * 80 + 8)


def test_roofline_is_bound_by_bytes_at_sobel_hd():
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    b = tb.frame_bytes(1, 1080, 1920, "float32")
    ops = tb.edge_ops(1, 1080, 1920, BANK)
    least, bound = tb.roofline_seconds(b, ops, peaks)
    assert bound == "hbm"
    assert abs(least - b / 819e9) < 1e-12
