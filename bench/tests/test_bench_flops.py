"""Operation and byte counts against hand counts at small shapes, and the
peak table."""
import pytest

from harness import flops, spec


def test_decode_attention_hand_count():
    # 2 query heads over 1 kv head of width 4; 5 cached positions in pages
    # of 4 -> 2 pages.  q.k and p.v: 2 * 4 flops each per head per position.
    f, b = flops.decode_attention(5, hq=2, hkv=1, hd=4, page_size=4,
                                  kv_bytes=2, act_bytes=2)
    assert f == 2 * 5 * (2 * 4 + 2 * 4)
    # k and v: 2 pages * 4 rows * 1 head * 4 dims * 2 bytes each; q and o:
    # 2 heads * 4 dims * 2 bytes each
    assert b == 2 * (2 * 4 * 1 * 4 * 2) + 2 * (2 * 4 * 2)


def test_matmul_flops_hand_count():
    s = {"d": 4, "hq": 2, "hkv": 1, "hd": 2, "ff": 8, "vocab": 10,
         "layers": 3}
    # q 4x4, k 4x2, v 4x2, o 4x4, gate/up 4x8, down 8x4, head 4x10
    per_layer = 16 + 8 + 8 + 16 + 32 + 32 + 32
    assert flops.matmul_flops_per_token(s) == 2 * (3 * per_layer + 40)
    assert flops.decode_token_flops(s, ctx=6) == \
        2 * (3 * per_layer + 40) + 3 * 4 * 2 * 2 * 6


def test_roofline_share_names_its_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_share(100.0, 5.0, 2.0, peaks) == (50.0, "flops")
    assert flops.roofline_share(10.0, 40.0, 8.0, peaks) == (50.0, "bytes")


def test_peaks_by_device_kind():
    p = spec.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")
