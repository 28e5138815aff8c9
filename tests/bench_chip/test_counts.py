"""The benchmark's FLOP and byte counts, at hand-checked shapes."""

import pytest

from harness import counts


def test_train_step_at_imagenet_1m_widths():
    # 1,000 pairs x (forward z L^T + weight gradient) x 21,504 x 1,000
    assert counts.train_step_flops(1000, 21504, 1000) == 86_016_000_000
    # both sides of 1,000 pairs, read L, write L: 4 x 21,504 x 1,000 f32
    assert counts.train_step_bytes(1000, 21504, 1000) == 344_064_000


def test_train_step_at_imagenet_63k_widths():
    assert counts.train_step_flops(100, 21504, 10000) == 86_016_000_000
    # 2 x 100 x 21,504 + 2 x 10,000 x 21,504 f32: L dominates
    assert counts.train_step_bytes(100, 21504, 10000) == 1_737_523_200


def test_exact_scan_over_a_million_rows():
    assert counts.topk_scan_flops(64, 1_000_000, 1000) == 128_000_000_000
    # gallery 4 GB + norms 4 MB + 64 projected queries
    assert counts.topk_scan_bytes(64, 1_000_000, 1000) == 4_004_256_000
    assert counts.query_flops(21504, 1000, 1_000_000) == (
        43_008_000 + 2_000_000_000)


@pytest.mark.parametrize("flops,nbytes,bound", [
    (86_016_000_000, 344_064_000, "flops"),     # imnet1m: 437 us vs 420 us
    (86_016_000_000, 1_737_523_200, "bytes"),   # imnet63k: 437 us vs 2.1 ms
    (128_000_000_000, 4_004_256_000, "bytes"),  # the 1M-row scan
])
def test_least_seconds_names_its_bound(flops, nbytes, bound):
    t, which = counts.least_seconds(flops, nbytes, 197e12, 819e9)
    assert which == bound
    assert t == pytest.approx(max(flops / 197e12, nbytes / 819e9))
