"""work.py and peaks.json give the arithmetic PERF.md states."""

import pytest

from benchmark import work


def test_v5e_peaks_and_unknown_part():
    p = work.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


def test_wide_cell_iteration_is_bandwidth_bound():
    nbytes, flops = work.sweep_iteration(12288, 262144, (1, 1), "float32")
    assert nbytes == 12288 * 262144 * 4  # one read of the 12 GiB matrix
    assert flops == 4 * 12288 * 262144
    least = work.least_seconds(nbytes, flops, work.peaks("TPU v5 lite"))
    assert least == pytest.approx(12 * 2**30 / 819e9)  # 15.7 ms


def test_tall_cell_charges_each_chip_its_row_block():
    nbytes, flops = work.sweep_iteration(196608, 65536, (4, 1), "float32")
    assert nbytes == 49152 * 65536 * 4
    assert flops == 4 * 49152 * 65536
    gbytes, gflops = work.guess(196608, 65536, (4, 1), "float32")
    assert gbytes == nbytes and gflops == flops // 2


def test_storage_type_sets_the_bytes():
    b16, _ = work.sweep_iteration(8, 256, (1, 1), "bfloat16")
    assert b16 == 8 * 256 * 2
