"""Operation and byte counts at the served shapes, checked by hand."""

import pytest

from bench.lib import cells


def test_gram_at_train_bucket_1024():
    # x1 = x2 = (1024, 128): D padded from 30 features to the 128 lanes
    flops, nbytes = cells.roofline("gram").cost(
        [(1024, 128), (1024, 128), (1, 1)], (1024, 1024))
    # 2*1024*1024*128 = 268,435,456; norms 2*2048*128 = 524,288;
    # elementwise 10*1024*1024 = 10,485,760
    assert flops == 268435456 + 524288 + 10485760
    # (1024*128*2 + 1 + 1024*1024) * 4 bytes
    assert nbytes == (262144 + 1 + 1048576) * 4


def test_gram_pool_cross_term():
    flops, nbytes = cells.roofline("gram").cost(
        [(1024, 128), (2560, 128), (1, 1)], (1024, 2560))
    assert flops == 2 * 1024 * 2560 * 128 + 2 * 3584 * 128 + 10 * 1024 * 2560
    assert nbytes == 4 * (1024 * 128 + 2560 * 128 + 1 + 1024 * 2560)


def test_tri_solve_inducing_by_pool():
    flops, nbytes = cells.roofline("tri_solve").cost(
        [(256, 256), (256, 2560)], (256, 2560))
    assert flops == 256 * 256 * 2560          # 167,772,160
    assert nbytes == 4 * (65536 + 2 * 655360)


def test_cholupdate_inducing_factor():
    flops, nbytes = cells.roofline("cholupdate").cost(
        [(256, 256), (1, 256)], (256, 256))
    assert flops == 3 * 65536
    assert nbytes == 4 * (2 * 65536 + 256)    # 525,312 bytes


def test_least_time_of_cholupdate_is_memory_bound():
    peaks = cells.peaks("TPU v5 lite")
    flops, nbytes = cells.roofline("cholupdate").cost(
        [(256, 256), (1, 256)], (256, 256))
    t = max(flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    assert t == pytest.approx(525312 / 819e9)   # about 0.64 us


def test_peaks_table_has_its_source_and_refuses_unknown_devices():
    table = cells.load_json(cells.BENCH / "peaks.json")
    assert "TPU v5e" in table["source"]
    assert cells.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    assert cells.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        cells.peaks("cpu")
