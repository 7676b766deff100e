"""Work counts (the OLS deployment's) and the peak table."""
import json

import pytest

import bench_tiny  # noqa: F401  (puts the benchmark on sys.path)
import harness
import work

ols = harness.load_deployment("ols")


def test_counts_follow_the_shapes():
    assert ols.assoc_ops(8192, 23000, 2048) == 2 * 8192 * 23000 * 2048
    # packed genotypes once, the float32 panel once per marker-batch sweep
    assert ols.assoc_bytes(8192, 23000, 2048) == 8192 * 5750 + 4 * 23000 * 2048
    assert ols.assoc_bytes(4, 5, 1) == 4 * 2 + 4 * 5


@pytest.mark.parametrize("traits, bound", [(2048, "compute"), (20480, "compute")])
def test_least_time_at_the_int8_peak(traits, bound):
    peak = work.peaks("TPU v5 lite")
    least, which = ols.least_seconds(8192, 23000, traits, peak)
    assert which == bound
    assert least == pytest.approx(2 * 8192 * 23000 * traits / 393e12)


def test_memory_bound_when_work_per_byte_is_low():
    least, which = ols.least_seconds(8192, 23000, 1, work.peaks("TPU v5 lite"))
    assert which == "memory"
    assert least == pytest.approx((8192 * 5750 + 4 * 23000) / 819e9)


def test_peaks_carry_their_source_and_published_values():
    with open(work.PEAKS) as f:
        table = json.load(f)
    assert "TPU v5e" in table["source"]
    v5e = table["devices"]["TPU v5 lite"]
    assert (v5e["bf16_flops_per_s"], v5e["int8_ops_per_s"], v5e["hbm_bytes_per_s"]) == (
        197e12, 393e12, 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        work.peaks("TPU v9 imaginary")
