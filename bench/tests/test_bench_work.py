"""The roofline's work count and the table of peaks."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench.work import least_seconds, peaks_for, topk_work  # noqa: E402


def test_topk_work_at_registry_scale():
    flops, nbytes = topk_work(64, 10**6, 384, 5)
    assert flops == 2 * 64 * 10**6 * 384 == 49_152_000_000
    # the table once, the queries once, K float32 scores and K int32 ids a row
    assert nbytes == 10**6 * 384 * 4 + 64 * 384 * 4 + 64 * 5 * 8 == 1_536_100_864


def test_least_time_is_the_hbm_bound_at_registry_scale():
    peak = peaks_for("TPU v5 lite")
    flops, nbytes = topk_work(64, 10**6, 384, 5)
    t = least_seconds(flops, nbytes, peak)
    assert t == pytest.approx(nbytes / 819e9)
    assert t > flops / 197e12  # 1.88 ms of reading against 0.25 ms of arithmetic
    assert t == pytest.approx(1.8756e-3, rel=1e-4)


def test_padding_rows_are_not_work():
    assert topk_work(3, 2413, 384, 5)[0] == 2 * 3 * 2413 * 384


def test_v5e_peaks_carry_their_source():
    peak = peaks_for("TPU v5 lite")
    assert peak["flops_per_s"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in peak["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5e", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError):
        peaks_for(kind)
