"""The yardstick's byte counts and the table of peaks (CPU only)."""
import pytest

from chipbench.tests import small  # noqa: F401  (puts src on sys.path)
from chipbench.work import byz_trim, peaks, pushsum_edge, social_innov


def test_pushsum_edge_bytes_by_hand():
    # N=4 agents, E=6 edges, d=3 (D=4 columns), f32: per edge src+dst
    # (8) + rho in and out (2*4*4=32) = 40; per node sigma read (16) +
    # receive sum written (16) = 32
    assert pushsum_edge.bytes_per_round(4, 6, 3) == 6 * 40 + 4 * 32
    assert pushsum_edge.bytes_per_round(4, 6, 3, K=3) == 3 * (6 * 40 + 4 * 32)
    # bfloat16 storage halves the state, not the f32 receive sums
    assert pushsum_edge.bytes_per_round(4, 6, 3, storage_bytes=2) == \
        6 * (8 + 16) + 4 * (8 + 16)


def test_social_innov_bytes_by_hand():
    # m=3, S=4, f32: z in and out (24) + mass (4) + cdf (16) + log table
    # (48) + belief written (12) = 104 per agent
    assert social_innov.bytes_per_round(5, 3, 4) == 5 * 104
    assert social_innov.bytes_per_round(5, 3, 4, K=2, storage_bytes=2) == \
        2 * 5 * (12 + 2 + 16 + 48 + 12)


def test_byz_trim_bytes_by_hand():
    # deg=7, P=9, f32: slots 7*(4+1+1)=42 + r read (36) + survivor sum
    # (36) + kept (4) = 118 per receiver
    assert byz_trim.bytes_per_round(8, 7, 9) == 8 * 118
    assert byz_trim.bytes_per_round(8, 7, 9, K=32) == 32 * 8 * 118


def test_peaks_of_v5e():
    p = peaks.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes"] == 16e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "", "tpu v5 lite"])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError):
        peaks.peaks(kind)
