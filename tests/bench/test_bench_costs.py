"""Logical operation and byte counts per kernel call, and the peaks."""
import pytest

from bench.lib import costs, trace
from bench.lib.peaks import PEAKS, peaks_for

V5E = PEAKS["TPU v5 lite"]


def test_fupdate_counts_the_unpadded_work():
    c = costs.fupdate(65_536, 30, 16)
    assert c.flops == 2 * 65_536 * 16 * 30 + 2 * 65_536 * 16
    # X and the selected block at 30 columns, delta, f in and out
    assert c.bytes == (65_536 * 30 + 16 * 30) * 4 + 16 * 4 + 2 * 65_536 * 4
    assert c.bound(V5E) == "memory"
    assert c.least_s(V5E) == pytest.approx(c.bytes / 819e9)


def test_decision_counts_live_rows_against_the_support_set():
    c = costs.decision(64, 284_807, 30)
    assert c.flops == 2 * 64 * 284_807 * 30 + 2 * 64 * 284_807
    assert c.bytes == (64 * 30 + 284_807 * 30) * 4 + 284_807 * 4 + 64 * 4
    assert c.bound(V5E) == "memory"
    big = costs.decision(4096, 10_000, 768)
    assert big.bound(V5E) == "compute"
    assert big.least_s(V5E) == pytest.approx(big.flops / 197e12)


def test_counts_do_not_depend_on_padding_or_tiles():
    # one row more is a row's work more, never a tile's
    a, b = costs.decision(64, 1000, 30), costs.decision(65, 1000, 30)
    assert b.flops - a.flops == 2 * 1000 * 30 + 2 * 1000
    assert costs.fupdate(1000, 30, 16).bytes < costs.fupdate(1000, 31, 16).bytes


def test_fupdate_calls_count_each_launch_at_its_own_rows():
    full = ((65_536, 1), (128, 1), (128, 1), (65_536, 1), (65_536, 512),
            (128, 512))
    bucket = ((4096, 1), (128, 1), (128, 1), (4096, 1), (4096, 512),
              (128, 512))
    padded = ((1024, 1), (128, 1), (128, 1), (1024, 1), (1024, 512),
              (128, 512))
    r = trace.Reduced(window_s=1.0, busy_s=1.0, kernel_s={},
                      kernel_calls={}, device_ops=[], idle_gaps=[],
                      kernel_shapes={"fupdate": {full: 5, bucket: 3}})
    assert sorted(costs.fupdate_calls(r, 65_536)) == [(4096, 3), (65_536, 5)]
    # rows padded past the cell's m are not work
    r.kernel_shapes["fupdate"] = {padded: 2}
    assert list(costs.fupdate_calls(r, 1000)) == [(1000, 2)]
    r.kernel_shapes = {}
    assert list(costs.fupdate_calls(r, 1000)) == []


def test_peaks_are_keyed_by_device_kind_and_unknown_kinds_fail():
    p = peaks_for("TPU v5 lite")
    assert (p.flops_per_s, p.hbm_bytes_per_s) == (197e12, 819e9)
    assert "TPU v5e" in p.source
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("cpu")
