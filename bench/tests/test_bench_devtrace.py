"""The trace reduction: busy union, idle share, collective share, gaps."""
import json
from pathlib import Path

import pytest

import tinycell  # noqa: F401  (puts bench/ on the path)

import devtrace

RECORDED = Path(__file__).with_name("data") / "trace_r110_small.json"


def test_union_merges_overlaps():
    assert devtrace.union([[5, 7], [0, 2], [1, 3], [7, 8]]) == [[0, 3], [5, 8]]


def test_hand_made_trace():
    # window 0..100 ns; device 0 busy 10..30 (two overlapping ops), 50..60
    # (an all-reduce) and 90..120 (cut at the window's end); device 1 idle
    # but for 0..50
    tr = {"window": [0, 100],
          "devices": {"0": [["fusion.1", 10, 15], ["fusion.2", 20, 10],
                            ["all-reduce.3", 50, 10], ["fusion.1", 90, 30]],
                      "1": [["all-gather.1", 0, 50]]},
          "host": [["round", 0, 100], ["PjitFunction(run)", 30, 25],
                   ["python", 60, 30]]}
    red = devtrace.reduce(tr)
    d0, d1 = red["devices"]["0"], red["devices"]["1"]
    assert d0["busy_s"] == pytest.approx(40e-9)
    assert d0["idle_share"] == pytest.approx(0.6)
    assert d0["collective_s"] == pytest.approx(10e-9)
    assert d1["idle_share"] == pytest.approx(0.5)
    assert d1["collective_s"] == pytest.approx(50e-9)
    assert red["window_s"] == pytest.approx(100e-9)
    # ops by time on device 0: fusion.1 15 + 10 (clipped), then the others
    assert red["device_ops"][0] == ["fusion.1", pytest.approx(25e-9)]
    # gaps on device 0: 60..90, 0..10, 30..50; each named by the host span
    # that overlaps it most, inside the round span
    assert [g[1] for g in red["idle_gaps"]] == pytest.approx([30e-9, 20e-9, 10e-9])
    assert [g[0] for g in red["idle_gaps"]] == [
        "round: python", "round: PjitFunction(run)", "round: no host span"]


def test_no_window_or_no_device_reads_nothing():
    assert devtrace.reduce({"window": None, "devices": {"0": []}, "host": []}) == {}
    assert devtrace.reduce({"window": [0, 1], "devices": {}, "host": []}) == {}


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_trace():
    """A slice of a chip trace (one v5e, r110.cohort.iid4): the reduction
    agrees with a plain count over the same events."""
    tr = json.loads(RECORDED.read_text())
    red = devtrace.reduce(tr)
    lo, hi = tr["window"]
    ev = sorted((max(s, lo), min(s + d, hi)) for _, s, d in tr["devices"]["0"]
                if min(s + d, hi) > max(s, lo))
    busy, end = 0, lo
    for s, e in ev:
        busy += max(0, e - max(s, end))
        end = max(end, e)
    assert red["devices"]["0"]["busy_s"] == pytest.approx(busy * 1e-9)
    assert 0.0 < red["devices"]["0"]["idle_share"] < 1.0
    assert red["devices"]["0"]["collective_s"] == 0.0
    assert sum(g[1] for g in red["idle_gaps"]) <= (hi - lo - busy) * 1e-9 * (1 + 1e-9)
