"""The trace reduction, on a small trace recorded on one TPU v5e by
``tools/record_test_trace.py``: four small jitted calls, each followed by
a 2 ms host pause, between benchmark spans."""
from pathlib import Path

import pytest

from yardstick import trace as T

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return T.reduce(str(DATA), "bench.window")


def test_busy_within_window(reduced):
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    # four 2 ms pauses with the device idle, at least
    assert reduced["window_s"] - reduced["busy_s"] > 0.008


def test_top_device_ops(reduced):
    ops = reduced["device_ops"]
    assert 0 < len(ops) <= T.TOP
    secs = [s for _, s in ops]
    assert secs == sorted(secs, reverse=True)
    assert sum(secs) <= reduced["busy_s"] * (1 + 1e-9) + 1e-12


def test_idle_gaps_named_by_host_span(reduced):
    gaps = reduced["idle_gaps"]
    assert 0 < len(gaps) <= T.TOP
    assert [s for _, s in gaps] == sorted((s for _, s in gaps),
                                         reverse=True)
    # the longest gaps are the host's pauses
    assert all(name == "bench.pause" for name, _ in gaps[:4])
    assert all(s > 0.0015 for _, s in gaps[:4])


def test_window_defaults_to_the_traced_activity():
    whole = T.reduce(str(DATA))
    assert whole["busy_s"] == pytest.approx(
        T.reduce(str(DATA), "bench.window")["busy_s"], rel=0.05)


def test_merge_unions_overlaps():
    assert T._merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
