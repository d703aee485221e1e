"""The trace reduction, on a small trace recorded on one TPU v5e chip
(``data/trace_small.xplane.pb``: three steps of a jitted scan of bf16
matmuls, each after a 2 ms host sleep in a ``bench.batch`` span, the
step in ``bench.step``, all inside ``bench.window``)."""
from pathlib import Path

import pytest

from benchmarks.chip import trace

SMALL = Path(__file__).resolve().parent / "data" / "trace_small.xplane.pb"


def test_interval_arithmetic():
    m = trace.merge([(0, 2), (1, 3), (5, 6), (6, 7)])
    assert m == [(0, 3), (5, 7)]
    assert trace.covered(m) == 5
    # collectives at [0,3) and [5,7); compute at [1,2) and [6,9)
    assert trace.subtract(m, [(1, 2), (6, 9)]) == 3


def test_op_names_and_self_time():
    assert trace.op_name("%fusion.3 = bf16[8]{0} fusion(%all-reduce.1)") \
        == "fusion.3"
    # a while loop [0, 10) around body ops [1, 4) and [5, 6)
    got = dict(trace.self_times([(0, 10, "while"), (1, 4, "a"),
                                 (5, 6, "b"), (12, 13, "c")]))
    assert got == {"while": 6, "a": 3, "b": 1, "c": 1}


def test_small_chip_trace():
    found = trace.reduce(SMALL)
    assert found["chips"] == 1
    assert 0 < found["busy_s"] < found["window_s"]
    assert found["idle_share"] == pytest.approx(
        1 - found["busy_s"] / found["window_s"])
    assert found["collective_s"] == 0
    assert len(found["device_ops"]) <= trace.TOP
    assert all(t > 0 for _, t in found["device_ops"])
    assert all(" " not in name for name, _ in found["device_ops"])
    labels = {name for name, _ in found["idle_gaps"]}
    # the host sleeps in bench.batch while the chip waits
    assert "bench.batch" in labels
    longest = max(t for _, t in found["idle_gaps"])
    assert longest >= 0.002
