"""``bench/trace.py`` on a hand-made trace and on a recorded one."""

from pathlib import Path

import pytest

from bench import trace

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"


def _planes():
    dev = {trace.OP_LINE: [("fusion.1", 10, 10), ("fusion.2", 15, 15),
                           ("scatter", 50, 10), ("late", 120, 5)],
           trace.MODULE_LINE: [("jit_step", 10, 20), ("jit_step", 50, 10),
                               ("jit_step", 120, 5)]}
    host = {"python": [("window", 0, 100), ("execute:q1", 5, 35),
                       ("fetch", 40, 30)]}
    return [("/device:TPU:0", dev), ("/host:CPU", host)]


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    s = trace.summarize(_planes())
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(30e-9)          # [10, 30) and [50, 60)
    assert s.idle_share == pytest.approx(0.7)
    assert s.launches == 2                           # the third is after
    assert s.devices == 1


def test_ops_and_gaps_are_ranked_and_labelled():
    s = trace.summarize(_planes())
    assert [n for n, _ in s.device_ops] == [
        "jit_step: fusion.2", "jit_step: fusion.1", "jit_step: scatter"]
    gaps = dict(s.idle_gaps)
    assert gaps == pytest.approx({"window x1": 40e-9, "fetch x1": 20e-9,
                                  "execute:q1 x1": 10e-9})


def test_no_window_or_no_device_reads_nothing():
    planes = _planes()
    assert trace.summarize([planes[0]]) is None
    assert trace.summarize([planes[1]]) is None


def test_recorded_tpu_trace():
    """A traced 1 s window of the resident scan cell at a tenth of its
    scale (SF 1) on one v5e: Q1, Q6, Q1, warm.  Kept are the device's
    module and op lines and the harness's spans; the summary is the one
    the full trace gave."""
    path = TESTDATA / "scan_sf1.xplane.pb"
    s = trace.summarize(trace.read(str(path)))
    assert s is not None and s.devices == 1
    assert 0 < s.busy_s < s.window_s
    assert s.launches == RECORDED_LAUNCHES
    assert s.busy_s == pytest.approx(RECORDED_BUSY_S, rel=1e-9)
    assert s.window_s == pytest.approx(1.3634114320000001, rel=1e-9)
    labels = {n.split(" x")[0] for n, _ in s.idle_gaps}
    assert labels <= {"window", "sql", "fetch", "execute:q1", "execute:q6"}
    assert s.device_ops[0][0].startswith("jit_step: %fusion")


RECORDED_LAUNCHES = 283         # 94.3 per query: 92 batch steps + 2
RECORDED_BUSY_S = 1.3289683490000002
