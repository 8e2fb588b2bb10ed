"""The trace reduction, on intervals and on a trace recorded on a v5e
(benchmarks/tests/record_trace.py wrote data/small.xplane.pb.gz)."""

import json
import os

import pytest

from benchmarks import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_and_gaps():
    busy = tr.union([(5, 7), (0, 2), (1, 3), (9, 9), (6, 8)])
    assert busy == [(0, 3), (5, 8)]
    assert tr.gaps(busy, 0, 10) == [(3, 5), (8, 10)]
    assert tr.gaps(busy, -1, 4) == [(-1, 0), (3, 4)]
    assert tr.clip(busy, 1, 6) == [(1, 3), (5, 6)]


def test_gap_named_by_innermost_annotation():
    notes = [(0, 100, "geomesa:sidecar.do_get"), (10, 50, "geomesa:density"),
             (20, 30, "geomesa:plan")]
    assert tr.name_gap((22, 28), notes) == "plan"
    assert tr.name_gap((40, 45), notes) == "density"
    assert tr.name_gap((60, 70), notes) == "sidecar.do_get"
    assert tr.name_gap((150, 160), notes) == "-"


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(DATA, "small.xplane.pb.gz")
    with open(os.path.join(DATA, "small.reduce.json")) as f:
        return tr.reduce(path), json.load(f)


def test_recorded_trace_reduces_as_on_the_chip(recorded):
    got, want = recorded
    assert got == pytest.approx(want)


def test_recorded_trace_is_sane(recorded):
    got, _ = recorded
    assert got["devices"] == 1
    assert 0 < got["busy_s"] <= got["window_s"]
    assert got["device_ops"] and all(t > 0 for _, t in got["device_ops"])
    idle = sum(t for _, t in got["idle_gaps"])
    assert idle <= got["window_s"] - got["busy_s"] + 1e-9
    assert any(name != "-" for name, _ in got["idle_gaps"])
