"""The device's idle time put down to the program's spans
(``tools/idle_by_span.py``), on synthetic events."""

import importlib.util

import pytest

from harness import trace as tr
from harness.loading import BENCH


@pytest.fixture(scope="module")
def tool():
    path = BENCH / "tools" / "idle_by_span.py"
    spec = importlib.util.spec_from_file_location("bench_idle_by_span", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def ev(name, s, d, plane="/host:CPU", line="python"):
    return tr.Event(plane, line, name, float(s), float(d))


def op(s, d, plane="/device:TPU:0"):
    return ev("fusion", s, d, plane=plane, line=tr.OPS_LINE)


# device busy [0, 10), [20, 30), [40, 50): idle [10, 20) and [30, 40)
OPS = [op(0, 10), op(20, 10), op(40, 10), op(0, 50, plane="/device:TPU:1")]


def test_merged_and_overlap(tool):
    assert tool.merged([(5, 8), (0, 3), (2, 4), (8, 9)]) == [(0, 4), (5, 9)]
    assert tool.overlap_ns([(0, 4), (5, 9)], [(3, 6)]) == 2
    assert tool.overlap_ns([], [(0, 1)]) == 0


def test_idle_inside_one_span_and_outside_all(tool):
    # collection covers the first idle gap and 5 busy units around it;
    # the second gap is under no program span
    events = OPS + [ev("fleet.collect", 5, 20), ev("Linearize", 32, 6)]
    out = tool.idle_by_span(events)
    assert out["idle_s"] == pytest.approx(20e-9)
    c = out["spans"]["fleet.collect"]
    assert c["count"] == 1 and c["s"] == pytest.approx(20e-9)
    assert c["idle_s"] == pytest.approx(10e-9)
    assert c["idle_share"] == pytest.approx(50.0)
    none = {"count": 0, "s": 0.0, "idle_s": 0.0, "idle_share": None}
    assert out["spans"]["fleet.flush"] == none
    assert out["untraced_idle_s"] == pytest.approx(10e-9)
    assert out["untraced_idle_share"] == pytest.approx(50.0)
    gaps = {round(g["at_s"] * 1e9): g for g in out["gaps"]}
    assert gaps[10]["span"] == "fleet.collect" and gaps[10]["host"] == "host"
    assert gaps[30]["span"] == "untraced" and gaps[30]["host"] == "Linearize"


def test_overlapping_annotations_count_idle_once(tool):
    """A flush and its child over the same gap: each name gets the idle
    inside it, and the untraced idle counts the union once."""
    events = OPS + [
        ev("fleet.flush", 8, 24),  # covers [8, 32): gap 1 and 2 units of gap 2
        ev("fleet.flush.wait", 12, 6),  # [12, 18), inside gap 1
        ev("fleet.flush.wait", 15, 10),  # [15, 25), overlaps the one above
    ]
    out = tool.idle_by_span(events)
    assert out["spans"]["fleet.flush"]["idle_s"] == pytest.approx(12e-9)
    wait = out["spans"]["fleet.flush.wait"]
    assert wait["count"] == 2 and wait["s"] == pytest.approx(13e-9)  # union [12, 25)
    assert wait["idle_s"] == pytest.approx(8e-9)  # [12, 20)
    assert out["untraced_idle_s"] == pytest.approx(8e-9)  # [32, 40)
    # the innermost span that covers a gap most names it
    assert out["gaps"][0]["span"] == "fleet.flush"


def test_no_device_reads_nothing(tool):
    assert tool.idle_by_span([ev("fleet.collect", 0, 5)]) == {}
