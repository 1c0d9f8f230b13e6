"""The reduction from a profiler trace to busy time and idle gaps, on
hand-made traces and on one recorded on the chip."""
import json
import os

import pytest

import tracereduce


def test_union_merges_overlaps_and_clips_to_window():
    busy, gaps = tracereduce.union([(5, 8), (0, 3), (2, 4), (9, 20)], 1, 12)
    assert busy == (4 - 1) + (8 - 5) + (12 - 9)
    assert gaps == [(4, 5), (8, 9)]


def test_union_of_nothing_is_one_gap():
    assert tracereduce.union([], 0, 10) == (0.0, [(0, 10)])


def test_gap_label_is_innermost_span():
    spans = [(0, 100, "window"), (10, 60, "step_call"), (20, 30, "data")]
    assert tracereduce.label(25, spans) == "data"
    assert tracereduce.label(40, spans) == "step_call"
    assert tracereduce.label(80, spans) == "host"


def test_reduce_on_hand_made_trace():
    tr = {"devices": {"/device:TPU:0": [(10, 30, "convolution.1"),
                                        (25, 40, "fusion.2"),
                                        (60, 90, "convolution.1")]},
          "spans": [(0, 100, "window"), (40, 60, "data")]}
    red = tracereduce.reduce(tr)
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(60e-9)
    assert red["breakdown"]["device_ops"][0] == ["convolution.1", pytest.approx(50e-9)]
    labels = dict((round(s * 1e9), n) for n, s in red["breakdown"]["idle_gaps"])
    assert labels == {20: "data", 10: "host"}


DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_reduce_on_recorded_chip_trace():
    """A trace of ``detnet.train`` recorded on a v5e (``record_trace.py``):
    the reduction finds the device's ops inside the window, its busy time
    and gaps fill the window, and it gives what the run reported."""
    with open(os.path.join(DATA, "detnet.train.spans.json")) as fh:
        spans = [tuple(s) for s in json.load(fh)]
    tr = tracereduce.load(os.path.join(DATA, "detnet.train.xplane.pb.gz"), spans)
    with open(os.path.join(DATA, "detnet.train.result.json")) as fh:
        res = json.load(fh)
    assert list(tr["devices"]) == ["/device:TPU:0"]
    (lo, hi), = [(s, e) for s, e, n in tr["spans"] if n == "window"]
    ops = tr["devices"]["/device:TPU:0"]
    busy, gaps = tracereduce.union([(s, e) for s, e, _ in ops], lo, hi)
    assert 0 < busy < hi - lo
    assert busy + sum(e - s for s, e in gaps) == pytest.approx(hi - lo)
    # the host's clock and the device's agree: the window opens with the
    # device idle, so its first op comes after the first step call opens
    calls = [(s, e) for s, e, n in tr["spans"] if n == "step_call"]
    assert len(calls) >= 2
    assert calls[0][0] <= min(o[0] for o in ops if lo <= o[0] <= hi)
    red = tracereduce.reduce(tr)
    assert red["window_s"] == res["device"]["window_s"]
    assert red["busy_s"] == res["device"]["busy_s"]
    assert red["breakdown"] == res["breakdown"]
    assert {n for n, _ in red["breakdown"]["idle_gaps"]} <= {"data", "step_call", "host"}
