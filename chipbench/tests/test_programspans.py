"""The split of the device's idle time by the program's trainer-loop spans
(``programspans``), on a hand-made trace and on one recorded on the chip."""
import json
import os

import pytest

import harness
import programspans
import tracereduce

# Two steps in a window of 100 ns. Step 0's first device op starts inside
# its dispatch; step 1's only after its fetch has begun.
STEPS = [
    (5, 55, "train.step"), (5, 10, "train.next"), (10, 15, "train.put"),
    (15, 25, "train.dispatch"), (25, 50, "train.fetch"), (50, 55, "train.hooks"),
    (60, 100, "train.step"), (60, 62, "train.next"), (62, 70, "train.put"),
    (70, 75, "train.dispatch"), (75, 95, "train.fetch"), (95, 100, "train.hooks"),
]
OPS = [(20, 30, "fusion.1"), (40, 45, "fusion.2"), (85, 92, "fusion.1")]
# gaps [0, 20], [30, 40], [45, 85], [92, 100], cut at the spans' edges
SHARES = {"input": (5 + 5 + 2 + 8) / 100,     # next and put of both steps
          "dispatch": (5 + 5) / 100,          # [15, 20], [70, 75]
          "launch": 10 / 100,                 # [75, 85]: step 1 not begun
          "fetch": (10 + 5 + 3) / 100,        # [30, 40], [45, 50], [92, 95]
          "other": (5 + 5 + 5 + 5) / 100}     # before step 0, hooks, between


def hand_made(devices):
    return {"devices": devices, "spans": sorted([(0, 100, "window"), *STEPS])}


def test_split_on_hand_made_trace():
    tr = hand_made({"/device:TPU:0": OPS})
    got = programspans.split(tr)
    assert got == pytest.approx(SHARES)
    assert sum(got.values()) == pytest.approx(tracereduce.reduce(tr)["idle_share"])


def test_split_averages_over_devices():
    """A second device busy all through halves every share, as it halves
    the device idle share."""
    tr = hand_made({"/device:TPU:0": OPS, "/device:TPU:1": [(0, 100, "fusion.9")]})
    got = programspans.split(tr)
    assert got == pytest.approx({k: v / 2 for k, v in SHARES.items()})
    assert sum(got.values()) == pytest.approx(tracereduce.reduce(tr)["idle_share"])


def test_no_window_or_no_program_spans_reads_nothing(monkeypatch):
    ctx = {"cell": {"name": "detnet.train"}}
    monkeypatch.setattr(harness.span, "events", [])
    assert programspans.share(ctx, "input") is None
    # a window in which the program recorded no span: one older than its
    # recorder, or a run that never entered the trainer loop
    monkeypatch.setattr(harness.span, "events", [(1, 2, "window")])
    assert programspans.share(ctx, "input") is None


DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PREFIX = os.path.join(DATA, "detnet.train.program")


@pytest.fixture(scope="module")
def recorded():
    """A ``detnet.train`` trace recorded on a v5e with the program's spans
    (``record_program_trace.py``), and the result line of its run."""
    with open(f"{PREFIX}.spans.json") as fh:
        window = [tuple(s) for s in json.load(fh) if s[2] == "window"]
    with open(f"{PREFIX}.program_spans.json") as fh:
        program = [tuple(s) for s in json.load(fh)]
    with open(f"{PREFIX}.result.json") as fh:
        res = json.load(fh)
    tr = tracereduce.load(f"{PREFIX}.xplane.pb.gz",
                          [*window, *((s, e, n) for s, e, n, _ in program)])
    # the program's spans with their step numbers, on the profile's clock
    off = window[0][0] - [s for s, _, n in tr["spans"] if n == "window"][0]
    program = [(s - off, e - off, n, k) for s, e, n, k in program]
    return tr, program, res


def test_recorded_shares_add_up_to_the_idle_share(recorded):
    tr, _, res = recorded
    got = programspans.split(tr)
    metrics = {k: v["value"] for k, v in res["metrics"].items()}
    for phase, v in got.items():
        assert 100 * v == pytest.approx(metrics[f"train.idle_{phase}_share"])
    assert 100 * sum(got.values()) == pytest.approx(
        metrics["train.device_idle_share"], abs=0.05)
    assert 100 * sum(got.values()) == pytest.approx(
        100 * tracereduce.reduce(tr)["idle_share"], abs=1e-9)


def test_recorded_steps_run_on_the_device_between_dispatch_and_fetch(recorded):
    """The program's clock and the device's agree: each step's device ops,
    those from its dispatch on, begin before its fetch ends, and the last
    of them ends by then, since the fetch waits for the step."""
    tr, program, _ = recorded
    (lo, hi), = [(s, e) for s, e, n in tr["spans"] if n == "window"]
    ops = tr["devices"]["/device:TPU:0"]
    by_step = {}
    for s, e, n, k in program:
        by_step.setdefault(k, {})[n] = (s, e)
    whole = [k for k, sp in sorted(by_step.items())
             if lo <= sp["train.step"][0] and sp["train.step"][1] <= hi]
    assert len(whole) >= 4
    for k in whole:
        d0 = by_step[k]["train.dispatch"][0]
        f1 = by_step[k]["train.fetch"][1]
        nxt = by_step.get(k + 1, {}).get("train.dispatch", (hi,))[0]
        mine = [o for o in ops if d0 <= o[0] < nxt]
        assert mine and mine[0][0] < f1
        assert max(e for _, e, _ in mine) <= f1
