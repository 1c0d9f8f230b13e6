"""The harness, run past its look for a chip on the CPU at a reduced size,
with the timed path broken underneath: ``correct`` has to come out false.
And the control, the plain reference put in the program's place one
precision below the configuration's, has to fail the cell's limits, judged
by the harness's own ``Outcome.correct``."""
import json

import pytest

import calibrate
import drive_train
import run
from conftest import shrink
from harness import Outcome, checks

TRAIN = ["edsnet.train", "detnet.train"]
SEED = 3_000_000_019          # more than 32 signed bits hold
# The faults a training cell can have. ``calibrate.py`` also reads an update
# applied with its sign turned; at this reduced size the loss moves too
# little for it, so it is read on the chip at the cells' own sizes only.
FAULTS = ("half_batch", "state_unchanged")


def result(capsys, cell, faults=()):
    rc = run.main(["--workload", cell, "--seed", str(SEED), "--seconds", "1",
                   "--trace", "0"], faults=frozenset(faults),
                  require_chip=False, overrides=shrink)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,faults,correct", [
    *[(c, (), True) for c in TRAIN],
    *[(c, (f,), False) for c in TRAIN for f in FAULTS],
])
def test_fault_is_caught(capsys, cell, faults, correct):
    res = result(capsys, cell, faults)
    assert res["correct"] is correct, res["checks"]
    assert list(res)[-1] == "checks"
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"}


def test_no_chip_no_result(capsys):
    rc = run.main(["--workload", "detnet.train", "--seed", "1", "--seconds",
                   "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("cell", TRAIN)
def test_control_fails_training_limits(cell):
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    c, cfg = run.find_cell(bench, cell)
    tr = run.load_json(run.HERE, "traffic", f"{c['traffic']}.json")
    limits = run.load_json(run.HERE, "limits", f"{cell}.json")
    cfg, tr = shrink(cfg, tr)
    got = calibrate.control(drive_train, cfg, tr, SEED)
    o = Outcome(window=None, attempted=0, failed=0, memory_peak_bytes=0,
                checks=checks(got, limits))
    assert not o.correct, o.checks
