"""The span recorder (``repro.spans``) and the trainer loop's spans, counter
and straggler warning, on the host CPU with a smoke DetNet."""
import itertools
import sys
import threading
import time

import jax
import pytest

from repro import spans
from repro.configs import get_smoke
from repro.data import synthetic
from repro.models import xr
from repro.models.params import materialize
from repro.train import loop

def test_recorder_keeps_order_and_ids():
    rec = spans.Recorder()
    lo = time.time_ns()
    for i in range(5):
        with rec.span("outer", i) as outer:
            with rec.span("inner", i):
                pass
        assert outer.end_ns >= outer.start_ns and outer.seconds >= 0
    evs = rec.events(lo, time.time_ns())
    assert [(n, k) for _, _, n, k in evs] == [
        (n, i) for i in range(5) for n in ("outer", "inner")]
    assert all(s <= e for s, e, _, _ in evs)
    assert [s for s, _, _, _ in evs] == sorted(s for s, _, _, _ in evs)
    # an interval that ends before any span began holds none
    assert rec.events(lo - 10, lo - 1) == []


def test_recorder_bounds_its_ring_and_refuses_dropped_intervals():
    rec = spans.Recorder()
    n = spans.CAPACITY + 10
    lo = time.time_ns()
    for i in range(n):
        with rec.span("s", i):
            pass
        if i == 19:
            mid = time.time_ns()
    hi = time.time_ns()
    assert len(rec._ring) == spans.CAPACITY
    # the first 10 spans were dropped: an interval that holds them is refused
    with pytest.raises(LookupError):
        rec.events(lo, hi)
    # one that begins after them is whole
    evs = rec.events(mid, hi)
    assert [k for *_, k in evs] == list(range(evs[0][3], n))
    assert evs[0][3] in (19, 20)


def test_recorder_counts_across_threads():
    rec = spans.Recorder()
    n, workers = 2000, 16

    def work(w):
        for i in range(n):
            rec.count("c")
            with rec.span("t", w * n + i):
                pass

    lo = time.time_ns()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(w,)) for w in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert rec.counters() == {"c": n * workers}
    evs = rec.events(lo, time.time_ns())
    assert sorted(k for *_, k in evs) == list(range(n * workers))


def _smoke():
    cfg = get_smoke("detnet")
    pdefs, sdefs = xr.param_defs(cfg)
    return (cfg, materialize(pdefs, jax.random.key(0)),
            materialize(sdefs, jax.random.key(1)))


def _train(batches, steps, heartbeat=None):
    cfg, params, state = _smoke()
    lo = time.time_ns()
    loop.run_xr_training(cfg, params, state, batches, loss_fn=xr.circle_loss,
                         steps=steps, lr=1e-3,
                         hooks=loop.TrainHooks(heartbeat=heartbeat, log_every=0))
    return spans.RECORDER.events(lo, time.time_ns())


def test_training_records_each_step_and_its_phases_in_order():
    """Each iteration dispatches its step, then loads the next step's batch
    (``train.next``, ``train.put`` with that step's number) before it
    fetches its own loss; only the first loads its own batch."""
    cfg = get_smoke("detnet")
    evs = _train(synthetic.fphab_batches(2, cfg.input_hw, cfg.in_channels), 6)
    steps = [ev for ev in evs if ev[2] == "train.step"]
    assert [k for *_, k in steps] == list(range(6))
    load = ["train.next", "train.put"]
    for s, e, _, k in steps:
        inside = [ev for ev in evs if ev[2] != "train.step" and s <= ev[0] <= e]
        own = [(n, k) for n in load] if k == 0 else []
        ahead = [(n, k + 1) for n in load] if k < 5 else []
        assert [(n, i) for _, _, n, i in inside] == (
            own + [("train.dispatch", k)] + ahead
            + [("train.fetch", k), ("train.hooks", k)])
        assert all(s <= a <= b <= e for a, b, _, _ in inside)
        assert all(b1 <= a2 for (_, b1, _, _), (a2, _, _, _)
                   in zip(inside, inside[1:]))


def test_step_traces_counts_a_retrace_and_the_warning_names_it(capsys):
    """The batch shape changes at step 11: the step traces again there, and
    that step's straggler warning names its dispatch and the retrace."""
    cfg = get_smoke("detnet")
    batches = itertools.chain(
        itertools.islice(synthetic.fphab_batches(2, cfg.input_hw,
                                                 cfg.in_channels), 11),
        synthetic.fphab_batches(3, cfg.input_hw, cfg.in_channels))
    base = spans.RECORDER.counters().get("train.step_traces", 0)
    seen = []
    _train(batches, 13, heartbeat=lambda step, dt: seen.append(
        spans.RECORDER.counters()["train.step_traces"] - base))
    assert seen == [1] * 11 + [2] * 2
    warned = [ln for ln in capsys.readouterr().out.splitlines()
              if ln.startswith("[straggler] step 11 ")]
    assert len(warned) == 1
    assert "train.dispatch" in warned[0] and "retraced" in warned[0]


def test_straggler_warning_names_the_slow_loader(capsys):
    """The loader sleeps while it makes the batch of step 12, which step 11
    prefetches: step 11 is the straggler, and the warning names the loader
    and the step the batch is for."""
    cfg = get_smoke("detnet")

    def slow_once():
        for i, b in enumerate(synthetic.fphab_batches(2, cfg.input_hw,
                                                      cfg.in_channels)):
            if i == 12:
                time.sleep(1.0)
            yield b

    _train(slow_once(), 14)
    warned = [ln for ln in capsys.readouterr().out.splitlines()
              if ln.startswith("[straggler] step 11 ")]
    assert len(warned) == 1
    assert "train.next" in warned[0] and "(batch for step 12)" in warned[0]
    assert "retraced" not in warned[0]
