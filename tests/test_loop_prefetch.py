"""The XR trainer loop's one batch of device-side prefetch: the same numbers
as a synchronous loop, how often it engages, a loader that runs dry, and
the loader index that checkpoints keep. Host CPU, smoke DetNet."""
import itertools
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.configs import get_smoke
from repro.data import synthetic
from repro.models import xr
from repro.models.params import materialize
from repro.train import checkpoint as ckpt
from repro.train import loop, optim

CFG = get_smoke("detnet")


def _weights():
    pdefs, sdefs = xr.param_defs(CFG)
    return (materialize(pdefs, jax.random.key(0)),
            materialize(sdefs, jax.random.key(1)))


def _batches():
    return synthetic.fphab_batches(2, CFG.input_hw, CFG.in_channels)


def _counted(drawn):
    """The loader, noting the index of each batch it hands out."""
    for b in _batches():
        drawn.append(b[1])
        yield b


def _run(batches, steps, heartbeat=None, **kw):
    params, state = _weights()
    return loop.run_xr_training(
        CFG, params, state, batches, loss_fn=xr.circle_loss, steps=steps,
        lr=1e-3, hooks=loop.TrainHooks(heartbeat=heartbeat, log_every=0), **kw)


def _prefetched():
    return spans.RECORDER.counters().get(loop.PREFETCHED, 0)


def test_prefetching_loop_matches_a_synchronous_loop_bitwise():
    steps = 5
    res = _run(_batches(), steps)

    # the loop's recipe, one step at a time, each batch put just before use
    params, state = _weights()
    lr_fn = optim.cosine_schedule(1e-3, warmup=min(50, steps // 10 + 1),
                                  total=steps)
    step_fn = loop.make_xr_step(CFG, xr.circle_loss, lr_fn)
    opt_state = optim.adamw_init(params)
    losses = []
    for step, (batch, _) in zip(range(steps), _batches()):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        params, state, opt_state, metrics = step_fn(
            params, state, opt_state, batch, jnp.asarray(step))
        losses.append(float(metrics["loss"]))

    assert len(set(losses)) == steps          # the batches are distinct
    assert res.losses == losses
    got = jax.tree.leaves((res.params, res.extras["state"]))
    want = jax.tree.leaves((params, state))
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("steps", [1, 5])
def test_prefetched_counts_every_batch_but_the_first(steps):
    drawn = []
    before = _prefetched()
    res = _run(_counted(drawn), steps)
    assert _prefetched() - before == steps - 1
    assert len(res.losses) == steps and len(drawn) == steps


def test_a_loader_that_runs_dry_ends_the_loop_after_its_last_batch():
    beats = []
    before = _prefetched()
    with pytest.raises(StopIteration):
        _run(itertools.islice(_batches(), 3), 5,
             heartbeat=lambda step, dt: beats.append(step))
    assert beats == [0, 1, 2]
    assert _prefetched() - before == 2


def test_checkpoints_keep_the_consumed_batch_loader_index(tmp_path):
    """Batch k of the loader carries the index 2(k+1). A periodic save and
    a preemption after step 2 keep the index of the batch that step
    consumed, not of batch 3, already prefetched."""
    periodic, preempted = str(tmp_path / "periodic"), str(tmp_path / "preempt")
    _run(_batches(), 4, ckpt_dir=periodic, ckpt_every=3)
    assert ckpt.latest_step(periodic) == 3
    _, _, extra = ckpt.restore(periodic, {"params": _weights()[0]})
    assert extra["loader_idx"] == 6

    drawn, seen = [], []

    def preempt_at_2(step, dt):
        seen.append(step)
        if step == 2:     # the loop's own SIGTERM handler, called directly
            signal.getsignal(signal.SIGTERM)(signal.SIGTERM, None)

    old = signal.getsignal(signal.SIGTERM)
    try:
        res = _run(_counted(drawn), 10, heartbeat=preempt_at_2, ckpt_dir=preempted,
                   ckpt_every=100)
    finally:
        signal.signal(signal.SIGTERM, old)
    assert seen == [0, 1, 2] and res.step == 3
    assert drawn == [2, 4, 6, 8]               # batch 3 was prefetched, dropped
    assert ckpt.latest_step(preempted) == 3
    _, _, extra = ckpt.restore(preempted, {"params": _weights()[0]})
    assert extra["loader_idx"] == 6


def test_a_resumed_run_skips_a_fresh_loader_past_the_checkpoint(tmp_path):
    """Resumed from the checkpoint after step 2 (loader index 4) with a
    loader built from its start, the loop drops batches 0 and 1 and trains
    step 2 on batch 2 (index 6), not on batch 0 again. A loader built to
    start at the checkpoint has nothing dropped."""
    d = str(tmp_path)
    _run(_batches(), 2, ckpt_dir=d, ckpt_every=2)
    drawn = []
    res = _run(_counted(drawn), 4, ckpt_dir=d, ckpt_every=3)
    assert res.step == 4 and len(res.losses) == 2
    assert drawn == [2, 4, 6, 8]
    assert ckpt.latest_step(d) == 3
    assert ckpt.restore(d, {})[2] == {"loader_idx": 6}

    started = ((None, i) for i in itertools.count(6, 2))
    assert next(loop._skip_to(started, 4)) == (None, 6)
