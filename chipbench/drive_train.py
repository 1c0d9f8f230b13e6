"""Driver for training traffic: one ``train.loop.run_xr_training`` call.

Set-up builds the weights on the device from the seed, renders a pool of
distinct host batches, and starts the trainer. The trainer's first
``warmup_steps`` steps trace and compile; they belong to set-up, and their
end is found through the trainer's heartbeat. The window then runs for the
run's seconds; with ``--trace 1`` a traced stretch follows it. The trainer's
own preemption path (SIGTERM) ends the call. The whole loop is the system
under test: the host-to-device copy of each batch, the jitted step, and the
per-step fetch of the loss.

The first ``compared_steps`` steps, which go through the same call and feed,
are checked against the plain reference after the window: each step's
loss, the first gradient as AdamW got it (from its first moment after one
step), and each leaf's change over those steps (see ``compare``), beside
the limits of the cell's limits file.
"""
from __future__ import annotations

import functools
import json
import signal
from contextlib import ExitStack
from functools import partial
from typing import Dict
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

import compare
import generate
import reference
from harness import Outcome, Run, checks, span

B1 = 0.9        # AdamW's first-moment decay in the configuration's recipe


@functools.lru_cache(maxsize=4)
def _reference_step(cfg_json: str):
    """The reference's jitted step, traced once per configuration."""
    return jax.jit(partial(reference.train_step, json.loads(cfg_json)))


@jax.jit
def _copy(tree):
    """A copy of every leaf, in one program, that outlives the donation."""
    return jax.tree.map(jnp.copy, tree)


class StepObserver:
    """Wraps the trainer's jitted step so that the first steps' inputs and
    results are copied out before the next step donates them. In the window
    it only passes the call through, inside a host span. ``faults`` plants
    a fault in the step for the tests and ``calibrate.py``."""

    def __init__(self, faults=frozenset()):
        self.calls = 0
        self.p0 = self.m1 = self.p3 = None
        self.faults = faults
        self.step = self.args = None

    def make(self, make_xr_step):
        def make_observed(*args, **kwargs):
            step = make_xr_step(*args, **kwargs)

            def observed(params, state, opt_state, batch, i):
                k = self.calls
                if k == 0:
                    self.p0 = _copy(params)
                    self.step, self.args = step, jax.tree.map(
                        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        (params, state, opt_state, batch, i))
                if self.faults:
                    kept = _copy((params, state, opt_state))
                with span("step_call"):
                    out = step(params, state, opt_state, batch, i)
                if "state_unchanged" in self.faults:
                    out = (*kept, out[3])
                if "update_negated" in self.faults:
                    out = (jax.tree.map(lambda a, b: 2 * a - b, kept[0], out[0]),
                           *out[1:])
                if k == 0:
                    self.m1 = _copy(out[2].m)
                elif k == 2:
                    self.p3 = _copy(out[0])
                self.calls += 1
                return out
            return observed
        return make_observed

    def program_peak_bytes(self) -> int:
        """The compiled step's peak as XLA plans it (loaded from the cache)."""
        ma = self.step.lower(*self.args).compile().memory_analysis()
        return int(getattr(ma, "peak_memory_in_bytes", 0) or 0)


def _feed(pool, half: bool):
    """Cycle through the pool forever, as the trainer's loader."""
    i = 0
    while True:
        with span("data"):
            b = pool[i % len(pool)]
            if half:
                b = {k: v[: len(v) // 2] for k, v in b.items()}
        i += 1
        yield b, i


def run(r: Run) -> Outcome:
    from repro.models import xr
    from repro.train import loop

    cfg, tr = r.cfg, r.traffic
    warm, batch = tr["warmup_steps"], tr["batch"]
    params = jax.jit(partial(reference.init_params, cfg))(r.key())
    state = reference.init_bn_state(cfg)
    pool = generate.train_pool(cfg, tr, r.seed)
    observer = StepObserver(r.faults)
    win = r.window()
    loss_fn = {"circle": xr.circle_loss, "dice": xr.dice_loss}[cfg["loss"]]
    marks = {}

    def heartbeat(step, dt):
        if step == warm - 1:
            win.open(r)
        elif step < warm:
            return
        elif "closed" not in marks and win.due():
            marks["closed"] = step
            win.close(steps=step - warm + 1)
            if not r.trace:
                signal.raise_signal(signal.SIGTERM)   # the trainer's preemption
            else:
                win.open_trace(r)
        elif "closed" in marks and win.trace_due():
            win.close_trace(steps=step - marks["closed"])
            signal.raise_signal(signal.SIGTERM)

    with ExitStack() as stack:
        stack.enter_context(mock.patch.object(
            loop, "make_xr_step", observer.make(loop.make_xr_step)))
        # the trainer leaves its preemption handler installed on return
        stack.callback(signal.signal, signal.SIGTERM,
                       signal.getsignal(signal.SIGTERM))
        res = loop.run_xr_training(
            r.program_config(), params, state,
            _feed(pool, "half_batch" in r.faults), loss_fn=loss_fn,
            steps=tr["total_steps"], lr=tr["lr"], ckpt_dir=None,
            resume=False, hooks=loop.TrainHooks(heartbeat=heartbeat,
                                                log_every=0))
    win.finish(r)
    device_peak = r.memory_peak()
    losses = list(res.losses)
    n_compared = tr["compared_steps"]
    failed = sum(1 for x in losses[warm:] if not np.isfinite(x))
    del res, params, state
    # the runtime's own peak leaves out XLA's temporaries (0.22 GB read on
    # a v5e against a 15.6 GB step), so the compiled step's peak counts too
    step_peak = observer.program_peak_bytes()
    out = Outcome(window=win, attempted=win.steps, failed=failed,
                  memory_peak_bytes=max(device_peak, step_peak))
    images = win.steps * batch
    out.e2e["train_images_per_s"] = images / win.seconds
    out.info.update(images=images, batch=batch, steps=win.steps,
                    device_peak_bytes=device_peak, step_peak_bytes=step_peak)
    if r.trace:
        out.info.update(traced_steps=win.traced_steps,
                        traced_images_per_s=win.traced_steps * batch
                        / win.traced_seconds)
    program = dict(losses=losses[:n_compared],
                   grad=jax.tree.map(lambda m: np.asarray(m, np.float64) / (1 - B1),
                                     observer.m1),
                   p0=observer.p0, p3=observer.p3)
    got, out.info["grad_worst_leaves"] = compared_numbers(cfg, tr, pool, program)
    out.checks = checks(got, r.limits)
    return out


def reference_steps(cfg: Dict, tr: Dict, pool, p0, dtype=jnp.float32):
    """The reference's first ``compared_steps`` steps from ``p0`` on the
    pool's first batches: losses, the first clipped gradient, and the
    parameters after the last of them."""
    p0 = params = jax.tree.map(lambda a: jnp.asarray(a, dtype), p0)
    m = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
    v = m
    step = _reference_step(json.dumps(cfg, sort_keys=True))
    losses, grad0 = [], None
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        for k in range(tr["compared_steps"]):
            b = {key: jnp.asarray(val) for key, val in pool[k].items()}
            lr = reference.learning_rate(k, tr["lr"], tr["lr_warmup"],
                                         tr["total_steps"])
            params, m, v, loss, grads = step(params, m, v, b, k + 1,
                                             jnp.float32(lr))
            losses.append(float(loss))
            if k == 0:
                grad0 = jax.tree.map(lambda g: np.asarray(g, np.float64), grads)
    return dict(losses=losses, grad=grad0, p0=p0, p3=params)


def _change(run: Dict):
    return jax.tree.map(lambda a, b: np.asarray(a, np.float64)
                        - np.asarray(b, np.float64), run["p3"], run["p0"])


def numbers(program: Dict, ref: Dict) -> Dict[str, float]:
    """The compared numbers of a training cell (see ``compare``)."""
    keep = compare.moving_leaves(ref["grad"])
    return {
        "loss": compare.loss_gap(program["losses"], ref["losses"]),
        "grad": compare.median_leaf_gap(program["grad"], ref["grad"]),
        "change": compare.worst_leaf_gap(_change(program), _change(ref), keep),
    }


def compared_numbers(cfg, tr, pool, program):
    """The reference's three steps from the program's first weights, the
    numbers, and the three leaves with the widest first-gradient gaps."""
    ref = reference_steps(cfg, tr, pool, program["p0"])
    gaps = compare.leaf_gaps(program["grad"], ref["grad"])
    worst = sorted(gaps.items(), key=lambda kv: -kv[1])[:3]
    return numbers(program, ref), worst
