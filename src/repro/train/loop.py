"""Training loops: one loop body for the XR step (paper workloads, BN-state
threading) and the LM/VLM step (router-state threading).

Step functions are pure and jit-donated, ``(params, state, opt_state, batch,
step) -> (params, state, opt_state, metrics)``: ``state`` is the XR nets'
BatchNorm statistics or an MoE router's correction bias and routed count
(empty for other LMs). The outer loop owns checkpointing (atomic + async),
resume-from-latest, loader-state capture, a preemption hook, and a per-step
heartbeat for straggler monitoring (DESIGN.md §7).
``run_xr_training`` and ``run_lm_training`` differ only in their step.
``lm_step`` is the one LM training step: the launcher (``launch/train.py``)
trains through ``run_lm_training`` on its mesh, and the dry-run
(``launch/dryrun.py``) lowers ``lm_step`` for the production mesh. A resumed
run skips its loader past the checkpoint's loader index (``_skip_to``).

The loop keeps one batch of device-side prefetch: once step k is
dispatched, the loader is called for step k+1 and that batch's copy to the
device is issued, while step k runs on the device; only then does the loop
wait for step k's loss. Each step still draws one batch and makes one copy,
in the loader's order; a batch prefetched before a preemption is dropped,
and a loader that runs dry ends the loop at the step that finds no batch.

Each step records host spans in ``repro.spans.RECORDER``, all with the
step number: ``train.step`` around the iteration, and inside it
``train.dispatch`` (the jitted step's call), ``train.fetch`` (the wait for
the loss) and ``train.hooks`` (heartbeat, straggler check, logging,
checkpoints, preemption). ``train.next`` (the loader) and ``train.put``
(the batch's copy to the device) carry the number of the step that consumes
the batch: they open inside the previous ``train.step``, between its
dispatch and its fetch, and inside its own only for the first step. The
counter ``train.step_traces`` counts traces of the step, and
``train.prefetched`` the batches put on the device before the previous
step's loss was fetched: steps - 1 in a run that reaches ``steps``. The
spans wrap the loop as it is: they add no synchronisation.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import signal
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterator, Optional

import jax
import jax.numpy as jnp

from repro import sharding, spans
from repro.train import checkpoint as ckpt_mod
from repro.train import optim

f32 = jnp.float32
STEP_TRACES = "train.step_traces"
PREFETCHED = "train.prefetched"


@dataclass
class TrainHooks:
    """Operational hooks for large-scale runs."""
    heartbeat: Optional[Callable[[int, float], None]] = None  # (step, dt)
    on_preempt: Optional[Callable[[int], None]] = None
    straggler_threshold: float = 3.0     # x median step time -> log warning
    log_every: int = 10


@dataclass
class TrainResult:
    params: Dict
    opt_state: object
    extras: Dict
    losses: list
    step: int


def make_xr_step(cfg, loss_fn, lr_fn, max_grad_norm: float = 1.0):
    """DetNet/EDSNet step: (params, bn_state, opt, batch, step) -> ..."""
    from repro.models import xr

    def step_fn(params, state, opt_state, batch, step):
        spans.RECORDER.count(STEP_TRACES)     # once per trace

        def loss_of(p):
            outs, new_state = xr.forward(cfg, p, state, batch["image"],
                                         train=True)
            loss, metrics = loss_fn(outs, batch)
            return loss, (new_state, metrics)

        (loss, (new_state, metrics)), grads = jax.value_and_grad(
            loss_of, has_aux=True)(params)
        grads, gnorm = optim.clip_by_global_norm(grads, max_grad_norm)
        params, opt_state = optim.adamw_update(
            grads, opt_state, params, lr=lr_fn(step))
        metrics = dict(metrics, loss=loss, grad_norm=gnorm)
        return params, new_state, opt_state, metrics

    return jax.jit(step_fn, donate_argnums=(0, 1, 2))


def lm_step(cfg, lr_fn, max_grad_norm: float = 1.0):
    """The LM/VLM step, unjitted: (params, router_state, opt, batch, step)
    -> ... The router state (``lm.init_router_state``) takes the step's MoE
    load; the metrics carry its running counts of routed slots
    (``routed_slots``) and of layer-steps on the routed experts' full-size
    path (``routed_fallbacks``). Under a bound mesh the new parameters and
    moments keep the layout their logical axes give (as the launcher
    places the parameters), so the next step reuses the compiled program;
    without one the step carries no sharding."""
    from repro.models import lm
    from repro.models.params import logical_axes

    axes = logical_axes(lm.param_defs(cfg))

    def step_fn(params, state, opt_state, batch, step):
        spans.RECORDER.count(STEP_TRACES)     # once per trace
        (loss, (load, metrics)), grads = jax.value_and_grad(
            lm.loss_and_load, has_aux=True, argnums=1)(
                cfg, params, batch, state.get("bias"))
        grads, gnorm = optim.clip_by_global_norm(grads, max_grad_norm)
        params, opt_state = optim.adamw_update(
            grads, opt_state, params, lr=lr_fn(step))
        params = sharding.constrain(params, axes)
        opt_state = opt_state._replace(m=sharding.constrain(opt_state.m, axes),
                                       v=sharding.constrain(opt_state.v, axes))
        state = lm.update_router_state(cfg, state, load)
        metrics = dict(metrics, loss=loss, grad_norm=gnorm)
        if "routed" in state:
            metrics["routed_slots"] = state["routed"]
            metrics["routed_fallbacks"] = state["fallbacks"]
        return params, state, opt_state, metrics

    return step_fn


def make_lm_step(cfg, lr_fn, max_grad_norm: float = 1.0):
    """``lm_step``, jitted, with the parameters, router state and optimizer
    state donated."""
    return jax.jit(lm_step(cfg, lr_fn, max_grad_norm), donate_argnums=(0, 1, 2))


def _schedule(lr: float, steps: int):
    return optim.cosine_schedule(lr, warmup=min(50, steps // 10 + 1),
                                 total=steps)


def run_xr_training(cfg, params, state, batches: Iterator, *,
                    loss_fn, steps: int, lr: float = 1e-3,
                    ckpt_dir: Optional[str] = None, ckpt_every: int = 100,
                    hooks: Optional[TrainHooks] = None,
                    resume: bool = True) -> TrainResult:
    """Train DetNet/EDSNet; ``state`` is the BatchNorm statistics."""
    return _train(make_xr_step(cfg, loss_fn, _schedule(lr, steps)), params,
                  state, batches, steps=steps, ckpt_dir=ckpt_dir,
                  ckpt_every=ckpt_every, hooks=hooks, resume=resume)


def run_lm_training(cfg, params, state, batches: Iterator, *, steps: int,
                    lr: float = 1e-3, ckpt_dir: Optional[str] = None,
                    ckpt_every: int = 100, hooks: Optional[TrainHooks] = None,
                    resume: bool = True) -> TrainResult:
    """Train an LM or VLM; ``state`` is ``lm.init_router_state(cfg)``."""
    return _train(make_lm_step(cfg, _schedule(lr, steps)), params, state,
                  batches, steps=steps, ckpt_dir=ckpt_dir,
                  ckpt_every=ckpt_every, hooks=hooks, resume=resume)


def _train(step_fn, params, state, batches: Iterator, *, steps: int,
           ckpt_dir: Optional[str], ckpt_every: int,
           hooks: Optional[TrainHooks], resume: bool) -> TrainResult:
    """The loop body both trainers share."""
    hooks = hooks if hooks is not None else TrainHooks()
    opt_state = optim.adamw_init(params)
    start = 0

    if ckpt_dir and resume and ckpt_mod.latest_step(ckpt_dir) is not None:
        tree = {"params": params, "state": state, "opt": opt_state}
        tree, start, extra = ckpt_mod.restore(
            ckpt_dir, tree, shardings=jax.tree.map(lambda x: x.sharding, tree))
        params, state, opt_state = tree["params"], tree["state"], tree["opt"]
        batches = _skip_to(batches, extra.get("loader_idx", 0))

    preempted = []
    with contextlib.suppress(ValueError):      # non-main thread
        signal.signal(signal.SIGTERM, lambda *_: preempted.append(True))

    losses, recent, writer = [], collections.deque(maxlen=256), None
    rec = spans.RECORDER
    ahead = None        # the next step's batch, or the loader's StopIteration
    for step in range(start, steps):
        with rec.span("train.step", step) as whole:
            t0 = time.monotonic()
            traces = rec.counters().get(STEP_TRACES, 0)
            if isinstance(ahead, StopIteration):
                raise ahead
            children = ()
            if ahead is None:
                ahead = _load(rec, batches, step)
                children = ahead[2]
            batch, loader_idx, _ = ahead
            with rec.span("train.dispatch", step) as s_dispatch:
                params, state, opt_state, metrics = step_fn(
                    params, state, opt_state, batch, jnp.asarray(step))
            children += (s_dispatch,)
            if step + 1 < steps:
                # the next batch's copy runs on the device beside this step
                try:
                    ahead = _load(rec, batches, step + 1)
                except StopIteration as e:
                    ahead = e
                else:
                    rec.count(PREFETCHED)
                    children += ahead[2]
            with rec.span("train.fetch", step) as s_fetch:
                loss = float(metrics["loss"])
            losses.append(loss)
            dt = time.monotonic() - t0
            with rec.span("train.hooks", step):
                if hooks.heartbeat:
                    hooks.heartbeat(step, dt)
                if len(recent) >= 10:
                    med = sorted(recent)[len(recent) // 2]
                    if dt > hooks.straggler_threshold * med:
                        _warn_straggler(step, dt, med, (*children, s_fetch),
                                        rec.counters().get(STEP_TRACES, 0) > traces)
                if hooks.log_every and step % hooks.log_every == 0:
                    print(f"step {step:5d} loss {loss:.4f} "
                          + " ".join(f"{k}={float(v):.4f}"
                                     for k, v in metrics.items()
                                     if k != "loss" and v.ndim == 0))
                if ckpt_dir and (step + 1) % ckpt_every == 0:
                    writer = ckpt_mod.save_async(
                        ckpt_dir, step + 1,
                        {"params": params, "state": state, "opt": opt_state},
                        extra={"loader_idx": loader_idx})
                if preempted:
                    if hooks.on_preempt:
                        hooks.on_preempt(step)
                    if ckpt_dir:
                        ckpt_mod.save(ckpt_dir, step + 1,
                                      {"params": params, "state": state,
                                       "opt": opt_state},
                                      extra={"loader_idx": loader_idx})
                    break
        recent.append(whole.seconds)
    if writer is not None:
        writer.join()
    return TrainResult(params, opt_state, {"state": state}, losses,
                       step + 1 if steps else 0)


def _load(rec, batches: Iterator, step: int):
    """The loader's next batch, put on the device for ``step``: the batch,
    its loader index and the two spans."""
    with rec.span("train.next", step) as s_next:
        batch, loader_idx = next(batches)
    with rec.span("train.put", step) as s_put:
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
    return batch, loader_idx, (s_next, s_put)


def _warn_straggler(step: int, dt: float, med: float, children, retraced: bool):
    """Name the slowest part of a slow step, the step its batch is for where
    that part prefetched the next one, and whether the step retraced."""
    slow = max(children, key=lambda s: s.seconds)
    print(f"[straggler] step {step} took {dt:.2f}s (median {med:.2f}s): "
          f"{slow.name} {slow.seconds:.2f}s"
          + (f" (batch for step {slow.step})" if slow.step != step else "")
          + ("; the step retraced" if retraced else ""))


def _skip_to(batches: Iterator, loader_idx: int) -> Iterator:
    """Resume a loader at a checkpoint: drop its items until the index it
    yields passes ``loader_idx``, the index the checkpoint's last batch
    carried. A loader built to start there has nothing dropped."""
    return itertools.dropwhile(lambda item: item[1] <= loader_idx, batches)
