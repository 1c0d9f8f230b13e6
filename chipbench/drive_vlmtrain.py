"""Driver for VLM training traffic: one ``train.loop.run_lm_training`` call.

It mirrors ``drive_train.py``. Set-up builds the weights on the device from
the seed (``reference_vlm.init_params``), renders a pool of distinct host
batches (frames and token ids, ``vlm_batch``), and starts the trainer with
the router state of the program's own ``lm.init_router_state``. The
trainer's first ``warmup_steps`` steps trace and compile and belong to
set-up; the window opens at the heartbeat of the last of them and runs for
the run's seconds; with ``--trace 1`` a traced stretch follows it. The
trainer's own preemption path (SIGTERM) ends the call.

A wrapper around the jitted step copies the weights before step 0, AdamW's
first moment after it and the weights after the last compared step to the
host (the chip has no room for them beside the step), and keeps each
step's running count of routed slots (``routed_slots``). After the window
the reference (``reference_vlm.py``) runs the same compared steps from the
same weights and batches, and ``numbers`` gives ``loss``, ``grad`` and
``change`` (``compare``, as in ``drive_train.py``) beside the cell's
limits. The host holds a few float32 copies of the 2.7 GB of weights at a
time: the changes are kept, not the weights after the steps, each taken
leaf by leaf.

A traced run also saves the compiled step's HLO text, whose ``op_name``
metadata names the stage of each op (``vlmtrace``), and reports the slots
the router sent to the held experts over the traced steps
(``traced_routed_slots``).

Faults for ``calibrate_vlm.py`` and the tests: ``half_batch``,
``state_unchanged`` and ``update_negated`` as in ``drive_train.py`` (the
last two in the compared steps only), and one for each mechanism of the
model, planted by replacing one function of the program's
``models.layers``: ``no_routed`` (routed experts left out), ``no_shared``
(shared experts left out), ``no_rope`` (MLA's rope part left out) and
``capacity`` (routing bounded at capacity factor 1.0, slots past an
expert's capacity dropped, in place of dropless routing).
"""
from __future__ import annotations

import functools
import json
import math
import os
import signal
from contextlib import ExitStack
from functools import partial
from typing import Dict, List
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

import compare
import harness
import reference
import reference_vlm
from harness import Outcome, Run, checks, span

B1 = 0.9
FAULTS = ("half_batch", "state_unchanged", "update_negated", "no_routed",
          "no_shared", "no_rope", "capacity")


# ---------------------------------------------------------------------------
# the program's configuration, from the file
# ---------------------------------------------------------------------------

SUPPORTED = {"q_lora_rank": None, "topk_method": "noaux_tc", "n_group": 1,
             "topk_group": 1, "norm_topk_prob": True, "scoring_func": "sigmoid",
             "moe_layer_freq": 1, "hidden_act": "silu", "rope_scaling": None,
             "attention_bias": False, "tie_word_embeddings": False,
             "seq_aux": True}


def program_config(cfg: Dict):
    """The configuration file as the program's ``ModelConfig``."""
    from repro.configs.base import ModelConfig
    for k, want in SUPPORTED.items():
        if cfg[k] != want:
            raise ValueError(f"{k}={cfg[k]!r}: the program runs {want!r} only")
    v = cfg["vision_config"]
    if v["init_pos_emb_height"] != v["init_pos_emb_width"]:
        raise ValueError("the program's position table is square")
    s = reference_vlm.sizes(cfg)
    return ModelConfig(
        name=cfg["name"], family="vlm", num_layers=cfg["num_hidden_layers"],
        first_dense_layers=cfg["first_k_dense_replace"], d_model=s["D"],
        num_heads=s["H"], num_kv_heads=cfg["num_key_value_heads"],
        head_dim=s["dv"], d_ff=s["F"], vocab_size=s["V"], kv_lora_rank=s["R"],
        qk_nope_head_dim=s["dn"], qk_rope_head_dim=s["dr"], v_head_dim=s["dv"],
        num_experts=s["E"], experts_per_token=s["K"], experts_held=s["G"],
        moe_d_ff=s["Fe"], num_shared_experts=cfg["n_shared_experts"],
        router_scoring=cfg["scoring_func"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        bias_update_rate=cfg["bias_update_speed"],
        seq_aux_weight=cfg["seq_aux_alpha"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"], num_image_tokens=(s["g"] // s["k"]) ** 2,
        vision_layers=s["Lv"], vision_d_model=s["Dv"], vision_heads=s["Hv"],
        vision_d_ff=s["Fv"], vision_patch=s["P"], vision_pos_grid=s["Gp"],
        vision_rope_theta=float(v["rope_theta"]), vision_merge=s["k"],
        vision_norm_eps=v["layer_norm_eps"], image_hw=cfg["image_size"],
        param_dtype=cfg["param_dtype"], compute_dtype=cfg["compute_dtype"])


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------

def vlm_sample(cfg: Dict, tr: Dict, seed: int, idx: int) -> Dict[str, np.ndarray]:
    """One training sequence, a pure function of ``(seed, idx)``: a camera
    frame of the file's size, normalised to [-1, 1] (a colour a patch, with
    sensor noise), and ``seq_len`` token ids drawn from the vocabulary
    slice, of which the first (the image positions) are placeholders."""
    rng = np.random.default_rng((seed, 7, idx))
    s = reference_vlm.sizes(cfg)
    hw, P = cfg["image_size"], s["P"]
    coarse = rng.uniform(-0.8, 0.8, (hw // P, hw // P, 3))
    frame = np.kron(coarse, np.ones((P, P, 1))) + rng.normal(0, 0.1, (hw, hw, 3))
    tokens = rng.integers(0, cfg["vocab_size"], tr["seq_len"])
    tokens[: (s["g"] // s["k"]) ** 2] = 0
    return {"pixels": np.clip(frame, -1, 1).astype(np.float32),
            "tokens": tokens.astype(np.int32)}


def vlm_batch(cfg: Dict, tr: Dict, seed: int, start: int, rows: int):
    s = [vlm_sample(cfg, tr, seed, start + i) for i in range(rows)]
    return {k: np.stack([x[k] for x in s]) for k in s[0]}


def train_pool(cfg: Dict, tr: Dict, seed: int) -> List[Dict[str, np.ndarray]]:
    b = tr["batch"]
    return [vlm_batch(cfg, tr, seed, i * b, b) for i in range(tr["pool_batches"])]


def _feed(pool, half: bool):
    i = 0
    while True:
        with span("data"):
            b = pool[i % len(pool)]
            if half:
                b = {k: v[: len(v) // 2] for k, v in b.items()}
        i += 1
        yield b, i


# ---------------------------------------------------------------------------
# the wrapped step, and the faults
# ---------------------------------------------------------------------------

class StepObserver:
    """Wraps the trainer's jitted step: copies to the host the weights
    before step 0, AdamW's first moment after it and the weights after step
    ``compared - 1``; keeps each step's ``routed_slots``. In the window it
    passes the call through inside a host span."""

    def __init__(self, compared: int, faults=frozenset()):
        self.calls = 0
        self.compared = compared
        self.faults = faults
        self.p0 = self.m1 = self.p3 = None
        self.routed: Dict[int, jax.Array] = {}
        self.step = self.args = None

    def make(self, make_lm_step):
        def make_observed(*args, **kwargs):
            step = make_lm_step(*args, **kwargs)

            def observed(params, state, opt_state, batch, i):
                k = self.calls
                if k == 0:
                    self.p0 = jax.device_get(params)
                    self.step, self.args = step, jax.tree.map(
                        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        (params, state, opt_state, batch, i))
                kept = None
                if k < self.compared and self.faults & {"state_unchanged",
                                                        "update_negated"}:
                    kept = jax.device_get((params, state, opt_state))
                with span("step_call"):
                    out = step(params, state, opt_state, batch, i)
                # the step's results leave the device before the planted
                # ones arrive: the chip holds one copy of the state only
                if kept is not None and "state_unchanged" in self.faults:
                    metrics = out[3]
                    del out
                    out = (*jax.device_put(kept), metrics)
                if kept is not None and "update_negated" in self.faults:
                    turned = jax.tree.map(lambda a, b: 2 * a - b, kept[0],
                                          jax.device_get(out[0]))
                    out = (None, *out[1:])
                    out = (jax.device_put(turned), *out[1:])
                if k == 0:
                    self.m1 = jax.device_get(out[2].m)
                if k == self.compared - 1:
                    self.p3 = jax.device_get(out[0])
                self.routed[k] = out[3]["routed_slots"]
                self.calls += 1
                return out
            return observed
        return make_observed

    def compiled(self):
        """The compiled step (loaded from the cache)."""
        return self.step.lower(*self.args).compile()


def _capacity_bounded(routed_experts):
    """``routed_experts`` with each expert's slots past ceil(T K / E), in
    token order, dropped: routing at capacity factor 1.0."""
    def bounded(cfg, p, xf, eidx, w):
        T, K = eidx.shape
        E = cfg.num_experts
        oh = jax.nn.one_hot(eidx.reshape(-1), E, dtype=jnp.int32)
        rank = jnp.sum((jnp.cumsum(oh, axis=0) - oh) * oh, axis=-1).reshape(T, K)
        return routed_experts(cfg, p, xf, eidx,
                              jnp.where(rank < math.ceil(T * K / E), w, 0.0))
    return bounded


def plant(stack: ExitStack, faults):
    """Replace the program's functions that the mechanism faults name."""
    from repro.models import layers
    if "no_routed" in faults:
        stack.enter_context(mock.patch.object(
            layers, "routed_experts",
            lambda cfg, p, xf, eidx, w: jnp.zeros(xf.shape, jnp.float32)))
    if "capacity" in faults:
        stack.enter_context(mock.patch.object(
            layers, "routed_experts", _capacity_bounded(layers.routed_experts)))
    if "no_shared" in faults:
        stack.enter_context(mock.patch.object(
            layers, "shared_experts",
            lambda cfg, p, xf: jnp.zeros(xf.shape, jnp.float32)))
    if "no_rope" in faults:
        stack.enter_context(mock.patch.object(
            layers, "mla_rope",
            lambda cfg, q_pe, k_pe, positions: (jnp.zeros_like(q_pe),
                                                jnp.zeros_like(k_pe))))


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def hlo_path(cell: str) -> str:
    return os.path.join(harness.WORK, "hlo", f"{cell}.txt")


def run(r: Run) -> Outcome:
    from repro.models import lm
    from repro.train import loop

    cfg, tr = r.cfg, r.traffic
    pcfg = program_config(cfg)
    warm, batch = tr["warmup_steps"], tr["batch"]
    params = jax.jit(partial(reference_vlm.init_params, cfg))(r.key())
    pool = train_pool(cfg, tr, r.seed)
    observer = StepObserver(tr["compared_steps"], r.faults)
    win = r.window()
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
            marks["traced_to"] = step
            win.close_trace(steps=step - marks["closed"])
            signal.raise_signal(signal.SIGTERM)

    with ExitStack() as stack:
        stack.enter_context(mock.patch.object(
            loop, "make_lm_step", observer.make(loop.make_lm_step)))
        plant(stack, r.faults)
        stack.callback(signal.signal, signal.SIGTERM,
                       signal.getsignal(signal.SIGTERM))
        res = loop.run_lm_training(
            pcfg, params, lm.init_router_state(pcfg),
            _feed(pool, "half_batch" in r.faults), steps=tr["total_steps"],
            lr=tr["lr"], ckpt_dir=None, resume=False,
            hooks=loop.TrainHooks(heartbeat=heartbeat, log_every=0))
    win.finish(r)
    device_peak = r.memory_peak()
    losses = list(res.losses)
    failed = sum(1 for x in losses[warm:] if not np.isfinite(x))
    del res, params
    compiled = observer.compiled()
    ma = compiled.memory_analysis()
    step_peak = int(getattr(ma, "peak_memory_in_bytes", 0) or 0)
    out = Outcome(window=win, attempted=win.steps, failed=failed,
                  memory_peak_bytes=max(device_peak, step_peak))
    images = win.steps * batch
    out.e2e["train_images_per_s"] = images / win.seconds
    out.info.update(images=images, batch=batch, steps=win.steps,
                    device_peak_bytes=device_peak, step_peak_bytes=step_peak)
    if r.trace:
        a, b = marks["closed"], marks["traced_to"]
        slots = np.asarray(jax.device_get(observer.routed[b]), np.int64) - np.asarray(
            jax.device_get(observer.routed[a]), np.int64)
        out.info.update(traced_steps=win.traced_steps,
                        traced_images_per_s=win.traced_steps * batch
                        / win.traced_seconds,
                        traced_routed_slots=int(slots.sum()))
        os.makedirs(os.path.dirname(hlo_path(r.cell)), exist_ok=True)
        with open(hlo_path(r.cell), "w") as fh:
            fh.write(compiled.as_text())
    del compiled
    observer.routed.clear()
    program = dict(losses=losses[: tr["compared_steps"]],
                   grad=jax.tree.map(lambda m: m / np.float32(1 - B1), observer.m1),
                   change=jax.tree.map(np.subtract, observer.p3, observer.p0))
    p0, observer.p0 = observer.p0, None
    observer.m1 = observer.p3 = None
    ref = reference_steps(cfg, tr, pool, p0)
    out.checks = checks(numbers(program, ref), r.limits)
    return out


def numbers(program: Dict, ref: Dict) -> Dict[str, float]:
    """The compared numbers of a training cell, as ``drive_train.numbers``,
    from each run's losses, first gradient and change over the steps."""
    keep = compare.moving_leaves(ref["grad"])
    return {
        "loss": compare.loss_gap(program["losses"], ref["losses"]),
        "grad": compare.median_leaf_gap(program["grad"], ref["grad"]),
        "change": compare.worst_leaf_gap(program["change"], ref["change"], keep),
    }


@functools.lru_cache(maxsize=2)
def _reference_step(cfg_json: str):
    return reference_vlm.make_step(json.loads(cfg_json))


def reference_steps(cfg: Dict, tr: Dict, pool, p0, dtype=jnp.float32):
    """The reference's first ``compared_steps`` steps from ``p0`` (host) on
    the pool's first batches: losses, the first clipped gradient, and each
    parameter's change over the steps, on the host."""
    step = _reference_step(json.dumps(cfg, sort_keys=True))
    s = reference_vlm.sizes(cfg)
    p0 = jax.tree.map(lambda a: np.asarray(a, dtype), p0)
    params = jax.device_put(p0)
    moments = [jax.tree.map(lambda a: np.zeros(a.shape, np.float32), p0)
               for _ in range(2)]
    bias = jnp.zeros((s["Lm"], s["E"]), jnp.float32)
    losses, grad0 = [], None
    for k in range(tr["compared_steps"]):
        lr = reference.learning_rate(k, tr["lr"], tr["lr_warmup"], tr["total_steps"])
        params, bias, loss, g = step(params, moments, bias, pool[k], k + 1, lr,
                                     tr["reference_microbatch"], keep_grad=k == 0)
        losses.append(loss)
        if k == 0:
            grad0 = g
    del moments
    change = jax.tree.map(lambda a, b: np.asarray(jax.device_get(a)) - b, params, p0)
    return dict(losses=losses, grad=grad0, change=change)


def control(cfg: Dict, tr: Dict, seed: int) -> Dict[str, float]:
    """The control's numbers on one seed: the reference computed with its
    parameters and residual stream in bfloat16, against the reference."""
    params = jax.device_get(jax.jit(partial(reference_vlm.init_params, cfg))(
        harness.seed_key(seed)))
    pool = train_pool(cfg, tr, seed)
    ref = reference_steps(cfg, tr, pool, params)
    low = reference_steps(cfg, tr, pool, params, dtype=jnp.bfloat16)
    return numbers(low, ref)

