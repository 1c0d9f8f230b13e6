"""Device time of the VLM training step by stage, for the ``vlm.*`` metrics.

The trace is the cell's ``.xplane.pb`` (``tracereduce``); its device ops
are named by their HLO instruction. ``drive_vlmtrain`` saves the
compiled step's HLO text (``hlo_path``), whose ``op_name`` metadata carries
the program's named scopes, also through the backward pass and the
rematerialised forward (``transpose(jvp(...))/.../mla/...``). An op's stage
is the scope of ``STAGES`` that its ``op_name`` names. The TPU's grouped
matmul (``lax.ragged_dot``) is a custom call whose ``op_name`` carries no
scope; it is found by its instruction name (``ragged-dot``, less its
metadata call) and is the routed experts' work. A loop's own op
(``while``) spans its body's ops and carries the scope around the loop
(the tower's scan is one): it is left out, so that each op counts once.

Only ops inside the step's own module (``jit_step_fn``, from the trace's
``XLA Modules`` line) and inside the ``window`` span count, clipped to it.
A program without the scopes, or a run without the HLO text, gives nothing.
"""
from __future__ import annotations

import functools
import os
import re
from collections import defaultdict
from typing import Dict, Optional, Tuple

import harness
import tracereduce

STAGES = ("vision", "projector", "mla", "moe.router", "moe.routed",
          "moe.shared", "dense_mlp", "lm_head")
GROUPS = {"vision": ("vision", "projector"), "mla": ("mla",),
          "moe": ("moe.router", "moe.routed", "moe.shared")}
GMM = re.compile(r"^ragged-dot(?!-metadata)")
STEP_MODULE = "jit_step_fn"
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?op_name="([^"]*)"')
_LOOP = re.compile(r" while\(")
_SCOPE = {s: re.compile(r"(?:^|[/(])" + re.escape(s) + r"(?:$|[/)])")
          for s in STAGES}


def op_names(hlo_text: str) -> Dict[str, str]:
    """Each instruction's ``op_name`` in the compiled module's text, loops
    left out."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m and not _LOOP.search(line):
            out[m.group(1)] = m.group(2)
    return out


def stage(name: str, op_name: Optional[str]) -> Optional[str]:
    """The stage of the op named ``name`` whose metadata is ``op_name``."""
    if GMM.match(name):
        return "moe.routed"
    if not op_name:
        return None
    found = [(m.start(), s) for s, rx in _SCOPE.items()
             for m in [rx.search(op_name)] if m]
    return max(found)[1] if found else None


def step_ops(pd, spans) -> Tuple[Dict, Tuple[float, float]]:
    """The step module's device ops per device, ``(start, end, name)`` on
    the profile's clock, and the window."""
    tr = tracereduce.from_profile(pd, spans)
    (lo, hi), = [(s, e) for s, e, n in tr["spans"] if n == "window"]
    modules = defaultdict(list)
    for plane in pd.planes:
        if plane.name in tr["devices"]:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules[plane.name] = sorted(
                        (ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events if ev.name.startswith(STEP_MODULE))
    out = {}
    for dev, ops in tr["devices"].items():
        mods = modules[dev]
        out[dev] = [op for op in ops if any(a <= op[0] <= b for a, b in mods)]
    return out, (lo, hi)


def stage_seconds(pd, spans, names: Dict[str, str]) -> Dict[str, float]:
    """Seconds of each stage, and of the grouped matmuls (``gmm``), in the
    window, averaged over the devices that ran the step."""
    ops, (lo, hi) = step_ops(pd, spans)
    total = defaultdict(float)
    for dev_ops in ops.values():
        for s, e, name in dev_ops:
            t = max(0.0, min(e, hi) - max(s, lo)) * 1e-9
            st = stage(name, names.get(name))
            if st:
                total[st] += t
            if GMM.match(name):
                total["gmm"] += t
    n = max(1, len(ops))
    return {k: v / n for k, v in total.items()}


@functools.lru_cache(maxsize=1)
def _cached(cell: str, window: Tuple[int, int, str]) -> Optional[Dict[str, float]]:
    from drive_vlmtrain import hlo_path
    if not os.path.exists(hlo_path(cell)):
        return None
    with open(hlo_path(cell)) as fh:
        names = op_names(fh.read())
    from jax.profiler import ProfileData
    path = tracereduce.find(os.path.join(harness.WORK, "trace", cell))
    return stage_seconds(ProfileData.from_file(path), [window], names)


def seconds(ctx: Dict) -> Optional[Dict[str, float]]:
    """The context's run: each stage's device seconds in the traced window."""
    windows = [sp for sp in harness.span.events if sp[2] == "window"]
    if not windows:
        return None
    return _cached(ctx["cell"]["name"], tuple(windows[0]))


def group_share(ctx: Dict, group: str) -> Optional[float]:
    """The group's share of the device's busy time in the window, in %."""
    sec = seconds(ctx)
    if not sec or not any(sec.get(s) for s in GROUPS[group]):
        return None
    return 100.0 * sum(sec.get(s, 0.0) for s in GROUPS[group]) / ctx["trace"]["busy_s"]
