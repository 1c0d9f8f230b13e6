"""The program's trainer-loop spans laid over the device trace: what the
host was doing while the device was idle, for the ``train.idle_*_share``
metrics.

The program records its spans in ``repro.spans.RECORDER`` on the clock of
the benchmark's ``window`` span: ``train.step`` around each loop iteration,
and inside it ``train.next``, ``train.put``, ``train.dispatch``,
``train.fetch`` and ``train.hooks``. Each stretch of the window in which no
device op ran (``tracereduce.union``) is cut at the program spans' edges,
and each piece goes to the innermost program span open in it
(``tracereduce.label``):

* ``input``: ``train.next`` or ``train.put``;
* ``dispatch``: ``train.dispatch``;
* ``launch``: ``train.fetch``, before the step's first device op;
* ``fetch``: ``train.fetch``, from the step's first device op on;
* ``other``: ``train.hooks``, a bare ``train.step``, or no program span.

A step's device ops are those that start at or after its
``train.dispatch`` opens. Over the same window and the same device ops as
``train.device_idle_share``, the five shares add up to it.

A program without the recorder, or one that recorded no span in the
window, gives no shares, and the readers report nothing.
"""
from __future__ import annotations

import bisect
import functools
import os
from typing import Dict, Optional, Sequence, Tuple

import harness
import tracereduce

PHASES = ("input", "dispatch", "launch", "fetch", "other")
PHASE_OF = {"train.next": "input", "train.put": "input",
            "train.dispatch": "dispatch", "train.fetch": "fetch"}


def split(tr: Dict) -> Dict[str, float]:
    """Each phase's share of the window, from a trace that ``tracereduce``
    loaded with the ``window`` span and the program's spans."""
    (lo, hi), = [(s, e) for s, e, n in tr["spans"] if n == "window"]
    prog = [sp for sp in tr["spans"] if sp[2] != "window"]
    # each step's spans, so that a gap is labelled from a handful of them
    steps = sorted(sp for sp in prog if sp[2] == "train.step")
    starts = [s for s, _, _ in steps]
    groups = [[sp] for sp in steps]
    for sp in prog:
        i = bisect.bisect_right(starts, sp[0]) - 1
        if sp[2] != "train.step" and i >= 0:
            groups[i].append(sp)
    edges = sorted({t for s, e, _ in prog for t in (s, e)})
    dispatches = sorted(s for s, _, n in prog if n == "train.dispatch")

    idle = dict.fromkeys(PHASES, 0.0)
    for ops in tr["devices"].values():
        op_starts = [s for s, _, _ in ops]
        _, gaps = tracereduce.union([(s, e) for s, e, _ in ops], lo, hi)
        for a, b in gaps:
            cuts = edges[bisect.bisect_right(edges, a):bisect.bisect_left(edges, b)]
            for p, q in zip([a, *cuts], [*cuts, b]):
                mid = (p + q) / 2
                i = bisect.bisect_right(starts, mid) - 1
                name = tracereduce.label(mid, groups[i] if i >= 0 else [])
                phase = PHASE_OF.get(name, "other")
                if phase == "fetch" and mid < _first_op(mid, dispatches, op_starts):
                    phase = "launch"
                idle[phase] += q - p
    scale = len(tr["devices"]) * (hi - lo)
    return {k: v / scale for k, v in idle.items()}


def _first_op(t: float, dispatches: Sequence[float], op_starts: Sequence[float]
              ) -> float:
    """The start of the first device op of the step whose fetch is open at
    ``t``: the first op at or after the last dispatch opened before ``t``."""
    j = bisect.bisect_right(dispatches, t) - 1
    if j < 0:
        return float("-inf")
    k = bisect.bisect_left(op_starts, dispatches[j])
    return op_starts[k] if k < len(op_starts) else float("inf")


@functools.lru_cache(maxsize=1)
def _shares(cell: str, window: Tuple[int, int, str]) -> Optional[Dict[str, float]]:
    try:
        from repro.spans import RECORDER
    except ImportError:          # a program older than its recorder
        return None
    program = [(s, e, n) for s, e, n, _ in RECORDER.events(window[0], window[1])]
    if not program:
        return None
    path = tracereduce.find(os.path.join(harness.WORK, "trace", cell))
    return split(tracereduce.load(path, [window, *program]))


def share(ctx: Dict, phase: str) -> Optional[float]:
    """The phase's share of the traced stretch of the context's run, in %."""
    windows = [sp for sp in harness.span.events if sp[2] == "window"]
    if not windows:
        return None
    shares = _shares(ctx["cell"]["name"], tuple(windows[0]))
    return None if shares is None else 100.0 * shares[phase]
