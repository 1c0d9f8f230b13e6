"""From a profiler trace of the window to the numbers the metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes. Device
operations are the events of the ``XLA Ops`` line of each ``/device:TPU:<n>``
plane, timed from the profile's start time. The host spans are the
benchmark's own (``harness.span``), on the host's wall clock, which the
profile's start time is read on. The traced window is the ``window`` span.

* busy: the union of the device-op intervals inside the window, averaged
  over the devices that ran any;
* idle gaps: the stretches of the window in which no op ran, each labelled
  with the innermost benchmark span open at its middle;
* breakdown: the ten ops with most device time, and the ten longest gaps.
"""
from __future__ import annotations

import glob
import gzip
import os
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

OPS_LINE = "XLA Ops"


def find(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str, spans: Sequence[Tuple[int, int, str]]) -> Dict:
    """Read an ``.xplane.pb``, or one gzipped (``.gz``), with the host spans
    recorded while it ran."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as fh:
            pd = ProfileData.from_serialized_xspace(fh.read())
    else:
        pd = ProfileData.from_file(path)
    return from_profile(pd, spans)


def from_profile(pd, spans: Sequence[Tuple[int, int, str]]) -> Dict:
    """Device ops per device, as ``(start_ns, end_ns, name)``, and the host
    spans moved onto the profile's clock."""
    devices: Dict[str, List[Tuple[float, float, str]]] = {}
    t0 = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    # on a TPU the event is named by its whole HLO text
                    name = ev.name.split(" = ")[0].lstrip("%")
                    ops.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                name))
            if ops:
                devices[plane.name] = sorted(ops)
        elif plane.name == "Task Environment":
            t0 = dict(plane.stats)["profile_start_time"]
    if t0 is None:
        raise ValueError("the trace has no profile start time")
    return {"devices": devices,
            "spans": sorted((s - t0, e - t0, n) for s, e, n in spans)}


def union(intervals: Sequence[Tuple[float, float]], lo: float, hi: float
          ) -> Tuple[float, List[Tuple[float, float]]]:
    """Covered length of ``[lo, hi]`` and the uncovered gaps, in order."""
    covered, gaps, cur = 0.0, [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= cur:
            continue
        if s > cur:
            gaps.append((cur, s))
            cur = s
        covered += e - cur
        cur = e
    if cur < hi:
        gaps.append((cur, hi))
    return covered, gaps


def label(t: float, spans: Sequence[Tuple[float, float, str]]) -> str:
    """The innermost benchmark span (other than the window) open at ``t``."""
    best, width = "host", float("inf")
    for s, e, name in spans:
        if name != "window" and s <= t <= e and e - s < width:
            best, width = name, e - s
    return best


def reduce(tr: Dict, top: int = 10) -> Dict:
    windows = [(s, e) for s, e, n in tr["spans"] if n == "window"]
    if not windows or not tr["devices"]:
        raise ValueError("the trace holds no window span or no device op")
    lo, hi = windows[0]
    busy, gaps, per_op = [], [], defaultdict(float)
    for ops in tr["devices"].values():
        b, g = union([(s, e) for s, e, _ in ops], lo, hi)
        busy.append(b)
        gaps.extend(g)
        for s, e, name in ops:
            per_op[name] += max(0.0, min(e, hi) - max(s, lo))
    n_dev = len(tr["devices"])
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy) / n_dev * 1e-9
    gaps.sort(key=lambda g: g[0] - g[1])
    by_label = [[label((s + e) / 2, tr["spans"]), (e - s) * 1e-9]
                for s, e in gaps[:top]]
    ops_top = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "breakdown": {"device_ops": [[k, v * 1e-9] for k, v in ops_top],
                      "idle_gaps": by_label},
    }
