"""A flight recorder of the program's own host spans and counters.

A span is ``(start_ns, end_ns, name, step)`` on ``time.time_ns()``: the wall
clock that a ``jax.profiler`` trace's ``profile_start_time`` is read on, so
a reader can lay the spans over the device's ops. ``step`` ties a span to
the training step (or other unit of work) it belongs to.

The ring keeps the newest ``CAPACITY`` spans, about ten thousand training
steps of the trainer loop's six spans, and recording is always on: a span
costs about a microsecond of host time against steps of tens of
milliseconds. ``events`` refuses an interval the ring no longer holds whole,
so a reader never attributes time to a span that was dropped.

Counters are plain integers by name, for events rarer than a span, such as
a retrace of the training step.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Dict, List, Tuple

CAPACITY = 65536

SpanEvent = Tuple[int, int, str, int]       # (start_ns, end_ns, name, step)


class Span:
    """One span being recorded; ``with`` stamps its start and end. After the
    block, ``start_ns`` and ``end_ns`` hold the stamps."""

    __slots__ = ("_rec", "name", "step", "start_ns", "end_ns")

    def __init__(self, rec: "Recorder", name: str, step: int):
        self._rec, self.name, self.step = rec, name, step

    def __enter__(self) -> "Span":
        self.start_ns = time.time_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.end_ns = time.time_ns()
        self._rec._add((self.start_ns, self.end_ns, self.name, self.step))
        return False

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


class Recorder:
    """A bounded ring of spans and a dict of counters, safe across threads."""

    def __init__(self):
        self._ring: collections.deque = collections.deque(maxlen=CAPACITY)
        self._lost_ns = None        # latest end of a span the ring dropped
        self._counters: Dict[str, int] = {}
        self._lock = threading.Lock()

    def span(self, name: str, step: int) -> Span:
        return Span(self, name, step)

    def _add(self, ev: SpanEvent):
        with self._lock:
            if len(self._ring) == CAPACITY:
                end = self._ring[0][1]
                if self._lost_ns is None or end > self._lost_ns:
                    self._lost_ns = end
            self._ring.append(ev)

    def events(self, lo_ns: int, hi_ns: int) -> List[SpanEvent]:
        """The spans that overlap ``[lo_ns, hi_ns]``, ordered by start.
        Raises ``LookupError`` if the ring has dropped one of them."""
        with self._lock:
            if self._lost_ns is not None and self._lost_ns >= lo_ns:
                raise LookupError(
                    f"the span ring dropped spans up to {self._lost_ns} ns, "
                    f"inside the interval from {lo_ns} ns")
            return sorted(ev for ev in self._ring
                          if ev[1] >= lo_ns and ev[0] <= hi_ns)

    def count(self, name: str, n: int = 1):
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def counters(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counters)


RECORDER = Recorder()
