"""What every driver shares: the run's inputs, its window, its outcome."""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import shutil
import sys
import time
from typing import Dict, FrozenSet, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".work")


def seed_key(seed: int):
    """The weights' PRNG key, drawn from the seed (any size of whole number)."""
    import jax
    return jax.random.key(int(np.random.default_rng((seed, 3)).integers(2 ** 31)))


class CompileCounter:
    """Counts the XLA programs the process obtains, and how many of them the
    persistent compilation cache supplied. JAX times every program it
    obtains as a backend compile, whether it compiled it or loaded it."""

    def __init__(self):
        self.programs = 0
        self.cache_hits = 0

    def install(self):
        from jax._src import monitoring
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1

    def _on_event(self, event: str, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @property
    def compiled(self) -> int:
        return self.programs - self.cache_hits


@dataclasses.dataclass
class Run:
    """One run of one cell, as the command line and the data files give it."""
    cell: str
    cfg: Dict
    traffic: Dict
    limits: Dict
    seed: int
    seconds: float
    trace: bool
    t_start: float                      # time.monotonic() at process start
    counter: CompileCounter
    faults: FrozenSet[str] = frozenset()
    device: Optional[object] = None

    def window(self) -> "Window":
        if not self.trace:
            return Window(self.seconds)
        return Window(self.seconds, self.traffic["trace_seconds"])

    def key(self):
        return seed_key(self.seed)

    def program_config(self):
        """The configuration file as the program's own config object."""
        from repro.configs.base import XRConfig
        fields = {f.name for f in dataclasses.fields(XRConfig)}
        kw = {k: v for k, v in self.cfg.items() if k in fields}
        kw["input_hw"] = tuple(kw["input_hw"])
        kw["stages"] = tuple(tuple(s) for s in kw["stages"])
        if "decoder_channels" in kw:
            kw["decoder_channels"] = tuple(kw["decoder_channels"])
        return XRConfig(**kw)

    def memory_peak(self) -> int:
        stats = self.device.memory_stats() if self.device is not None else None
        return int((stats or {}).get("peak_bytes_in_use", 0))

    def trace_dir(self) -> str:
        return os.path.join(WORK, "trace", self.cell)


class Spans:
    """The benchmark's own host spans, ``(start_ns, end_ns, name)`` on the
    host's wall clock, which the profiler's trace shares. They are recorded
    here, and only while the profiler runs, because the profiler's host
    tracer is left off: it costs the host time for every device op (about
    0.6 s a ``detnet.train`` step, which then leaves the device idle)."""

    def __init__(self):
        self.on = False
        self.events: List = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.time_ns() if self.on else None
        try:
            yield
        finally:
            if t0 is not None:
                self.events.append((t0, time.time_ns(), name))


span = Spans()


class Window:
    """The measured window: opened when set-up ends, closed after
    ``seconds``. With ``--trace 1`` a traced stretch of ``trace_seconds``
    follows it, so that the end-to-end rates and ``train_mfu`` are taken
    with the profiler off, and the trace is read only for what the device
    did."""

    def __init__(self, seconds: float, trace_seconds: Optional[float] = None):
        self.seconds_wanted = seconds
        self.trace_seconds = trace_seconds
        self.t0 = self.t1 = self.tt0 = self.tt1 = None
        self.setup_s = None
        self.compiles_setup = self.compiles_window = None
        self.steps = self.traced_steps = 0
        self._span = None
        self._tracing = False

    def open(self, r: Run):
        self.compiles_setup = r.counter.programs
        self.t0 = time.monotonic()
        self.setup_s = self.t0 - r.t_start

    def due(self) -> bool:
        return time.monotonic() - self.t0 >= self.seconds_wanted

    def close(self, steps: int):
        self.t1 = time.monotonic()
        self.steps = steps

    def open_trace(self, r: Run):
        """Start the profiler, device ops only, and the host spans."""
        import jax
        shutil.rmtree(r.trace_dir(), ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(r.trace_dir(), profiler_options=opts)
        self._tracing = True
        span.events.clear()
        span.on = True
        self._span = span("window")
        self._span.__enter__()
        self.tt0 = time.monotonic()

    def trace_due(self) -> bool:
        return time.monotonic() - self.tt0 >= self.trace_seconds

    def close_trace(self, steps: int):
        self.tt1 = time.monotonic()
        self.traced_steps = steps
        self._span.__exit__(None, None, None)
        self._span = None
        span.on = False

    def finish(self, r: Run):
        """After the window: stop the profiler and count late compiles."""
        import jax
        if self._tracing:
            jax.profiler.stop_trace()
            self._tracing = False
        self.compiles_window = r.counter.programs - self.compiles_setup

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    @property
    def traced_seconds(self) -> float:
        return self.tt1 - self.tt0


@dataclasses.dataclass
class Outcome:
    window: Window
    attempted: int
    failed: int
    memory_peak_bytes: int
    e2e: Dict[str, float] = dataclasses.field(default_factory=dict)
    checks: Dict[str, Dict[str, float]] = dataclasses.field(default_factory=dict)
    info: Dict = dataclasses.field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            np.isfinite(c["value"]) and c["value"] <= c["limit"]
            for c in self.checks.values()) and self.failed == 0


def load_json(*parts) -> Dict:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def say(*args):
    print(*args, file=sys.stderr, flush=True)


def checks(numbers: Dict[str, float], limits: Dict[str, float]
           ) -> Dict[str, Dict[str, float]]:
    """Each number that the limits file names, beside its limit."""
    return {k: {"value": numbers.get(k, float("inf")), "limit": lim}
            for k, lim in limits.items()}


def check_lines(checks: Dict[str, Dict[str, float]]) -> List[str]:
    return [f"check {k}: {c['value']!r} (limit {c['limit']!r})"
            for k, c in checks.items()]
