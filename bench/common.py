"""Pieces every driver and reader shares: the run record, the compile log,
the table of peaks, and the profiler window."""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import tempfile
import time
from typing import Any, Dict, List, Optional

import jax

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip; a device not in the table is an
    error."""
    table = load_json(BENCH_DIR, "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table has {sorted(table)}")
    return table[device_kind]


class CompileLog:
    """Backend compiles and persistent-cache hits, from JAX's own monitoring
    events (a program served by the cache counts as a hit, not a compile)."""

    def __init__(self):
        self.secs, self.n, self.hits, self.misses = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.n += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


@dataclasses.dataclass
class Check:
    """One number compared with its limit; ``ok`` is value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Run:
    """What a driver hands back: counts, end-to-end metrics, the records
    readers take per-layer metrics from, and the checks of ``correct``."""
    attempted: int = 0
    failed: int = 0
    metrics: Dict[str, float] = dataclasses.field(default_factory=dict)
    requests: List[dict] = dataclasses.field(default_factory=list)
    steps: List[dict] = dataclasses.field(default_factory=list)
    window_s: float = 0.0
    trace: Optional[dict] = None
    checks: List[Check] = dataclasses.field(default_factory=list)
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)
    memory_peak_bytes: int = 0
    # what the check compared, for bench/control.py to read again
    compared: Dict[str, Any] = dataclasses.field(default_factory=dict)


def memory_peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.devices())) if stats else 0


class SubTrace:
    """With ``on``, traces a part of the measured window with the JAX
    profiler: from ``START`` of the window's length after it opens, for
    ``LENGTH`` of it (at most ``MAX_S``), marked by a ``bench.window`` host
    annotation. The driver calls :meth:`poll` with the seconds since the
    window opened as it goes, and :meth:`finish` once the window and its
    drain are over: only then is the trace stopped, written and reduced
    into ``run.trace`` (stopping takes seconds, which inside the window
    would stall the traffic). The device keeps the first events of the
    trace when its buffer fills, which a part of ``MAX_S`` stays within."""
    START, LENGTH, MAX_S = 0.4, 0.5, 10.0

    def __init__(self, on: bool, run: Run, seconds: float):
        self.on, self.run = on, run
        self.t0 = self.START * seconds
        self.t1 = self.t0 + min(self.LENGTH * seconds, self.MAX_S)
        self.dir = self.mark = None

    def poll(self, elapsed: float) -> None:
        if not self.on:
            return
        if self.dir is None and elapsed >= self.t0:
            t = time.perf_counter()
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self.dir)
            self.mark = jax.profiler.TraceAnnotation("bench.window")
            self.mark.__enter__()
            self.run.notes["trace_start_s"] = time.perf_counter() - t
        elif self.mark is not None and elapsed >= self.t1:
            self.mark.__exit__(None, None, None)
            self.mark = None

    def finish(self) -> None:
        if self.dir is None:
            return
        if self.mark is not None:
            self.mark.__exit__(None, None, None)
        t = time.perf_counter()
        jax.profiler.stop_trace()
        from bench import trace_reduce
        try:
            self.run.trace = trace_reduce.reduce_dir(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        self.run.notes["trace_stop_and_reduce_s"] = time.perf_counter() - t
        self.run.notes["idle_by_host_span_s"] = self.run.trace[
            "idle_by_host_span"]
