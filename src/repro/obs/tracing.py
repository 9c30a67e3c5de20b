"""Structured tracing: named spans on the host clock and the profiler's.

The serve/train loops are host-driven: every micro-batch is a sequence of
host stages (assemble/rewrite, jitted device step, telemetry, maybe a
replan+migrate+swap) and the p99 question is always "which stage did the
spike live in". ``Tracer.span`` answers it on two clocks at once:

* **The profiler's.** Every span opens a ``jax.profiler.TraceAnnotation``
  of its name and ``args``, so any jax profiler session (``jax.profiler.trace``,
  XProf, the benchmark's ``--trace 1``) shows it on the host's track beside
  the device ops it dispatched. Outside a session the annotation records
  nothing and costs about as much as an empty ``with``.
* **The tracer's own record.** With ``enabled`` set, the span is also timed
  with ``perf_counter`` into ``records``, which
  ``trace_export.write_chrome_trace`` turns into the Chrome trace-event JSON
  Perfetto loads directly (``--trace-out``).

Contracts:

* **No device-sync side effects.** A span only reads the host clock. The
  caller decides where device work is forced (the serve loops already call
  ``jax.block_until_ready`` at the device-step boundary); a span around an
  UN-synced dispatch measures dispatch cost, which is sometimes exactly what
  you want. An annotation is not traced into a jitted function, so tracing a
  jit'd step cannot add executables (tests/test_obs.py pins the
  zero-recompile assert).
* **``enabled`` gates the record, not the annotation.** ``Tracer(enabled=
  False)`` (or the shared ``NULL_TRACER``) records nothing but still
  annotates, so instrumented code paths keep one shape whether or not
  ``--trace-out`` was passed, and reach a profiler trace either way.
* **jax-free import.** This module imports no jax: producers include the
  deliberately jax-free ``repro.dist.fault``. A span annotates only once
  ``jax.profiler`` is loaded (``import jax`` loads it); before that no
  profiler session can exist, and a span is a plain context.
* **Thread-correct nesting.** The open-span stack is thread-local; records
  carry the thread id so a future background-planner thread shows up as its
  own Perfetto track.
"""
from __future__ import annotations

import contextlib
import dataclasses
import sys
import threading
import time


@dataclasses.dataclass
class SpanRecord:
    """One completed span (Chrome trace 'X' event)."""

    name: str
    ts_us: float               # start, microseconds since the tracer epoch
    dur_us: float
    tid: int
    depth: int                 # nesting depth at start (0 = top level)
    args: dict


@dataclasses.dataclass
class InstantRecord:
    """A point event (Chrome trace 'i' event) — swap landed, fault fired."""

    name: str
    ts_us: float
    tid: int
    args: dict


@dataclasses.dataclass
class CounterRecord:
    """A gauge sample (Chrome trace 'C' event) — per-bank traffic, rolling
    p99. Perfetto renders each ``values`` key as one series in a counter
    track named ``name``, so a time-series of these becomes a load lane."""

    name: str
    ts_us: float
    tid: int
    values: dict


def _annotation(name: str, args: dict):
    """The profiler annotation of a span: a ``TraceAnnotation`` once jax's
    profiler is loaded, else a context that does nothing."""
    prof = sys.modules.get("jax.profiler")
    if prof is None:
        return contextlib.nullcontext()
    return prof.TraceAnnotation(name, **args)


class Tracer:
    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self.records: list[SpanRecord] = []
        self.instants: list[InstantRecord] = []
        self.counters: list[CounterRecord] = []
        self._epoch = time.perf_counter()
        self._local = threading.local()
        self._lock = threading.Lock()

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def span(self, name: str, **args):
        """Time a host stage. Nestable; ``args`` land in the trace event's
        ``args`` payload and the annotation's (keep them small and
        JSON-serializable)."""
        ann = _annotation(name, args)
        if not self.enabled:
            return ann
        return self._recorded(name, args, ann)

    @contextlib.contextmanager
    def _recorded(self, name: str, args: dict, ann):
        stack = self._stack()
        depth = len(stack)
        stack.append(name)
        t0 = time.perf_counter()
        try:
            with ann:
                yield
        finally:
            t1 = time.perf_counter()
            stack.pop()
            rec = SpanRecord(name=name, ts_us=(t0 - self._epoch) * 1e6,
                             dur_us=(t1 - t0) * 1e6,
                             tid=threading.get_ident(), depth=depth,
                             args=dict(args))
            with self._lock:
                self.records.append(rec)

    def instant(self, name: str, **args) -> None:
        """Mark a point in time (a swap landing, a fault firing)."""
        if not self.enabled:
            return
        rec = InstantRecord(name=name,
                            ts_us=(time.perf_counter() - self._epoch) * 1e6,
                            tid=threading.get_ident(), args=dict(args))
        with self._lock:
            self.instants.append(rec)

    def counter(self, name: str, **values) -> None:
        """Sample a gauge time-series (Chrome 'C' event): one call per
        batch per track; each keyword becomes a series in the track."""
        if not self.enabled:
            return
        rec = CounterRecord(name=name,
                            ts_us=(time.perf_counter() - self._epoch) * 1e6,
                            tid=threading.get_ident(),
                            values={k: float(v) for k, v in values.items()})
        with self._lock:
            self.counters.append(rec)

    # -- inspection helpers (tests, summaries) -------------------------------

    def span_names(self) -> set[str]:
        return {r.name for r in self.records}

    def spans(self, name: str) -> list[SpanRecord]:
        return [r for r in self.records if r.name == name]

    def total_us(self, name: str) -> float:
        """Summed duration of TOP-LEVEL-of-their-name spans. (Nested
        same-name spans would double-count; the serve loops don't nest
        same-name spans.)"""
        return sum(r.dur_us for r in self.records if r.name == name)


NULL_TRACER = Tracer(enabled=False)
