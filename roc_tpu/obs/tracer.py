"""Host-side span tracer: ring buffer, monotonic clocks, Chrome export,
and the same spans as `jax.profiler` trace annotations.

The repo's timing story before this module was ad hoc: epoch wall-clock in
the driver, probe loops in the balancer, `_time.perf_counter()` pairs in
`reshard` — each with its own roclint waiver and no common schema.  This
module is now the ONE sanctioned wall-clock site (the `raw-timing` lint
rule in roc_tpu/analysis/lint.py enforces it): everything times through

    with obs.span("epoch", epoch=3) as sp:
        ...
    wall = sp.dur_s

A span ALWAYS measures (callers like the driver's epoch loop and the
balance probe use `dur_s` as their timing primitive, tracing on or off);
it is only *recorded* into the ring when tracing is enabled — via
``ROC_OBS=1`` in the environment, ``-obs`` on the CLI, or ``enable()``.
Disabled spans cost two `perf_counter_ns` calls and a list append/pop
(~1 µs; the selftest and tests/test_obs.py gate this), so instrumentation
stays on the hot path unconditionally.

Two clocks, one span system.  The ring stamps `time.perf_counter_ns`,
which no device trace shares: its export is Chrome trace-event JSON
(`{"traceEvents": [{"ph": "X", ...}]}`, timestamps/durations in
microseconds), loadable in Perfetto / chrome://tracing on its own.  To put
the spans on the clock the device plane uses, the same switch arms a
bridge: while armed, every span is *also* a `jax.profiler.TraceAnnotation`
named ``roc.<name>`` (span args as the annotation's stats), opened and
closed with the span, so a live profiler session (the benchmark's
`--trace 1`, the CLI's `-profile DIR`) holds the program's spans on the
host plane of its `.xplane.pb`, nested as the spans nest.  `annotate()`
arms the bridge alone, for a profiled window without `-obs`.  This module
imports no JAX: `jax.profiler` is looked up by the first span after
arming, and where it cannot be imported the spans go on without it.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Set

DEFAULT_CAPACITY = 65536  # spans kept; old ones fall off the ring
ANNOTATION_PREFIX = "roc."  # a span's name in a jax.profiler trace


class Span:
    """One closed span.  ``start_ns`` is `time.perf_counter_ns` (monotonic,
    process-local — NOT wall time); ``depth`` is the nesting level within
    its thread at open time (0 = top level)."""

    __slots__ = ("name", "start_ns", "dur_ns", "tid", "depth", "args")

    def __init__(self, name: str, start_ns: int, dur_ns: int, tid: int,
                 depth: int, args: Optional[dict]):
        self.name = name
        self.start_ns = start_ns
        self.dur_ns = dur_ns
        self.tid = tid
        self.depth = depth
        self.args = args

    @property
    def dur_s(self) -> float:
        return self.dur_ns / 1e9

    def to_event(self) -> dict:
        """Chrome trace-event "complete" ("X") event, microsecond units."""
        ev = {"ph": "X", "name": self.name, "cat": "roc",
              "ts": self.start_ns / 1e3, "dur": self.dur_ns / 1e3,
              "pid": os.getpid(), "tid": self.tid}
        if self.args:
            ev["args"] = {k: _jsonable(v) for k, v in self.args.items()}
        return ev


def _jsonable(v):
    try:
        json.dumps(v)
        return v
    except TypeError:
        return str(v)


class _SpanCtx:
    """Context manager for one span: measures on exit, records into the
    tracer's ring only when tracing is enabled at close time."""

    __slots__ = ("_tracer", "name", "args", "start_ns", "dur_ns", "depth",
                 "_ann")

    def __init__(self, tracer: "SpanTracer", name: str, args: dict):
        self._tracer = tracer
        self.name = name
        self.args = args
        self.start_ns = 0
        self.dur_ns = 0
        self.depth = 0
        self._ann = None

    def __enter__(self) -> "_SpanCtx":
        t = self._tracer
        stack = t._stack()
        self.depth = len(stack)
        stack.append(self)
        make = t._annotation
        if make is not None:
            # the annotation encloses the span's own clock reads
            self._ann = make(ANNOTATION_PREFIX + self.name, **self.args)
            if self._ann is not None:
                self._ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.dur_ns = time.perf_counter_ns() - self.start_ns
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        t = self._tracer
        stack = t._stack()
        if stack and stack[-1] is self:
            stack.pop()
        if t.enabled:
            t._ring.append(Span(self.name, self.start_ns, self.dur_ns,
                                threading.get_ident(), self.depth,
                                self.args or None))
        return False

    @property
    def dur_s(self) -> float:
        return self.dur_ns / 1e9


class SpanTracer:
    """Ring buffer of closed spans + per-thread open-span stacks."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.enabled = False
        self._annotation = None     # makes a span's TraceAnnotation when armed
        self._ring: deque = deque(maxlen=capacity)
        self._tls = threading.local()

    def annotate(self, on: bool = True) -> bool:
        """Arm (or disarm) the trace annotations alone; returns whether
        they were armed before, for a caller that restores it."""
        was = self._annotation is not None
        if bool(on) != was:
            self._annotation = self._first_annotation if on else None
        return was

    def _first_annotation(self, name: str, **args):
        """The first span after arming looks `jax.profiler` up (not this
        module's import: kernel modules import it before JAX).  Without a
        usable profiler the bridge disarms and the spans go on alone."""
        try:
            from jax.profiler import TraceAnnotation
        except Exception:
            self._annotation = None
            return None
        if self._annotation is not None:    # not disarmed meanwhile
            self._annotation = TraceAnnotation
        return TraceAnnotation(name, **args)

    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def span(self, name: str, **args) -> _SpanCtx:
        return _SpanCtx(self, name, args)

    def spans(self) -> List[Span]:
        return list(self._ring)

    def span_types(self) -> Set[str]:
        return {s.name for s in self._ring}

    def clear(self):
        self._ring.clear()

    def summary(self) -> Dict[str, dict]:
        """Per-span-type aggregate: count, total/mean/max seconds."""
        out: Dict[str, dict] = {}
        for s in self._ring:
            st = out.setdefault(s.name, {"count": 0, "total_s": 0.0,
                                         "max_s": 0.0})
            st["count"] += 1
            st["total_s"] += s.dur_s
            st["max_s"] = max(st["max_s"], s.dur_s)
        for st in out.values():
            st["mean_s"] = st["total_s"] / st["count"]
        return out

    def to_chrome_trace(self) -> dict:
        return {"traceEvents": [s.to_event() for s in self._ring],
                "displayTimeUnit": "ms"}

    def write_chrome_trace(self, path: str) -> bool:
        """Best-effort write (observability must never kill a run)."""
        try:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            with open(path, "w", encoding="utf-8") as f:
                json.dump(self.to_chrome_trace(), f)
                f.write("\n")
            return True
        except OSError:
            return False


def validate_chrome_trace(obj) -> List[str]:
    """Schema problems in a Chrome trace dict ([] = Perfetto-loadable).
    Used by the tests and `python -m roc_tpu.obs selftest`."""
    problems: List[str] = []
    if not isinstance(obj, dict) or "traceEvents" not in obj:
        return ["top level must be a dict with a 'traceEvents' list"]
    events = obj["traceEvents"]
    if not isinstance(events, list):
        return ["'traceEvents' must be a list"]
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            problems.append(f"event {i}: not a dict")
            continue
        for key in ("ph", "name", "ts", "pid", "tid"):
            if key not in ev:
                problems.append(f"event {i}: missing {key!r}")
        if ev.get("ph") == "X" and "dur" not in ev:
            problems.append(f"event {i}: complete event missing 'dur'")
        for key in ("ts", "dur"):
            if key in ev and not isinstance(ev[key], (int, float)):
                problems.append(f"event {i}: {key!r} not numeric")
    return problems


# -- module singleton ------------------------------------------------------
# ROC_OBS=1 arms tracing at import so driverless entry points (bench.py,
# pytest fixtures) record without plumbing a flag; Config mirrors the same
# env into cfg.obs and the driver calls enable() for the CLI path.

_TRACER = SpanTracer()


def get_tracer() -> SpanTracer:
    return _TRACER


def span(name: str, **args) -> _SpanCtx:
    return _TRACER.span(name, **args)


def enable(on: bool = True):
    """The one switch: record spans into the ring and mirror them as
    ``roc.<name>`` trace annotations."""
    _TRACER.enabled = bool(on)
    _TRACER.annotate(on)


def annotate(on: bool = True) -> bool:
    """Arm the trace annotations without recording (a `-profile` window
    without `-obs`); returns the previous state."""
    return _TRACER.annotate(on)


def enabled() -> bool:
    return _TRACER.enabled


enable(os.environ.get("ROC_OBS", "") == "1")
