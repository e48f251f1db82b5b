"""Retrace guard: turn the frozen-shape invariant into an enforced property.

PR "balance" froze the padded shard shape precisely so that jit caches
survive a mid-run repartition — but nothing *enforced* it: a plan whose
chunk count drifts, a dtype that flips, or a step function rebuilt with a
new static argument silently retraces, and the cost shows up as an
unattributable per-epoch latency spike (the exact anomaly class PR 1
spent a cycle root-causing).  This module counts actual ``jax.jit``
tracings per step function and asserts that steady-state epochs (2..N)
and same-shape balancer reshards add **zero** new traces.

Mechanism: the step functions call :func:`note_trace` as their first
statement.  A Python function body only executes while jax is tracing it
— after the first compile the recorded XLA program runs without touching
Python — so the call is a perfect retrace counter with zero steady-state
overhead.  ``BaseTrainer.train`` reports epoch boundaries via
:func:`epoch_boundary`; an active :class:`RetraceGuard` arms itself after
``warmup`` boundaries and from then on treats every new trace as a
violation.
"""

from __future__ import annotations

from collections import Counter
from typing import List, Optional


class RetraceError(AssertionError):
    """A step function was re-traced after the guard armed."""


_ACTIVE: List["RetraceGuard"] = []


def note_trace(name: str) -> None:
    """Called from inside step functions at trace time (and only then)."""
    for g in _ACTIVE:
        g._note(name)


def epoch_boundary(epochs_done: int) -> None:
    """Called by the trainer after each completed epoch."""
    for g in _ACTIVE:
        g._boundary(epochs_done)


def active() -> Optional["RetraceGuard"]:
    """The innermost active guard, if any (the SpmdTrainer hook)."""
    return _ACTIVE[-1] if _ACTIVE else None


class RetraceGuard:
    """Context manager counting jit tracings per step function.

    ``warmup``: epoch boundaries to allow before arming (default 1 — the
    first epoch legitimately traces everything it touches; epochs 2..N
    must not).  ``on_violation``: "raise" aborts at the offending trace
    with the step name in the traceback (tests); "record" accumulates
    violations for a post-run report (the ``-analyze`` CLI, where a
    structure-changing reshard may be a deliberate choice whose recompile
    the operator wants *reported*, not fatal).
    """

    def __init__(self, warmup: int = 1, on_violation: str = "raise"):
        assert on_violation in ("raise", "record")
        self.warmup = int(warmup)
        self.on_violation = on_violation
        self.counts: Counter = Counter()
        self.violations: List[str] = []
        self._armed = False
        self._boundaries = 0

    def __enter__(self) -> "RetraceGuard":
        _ACTIVE.append(self)
        return self

    def __exit__(self, *exc):
        # idempotent: an engine closed from two threads at once (a kill
        # racing a close, tests/test_fleet.py's chaos) exits its guard twice
        if self in _ACTIVE:
            try:
                _ACTIVE.remove(self)
            except ValueError:  # roclint: allow(silent-swallow) — the other closer won the race
                pass
        return False

    # -- wiring (called via the module-level hooks) -----------------------
    def _note(self, name: str) -> None:
        self.counts[name] += 1
        if self._armed:
            msg = (f"retrace of {name!r} after {self._boundaries} "
                   f"epoch(s): a steady-state step recompiled (shape/"
                   f"dtype/plan-structure drift broke the frozen-shape "
                   f"invariant)")
            self.violations.append(msg)
            if self.on_violation == "raise":
                raise RetraceError(msg)

    def _boundary(self, epochs_done: int) -> None:
        self._boundaries += 1
        if self._boundaries >= self.warmup:
            self._armed = True

    # -- assertions / reporting ------------------------------------------
    def arm(self) -> None:
        """Arm immediately (e.g. right before a reshard that must hit
        every cache)."""
        self._armed = True

    def snapshot(self) -> dict:
        """Current per-step trace counts (copy)."""
        return dict(self.counts)

    def assert_no_new_traces(self, baseline: dict) -> None:
        """Raise unless counts match ``baseline`` exactly."""
        grew = {k: (baseline.get(k, 0), v) for k, v in self.counts.items()
                if v != baseline.get(k, 0)}
        if grew:
            raise RetraceError(f"new traces since snapshot: {grew}")

    def assert_clean(self) -> None:
        if self.violations:
            raise RetraceError("; ".join(self.violations))

    def report(self) -> str:
        lines = [f"# retrace guard: {sum(self.counts.values())} trace(s) "
                 f"across {len(self.counts)} step fn(s)"]
        for name, n in sorted(self.counts.items()):
            lines.append(f"#   {name}: {n}")
        for v in self.violations:
            lines.append(f"#   VIOLATION: {v}")
        return "\n".join(lines)
