"""Generic readers of per-layer metrics.  One data file per metric under
``benchmark/layer_metrics/<name>.json`` says where the number comes from
(``source``) and how it is reduced (``reduce``); a later PR adds a metric
for a new scope, span or counter as a file, with no code here.

Sources:
  device_scope       self time of the device instructions, inside the traced
                     epochs, whose "<name> <opcode> <result type>" matches
                     ``match`` (and not ``exclude``; with ``inside``, only
                     those that run inside an instruction matching it, as a
                     scan's body does inside its `while`).  ``partition:
                     true`` marks the scopes that split the busy time
                     between them: an instruction two of them match is an
                     error.  ``required_for_backend``: when the program
                     resolved that aggregation backend on a chip and nothing
                     matches, the read fails.  Kernels are found by the
                     name of the jitted function around them, so a rename
                     in the program would otherwise move their time into
                     ``device_rest`` without a word.
  device_rest        busy time inside the traced epochs that no
                     ``partition`` scope and no collective claimed.
  device_collective  ``part``: "in_flight" | "exposed" time of collectives.
  device_idle        1 - busy / window over the whole traced window.
  annotation_gap     per ``annotation`` event (bench.epoch): its length
                     less the device busy time inside it; the median.
  host_span          the program's `obs` spans named in ``spans``, summed.
  counter            a number the run counted, by ``counter``.

Reductions: ms_per_epoch, share_of_window (%), roofline_share (%, with
``shapes_fn`` from benchmark/roofline.py), seconds, value.  ``across``:
"mean" (default) or "max" over the cell's devices.

A reader that finds nothing to read returns None, and the harness leaves
the metric out of the line.
"""

from __future__ import annotations

import statistics

from benchmark import roofline, trace_reduce

EPOCH = "bench.epoch"
WINDOW = "bench.window"


class TracedRun:
    """What a traced run collected, as the readers see it."""

    def __init__(self, trace, specs: list, obs_spans: dict, counters: dict,
                 shapes: dict, device_kind: str):
        self.trace = trace
        self.specs = specs              # every metric file of this cell
        self.obs_spans = obs_spans      # span name -> [seconds]
        self.counters = counters
        self.shapes = shapes
        self.device_kind = device_kind
        self.epochs = trace.windows(EPOCH) if trace else []
        self.epoch_ops = {d: trace_reduce.clip(ops, self.epochs)
                          for d, ops in trace.devices.items()} if trace else {}
        self.epoch_async = {d: trace_reduce.clip(ops, self.epochs)
                            for d, ops in trace.async_ops.items()} \
            if trace else {}

    def window(self):
        w = self.trace.windows(WINDOW) if self.trace else []
        return w[0] if w else None

    def window_busy_ns(self) -> float:
        """Device busy time inside the traced window, mean over devices."""
        w = self.window()
        return statistics.fmean(
            trace_reduce.busy_ns(trace_reduce.clip(ops, [w]))
            for ops in self.trace.devices.values())

    def across(self, spec: dict, per_device: list):
        if not per_device:
            return None
        return max(per_device) if spec.get("across") == "max" \
            else statistics.fmean(per_device)

    def partition_scopes(self) -> list:
        return [s for s in self.specs
                if s.get("source") == "device_scope" and s.get("partition")]


def _selected(ops: list, spec: dict) -> list:
    return trace_reduce.select(ops, spec["match"], spec.get("exclude", ""),
                               spec.get("inside", ""))


def _scope_ns(run: TracedRun, spec: dict):
    if not run.epoch_ops:
        return None
    found = [_selected(ops, spec) for ops in run.epoch_ops.values()]
    need = spec.get("required_for_backend")
    if need and need == run.shapes.get("backend") and not any(found):
        raise ValueError(
            f"{spec['name']}: the program resolved the {need} backend and "
            f"no device instruction matches {spec['match']!r}; the kernel "
            f"was renamed or did not run")
    return run.across(spec, [sum(o.self_dur for o in ops) for ops in found])


def _rest_ns(run: TracedRun, spec: dict):
    if not run.epoch_ops:
        return None
    per_device = []
    for ops in run.epoch_ops.values():
        claimed: dict = {}
        for s in run.partition_scopes():
            for o in _selected(ops, s):
                if id(o) in claimed:
                    raise ValueError(
                        f"{o.name}: claimed by {claimed[id(o)]} and "
                        f"{s['name']}; partition scopes may not overlap")
                claimed[id(o)] = s["name"]
        per_device.append(sum(
            o.self_dur for o in ops
            if id(o) not in claimed and not trace_reduce.is_collective(o)))
    return run.across(spec, per_device)


def _collective_ns(run: TracedRun, spec: dict):
    if not run.epoch_ops:
        return None
    i = {"in_flight": 0, "exposed": 1}[spec["part"]]
    return run.across(spec, [
        trace_reduce.collective_ns(ops, run.epoch_async.get(d, ()))[i]
        for d, ops in run.epoch_ops.items()])


def _idle_share(run: TracedRun, spec: dict):
    w = run.window()
    if w is None or not run.trace.devices:
        return None
    return 100.0 * (1.0 - run.window_busy_ns() / (w[1] - w[0]))


def _annotation_gap_ms(run: TracedRun, spec: dict):
    if not run.trace or not run.trace.devices:
        return None
    gaps = []
    for a, b in run.trace.windows(spec["annotation"]):
        busy = statistics.fmean(
            trace_reduce.busy_ns(trace_reduce.clip(ops, [(a, b)]))
            for ops in run.trace.devices.values())
        gaps.append((b - a - busy) / 1e6)
    return statistics.median(gaps) if gaps else None


def _host_span_s(run: TracedRun, spec: dict):
    found = [d for name in spec["spans"]
             for d in run.obs_spans.get(name, [])]
    return sum(found) if found else None


def _counter(run: TracedRun, spec: dict):
    return run.counters.get(spec["counter"])


READERS = {
    "device_scope": _scope_ns, "device_rest": _rest_ns,
    "device_collective": _collective_ns, "device_idle": _idle_share,
    "annotation_gap": _annotation_gap_ms, "host_span": _host_span_s,
    "counter": _counter,
}


def read(run: TracedRun, spec: dict):
    """The metric's value, or None where there is nothing to read."""
    raw = READERS[spec["source"]](run, spec)
    if raw is None:
        return None
    how = spec["reduce"]
    if how == "ms_per_epoch":
        return raw / 1e6 / len(run.epochs) if run.epochs else None
    if how == "roofline_share":
        if not raw or not run.epochs:
            return None
        least, _ = roofline.least_seconds(spec["shapes_fn"], run.shapes,
                                          run.device_kind)
        return 100.0 * least / (raw / 1e9 / len(run.epochs))
    if how in ("share_of_window", "seconds", "value", "ms"):
        return raw
    raise ValueError(f"{spec['name']}: unknown reduce {how!r}")
