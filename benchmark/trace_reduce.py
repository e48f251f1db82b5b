"""From a profiler trace (`.xplane.pb`) to device times.

`jax.profiler.ProfileData` reads the file with nothing but JAX.  What a TPU
trace of this installation holds (jax 0.9.0, libtpu 0.0.34; looked at by
hand, PERF.md section 6, PR 22): one plane per chip, ``/device:TPU:<n>``.
Its line ``XLA Ops`` carries one event per executed HLO instruction, start
and duration in nanoseconds on a clock all planes share; the event's name
is the instruction's whole HLO text (``%_p1_flat_run.12 = f32[2170880,256]
custom-call(...)``) and its stats hold only device offsets: the
`jax.named_scope` path is *not* in the trace, so a kernel is found by its
instruction name, which for a Pallas call is the name of the jitted
function around it (``_p1_flat_run``, ``_p2_run``).  Its line ``Async XLA
Ops`` carries asynchronous instructions from their ``-start`` to the end of
their ``-done`` (copies, collectives).  ``XLA Modules`` has one event per
program run (``jit_train_step(...)``).  The host plane, ``/host:CPU``, has
the benchmark's own `TraceAnnotation`s (``bench.epoch``, ``bench.eval``,
``bench.window``) on the line of the Python thread.

Events on the ops line nest (a `while` encloses its body's instructions), so
every time here is *self* time: an event's duration less its children's.
Self times of one line add up to the line's busy time, so per-scope sums
partition the busy time and can be checked against it.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
ANNOTATION_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"^(all-to-all|all-reduce|all-gather|reduce-scatter|collective-permute"
    r"|collective-broadcast|ragged-all-to-all)(-start|-done)?$")
# `%name = type opcode(operands...)`: the opcode is the first lower-case
# word before a parenthesis (types hold only `T(`, `S(` and digits there)
HLO_TEXT = re.compile(r"^%?(?P<name>\S+) = (?P<type>\(?\S+).*?"
                      r"\s(?P<opcode>[a-z][a-z\-]*)\(")


@dataclasses.dataclass
class Op:
    name: str        # the instruction's name, without its operands
    opcode: str      # custom-call, fusion, while, all-to-all-start, ...
    scope: str       # "<name> <opcode> <result type>": what a regex searches
    start: float     # ns
    dur: float       # ns
    self_dur: float = 0.0
    inside: str = ""  # scopes of the events that enclose it, outermost first


def make_op(text: str, start: float, dur: float) -> Op:
    """An Op from an event name: the whole HLO text on a TPU, the bare
    instruction name elsewhere (``all-to-all.3``)."""
    m = HLO_TEXT.match(text)
    if m:
        name, opcode = m.group("name"), m.group("opcode")
        kind = m.group("type").split("{")[0]
    else:
        name = text.lstrip("%")
        opcode, kind = re.sub(r"[._]\d+$", "", name), ""
    return Op(name, opcode, f"{name} {opcode} {kind}".strip(), start, dur)


@dataclasses.dataclass
class Trace:
    devices: dict        # device ordinal -> [Op] of the ops line, by start
    annotations: list    # (name, start ns, dur ns) of the bench.* host events
    async_ops: dict = dataclasses.field(default_factory=dict)

    def windows(self, name: str) -> list:
        return sorted((s, s + d) for n, s, d in self.annotations if n == name)


def find_xplane(trace_dir: str) -> str:
    """The newest `.xplane.pb` under a `jax.profiler` log directory."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def _self_times(ops: list) -> None:
    """Fill ``self_dur``: duration less the durations of direct children
    (events that start inside an open event nest under it), and
    ``inside``: what encloses the event (a `while`'s body runs inside it)."""
    stack: list = []
    for op in ops:
        while stack and op.start >= stack[-1].start + stack[-1].dur:
            stack.pop()
        op.self_dur = op.dur
        if stack:
            stack[-1].self_dur -= op.dur
            op.inside = " > ".join(o.scope for o in stack)
        stack.append(op)
    for op in ops:
        op.self_dur = max(op.self_dur, 0.0)


def load(path: str, cpu_stand_in: bool = False) -> Trace:
    """``cpu_stand_in``: a CPU trace has no device plane; the rehearsal
    reads the host threads' events that carry an ``hlo_op`` stat in its
    place, by their ``device_ordinal``, so that the same code is walked."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, annotations, async_ops = {}, [], {}
    stand_in: dict = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name not in (OPS_LINE, ASYNC_LINE):
                    continue
                ops = [make_op(ev.name, float(ev.start_ns),
                               float(ev.duration_ns)) for ev in line.events]
                ops.sort(key=lambda o: (o.start, -o.dur))
                if line.name == OPS_LINE:
                    _self_times(ops)
                    devices[int(m.group(2))] = ops
                else:
                    async_ops[int(m.group(2))] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(ANNOTATION_PREFIX):
                        annotations.append((ev.name, float(ev.start_ns),
                                            float(ev.duration_ns)))
                    elif cpu_stand_in:
                        stats = dict(ev.stats)
                        if "hlo_op" in stats:
                            stand_in.setdefault(
                                int(stats.get("device_ordinal", 0)),
                                []).append(make_op(
                                    ev.name, float(ev.start_ns),
                                    float(ev.duration_ns)))
    if cpu_stand_in and not devices:
        for ordinal, ops in stand_in.items():
            ops.sort(key=lambda o: (o.start, -o.dur))
            _self_times(ops)
            devices[ordinal] = ops
    return Trace(devices, sorted(annotations, key=lambda a: a[1]), async_ops)


def clip(ops: list, windows: list) -> list:
    """The ops that start inside one of the (start, end) windows."""
    if not windows:
        return []
    return [o for o in ops if any(a <= o.start < b for a, b in windows)]


def busy_intervals(ops: list) -> list:
    """Union of the ops' intervals as sorted disjoint (start, end)."""
    out: list = []
    for o in ops:                       # sorted by start
        end = o.start + o.dur
        if out and o.start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1][1] = end
        else:
            out.append([o.start, end])
    return [(a, b) for a, b in out]


def busy_ns(ops: list) -> float:
    return sum(b - a for a, b in busy_intervals(ops))


def scope_ns(ops: list, match: str, exclude: str = "",
             inside: str = "") -> float:
    """Self time of the selected ops."""
    return sum(o.self_dur for o in select(ops, match, exclude, inside))


def select(ops: list, match: str, exclude: str = "",
           inside: str = "") -> list:
    """The ops whose scope matches ``match`` and not ``exclude`` and, with
    ``inside``, that run inside an event whose scope matches it."""
    want = re.compile(match)
    skip = re.compile(exclude) if exclude else None
    within = re.compile(inside) if inside else None
    return [o for o in ops if want.search(o.scope)
            and not (skip and skip.search(o.scope))
            and not (within and not within.search(o.inside))]


def is_collective(op: Op) -> bool:
    return COLLECTIVE.match(op.opcode) is not None


def collective_ns(ops: list, async_ops: list = ()) -> tuple:
    """(in flight, exposed) nanoseconds of the collectives on one device.

    The ops line is the core's serial instruction stream, so whatever time a
    collective instruction holds it, nothing else computes: that is the
    exposed part (a synchronous collective whole, an asynchronous one its
    `-start` and the wait in its `-done`).  In flight is longer: an
    asynchronous collective runs from its `-start` to the end of its
    `-done`, compute in between hiding it; the trace draws that whole span
    on the async line.  Without an async line the spans are paired up from
    the ops line."""
    exposed = in_flight = 0.0
    on_async_line = [o for o in async_ops if is_collective(o)]
    in_flight += sum(o.dur for o in on_async_line)
    open_: dict = {}
    for o in ops:
        m = COLLECTIVE.match(o.opcode)
        if m is None:
            continue
        exposed += o.self_dur
        kind, phase = m.group(1), m.group(2)
        if phase is None:
            in_flight += o.dur
        elif on_async_line:
            continue
        elif phase == "-start":
            open_.setdefault(kind, []).append(o.start)
        elif open_.get(kind):
            in_flight += o.start + o.dur - open_[kind].pop(0)
    return in_flight, exposed


def top_ops(ops: list, k: int = 10) -> list:
    """[[instruction name, self seconds]] of the k instructions with most."""
    total: dict = {}
    for o in ops:
        total[o.scope] = total.get(o.scope, 0.0) + o.self_dur
    best = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[n, ns / 1e9] for n, ns in best]


def idle_gaps(ops: list, window: tuple, annotations: list, k: int = 5) -> list:
    """[[annotation, seconds]] of the k longest gaps between busy intervals
    inside ``window``, each named by the innermost bench.* annotation that
    covers its middle (what the host was doing)."""
    a, b = window
    gaps, at = [], a
    for s, e in busy_intervals(ops):
        if e <= a or s >= b:
            continue
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if b > at:
        gaps.append((at, b))
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = (s + e) / 2
        cover = [(d, n) for n, t, d in annotations if t <= mid < t + d]
        out.append([min(cover)[1] if cover else "outside bench.*",
                    (e - s) / 1e9])
    return out


def describe(path: str, per_line: int = 12) -> str:
    """What a trace file holds, for reading by hand: planes, lines, event
    counts, the first events of each line with their stats."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = [f"{path} ({os.path.getsize(path)} bytes)"]
    for plane in data.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(evs)} events")
            names: dict = {}
            for ev in evs:
                names[ev.name] = names.get(ev.name, 0) + ev.duration_ns
            for n, ns in sorted(names.items(), key=lambda kv: -kv[1])[
                    :per_line]:
                ev = next(e for e in evs if e.name == n)
                out.append(f"    {ns / 1e6:12.3f} ms  {n!r}  first at "
                           f"{ev.start_ns:.0f} ns, stats {dict(ev.stats)}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys
    print(describe(find_xplane(sys.argv[1]),
                   int(sys.argv[2]) if len(sys.argv) > 2 else 12))
