"""Render a -obs run's trace + metrics into a text summary, plus the
preflight selftest.

`python -m roc_tpu.obs report -dir roc_obs` reads the two artifacts a
`-obs` run writes (trace.json, metrics.jsonl) and prints per-span-type
aggregates, the epoch/loss trajectory, and any watchdog alerts — the
10-second answer to "where did this run spend its time" without opening
Perfetto.  `selftest` is the preflight/CI gate: tracer schema validity,
watchdog fire/quiet behavior, and the span overhead bound, all stdlib-only
(no jax import) so it runs in ~100 ms.
"""

from __future__ import annotations

import glob
import json
import os
import re
from typing import List

from roc_tpu.obs.metrics import load_jsonl
from roc_tpu.obs.tracer import SpanTracer, validate_chrome_trace
from roc_tpu.obs.watchdog import PerfWatchdog

# Gates for the selftest's overhead check.  A disabled span is two
# perf_counter_ns calls + a list push/pop; an enabled one adds a ring
# append.  50 us/span is ~100x the measured cost — the gate catches a
# pathological regression (lock contention, accidental I/O), not jitter.
MAX_SPAN_OVERHEAD_S = 50e-6


def summarize_trace(trace: dict) -> List[str]:
    by_name: dict = {}
    for ev in trace.get("traceEvents", []):
        st = by_name.setdefault(ev.get("name", "?"),
                                {"count": 0, "total_us": 0.0, "max_us": 0.0})
        st["count"] += 1
        dur = float(ev.get("dur", 0.0))
        st["total_us"] += dur
        st["max_us"] = max(st["max_us"], dur)
    lines = [f"# spans ({len(by_name)} types)"]
    for name, st in sorted(by_name.items(), key=lambda kv: -kv[1]["total_us"]):
        mean = st["total_us"] / st["count"]
        lines.append(f"#   {name:<16} x{st['count']:<5} "
                     f"total {st['total_us'] / 1e3:9.2f} ms  "
                     f"mean {mean / 1e3:8.3f} ms  "
                     f"max {st['max_us'] / 1e3:8.3f} ms")
    return lines


def _alert_detail(a: dict) -> str:
    """Generic one-line rendering of a watchdog alert's numeric fields —
    no per-kind template, so a new alert kind (stream-stall,
    calibration-drift, whatever comes next) renders correctly instead of
    falling into a slow-epoch-shaped else branch."""
    parts = []
    for k in sorted(a):
        v = a[k]
        if k in ("kind", "epoch") or isinstance(v, bool) \
                or not isinstance(v, (int, float)):
            continue
        parts.append(f"{k}={v:.4g}")
    return ", ".join(parts)


def exchange_line(info: dict) -> str:
    """The sharded trainer's start-up line from its `exchange` record
    (SpmdTrainer.exchange_info): every field as ``key=value`` in the
    record's order, the backend's reason last in brackets.  One format for
    stderr and for this report."""
    fields = " ".join(
        f"{k}={v:.4f}" if isinstance(v, float) else f"{k}={v}"
        for k, v in info.items()
        if v is not None and k not in ("type", "agg_backend_reason"))
    return f"# exchange: {fields} ({info.get('agg_backend_reason', '?')})"


def attention_line(info: dict) -> str:
    """An attention model's start-up line from its `attention` record
    (BaseTrainer._announce_attention_info): every field as ``key=value``
    in the record's order, the backend first.  One format for stderr and
    for this report."""
    fields = " ".join(
        f"{k}={v:.4f}" if isinstance(v, float) else f"{k}={v}"
        for k, v in info.items() if k not in ("type", "backend"))
    return f"# attention: backend={info.get('backend', '?')} {fields}"


def summarize_metrics(records: List[dict]) -> List[str]:
    epochs = [r for r in records if r.get("type") == "metrics"]
    alerts = [r for r in records if r.get("type") == "watchdog"]
    trains = [r for r in records if r.get("type") == "train"]
    lines: List[str] = []
    # record-type census first, fully generic: every "type" in the stream
    # counts, including kinds this renderer knows nothing about
    by_type: dict = {}
    for r in records:
        t = str(r.get("type", "?"))
        by_type[t] = by_type.get(t, 0) + 1
    if by_type:
        lines.append("# records: " + ", ".join(
            f"{t} x{n}" for t, n in sorted(by_type.items())))
    if epochs:
        walls = [r["wall_s"] for r in epochs if "wall_s" in r]
        med = sorted(walls)[len(walls) // 2] if walls else 0.0
        lines.append(f"# metrics: {len(epochs)} epochs, "
                     f"median {med * 1e3:.1f} ms/epoch")
        last = epochs[-1]
        for key in ("loss", "grad_norm", "param_norm", "wire_bytes",
                    "mfu", "roofline_frac"):
            if key in last:
                lines.append(f"#   final {key} = {last[key]:.6g}")
    for r in records:
        if r.get("type") == "attention":
            lines.append(attention_line(r))
        elif r.get("type") == "exchange":
            lines.append(exchange_line(r))
    for r in trains:
        lines.append(f"#   verdict: {r.get('watchdog_verdict', '?')} "
                     f"({r.get('epochs', '?')} epochs, "
                     f"total {r.get('total_s', 0):.2f}s)")
    if alerts:
        lines.append(f"# watchdog alerts ({len(alerts)}):")
        for a in alerts:
            lines.append(f"#   {a.get('kind', '?')} @ epoch "
                         f"{a.get('epoch', '?')}: {_alert_detail(a)}")
    elif epochs or trains:
        lines.append("# watchdog: no alerts")
    if any(r.get("type") in ("prediction", "measurement") for r in records):
        lines.extend(summarize_calibration(records))
    return lines


def summarize_calibration(records: List[dict]) -> List[str]:
    """Per-cost-model calibration table over a stream's ledger records
    (the body of `python -m roc_tpu.obs calibration`)."""
    from roc_tpu.obs.ledger import calibration_report, validate_records
    problems = validate_records(records)
    rep = calibration_report(records)
    lines = [f"# calibration: {len(rep['models'])} paired model(s), "
             f"{rep['predictions']} predictions "
             f"({rep['unpaired_predictions']} unpaired), "
             f"{rep['unpaired_measurements']} unpaired measurement(s)"]
    for name in sorted(rep["models"]):
        m = rep["models"][name]
        lines.append(f"#   {name:<14} x{m['pairs']:<4} "
                     f"ratio mean {m['ratio_mean']:.4g}  "
                     f"[{m['ratio_min']:.4g}, {m['ratio_max']:.4g}]  "
                     f"({m['units']})")
    if problems:
        lines.append(f"# calibration: {len(problems)} schema problem(s): "
                     f"{problems[0]}")
    return lines


def report(trace_path: str = "", metrics_path: str = "") -> str:
    lines: List[str] = []
    if trace_path:
        try:
            with open(trace_path, encoding="utf-8") as f:
                trace = json.load(f)
        except (OSError, ValueError) as e:
            lines.append(f"# trace: unreadable ({e})")
        else:
            problems = validate_chrome_trace(trace)
            if problems:
                lines.append(f"# trace: {len(problems)} schema problem(s): "
                             f"{problems[0]}")
            lines.extend(summarize_trace(trace))
    if metrics_path:
        records = load_jsonl(metrics_path)
        if records:
            lines.extend(summarize_metrics(records))
        else:
            lines.append(f"# metrics: no records at {metrics_path}")
    return "\n".join(lines) if lines else "# nothing to report"


# -- device time by program op (`report -profile DIR`) ---------------------
#
# A `-profile DIR` run leaves a `jax.profiler` trace and, beside it,
# `roc_scopes.json` (BaseTrainer._write_device_scopes): per program the
# compiled step's {instruction name: (op, pass, part)}.  The trace names
# every device event by its instruction, so the two join by name.  What the
# trace holds (jax 0.9.0, libtpu 0.0.34): one plane a chip,
# ``/device:TPU:<n>``, whose line ``XLA Ops`` has one event per executed
# instruction (the name is the instruction's whole HLO text, ``%fusion.9 =
# f32[...] fusion(...)``) and whose line ``XLA Modules`` has one per program
# run (``jit_train_step(<fingerprint>)``); events nest (a `while` encloses
# its body), so every time below is SELF time, duration less children, and
# the self times of a line add up to its busy time.  A CPU trace has no
# device plane: the host threads' events that carry an ``hlo_op`` stat
# stand in, a line a thread, with ``hlo_module`` naming the program.

SCOPES_FILE = "roc_scopes.json"
NO_SCOPE = (None, "", None)     # the key of what no `roc.` scope claims
_DEVICE_PLANE = re.compile(r"^/device:(?:TPU|GPU):(\d+)$")
_INSTRUCTION = re.compile(r"^%?([^\s=(]+)")


def find_xplane(profile_dir: str) -> str:
    """The newest `.xplane.pb` under a `jax.profiler` log directory."""
    found = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir!r}")
    return max(found, key=os.path.getmtime)


def _self_times(events: list) -> list:
    """[[name, program, start, dur, self]] of one line's [name, program,
    start, dur] events: self = duration less the direct children's (an
    event that starts inside an open one nests under it)."""
    events.sort(key=lambda e: (e[2], -e[3]))
    stack: list = []
    for ev in events:
        while stack and ev[2] >= stack[-1][2] + stack[-1][3]:
            stack.pop()
        ev.append(ev[3])
        if stack:
            stack[-1][4] -= ev[3]
        stack.append(ev)
    for ev in events:
        ev[4] = max(ev[4], 0.0)
    return events


def _busy_intervals(events: list) -> list:
    out: list = []
    for _, _, start, dur, _ in events:          # sorted by start
        end = start + dur
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def read_device_events(xplane_path: str) -> tuple:
    """({chip: [line, ...]}, {chip: {program: runs}}, annotations): a line
    is [[instruction, program, start ns, duration ns, self ns], ...] by
    start; annotations are the host's ``roc.*`` events as (name, start,
    duration)."""
    import bisect

    from jax.profiler import ProfileData

    from roc_tpu.obs.tracer import ANNOTATION_PREFIX
    data = ProfileData.from_file(xplane_path)
    chips: dict = {}
    runs: dict = {}
    hosts = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m is None:
            if plane.name.startswith("/host:"):
                hosts.append(plane)
            continue
        chip = int(m.group(1))
        lines = {line.name: line for line in plane.lines}
        ran = sorted(
            (float(ev.start_ns), float(ev.start_ns + ev.duration_ns),
             ev.name.split("(")[0])
            for ev in (lines["XLA Modules"].events
                       if "XLA Modules" in lines else ()))
        starts = [r[0] for r in ran]
        events = []
        for ev in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
            start = float(ev.start_ns)
            i = bisect.bisect_right(starts, start) - 1
            program = ran[i][2] if i >= 0 and start < ran[i][1] else ""
            events.append([_INSTRUCTION.match(ev.name).group(1), program,
                           start, float(ev.duration_ns)])
        chips[chip] = [_self_times(events)]
        runs[chip] = {}
        for _, _, program in ran:
            runs[chip][program] = runs[chip].get(program, 0) + 1
    annotations = []
    run_ids: dict = {}
    for plane in hosts:
        for line in plane.lines:
            events: dict = {}
            for ev in line.events:
                if ev.name.startswith(ANNOTATION_PREFIX):
                    annotations.append((ev.name, float(ev.start_ns),
                                        float(ev.duration_ns)))
                elif not runs:          # no device plane: the stand-in
                    stats = dict(ev.stats)
                    if "hlo_op" not in stats:
                        continue
                    chip = int(stats.get("device_ordinal", 0))
                    program = str(stats.get("hlo_module", ""))
                    events.setdefault(chip, []).append(
                        [str(stats["hlo_op"]), program,
                         float(ev.start_ns), float(ev.duration_ns)])
                    run_ids.setdefault(chip, {}).setdefault(
                        program, set()).add(stats.get("run_id"))
            for chip, evs in events.items():
                chips.setdefault(chip, []).append(_self_times(evs))
    for chip, by_program in run_ids.items():
        runs[chip] = {program: len(ids) for program, ids in
                      by_program.items()}
    return chips, runs, sorted(annotations, key=lambda a: a[1])


def scope_times(lines: list, program: dict) -> dict:
    """{(op, pass, part): self ns} of the events of one program on one
    chip's lines, by its instruction map; what no scope claims (an
    instruction the map lacks, or one under no `roc.` scope) is under
    :data:`NO_SCOPE`."""
    out: dict = {}
    scopes_of = program["scopes"]
    for events in lines:
        for name, module, _, _, self_ns in events:
            if module != program["module"]:
                continue
            op, pass_, part = scopes_of.get(name) or NO_SCOPE
            key = (op, pass_, part) if op else NO_SCOPE
            out[key] = out.get(key, 0.0) + self_ns
    return out


def idle_gaps(lines: list, annotations: list, k: int = 5) -> list:
    """[(seconds, annotation)] of the k longest gaps between a chip's busy
    intervals, each named by the innermost ``roc.*`` annotation over its
    middle (what the host was doing meanwhile)."""
    events = sorted((e for line in lines for e in line),
                    key=lambda e: e[2])
    busy = _busy_intervals(events)
    gaps = [(b[0] - a[1], (a[1] + b[0]) / 2) for a, b in zip(busy, busy[1:])
            if b[0] > a[1]]
    out = []
    for length, mid in sorted(gaps, reverse=True)[:k]:
        cover = [(d, n) for n, t, d in annotations if t <= mid < t + d]
        out.append((length / 1e9,
                    min(cover)[1] if cover else "outside roc.*"))
    return out


def device_report(profile_dir: str) -> str:
    """The text `python -m roc_tpu.obs report -profile DIR` prints: per
    program and chip the device self time by op x pass x part (ms a
    ``roc.epoch`` span for the train step, ms a run for the others), the
    share of busy time under no scope, the mixed-fusion count, and the
    chip's five longest idle gaps by annotation."""
    with open(os.path.join(profile_dir, SCOPES_FILE), encoding="utf-8") as f:
        record = json.load(f)
    path = find_xplane(profile_dir)
    chips, runs, annotations = read_device_events(path)
    epochs = sum(n == "roc.epoch" for n, _, _ in annotations)
    layer = {f"roc.{o['index']:02d}_{o['kind']}": o["layer"]
             for o in record.get("ops", ())}
    lines = [f"# device time by program op: {path}",
             f"#   names from jax {record.get('jax', '?')}, "
             f"{record.get('platform_version', '?')}; {epochs} roc.epoch "
             f"span(s) in the trace"]
    for chip in sorted(chips):
        for name, program in record["programs"].items():
            times = scope_times(chips[chip], program)
            if not times:
                continue
            per, what = (epochs, "epoch") if name == "train" and epochs \
                else (max(runs[chip].get(program["module"], 1), 1), "run")
            busy = sum(times.values())
            unscoped = times.get(NO_SCOPE, 0.0)
            lines.append(
                f"# program {name} ({program['module']}), chip {chip}: busy "
                f"{busy / 1e6 / per:.3f} ms a {what} over {per} {what}(s); "
                f"on no scope {100.0 * unscoped / max(busy, 1.0):.3f} %; "
                f"{program['mixed_fusions']} fusion(s) mix op scopes")
            for (op, pass_, part), ns in sorted(
                    times.items(), key=lambda kv: (kv[0][0] is None, str(
                        kv[0][0]), kv[0][1], str(kv[0][2]))):
                where = f"L{layer[op]}" if op in layer else ""
                lines.append(
                    f"#   {op or '(no scope)':<18} {where:<4} {pass_:<6} "
                    f"{part or '':<8} {ns / 1e6 / per:12.3f} ms "
                    f"{100.0 * ns / max(busy, 1.0):7.3f} %")
        gaps = idle_gaps(chips[chip], annotations)
        lines.append(f"# idle, chip {chip}: the {len(gaps)} longest gap(s)")
        for seconds, name in gaps:
            lines.append(f"#   {seconds * 1e3:10.3f} ms  {name}")
    return "\n".join(lines)


# -- calibration (the ledger's CLI + preflight gate) -----------------------

CALIB_MIN_MODELS = 5
# Sanity bands (measured/predicted mean ratio) for the models a CPU run
# can actually check.  The step-count predictors are exact by
# construction; the byte analytics get float32-channel + approximation
# slack; overlap_frac just has to be a sane fraction.  step_time is
# deliberately absent — its constants are TPU-fit, so a CPU ratio is
# reported but never judged (same rule the watchdog applies).
CALIB_BOUNDS = {
    "plan_steps": (0.999, 1.001),
    "staging_rows": (0.999, 1.001),
    "wire_bytes": (0.99, 1.01),
    "overlap_frac": (0.02, 1.5),
    "arg_bytes": (0.9, 1.1),
}


def calibration(metrics_path: str, out=print) -> int:
    """`python -m roc_tpu.obs calibration`: join and report a stream's
    ledger records.  0 = schema-valid records found, 1 = schema problems,
    2 = no ledger records at all."""
    records = load_jsonl(metrics_path)
    if not any(r.get("type") in ("prediction", "measurement")
               for r in records):
        out(f"# no ledger records at {metrics_path!r} "
            "(run with -obs / ROC_OBS=1 first)")
        return 2
    from roc_tpu.obs.ledger import validate_records
    for line in summarize_calibration(records):
        out(line)
    return 1 if validate_records(records) else 0


def calibration_selftest(out=print) -> int:
    """Preflight calibration gate: a 3-epoch CPU run (in-core + streamed)
    plus a binned plan build and an XLA buffer cross-check must produce
    paired records for >= CALIB_MIN_MODELS distinct cost models, the
    stream must validate against the record schema, and every
    CPU-checkable model's mean ratio must sit inside CALIB_BOUNDS."""
    import os
    import tempfile

    import numpy as np

    from roc_tpu.graph import datasets
    from roc_tpu.models import build_gcn
    from roc_tpu.obs.ledger import (calibration_report, get_ledger,
                                    validate_records)
    from roc_tpu.obs.metrics import MetricsRegistry
    from roc_tpu.train.config import Config
    from roc_tpu.train.driver import Trainer

    failures: List[str] = []
    quiet = lambda *a, **k: None  # noqa: E731
    with tempfile.TemporaryDirectory(prefix="roc_calib_") as td:
        jsonl = os.path.join(td, "metrics.jsonl")
        ds = datasets.synthetic("calib", 120, 4.0, 8, 3, n_train=30,
                                n_val=30, n_test=30, seed=7)
        # (a) in-core trainer: step_time / peak-memory predictions, epoch
        # wall measurements — the normal -obs wiring end to end
        cfg = Config(layers=[8, 8, 3], num_epochs=3, eval_every=1000,
                     dropout_rate=0.0, obs=True, obs_dir=td)
        tr = Trainer(cfg, ds, build_gcn(cfg.layers, 0.0))
        tr.train(print_fn=quiet)
        # (b) stream executor: overlap_frac + host-wire byte pairs
        from roc_tpu.stream.executor import StreamTrainer
        scfg = Config(layers=[8, 8, 3], num_epochs=3, num_parts=2,
                      stream=True, stream_slots=2, eval_every=1000,
                      dropout_rate=0.0, obs=True, obs_dir=td)
        st = StreamTrainer(scfg, ds, build_gcn(scfg.layers, 0.0))
        st.train(print_fn=quiet)
        # (c) binned schedule: choose_geometry predicts, the built plan
        # measures (exact-by-construction pairs)
        led = get_ledger()
        reg = MetricsRegistry(jsonl_path=jsonl)
        led.attach(reg.emit)
        from roc_tpu.ops.pallas import binned as B
        rng = np.random.default_rng(0)
        E, N = 4000, 512
        src = rng.integers(0, N, E).astype(np.int64)
        dst = rng.integers(0, N, E).astype(np.int64)
        geom, _ = B.choose_geometry(src, dst, N, N, force=True)
        if geom is not None and geom.hub_minc == 0:
            B.build_binned_plan(src, dst, N, N, geom=geom)
        else:  # hybrid winner: pin a plain preset so the pair still joins
            geom, _ = B.choose_geometry(src, dst, N, N, force=True,
                                        candidates=[B.GEOM_FLAT])
            B.build_binned_plan(src, dst, N, N, geom=geom)
        # (d/e) XLA cross-checks where the backend implements
        # memory_analysis: analytic argument bytes and the planner's peak
        # against the compiled step's own buffer accounting
        from roc_tpu import memory
        stats = memory.xla_memory_stats(tr)
        if stats.get("argument_bytes"):
            led.predict("arg_bytes", "selftest", memory.step_arg_bytes(tr),
                        "bytes")
            led.measure("arg_bytes", "selftest",
                        stats["argument_bytes"] + stats.get("alias_bytes", 0),
                        "bytes")
            led.predict("peak_memory", "selftest-xla",
                        tr.mem_plan.predicted_peak_bytes, "bytes")
            led.measure("peak_memory", "selftest-xla",
                        stats["argument_bytes"] + stats.get("output_bytes", 0)
                        + stats.get("temp_bytes", 0), "bytes")
        led.detach()
        records = load_jsonl(jsonl)

    problems = validate_records(records)
    if problems:
        failures.append(f"{len(problems)} schema problem(s): {problems[0]}")
    rep = calibration_report(records)
    models = rep["models"]
    if len(models) < CALIB_MIN_MODELS:
        failures.append(f"only {len(models)} paired cost model(s) "
                        f"({sorted(models)}), need {CALIB_MIN_MODELS}")
    for name, (lo, hi) in CALIB_BOUNDS.items():
        m = models.get(name)
        if m and not (lo <= m["ratio_mean"] <= hi):
            failures.append(f"{name} mean ratio {m['ratio_mean']:.4g} "
                            f"outside [{lo}, {hi}]")
    if failures:
        for f_ in failures:
            out(f"calibration selftest FAIL: {f_}")
        return 1
    out(f"calibration selftest ok ({len(models)} paired models: "
        + ", ".join(f"{n} @ {models[n]['ratio_mean']:.3g}"
                    for n in sorted(models)) + ")")
    return 0


# -- selftest (the preflight obs gate) -------------------------------------

def selftest(out=print) -> int:
    """0 when the obs layer holds its own contracts; 1 with a reason."""
    failures: List[str] = []

    # 1. tracer: nesting depths + Perfetto-loadable export
    tr = SpanTracer(capacity=64)
    tr.enabled = True
    with tr.span("outer", case="selftest"):
        with tr.span("inner"):
            pass
    spans = {s.name: s for s in tr.spans()}
    if set(spans) != {"outer", "inner"}:
        failures.append(f"tracer recorded {sorted(spans)}, "
                        "expected inner+outer")
    elif not (spans["inner"].depth == 1 and spans["outer"].depth == 0):
        failures.append("span nesting depths wrong")
    problems = validate_chrome_trace(tr.to_chrome_trace())
    if problems:
        failures.append(f"chrome-trace schema: {problems[0]}")
    try:
        json.dumps(tr.to_chrome_trace())
    except TypeError as e:
        failures.append(f"trace not JSON-serializable: {e}")

    # 2. watchdog: fires on an injected 3x epoch, quiet on a clean run
    wd = PerfWatchdog()
    for epoch in range(5):
        if wd.observe_epoch(epoch, 0.1) is not None:
            failures.append("watchdog fired on a clean warmup")
            break
    if wd.observe_epoch(5, 0.3) is None:
        failures.append("watchdog missed an injected 3x slow epoch")
    clean = PerfWatchdog()
    noise = [0.1, 0.102, 0.098, 0.101, 0.099, 0.103, 0.097]
    if any(clean.observe_epoch(i, t) for i, t in enumerate(noise)):
        failures.append("watchdog fired on +-3% noise")
    if not clean.observe_shards(0, [0.1, 0.1, 0.1, 0.5]):
        failures.append("watchdog missed a 5x shard straggler")

    # 3. overhead: disabled spans (the always-on steady state) stay cheap
    tr2 = SpanTracer()
    reps = 2000
    with tr2.span("gate") as gate:   # obs times itself — no raw clocks
        for _ in range(reps):
            with tr2.span("probe"):
                pass
    per_span = gate.dur_s / reps
    if per_span > MAX_SPAN_OVERHEAD_S:
        failures.append(f"span overhead {per_span * 1e6:.1f} us > "
                        f"{MAX_SPAN_OVERHEAD_S * 1e6:.0f} us")

    if failures:
        for f_ in failures:
            out(f"obs selftest FAIL: {f_}")
        return 1
    out(f"obs selftest ok (span overhead {per_span * 1e6:.2f} us, "
        f"watchdog fire/quiet verified, trace schema valid)")
    return 0
