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

import json
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
    (BaseTrainer._announce_attention): every field as ``key=value`` in the
    record's order, the backend first.  One format for stderr and for this
    report."""
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
