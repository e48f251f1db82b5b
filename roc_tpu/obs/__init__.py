"""Unified runtime observability: span tracer, metrics channel, watchdog.

  tracer.py    host-side span ring (the ONE sanctioned wall-clock site —
               roclint's raw-timing rule), Chrome trace-event export, and
               the same spans as `roc.<name>` jax.profiler annotations
  channel.py   in-graph metrics riding the jitted step's return pytree
               (zero host syncs / collectives / retraces)
  metrics.py   registry + exporters over the balance-telemetry JSONL schema
  watchdog.py  EWMA slow-epoch + shard-straggler detector, budget-seeded
  roofline.py  THE peak-FLOPs/BW constants + op-IR FLOPs/bytes accounting
               (stdlib-only, like the tracer — kernel modules import it)
  ledger.py    calibration ledger: content-keyed prediction/measurement
               records, joined by `python -m roc_tpu.obs calibration`
  report.py    `python -m roc_tpu.obs report` + the preflight selftest

Entry points: `with obs.span("phase"): ...` anywhere on the host;
`-obs` / ROC_OBS=1 to record and export; driver/train wires the rest.

Only the tracer is imported eagerly (stdlib-only, so kernel modules can
span without pulling jax/numpy at import time); the jax/numpy-facing
pieces load on first attribute access.
"""

from roc_tpu.obs.tracer import (SpanTracer, annotate, enable, enabled,
                                get_tracer, span, validate_chrome_trace)

__all__ = ["SpanTracer", "annotate", "enable", "enabled", "get_tracer", "span",
           "validate_chrome_trace", "MetricsRegistry", "PerfWatchdog",
           "channel", "load_jsonl", "seed_for_graph", "roofline", "ledger",
           "get_ledger"]


# import_module (not `from ... import`): a from-import of a submodule not
# yet in sys.modules re-enters this __getattr__ and recurses
_LAZY = {"MetricsRegistry": ("roc_tpu.obs.metrics", "MetricsRegistry"),
         "load_jsonl": ("roc_tpu.obs.metrics", "load_jsonl"),
         "PerfWatchdog": ("roc_tpu.obs.watchdog", "PerfWatchdog"),
         "seed_for_graph": ("roc_tpu.obs.watchdog", "seed_for_graph"),
         "channel": ("roc_tpu.obs.channel", None),
         "roofline": ("roc_tpu.obs.roofline", None),
         "ledger": ("roc_tpu.obs.ledger", None),
         "get_ledger": ("roc_tpu.obs.ledger", "get_ledger")}


def __getattr__(name):
    if name in _LAZY:
        import importlib
        mod_name, attr = _LAZY[name]
        mod = importlib.import_module(mod_name)
        return getattr(mod, attr) if attr else mod
    raise AttributeError(f"module 'roc_tpu.obs' has no attribute {name!r}")
