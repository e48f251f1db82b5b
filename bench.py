"""Benchmark: full-graph GCN training throughput (the reference's canonical
workload, test.sh:8 — 2-layer GCN, Reddit-shaped graph, layers 602-256-41).

Prints ONE JSON line:
  {"metric": ..., "value": ..., "unit": ..., "vs_baseline": ...}
A time is a TPU time or it is not taken: when JAX's backend is not "tpu"
the script exits 2 before building anything.  Any later failure — a
kernel that does not compile included — prints one JSON line with an
"error" field and exits 1; no other backend is timed in its place.

The graph is a deterministic synthetic Reddit-scale stand-in (zero-egress
environment; same node/feature/class counts as reddit-dgl, ~23.5M in-edges).
Metric is wall-clock per training epoch (fwd+bwd+Adam, full graph, no
sampling).  vs_baseline compares against REF_EPOCH_S, the reference system's
single-GPU epoch time for this workload; the reference repo publishes no
numbers (BASELINE.md), so REF_EPOCH_S holds the MLSys'20 paper's reported
~1 s/epoch for single-GPU full-graph Reddit until a measured value replaces
it.  vs_baseline > 1 means faster than that reference number.

Env knobs:
  ROC_BENCH_BACKEND  aggregation backend: auto|xla|matmul|binned (default auto;
                     "pallas" is accepted as an alias of binned)
  ROC_BENCH_PRECISION  aggregation precision, honored by BOTH plan
                     backends since round 3: fast (default; one designed
                     bf16 feature rounding, golden curves within +-1
                     sample of fp32 — docs/GOLDEN.md) | exact (fp32 end
                     to end: matmul highest-precision dots, binned fp32
                     staging + 3-way split dots)
  ROC_BENCH_EPOCHS   measured epochs (default 10)
  ROC_BENCH_SCALE    graph-size multiplier for smoke tests (default 1.0;
                     the canonical metric requires 1.0 — smaller scales
                     annotate the metric name)
  ROC_BENCH_SHAPE    reddit (default) | products: full shape preset —
                     nodes, degree AND layers default per shape, so
                     `ROC_BENCH_SHAPE=products python bench.py` is the
                     whole north-star invocation
  ROC_BENCH_AB       comma list of backends, e.g. "matmul,binned": measure
                     every leg in THIS process (same dataset/warmup, per-
                     epoch times in the artifact); value = slowest/fastest
                     ratio, unit "x" — the forced-vs-auto anomaly check
  ROC_BENCH_MEM      1: attach a memory-planner block to the artifact —
                     the chosen plan (ROC_MEM_PLAN / ROC_MEM_BUDGET drive
                     it through Config), predicted vs measured peak HBM
                     bytes, and the predicted step-time delta vs all-KEEP
                     and all-REMAT (roc_tpu/memory)
"""

import json
import os
import sys
import time
import traceback

REF_EPOCH_S = 1.0  # assumed reference (see module docstring); >1.0 = we win

def _env(name, default, cast):
    """Env knob with a safe fallback — a malformed value must not break the
    one-JSON-line contract (these parse at import time, before main's
    try/except)."""
    try:
        return cast(os.environ.get(name, default))
    except (ValueError, TypeError):
        print(f"# ignoring malformed {name}={os.environ[name]!r}",
              file=sys.stderr)
        return cast(default)


SCALE = _env("ROC_BENCH_SCALE", "1.0", float)
# Shape overrides (round 4): ROC_BENCH_NODES / ROC_BENCH_DEG retarget the
# synthetic graph, e.g. the ogbn-products shape of the BASELINE.json north
# star (2,449,029 nodes, deg ~51, layers 100-256-47):
#   ROC_BENCH_SHAPE=products ROC_BENCH_NODES=2449029 ROC_BENCH_DEG=51 \
#   ROC_BENCH_LAYERS=100-256-47 python bench.py
# ROC_BENCH_SHAPE only labels the metric; vs_baseline stays null off the
# canonical reddit shape (the reference figure is a Reddit number).
SHAPE = os.environ.get("ROC_BENCH_SHAPE", "reddit")
# Shape presets: `ROC_BENCH_SHAPE=products python bench.py` is the whole
# north-star invocation — nodes/degree/layers default per shape (explicit
# ROC_BENCH_NODES/DEG/LAYERS still override).  Unknown shape names keep
# the reddit defaults (the name only labels the metric).
_SHAPE_DEFAULTS = {
    "reddit": (str(232_965), "50.0", [602, 256, 41]),
    "products": (str(2_449_029), "51.0", [100, 256, 47]),
}
_DEF_NODES, _DEF_DEG, _DEF_LAYERS = _SHAPE_DEFAULTS.get(
    SHAPE, _SHAPE_DEFAULTS["reddit"])
NODES = int(_env("ROC_BENCH_NODES", _DEF_NODES, int) * SCALE)
# ROC_BENCH_MODEL=gat measures the attention path (plan backend on TPU);
# non-gcn runs annotate the metric name and report vs_baseline null (the
# reference figure is a GCN number).  ROC_BENCH_LAYERS overrides the hidden
# sizes (e.g. 602-64-41 with 4 heads = 256 total hidden for a GAT run
# comparable to the canonical GCN).
MODEL = os.environ.get("ROC_BENCH_MODEL", "gcn")
HEADS = _env("ROC_BENCH_HEADS", "4", int)
_layers_env = os.environ.get("ROC_BENCH_LAYERS", "")
LAYERS = [int(v) for v in _layers_env.split("-")] if _layers_env \
    else list(_DEF_LAYERS)
# The synthetic graph's feature/class dims follow the layer spec (the
# driver asserts they agree).
IN_DIM, CLASSES = LAYERS[0], LAYERS[-1]
AVG_DEG = _env("ROC_BENCH_DEG", _DEF_DEG, float)
WARMUP = 3
MEASURED = _env("ROC_BENCH_EPOCHS", "10", int)
BACKEND = os.environ.get("ROC_BENCH_BACKEND", "auto")
# The canonical metric is defined with precision=fast (single-pass bf16
# one-hot dots; golden-curve-validated, docs/GOLDEN.md).  Overriding to
# exact annotates the metric name so histories are never conflated.
PRECISION = os.environ.get("ROC_BENCH_PRECISION", "fast")
# ROC_BENCH_REORDER=1|auto: RCM locality pass before training
# (graph/reorder.py; "auto" keeps the order only on a measured >=10%
# padded-row reduction) — annotates the metric; canonical stays off.
_REORDER_RAW = os.environ.get("ROC_BENCH_REORDER", "0")
REORDER = {"0": "off", "": "off", "1": "on"}.get(_REORDER_RAW,
                                                 _REORDER_RAW)
if REORDER not in ("off", "on", "auto"):
    # fail BEFORE the (minutes-long at products shape) graph build, and
    # before the bogus value bakes into METRIC
    print(f"# ignoring malformed ROC_BENCH_REORDER={_REORDER_RAW!r} "
          f"(want 0|1|auto)", file=sys.stderr)
    REORDER = "off"
# ROC_BENCH_INTER=ring: inter-community edges go to ring-adjacent
# communities (hierarchical locality, the structure real co-purchase
# graphs have) instead of uniformly — the case a locality reorder can
# exploit.  Annotates the metric; canonical stays uniform.
INTER = os.environ.get("ROC_BENCH_INTER", "uniform")
# ROC_BENCH_AB="matmul,binned" (any comma list of backends): measure every
# leg in THIS process, same dataset, same warmup discipline, per-epoch
# times in the artifact.  The round-5 forced-vs-auto anomaly (256 s vs
# 30 s on byte-identical HLO, docs/PERF.md) was exactly cross-invocation
# harness state — first-invocation compile effects landing inside the
# measured window of one leg and not the other.  A same-process A/B
# removes that class of artifact by construction; the reported value is
# the slowest/fastest leg ratio (unit "x", 1.0 = parity).
AB = [s.strip() for s in os.environ.get("ROC_BENCH_AB", "").split(",")
      if s.strip()]
# ROC_BENCH_BALANCE_EVERY=N: run the online cost-model load balancer
# (roc_tpu/balance/) every N measured epochs; rebalance events + the latest
# per-part probe timings land in the artifact.  Annotates the metric;
# epoch_times stay pure epoch wall times (balance rounds run between the
# timed epochs — see TrainStats), but the canonical vs_baseline claim
# stays balance-off.
BALANCE_EVERY = _env("ROC_BENCH_BALANCE_EVERY", "0", int)
# ROC_BENCH_ANALYZE=1: attach a static-analysis block to the artifact —
# the lowered train/eval steps' collective counts + f64 invariants
# (roc_tpu.analysis.audit_trainer) and the retrace-guard trace counts
# observed across the measured window (expected: zero — any retrace there
# is exactly the per-epoch recompile class the guard exists to catch).
ANALYZE = _env("ROC_BENCH_ANALYZE", "0", int)
# ROC_BENCH_MEM=1: attach the memory-planner artifact block (see module
# docstring).  The plan itself comes from ROC_MEM_PLAN / ROC_MEM_BUDGET,
# which Config.__post_init__ reads when build_and_warm constructs it; a
# non-default plan changes the traced program, so it annotates the metric
# and the canonical vs_baseline claim stays plan-off.
MEM = _env("ROC_BENCH_MEM", "0", int)
MEM_PLAN = os.environ.get("ROC_MEM_PLAN", "keep")
# ROC_BENCH_STREAM=1: run the measured legs through the out-of-core
# host-streaming executor (-stream; ROC_STREAM is set for the built
# Config).  The artifact gains a "stream" block with the measured
# stall/transfer split and overlap fraction — the exit-criterion number
# for the out-of-core ROADMAP item.  Streamed legs annotate the metric
# and are excluded from vs_baseline: they time a different executor.
# ROC_STREAM_SLOTS sets the prefetch ring depth.
STREAM = _env("ROC_BENCH_STREAM", "0", int)
STREAM_SLOTS = _env("ROC_STREAM_SLOTS", "2", int)
# ROC_STREAM_SPILL=DIR (the same env Config.__post_init__ honors): the
# boundary stores rotate through CRC'd NVMe memmaps under DIR — the
# third storage tier.  Spill legs annotate the metric and inherit the
# stream exclusions (a spill leg is by construction a streamed leg, so
# vs_baseline already skips it).
STREAM_SPILL = os.environ.get("ROC_STREAM_SPILL", "")
# ROC_BENCH_SERVE=1: after the training measurement, stand up the serving
# engine (roc_tpu/serve) on the same graph/model and offer an open-loop
# query load.  The artifact gains a "serve" block (p50/p99/qps/
# cold_start_s).  Serving legs annotate the metric and are excluded from
# vs_baseline: request latency is a different claim than epoch time and
# must never blend into it (tools/serve_bench.py owns the standalone
# BENCH_SERVE.json artifact; this block is the riding-along capture).
SERVE = _env("ROC_BENCH_SERVE", "0", int)
SERVE_REQUESTS = _env("ROC_BENCH_SERVE_REQUESTS", "100", int)
SERVE_QPS = _env("ROC_BENCH_SERVE_QPS", "50.0", float)
# ROC_BF16_STORAGE=1 (the same env Config.__post_init__ honors): features
# stored/staged/exchanged as bf16, fp32 accumulation.  Every artifact is
# stamped with the storage dtype; bf16 legs annotate the metric and are
# excluded from vs_baseline — the reference figures are fp32-storage
# numbers.
DTYPE = "bf16" if os.environ.get("ROC_BF16_STORAGE") == "1" else "fp32"
# The canonical metric (the one vs_baseline speaks to) is the unmodified
# Reddit shape; shape overrides annotate the metric name so histories are
# never conflated.
CANONICAL_SHAPE = (SHAPE == "reddit"
                   and "ROC_BENCH_NODES" not in os.environ
                   and "ROC_BENCH_DEG" not in os.environ
                   and LAYERS == [602, 256, 41]
                   and INTER == "uniform")
METRIC = (f"{MODEL}_{SHAPE}{'-'.join(map(str, LAYERS))}"
          + (f"_heads{HEADS}" if MODEL == "gat" else "")
          + "_epoch_time"
          + ("" if SCALE == 1.0 else f"_scale{SCALE:g}")
          + ("" if PRECISION == "fast" else f"_{PRECISION}")
          + ("" if REORDER == "off" else f"_reorder-{REORDER}")
          + ("" if INTER == "uniform" else f"_inter-{INTER}")
          + ("" if BALANCE_EVERY == 0 else f"_balance{BALANCE_EVERY}")
          + ("" if MEM_PLAN == "keep" else f"_mem-{MEM_PLAN}")
          + ("" if DTYPE == "fp32" else f"_{DTYPE}")
          + ("" if not STREAM else f"_stream{STREAM_SLOTS}")
          + ("" if not (STREAM and STREAM_SPILL) else "_spill")
          + ("" if not SERVE else "_serve"))

# --- absolute-perf accounting (VERDICT r3 item 4) -------------------------
# REF_EPOCH_S above is a recalled figure with ±30% uncertainty; mfu /
# roofline_frac let the artifact be judged on absolutes.  The peaks
# (keyed by device_kind) and the epoch FLOPs/bytes accounting live in
# roc_tpu/obs/roofline.py — the single definition site — and are fed from
# the trained model's op IR, so residual projections, GAT head folding,
# and SAGE concat widths are counted from what actually ran instead of
# re-derived here.


def _require_tpu():
    """Device stamp for a TPU run; exit 2 at once on anything else (JAX
    itself falls back to the CPU when libtpu finds no chip)."""
    from roc_tpu import cache, device
    cache.enable_compile_cache()
    dev = device.describe()
    print(f"# {device.banner()}", file=sys.stderr)
    if not device.on_tpu():
        print(f"bench.py: backend is {dev['platform']!r}, not 'tpu' — "
              f"nothing timed", file=sys.stderr)
        sys.exit(2)
    return dev


def _cached_dataset():
    """The synthetic Reddit-shape graph costs ~46 s to generate at full
    scale; cache it under the checkout's .cache/ so repeated bench
    invocations skip the build.  Cache key = every generation input."""
    import hashlib

    import numpy as np

    from roc_tpu.cache import cache_dir
    from roc_tpu.graph import datasets

    # v1: bump when datasets.synthetic's construction or defaults
    # (p_intra=0.8, feature_snr=1.0) change — the key must cover every
    # input that shapes the generated data.
    if CANONICAL_SHAPE:
        splits = dict(n_train=int(153431 * SCALE),
                      n_val=int(23831 * SCALE), n_test=int(55703 * SCALE))
    else:   # overridden shapes: proportional masks (timing-irrelevant)
        splits = dict(n_train=int(NODES * 0.6), n_val=int(NODES * 0.1),
                      n_test=int(NODES * 0.2))
    args = dict(gen="synthetic-v1", p_intra=0.8, feature_snr=1.0,
                num_nodes=NODES, avg_degree=AVG_DEG, in_dim=IN_DIM,
                num_classes=CLASSES, seed=1, inter=INTER, **splits)
    key = "_".join(f"{k}={v}" for k, v in sorted(args.items()))
    digest = hashlib.sha1(key.encode()).hexdigest()[:12]
    path = os.path.join(cache_dir("bench"), f"roc_bench_{digest}.npz")
    try:
        with np.load(path, allow_pickle=False) as z:
            if z["key"].item() == key:
                from roc_tpu.graph.csr import Csr
                g = Csr(num_nodes=int(args["num_nodes"]),
                        num_edges=int(z["col_idx"].shape[0]),
                        row_ptr=z["row_ptr"], col_idx=z["col_idx"])
                return datasets.Dataset(
                    name=f"{SHAPE}-bench", graph=g, features=z["features"],
                    labels=None, label_ids=z["label_ids"], mask=z["mask"],
                    in_dim=IN_DIM, num_classes=CLASSES)
    except Exception:            # corrupt/missing cache: regenerate
        pass  # roclint: allow(silent-swallow) — fall through rebuilds it
    ds = datasets.synthetic(f"{SHAPE}-bench", NODES, AVG_DEG, IN_DIM, CLASSES,
                            n_train=args["n_train"], n_val=args["n_val"],
                            n_test=args["n_test"], seed=1, inter_mode=INTER)
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp"   # private tmp: concurrent runs
        with open(tmp, "wb") as f:       # exact name; savez won't rename
            np.savez(f, key=np.array(key), row_ptr=ds.graph.row_ptr,
                     col_idx=ds.graph.col_idx, features=ds.features,
                     label_ids=ds.label_ids, mask=ds.mask)
        os.replace(tmp, path)
    except OSError:
        # roclint: allow(silent-swallow) — cache is best-effort
        pass
    return ds


def run():
    import jax

    from roc_tpu.graph import datasets
    from roc_tpu.models import build_model
    from roc_tpu.train.config import Config
    from roc_tpu.train.driver import device_sync, make_trainer

    if BACKEND not in ("auto", "xla", "matmul", "pallas", "binned"):
        raise ValueError(f"ROC_BENCH_BACKEND={BACKEND!r}: "
                         f"must be auto|xla|matmul|binned (or the alias "
                         f"pallas)")
    if PRECISION not in ("exact", "fast"):
        raise ValueError(f"ROC_BENCH_PRECISION={PRECISION!r}: "
                         f"must be exact|fast")
    dev = _require_tpu()
    n_dev = dev["count"]

    t0 = time.time()
    ds = _cached_dataset()
    print(f"# graph ready: {ds.graph.num_nodes} nodes "
          f"{ds.graph.num_edges} edges ({time.time()-t0:.1f}s)",
          file=sys.stderr)
    if REORDER != "off":
        from roc_tpu.graph.reorder import maybe_reorder_dataset
        t0 = time.time()
        ds, _, note = maybe_reorder_dataset(ds, REORDER)
        print(f"# {note} ({time.time()-t0:.1f}s)", file=sys.stderr)

    def build_and_warm(backend):
        cfg = Config(layers=LAYERS, num_epochs=1, learning_rate=0.01,
                     weight_decay=1e-4, dropout_rate=0.5, eval_every=10**9,
                     num_parts=max(n_dev, 2) if STREAM else n_dev,
                     halo=True, aggregate_backend=backend,
                     aggregate_precision=PRECISION, model=MODEL, heads=HEADS,
                     balance_every=BALANCE_EVERY,
                     stream=bool(STREAM), stream_slots=STREAM_SLOTS)
        # aggr="": each model's own default (gcn sum, sage avg, ...) so the
        # metric name labels what actually ran
        model = build_model(MODEL, LAYERS, cfg.dropout_rate, "",
                            heads=HEADS)
        tr = make_trainer(cfg, ds, model)
        # device_sync fetches the loss to the host: each epoch's params feed
        # the next, so syncing the last loss transitively waits on every
        # step.  A kernel that does not compile raises here and fails the
        # run — no other backend is timed in its place.
        loss = None
        for _ in range(WARMUP):
            loss = tr.run_epoch()
        device_sync(loss)
        return tr

    def measure(tr):
        """Measured epochs via the driver's own train() loop — TrainStats
        is the single source of epoch timings (no bench-side re-derivation).
        Each epoch is host-synced inside train(); that per-epoch sync costs
        one device round trip (~ms against ~0.6 s epochs) and buys the
        first-epoch-inflation visibility the round-5 anomaly hunt needed —
        a wedged first invocation shows up as one outlier sample instead of
        silently inflating the mean.  Balance rounds (if enabled) run
        between the timed epochs, so epoch_times stay pure."""
        import gc
        gc.collect()               # no GC pause inside the measured loop
        tr.config.num_epochs = MEASURED
        return tr.train(print_fn=lambda *_: None)

    if AB:
        legs = {}
        for b in AB:
            tr = build_and_warm(b)
            times = measure(tr).epoch_times
            legs[b] = {
                "value": round(sum(times) / len(times), 4),
                "backend": tr.gdata.backend,
                "epoch_s_min": round(min(times), 4),
                "epoch_times": [round(t, 4) for t in times],
            }
            del tr                 # drop the leg's HBM before the next
        vals = [leg["value"] for leg in legs.values()]
        return {
            "metric": METRIC + "_ab_" + "-vs-".join(AB),
            "value": round(max(vals) / min(vals), 4),
            "unit": "x",
            "vs_baseline": None,
            "platform": jax.default_backend(),
            "ab": legs,
        }

    trainer = build_and_warm(BACKEND)
    guard = None
    if ANALYZE:
        from roc_tpu.analysis import RetraceGuard
        with RetraceGuard(on_violation="record") as guard:
            stats = measure(trainer)
    else:
        stats = measure(trainer)
    times = stats.epoch_times
    epoch_s = sum(times) / len(times)

    edges_per_sec_per_chip = ds.graph.num_edges / epoch_s / n_dev
    # what actually ran (auto resolves); the streaming executor drives the
    # segment ops directly and has no per-device gdata bundle
    resolved = getattr(getattr(trainer, "gdata", None), "backend",
                       "stream" if STREAM else "none")
    from roc_tpu import device
    print(f"# {epoch_s*1e3:.1f} ms/epoch {device.banner()} "
          f"backend={resolved} "
          f"{edges_per_sec_per_chip/1e6:.1f}M edges/s/chip", file=sys.stderr)
    # Absolute figures (judge-auditable without the ±30% REF_EPOCH_S):
    # mfu = achieved model-FLOPs/s over the chip's bf16 peak; roofline_frac
    # = best-possible epoch time (max of compute- and memory-bound lower
    # bounds) over the measured one — 1.0 means at the roofline.  Peaks are
    # keyed by device_kind (roofline.PEAKS); an unknown kind raises.
    from roc_tpu.obs import roofline
    flops, min_bytes = roofline.model_flops_bytes(
        trainer.model, NODES, ds.graph.num_edges, precision=PRECISION)
    kind = dev["kind"]
    result = {
        "metric": METRIC,
        "value": round(epoch_s, 4),
        "unit": "s",
        # the reference figure is a GCN number measured on the UN-reordered
        # canonical shape; other models and reordered runs report null (a
        # reorder-on ratio against the un-reordered reference figure would
        # mislead even though the metric name is annotated)
        "vs_baseline": round(REF_EPOCH_S / epoch_s, 3)
        if MODEL == "gcn" and CANONICAL_SHAPE and REORDER == "off"
        and BALANCE_EVERY == 0 and MEM_PLAN == "keep"
        and DTYPE == "fp32" and not STREAM
        and not SERVE else None,
        "backend": resolved,                   # what auto resolved to
        "dtype": DTYPE,                        # feature-storage dtype
        "platform": dev["platform"],
        "device": dev,
        "edges_per_sec_per_chip": round(edges_per_sec_per_chip),
        "model_tflops_per_epoch": round(flops / 1e12, 4),
        "mfu": round(roofline.mfu(flops, epoch_s, n_dev, kind), 4),
        "roofline_frac": round(roofline.roofline_frac(
            flops, min_bytes, epoch_s, n_dev, kind), 4),
        # per-epoch samples: outliers (first-invocation state, GC) are
        # visible instead of silently folded into the mean
        "epoch_s_min": round(min(times), 4),
        "epoch_s_max": round(max(times), 4),
        "epoch_times": [round(t, 4) for t in times],
        # same convention per epoch: a first-invocation outlier shows up
        # as a dented sample instead of silently dragging the aggregate
        "mfu_per_epoch": [round(roofline.mfu(flops, t, n_dev, kind), 4)
                          for t in times],
        "roofline_frac_per_epoch": [
            round(roofline.roofline_frac(flops, min_bytes, t, n_dev, kind),
                  4) for t in times],
    }
    if os.environ.get("ROC_BINNED_FLAT") == "1":
        # flat-schedule A/B leg (spmd honors the same env when building
        # shard plans) — stamp it so paired artifacts are distinguishable
        result["binned_flat"] = True
    if ANALYZE:
        from roc_tpu import analysis
        rep = analysis.audit_trainer(trainer)
        result["analysis"] = {
            "key": rep.key,
            "train_ops": rep.steps["train"]["ops"],
            "f64_lines": rep.steps["train"]["f64_lines"],
            "convert_f64": rep.steps["train"]["convert_f64"],
            "invariant_violations": analysis.check_invariants(rep),
            # traces observed during the measured window (warmup compiled
            # everything, so anything non-zero here is a mid-run recompile)
            "measured_retraces": guard.snapshot(),  # roclint: allow(unledgered-prediction) — artifact stamping of a guard counter, not a new prediction site
            "retrace_violations": guard.violations,
        }
    if BALANCE_EVERY:
        bal = {"events": stats.rebalance_events}
        mgr = getattr(trainer, "balancer", None)
        if mgr is not None:          # latest per-part probe timings
            probes = mgr.telemetry.samples()
            latest = probes[-trainer.config.num_parts:]
            bal["part_probe_s"] = [round(s.time_s, 7) for s in latest]
            bal["part_edges"] = [s.edges for s in latest]
        else:                        # e.g. single device -> Trainer path
            bal["note"] = "balancer unsupported for this trainer mode"
        result["balance"] = bal
    if MEM:
        from roc_tpu import memory
        est = getattr(trainer, "mem_estimate", None)
        plan = getattr(trainer, "mem_plan", None)
        mem = {"note": "trainer built without a memory plan"}
        if plan is not None and est is not None:
            # all-KEEP / all-REMAT reference points come from the same
            # estimate the chosen plan was optimized against, so the deltas
            # are exactly what the DP traded off (predicted, not re-run —
            # measuring three warm programs would triple the bench budget)
            keep = memory.plan_memory(est, mode="keep")
            remat = memory.plan_memory(est, mode="remat")
            mem = {
                "plan": plan.to_dict(),
                # artifact stamping of already-ledgered values (the memory
                # watchdog pairs these via the calibration ledger)
                "predicted_peak_bytes": plan.predicted_peak_bytes,  # roclint: allow(unledgered-prediction) — artifact stamping of already-ledgered values
                "measured_peak_bytes": memory.measured_peak_bytes(),  # roclint: allow(unledgered-prediction) — artifact stamping of already-ledgered values
                "epoch_peak_hbm_bytes": (stats.peak_hbm_bytes[-1]
                                         if stats.peak_hbm_bytes else None),
                "peak_hbm_source": stats.peak_hbm_source,
                "keep_peak_bytes": keep.predicted_peak_bytes,
                "remat_peak_bytes": remat.predicted_peak_bytes,
                "step_delta_vs_keep": round(
                    plan.predicted_step_s / keep.predicted_step_s - 1, 4),
                "step_delta_vs_remat": round(
                    plan.predicted_step_s / remat.predicted_step_s - 1, 4),
            }
        if plan is not None and plan.any_offload():
            # bench legs must not claim host offload before the streaming
            # executor is the one running: an OFFLOAD verdict lowered by the
            # in-core trainers rematerializes instead (planner docstring)
            mem["offload_executes_as"] = plan.offload_executes_as
        result["memory"] = mem
    if STREAM:
        # the ISSUE-9 exit criterion: the artifact records the *measured*
        # stream/compute overlap fraction, not a predicted one
        st = getattr(trainer, "stream_stats", None)
        result["stream"] = st() if callable(st) else {
            "note": "trainer has no stream stats (fell back to in-core)"}
        # top-level tier stamps for hw_revalidate step 5's paired legs:
        # stream_stats carries them too when the executor ran, but the
        # top-level copy survives the fell-back-to-in-core note above
        result["stream_dtype"] = DTYPE
        result["stream_spill"] = STREAM_SPILL
    if SERVE:
        # serving leg: same graph/model, the engine's own cold start (the
        # trainer above already warmed this process's plan cache, so
        # plan_builds pins the zero-rebuild contract on real shapes too)
        from roc_tpu.serve import ServeEngine, run_load
        with ServeEngine(trainer.config, ds, trainer.model) as eng:
            eng.warmup()
            load = run_load(eng, n_requests=SERVE_REQUESTS, qps=SERVE_QPS)
            result["serve"] = dict(
                load, cold_start_s=eng.cold_start_stats["cold_start_s"],
                plan_builds=eng.cold_start_stats["plan_builds"],
                buckets=eng.cold_start_stats["buckets"])
    reg = getattr(trainer, "_metrics", None)
    if reg is not None:
        # -obs / ROC_OBS=1 run: stamp the unified metrics block (the
        # canonical-claim conditions below are unchanged — obs observes,
        # it never annotates the metric itself)
        from roc_tpu import obs
        wd = getattr(trainer, "watchdog", None)
        result["metrics"] = {
            "grad_norms": [round(v, 6)
                           for v in reg.series("metrics", "grad_norm")],
            "wire_bytes_per_step": (
                int(reg.latest["metrics_wire_bytes"])
                if "metrics_wire_bytes" in reg.latest else None),
            "watchdog_verdict": wd.verdict() if wd is not None else "off",
            "watchdog_alerts": list(wd.alerts) if wd is not None else [],
            "span_types": sorted(obs.get_tracer().span_types()),
        }
    # Tuned-tier status (roc_tpu/tune): whether a tuned store was in
    # reach of this run's choose_geometry calls, and how it was produced.
    # ROC_AUTOTUNE=1 makes the run sweep+persist before its plan builds.
    try:
        from roc_tpu.tune import store as _tstore
        _tp = _tstore.tuned_store_path()
        _doc = _tstore.load_store(_tp) if _tp else None
        result["tuned"] = {
            "autotune": bool(getattr(trainer.config, "autotune", False)),
            "store": _tp or "",
            "entries": len(_doc["entries"]) if _doc else 0,
            "source": ("surrogate" if _doc.get("interpret", True)
                       else "device") if _doc else "",
        }
    except Exception:
        result["tuned"] = {"autotune": False, "store": "", "entries": 0,
                           "source": ""}
    return result


def main():
    try:
        result = run()
    except Exception as e:
        traceback.print_exc(file=sys.stderr)
        result = {
            "metric": METRIC,
            "value": None,
            "unit": "s",
            "vs_baseline": None,
            "error": f"{type(e).__name__}: {e}",
        }
    print(json.dumps(result))
    sys.exit(0 if result.get("error") is None else 1)


if __name__ == "__main__":
    main()
