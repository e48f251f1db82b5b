"""One run of one cell: `python3 benchmark/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>`, from the root of a checkout.

The cell, its configuration, its traffic recipe and its per-layer metrics
are looked up by name in `BENCHMARK.json` and the files it points to; no
cell is named in code.  The run makes the graph from the recipe and the
seed, builds the model and the trainer through the program's own entry
points (`build_model`, `make_trainer`), warms up the train and evaluation
programs, and then

  --trace 0  runs the training job as a user runs it, `trainer.train()` for
             `eval_every` epochs at a time (so with the reference's
             evaluation pass), until `--seconds` have passed, and reports
             the cell's end-to-end metrics;
  --trace 1  wraps three epochs and one evaluation in `jax.profiler` and
             reports the cell's per-layer metrics, read from the trace, the
             program's `obs` spans and the run's counters by the generic
             readers of `benchmark/layer_metrics.py`.

The last line of standard output is the result object; everything else
(`# bench:` lines) goes before it and into `run.json` under `--out`
(default `.cache/bench_runs/<workload>/`).  Without a TPU, or with fewer
chips than the cell asks, the run exits 2 and prints no result;
`--rehearse-cpu` walks the same code on virtual CPU devices for the tiny
cells of `benchmark/rehearsal/manifest.json` and never reports `correct`.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()   # as near to process start as Python allows

import argparse     # noqa: E402
import contextlib   # noqa: E402
import gc           # noqa: E402
import importlib    # noqa: E402
import json         # noqa: E402
import math         # noqa: E402
import os           # noqa: E402
import shutil       # noqa: E402
import statistics   # noqa: E402
import sys          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

WARMUP_EPOCHS = 2       # the first compiles; the second shows the steady
                        # program (donated buffers, committed layouts)
TRACED_EPOCHS = 3
REHEARSAL_MANIFEST = os.path.join("benchmark", "rehearsal", "manifest.json")


def say(msg: str) -> None:
    print(f"# bench: {msg}", flush=True)


class Clock:
    """Seconds since process start, and named phases of set-up."""

    def __init__(self):
        self.phases: dict = {}

    @staticmethod
    def now() -> float:
        return time.perf_counter() - T_START

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = self.now()
        yield
        self.phases[name] = self.phases.get(name, 0.0) + self.now() - t0
        say(f"set-up: {name} {self.phases[name]:.2f} s")


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    p.add_argument("--manifest", default="",
                   help="manifest to read (default BENCHMARK.json; with "
                        "--rehearse-cpu the rehearsal manifest)")
    p.add_argument("--out", default="",
                   help="directory for run.json and the trace (default "
                        ".cache/bench_runs/<workload>)")
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="walk the code on virtual CPU devices; never correct")
    p.add_argument("--keep-trace", action="store_true",
                   help="leave the .xplane.pb under --out")
    return p.parse_args(argv)


def make_config(conf: dict, recipe: dict, cell: dict, seed: int):
    """The program's Config for this cell: optimiser settings from the
    configuration file, parts = chips, the seed, and whatever Config fields
    the recipe's ``job`` sets (how a later cell asks for `-reorder`)."""
    import dataclasses

    from roc_tpu.train.config import Config
    cfg = Config(
        layers=list(conf["layers"]), model=conf["model"],
        heads=int(conf.get("heads", 8)),
        learning_rate=float(conf["learning_rate"]),
        weight_decay=float(conf["weight_decay"]),
        dropout_rate=float(conf["dropout"]),
        decay_rate=float(conf.get("decay_rate", 1.0)),
        decay_steps=int(conf.get("decay_steps", 100)),
        eval_every=int(recipe.get("job", {}).get(
            "eval_every", conf["eval_every"])),
        aggregate_precision=conf["precision"],
        aggregate_backend=conf.get("aggregate_backend", "auto"),
        num_parts=int(cell["chips"]), seed=int(seed), num_epochs=1)
    known = {f.name for f in dataclasses.fields(Config)}
    for key, value in recipe.get("job", {}).items():
        if key not in known:
            raise ValueError(f"recipe job key {key!r} is no Config field")
        setattr(cfg, key, value)
    return cfg


def aggregate_widths(model) -> list:
    """Feature width at each aggregate/gat op, in op order."""
    widths, width = [], model.input.dim
    for op in model.ops:
        if op.kind == "linear":
            width = int(op.attrs["out_dim"])
        elif op.kind == "gat":
            width = int(op.attrs["heads"]) * int(op.attrs["head_dim"])
            widths.append(width)
        elif op.kind == "aggregate":
            widths.append(width)
    return widths


def program_logits(trainer, params_host) -> "np.ndarray":
    """The program's evaluation-mode logits for ``params_host``, in the
    graph's own row order (a sharded trainer pads and permutes)."""
    import jax
    import numpy as np
    trainer.params = jax.tree.map(
        lambda h, cur: jax.device_put(h, cur.sharding), params_host,
        trainer.params)
    out = np.asarray(trainer.predict_logits())
    part = getattr(trainer, "part", None)
    return part.unpad_nodes(out) if part is not None else out


def peak_bytes(devices, rehearse: bool) -> int:
    """Peak HBM held on the fullest of the cell's devices: the allocator's
    `peak_bytes_in_use` (live arrays: features, plans, parameters) plus its
    `peak_bytes_reserved` (the scratch the loaded programs reserve for their
    temporaries, which `bytes_in_use` does not count).  On the v5e the two
    add up to what no one else can have: `largest_free_block_bytes` =
    `bytes_limit` - `bytes_in_use` - `bytes_reserved` to the byte, and the
    sum is within 1 % of the compiler's arguments + temporaries for the
    train step (PERF.md section 6, PR 22).  A device whose memory_stats()
    lacks either number fails the run; no estimate stands in.  (A CPU
    reports none: the rehearsal says 0.)"""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        missing = [k for k in ("peak_bytes_in_use", "peak_bytes_reserved")
                   if k not in stats]
        if missing:
            if rehearse:
                return 0
            raise RuntimeError(f"{d}: memory_stats() reports no "
                               f"{' and no '.join(missing)}")
        peaks.append(int(stats["peak_bytes_in_use"])
                     + int(stats["peak_bytes_reserved"]))
    return max(peaks)


def main(argv) -> int:
    args = parse(argv)
    from benchmark import manifest as mf
    manifest_path = args.manifest or (
        REHEARSAL_MANIFEST if args.rehearse_cpu else "BENCHMARK.json")
    m = mf.load(os.path.join(ROOT, manifest_path))
    cell = mf.cell(m, args.workload)
    chips = int(cell["chips"])
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}")

    clock = Clock()
    with clock.phase("import"):
        import jax
        import numpy as np

        from benchmark import checks, graphgen, layer_metrics, trace_reduce
        from benchmark import roofline as bench_roofline
        from roc_tpu import cache, obs
        from roc_tpu.analysis import RetraceGuard
        from roc_tpu.models import build_model
        from roc_tpu.train.driver import make_trainer
        devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if not args.rehearse_cpu:
        if dev["platform"] != "tpu" or len(devices) < chips:
            print(f"benchmark: cell {cell['name']} needs {chips} TPU "
                  f"chip(s); JAX found {dev}.  Nothing run.",
                  file=sys.stderr)
            return 2
        bench_roofline.peaks_for(dev["kind"])    # unknown kind: an error
    tag = "[REHEARSAL cpu] " if args.rehearse_cpu else ""
    say(f"{tag}{cell['name']} seed {args.seed} trace {args.trace}; {dev}; "
        f"jax {jax.__version__}; compile cache at "
        f"{cache.enable_compile_cache()}")
    out_dir = os.path.join(ROOT, args.out or os.path.join(
        ".cache", "bench_runs", cell["name"]))
    os.makedirs(out_dir, exist_ok=True)
    compiles = checks.CompileCounter()
    if args.trace:
        obs.enable(True)        # record the program's host spans

    conf = mf.load(os.path.join(ROOT,
                                mf.config_entry(m, cell["config"])["file"]))
    recipe = graphgen.load_recipe(mf.traffic_path(m, cell))
    if "structure_seed" not in recipe:
        # The program's plan cache (content-keyed, ~450 MB of Reddit plans)
        # serves a recipe whose edges are the same in every run.  A graph
        # that follows --seed would never hit and only fill the checkout's
        # disk: opt out through the program's own switch.
        os.environ.setdefault("ROC_PLAN_CACHE", "0")
    layers = list(conf["layers"])
    with clock.phase("graph"):
        ds = graphgen.generate(recipe, layers[0], layers[-1], args.seed,
                               name=cell["traffic"])
    info = {"cell": cell["name"], "seed": args.seed, "trace": args.trace,
            "device": dev, "graph": graphgen.degree_stats(ds.graph)}
    say(f"graph: {info['graph']}")

    cfg = make_config(conf, recipe, cell, args.seed)
    model = build_model(cfg.model, cfg.layers, cfg.dropout_rate, cfg.aggr,
                        heads=cfg.heads)
    with clock.phase("trainer"):
        trainer = make_trainer(cfg, ds, model)
    gd = trainer.gdata
    info["program"] = {
        "backend": gd.backend, "geometries": checks.geometries(gd),
        "exchange": getattr(trainer, "_exchange_mode", None),
        "trainer": type(trainer).__name__}
    part = getattr(trainer, "part", None)
    if part is not None:
        live = np.asarray(part.num_edges_valid, np.float64)
        halo = getattr(trainer, "halo", None)
        info["program"]["shards"] = {
            "parts": int(part.num_parts), "rows": int(part.shard_nodes),
            "edges_padded": int(part.shard_edges),
            "edges_live": [int(v) for v in live],
            "padded_max_tax": float(part.shard_edges * part.num_parts
                                    / max(live.sum(), 1.0) - 1.0),
            "halo_rows_per_peer": int(halo.K) if halo is not None else None}
    say(f"program: {json.dumps(info['program'])}")
    params0 = jax.device_get(trainer.params)

    # record every epoch's loss through the program's own loop
    losses, evals = [], []
    run_epoch, evaluate = trainer.run_epoch, trainer.evaluate

    def recording_run_epoch():
        with jax.profiler.TraceAnnotation("bench.epoch"):
            loss = run_epoch()
            losses.append(loss)
            # the loop's own device_sync follows at once and finds the
            # value ready: the annotation then spans dispatch to completion
            jax.block_until_ready(loss)
        return loss

    def recording_evaluate():
        with jax.profiler.TraceAnnotation("bench.eval"):
            out = jax.block_until_ready(evaluate())
        evals.append(out)
        return out

    trainer.run_epoch = recording_run_epoch
    trainer.evaluate = recording_evaluate
    quiet = [].append       # the program's metric lines are not results

    with clock.phase("warmup"):
        cfg.num_epochs = WARMUP_EPOCHS
        trainer.train(print_fn=quiet)
    setup = compiles.since((0.0, 0, 0))
    setup_s = clock.now()
    clock.phases["compile_s"] = setup["compile_s"]
    say(f"set-up {setup_s:.2f} s in all; compiled {setup['compiled']} "
        f"program(s) in {setup['compile_s']:.2f} s, loaded "
        f"{setup['cache_hits']} from the cache")

    # ---- the measured window -------------------------------------------
    # set-up leaves millions of objects behind; a full collection in the
    # middle of an epoch would be read as the program's time
    gc.collect()
    gc.freeze()
    epoch_times: list = []
    window_error = ""
    stats = None
    n_warm = len(losses)
    mark = compiles.mark()
    trace_dir = os.path.join(out_dir, "trace")
    with RetraceGuard(warmup=0, on_violation="record") as guard:
        guard.arm()
        t0 = clock.now()
        try:
            if args.trace:
                shutil.rmtree(trace_dir, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                try:
                    with jax.profiler.TraceAnnotation("bench.window"):
                        cfg.num_epochs = TRACED_EPOCHS
                        stats = trainer.train(print_fn=quiet)
                        epoch_times += stats.epoch_times
                        if not evals[1:]:   # none fell into these epochs
                            trainer.evaluate()
                finally:
                    jax.profiler.stop_trace()
            else:
                cfg.num_epochs = cfg.eval_every
                while True:
                    stats = trainer.train(print_fn=quiet)
                    epoch_times += stats.epoch_times
                    if clock.now() - t0 >= args.seconds:
                        break
        except Exception as e:   # an epoch raised: it failed, say so
            import traceback
            traceback.print_exc()
            window_error = f"{type(e).__name__}: {e}"[:300]
        window_s = clock.now() - t0
    in_window = compiles.since(mark)
    used = devices[:chips]
    peak = peak_bytes(used, args.rehearse_cpu)
    say(f"memory_stats of {used[0]}: {used[0].memory_stats()}")
    losses_f = [float(np.asarray(v)) for v in losses]
    attempted = len(losses_f) - n_warm
    failed = int(trainer._nf_skips) + (1 if window_error else 0)
    ordered = sorted(epoch_times)
    say(f"window: {window_s:.3f} s, {attempted} epochs, {len(evals)} "
        f"evaluation(s) since start; epoch median "
        f"{statistics.median(epoch_times) if epoch_times else math.nan:.6f}"
        f" s over {len(epoch_times)} samples, slowest "
        f"{ordered[-1] if ordered else math.nan:.6f} s; peak HBM "
        f"{peak / 2**30:.3f} GiB; in the window: {in_window}")
    say(f"losses: first {losses_f[0]:.4f}, last {losses_f[-1]:.4f}")

    # ---- correct? (outside the timed window) ---------------------------
    check = {
        "tpu_with_the_cells_chips":
            dev["platform"] == "tpu" and len(devices) >= chips,
        "one_part_per_device":
            chips == 1 or checks.one_part_per_device(trainer, chips),
        "no_compile_in_window": in_window["requests"] == 0,
        "no_retrace_in_window": not guard.violations,
        "losses_finite": all(math.isfinite(v) for v in losses_f),
        "loss_fell": losses_f[-1] < losses_f[0],
        "no_epoch_failed": failed == 0,
    }
    paramsN = jax.device_get(trainer.params)
    backend = gd.backend
    with clock.phase("reference"):
        got = [program_logits(trainer, p) for p in (params0, paramsN)]
        trainer.run_epoch, trainer.evaluate = run_epoch, evaluate
        del trainer, gd, run_epoch, evaluate, stats
        gc.collect()
        ref = importlib.import_module(
            "benchmark.references." + conf.get("reference", conf["model"]))
        for which, p, g in zip(("initial", "final"), (params0, paramsN), got):
            want = ref.reference_logits(p, ds, layers, device=used[0])
            err = checks.rel_fro(g, want)
            info[f"logits_rel_fro_{which}"] = err
            check[f"logits_match_reference_{which}"] = \
                err <= checks.logits_tol(backend, which)
            del want
    say(f"logits vs reference (relative Frobenius error) on the {backend} "
        f"backend: initial {info['logits_rel_fro_initial']:.3e} (bound "
        f"{checks.logits_tol(backend, 'initial'):g}), final "
        f"{info['logits_rel_fro_final']:.3e} (bound "
        f"{checks.logits_tol(backend, 'final'):g})")
    correct = all(check.values())
    say(f"checks: {json.dumps(check)}")

    # ---- metrics -------------------------------------------------------
    device = {**dev, "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if args.trace:
        spans: dict = {}
        for s in obs.get_tracer().spans():
            spans.setdefault(s.name, []).append(s.dur_s)
        trace = trace_reduce.load(trace_reduce.find_xplane(trace_dir),
                                  cpu_stand_in=args.rehearse_cpu)
        wanted = mf.metrics_for(m, "per_layer", cell["name"])
        specs = [mf.layer_metric_spec(m, cell, e["name"]) for e in wanted]
        shapes = {"chips": chips, "nodes": ds.graph.num_nodes,
                  "in_edges": ds.graph.num_edges,
                  "precision": cfg.aggregate_precision,
                  "aggregate_widths": aggregate_widths(model),
                  "layers": layers,
                  "backend": None if args.rehearse_cpu else backend}
        counters = {"graph_s": clock.phases["graph"],
                    "compile_s": setup["compile_s"],
                    "trainer_s": clock.phases["trainer"],
                    "warmup_s": clock.phases["warmup"]}
        run = layer_metrics.TracedRun(
            trace, specs, spans, counters, shapes,
            dev["kind"] if not args.rehearse_cpu else "TPU v5 lite")
        metrics = {}
        for spec in specs:
            value = layer_metrics.read(run, spec)
            if value is not None:
                metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
        w = run.window()
        if w is not None and trace.devices:
            device["busy_s"] = run.window_busy_ns() / 1e9
            device["window_s"] = (w[1] - w[0]) / 1e9
            first = trace.devices[min(trace.devices)]
            result["breakdown"] = {
                "device_ops": trace_reduce.top_ops(
                    trace_reduce.clip(first, [w]), 10),
                "idle_gaps": trace_reduce.idle_gaps(
                    trace_reduce.clip(first, [w]), w, trace.annotations, 5)}
            epoch_busy = statistics.fmean(
                trace_reduce.busy_ns(ops) for ops in run.epoch_ops.values())
            info["traced"] = {
                "epochs": len(run.epochs),
                "epoch_busy_ms": epoch_busy / 1e6 / max(len(run.epochs), 1),
                "spans": {k: [len(v), sum(v)] for k, v in spans.items()}}
            say(f"traced: {json.dumps(info['traced'])}")
        if not args.keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values = {
            "epoch_s": statistics.median(epoch_times) if epoch_times
            else math.nan,
            "edges_per_s_per_chip":
                ds.graph.num_edges * attempted / window_s / chips,
            "peak_hbm_gib": peak / 2**30,
            "setup_s": setup_s,
        }
        metrics = {e["name"]: {"value": values[e["name"]], "unit": e["unit"]}
                   for e in mf.metrics_for(m, "end_to_end", cell["name"])}
    result["metrics"] = metrics
    result["device"] = device
    info.update(setup_phases=clock.phases, checks=check, result=result,
                losses=losses_f, epoch_times=epoch_times,
                window_error=window_error)
    with open(os.path.join(out_dir, "run.json"), "w", encoding="utf-8") as f:
        json.dump(info, f, indent=1)
    say(f"phases: {json.dumps(clock.phases)}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
