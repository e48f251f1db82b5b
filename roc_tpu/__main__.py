"""CLI entry: ``python -m roc_tpu -dataset cora -layers 1433-16-7 -e 200 ...``

Mirrors the reference binary's invocation shape (test.sh:8):
    ./gnn -ll:gpu 1 ... -lr 0.01 -decay 0.0001 -dropout 0.5 \
          -layers 602-256-41 -file dataset/reddit-dgl -e 3000
Here `-file <prefix>` consumes the same on-disk dataset format; `-dataset
<name>` generates a deterministic synthetic stand-in (no-network builds).
"""

from __future__ import annotations

import math
import sys

from roc_tpu import cache, device
from roc_tpu.graph import datasets
from roc_tpu.models import build_model
from roc_tpu.train.config import parse_args
from roc_tpu.train.driver import make_trainer


def main(argv=None) -> int:
    cfg = parse_args(sys.argv[1:] if argv is None else argv)
    if cfg.multihost:
        # DCN path: each host contributes its local devices to one global
        # mesh (the analog of the reference's Legion/GASNet multi-machine
        # launch, Makefile:26).  Coordinator/process env comes from the
        # cluster (GKE/TPU-VM auto-detection inside initialize()).
        import jax
        jax.distributed.initialize()
    cache.enable_compile_cache()
    if not cfg.layers:
        print("error: -layers is required (e.g. -layers 1433-16-7)",
              file=sys.stderr)
        return 2
    if cfg.perhost_load and (cfg.num_parts < 2 or not cfg.filename):
        print("error: -perhost requires -file and -parts > 1",
              file=sys.stderr)
        return 2
    if cfg.exchange == "ring" and cfg.edge_shard in (True, "on"):
        print("error: -exchange ring and -edge-shard are mutually "
              "exclusive distribution strategies", file=sys.stderr)
        return 2
    if cfg.edge_shard in (True, "on") and (
            cfg.num_parts < 2 or cfg.aggr in ("max", "min")):
        print("error: -edge-shard supports sum/avg aggregation and needs "
              "-parts > 1 (since round 4 it composes with -perhost given "
              "the .t.lux transposed sidecar)", file=sys.stderr)
        return 2
    if cfg.perhost_load and cfg.check_sharding:
        # the checker's single-device reference needs the whole graph on one
        # host — the opposite of what -perhost promises
        print("error: -check-sharding needs the full graph on one host; "
              "run it without -perhost", file=sys.stderr)
        return 2
    if cfg.stream:
        if cfg.num_parts < 2:
            print("error: -stream needs -parts >= 2 (shards rotate through "
                  "the device slots; one shard streams nothing)",
                  file=sys.stderr)
            return 2
        if cfg.edge_shard in (True, "on") or cfg.exchange == "ring":
            print("error: -stream schedules its own shard rotation; "
                  "-edge-shard / -exchange ring do not compose with it",
                  file=sys.stderr)
            return 2
        if cfg.multihost:
            print("error: -stream is single-process — it trades host "
                  "memory for device memory instead of scaling out; "
                  "drop -multihost", file=sys.stderr)
            return 2
        if cfg.check_sharding or cfg.analyze:
            print("error: -check-sharding/-analyze audit the in-core SPMD "
                  "step; run them without -stream", file=sys.stderr)
            return 2
        if cfg.use_bf16:
            print("error: -stream computes in fp32; the streamed storage "
                  "cut is -bf16-storage (bf16 slots, fp32 accumulation)",
                  file=sys.stderr)
            return 2
    # Config banner, mirroring gnn.cc:48-60.
    print("        ===== GNN settings =====", file=sys.stderr)
    print(f"        dataset = {cfg.filename or cfg.dataset} seed = {cfg.seed}\n"
          f"        num_epochs = {cfg.num_epochs} learning_rate = {cfg.learning_rate:.4f}\n"
          f"        weight_decay = {cfg.weight_decay:.4f} dropout_rate = {cfg.dropout_rate:.4f}\n"
          f"        decay_rate = {cfg.decay_rate:.4f} decay_steps = {cfg.decay_steps}",
          file=sys.stderr)
    print(f"        Layers: {' '.join(map(str, cfg.layers))}", file=sys.stderr)
    # JAX falls back to the CPU when libtpu finds no chip: say where the
    # run landed before it spends any time there.
    print(f"        {device.banner()}", file=sys.stderr)

    if cfg.filename:
        ds = datasets.load_roc_dataset(cfg.filename, cfg.layers[0],
                                       cfg.layers[-1], lazy=cfg.lazy_load,
                                       graph_stub=cfg.perhost_load)
    elif cfg.dataset:
        ds = datasets.get(cfg.dataset, seed=cfg.seed)
        assert ds.in_dim == cfg.layers[0], (
            f"-layers head {cfg.layers[0]} != dataset in_dim {ds.in_dim}")
        assert ds.num_classes == cfg.layers[-1], (
            f"-layers tail {cfg.layers[-1]} != dataset classes {ds.num_classes}")
    else:
        print("error: one of -file or -dataset is required", file=sys.stderr)
        return 2

    if cfg.reorder not in (False, None, "off"):
        import time as _time

        from roc_tpu.graph.reorder import maybe_reorder_dataset
        if cfg.perhost_load:
            print("error: -reorder needs the whole graph in memory; "
                  "incompatible with -perhost (preprocess the dataset "
                  "offline instead)", file=sys.stderr)
            return 2
        t0 = _time.time()
        ds, _, note = maybe_reorder_dataset(ds, cfg.reorder)
        print(f"# {note} ({ds.graph.num_nodes} nodes, "
              f"{_time.time() - t0:.1f}s)", file=sys.stderr)

    model = build_model(cfg.model, cfg.layers, cfg.dropout_rate, cfg.aggr,
                        heads=cfg.heads)

    # One trainer build — the partition, the plans, and the compiled steps
    # are shared by -check-sharding, -analyze, and the training run.
    trainer = make_trainer(cfg, ds, model)
    gd = getattr(trainer, "gdata", None)       # the stream executor has none
    print(f"        aggregate_backend = {cfg.aggregate_backend} -> "
          f"{getattr(gd, 'backend', 'stream')} "
          f"(pallas_interpret={not device.on_tpu()})", file=sys.stderr)
    if cfg.check_sharding and cfg.num_parts > 1:
        from roc_tpu.parallel.check import check_shard_consistency
        check_shard_consistency(cfg, ds, model, sharded_trainer=trainer)
        print("# shard-consistency check passed "
              f"({cfg.num_parts} parts, halo={cfg.halo})", file=sys.stderr)

    if not cfg.analyze:
        return _exit_code(trainer.train())

    # -analyze: static audit of the lowered steps before the run, retrace
    # report after it.  Budget diffs apply only when this exact config has
    # a manifest entry (the committed matrix covers the roc-audit dataset);
    # the f64/convert invariants apply to every config.
    from roc_tpu import analysis
    report = analysis.audit_trainer(trainer)
    print(report.summary(), file=sys.stderr)
    violations = analysis.check_invariants(report)
    budgets = analysis.load_budgets()
    if report.key in budgets:
        violations += analysis.compare_report(report, budgets[report.key])
    with analysis.RetraceGuard(on_violation="record") as guard:
        stats = trainer.train()
    print(guard.report(), file=sys.stderr)
    violations += guard.violations
    if violations:
        for v in violations:
            print(f"# ANALYZE VIOLATION: {v}", file=sys.stderr)
        return 3
    print("# -analyze: clean (collective audit + retrace guard)",
          file=sys.stderr)
    return _exit_code(stats)


def _exit_code(stats) -> int:
    """The non-finite guard skips bad updates and train() returns; a run
    that ENDS non-finite has still failed."""
    if math.isfinite(stats.final_loss):
        return 0
    print(f"error: final loss is {stats.final_loss} (non-finite)",
          file=sys.stderr)
    return 1


if __name__ == "__main__":
    sys.exit(main())
