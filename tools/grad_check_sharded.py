"""Loss and gradients of a multi-chip cell's own train step against the plain
reference: the backward halo exchange and the gradient all-reduce, which no
run of a cell compares (`correct` holds evaluation logits only) and which
`benchmark/grad_check.py` cannot reach (one chip, and it differentiates
through `make_gctx`, not through the sharded step).

    chiprun --chips 4 -- python3 tools/grad_check_sharded.py \
        --workload gcn-products.p4 --nodes 1028592 --seed 1

The cell's configuration and recipe, dropout off, at `--nodes` nodes (0 =
the recipe's; the reference's `loss_and_grads` must fit one chip beside
nothing else: about 7 GB at 42 % of the products shape, 17 GB at all of it),
one part a chip.  One train step from zero Adam moments with no weight decay
leaves ``m = (1 - beta1) * g``, so ``m / (1 - beta1)`` is the gradient the
step applied, all-reduced over the parts (tests/test_products_config.py
reads the same at toy widths).  The trainer is dropped before the reference
runs.  Prints one JSON line; exits 1 when a weight gradient's relative
Frobenius error is over `checks.GRAD_REL_FRO_TOL`, 2 without a TPU.
`--rehearse-cpu` walks it on virtual CPU devices.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--nodes", type=int, default=0)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--manifest", default="BENCHMARK.json")
    p.add_argument("--rehearse-cpu", action="store_true")
    args = p.parse_args(argv)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "xla_force_host_platform_device_count" not in os.environ.get(
                "XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
                " --xla_force_host_platform_device_count=4"
    os.environ.setdefault("ROC_PLAN_CACHE", "0")
    import jax
    import numpy as np

    from benchmark import checks, graphgen
    from benchmark import manifest as mf
    from benchmark import run as bench_run
    from roc_tpu import cache
    from roc_tpu.models import build_model
    from roc_tpu.train.driver import make_trainer
    m = mf.load(os.path.join(ROOT, args.manifest))
    cell = mf.cell(m, args.workload)
    parts = int(cell["chips"])
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        print(f"grad_check_sharded: no TPU ({dev}); nothing run.",
              file=sys.stderr)
        return 2
    cache.enable_compile_cache()
    conf = dict(mf.load(os.path.join(
        ROOT, mf.config_entry(m, cell["config"])["file"])), dropout=0.0)
    if float(conf["weight_decay"]) != 0.0:
        raise SystemExit("weight decay folds into Adam's first moment: "
                         "m / (1 - beta1) is the gradient only without it")
    recipe = graphgen.load_recipe(mf.traffic_path(m, cell))
    if args.nodes:
        whole = recipe["nodes"]
        recipe.update(nodes=args.nodes, splits={
            k: int(v * args.nodes / whole)
            for k, v in recipe["splits"].items()})
    layers = list(conf["layers"])
    ds = graphgen.generate(recipe, layers[0], layers[-1], args.seed)
    cfg = bench_run.make_config(conf, recipe, cell, args.seed)
    cfg.eval_every = 10**9
    trainer = make_trainer(cfg, ds, build_model(
        cfg.model, cfg.layers, 0.0, cfg.aggr, heads=cfg.heads))
    if parts > 1 and not args.rehearse_cpu \
            and not checks.one_part_per_device(trainer, parts):
        raise SystemExit(f"{parts} parts need {parts} devices, one each")
    info = trainer.exchange_info() if parts > 1 else {}
    params = jax.device_get(trainer.params)
    loss = float(np.asarray(trainer.run_epoch()))   # the train step itself
    moments = jax.device_get(trainer.opt_state.m)
    beta1 = float(trainer.optimizer.beta1)
    backend = trainer.gdata.backend
    del trainer
    gc.collect()
    jax.clear_caches()
    ref = importlib.import_module(
        "benchmark.references." + conf.get("reference", conf["model"]))
    rloss, rgrads = jax.device_get(ref.loss_and_grads(params, ds, layers))
    out = {"workload": cell["name"], "seed": args.seed,
           "nodes": int(ds.graph.num_nodes),
           "in_edges": int(ds.graph.num_edges), "parts": parts,
           "backend": backend, "why": info.get("agg_backend_reason"),
           "exchange": info.get("mode"),
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices())},
           "loss": loss, "reference_loss": float(rloss),
           "loss_rel": abs(loss - float(rloss)) / abs(float(rloss)),
           "grad_rel_fro": {k: checks.rel_fro(
               np.asarray(moments[k]) / (1.0 - beta1), rgrads[k])
               for k in sorted(moments)}}
    print(json.dumps(out), flush=True)
    worst = max(out["grad_rel_fro"].values())
    return 0 if worst <= checks.GRAD_REL_FRO_TOL else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
