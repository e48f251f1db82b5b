"""Loss and gradients of the program against the plain reference, at a
cell's real size, dropout off: `python3 benchmark/grad_check.py --workload
gcn-reddit.regular --seed 1` on the cell's chip.

Not a per-run cost: one chip run per configuration, its numbers recorded in
PERF.md.  (`tests/benchmark/test_benchmark_reference.py` makes the same
comparison at small size on the CPU.)  The program's side is `model.loss`
over the trainer's own graph data and backend, differentiated by JAX through
the kernels' custom gradients; the reference's is
`benchmark/references/<family>.py:loss_and_grads`.  One-chip cells only.
Prints one JSON line; exits 1 when a weight gradient's relative Frobenius
error is over `checks.GRAD_REL_FRO_TOL` (3e-3; measured 2.7e-4 and 6.2e-4).
No run of a cell makes this comparison: a PR that lowers precision in the
backward pass alone passes `correct` and is caught only here.
"""

from __future__ import annotations

import argparse
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
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--manifest", default="")
    p.add_argument("--rehearse-cpu", action="store_true")
    args = p.parse_args(argv)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("ROC_PLAN_CACHE", "0")
    from benchmark import checks, graphgen
    from benchmark import manifest as mf
    from benchmark import run as bench_run
    m = mf.load(os.path.join(ROOT, args.manifest or (
        bench_run.REHEARSAL_MANIFEST if args.rehearse_cpu
        else "BENCHMARK.json")))
    cell = mf.cell(m, args.workload)
    if cell["chips"] != 1:
        raise SystemExit("grad_check compares on one chip only")

    import jax
    from roc_tpu import cache
    from roc_tpu.models import build_model
    from roc_tpu.train.driver import make_gctx, make_trainer
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        print(f"grad_check: no TPU ({dev}); nothing run.", file=sys.stderr)
        return 2
    cache.enable_compile_cache()
    conf = mf.load(os.path.join(ROOT,
                                mf.config_entry(m, cell["config"])["file"]))
    conf = dict(conf, dropout=0.0)
    recipe = graphgen.load_recipe(mf.traffic_path(m, cell))
    layers = list(conf["layers"])
    ds = graphgen.generate(recipe, layers[0], layers[-1], args.seed)
    cfg = bench_run.make_config(conf, recipe, cell, args.seed)
    model = build_model(cfg.model, cfg.layers, 0.0, cfg.aggr, heads=cfg.heads)
    trainer = make_trainer(cfg, ds, model)
    n = ds.graph.num_nodes

    @jax.jit
    def program(params, x, labels, mask, gdata):
        gctx = make_gctx(gdata, n)
        return jax.value_and_grad(model.loss)(params, x, labels, mask, gctx,
                                              key=None, train=False)

    loss, grads = jax.device_get(program(
        trainer.params, trainer.x, trainer.labels, trainer.mask,
        trainer.gdata))
    params = jax.device_get(trainer.params)
    backend = trainer.gdata.backend
    del trainer
    ref = importlib.import_module(
        "benchmark.references." + conf.get("reference", conf["model"]))
    rloss, rgrads = jax.device_get(ref.loss_and_grads(params, ds, layers))
    out = {"workload": cell["name"], "seed": args.seed, "backend": backend,
           "device": {"platform": dev.platform, "kind": dev.device_kind},
           "loss": float(loss), "reference_loss": float(rloss),
           "loss_rel": abs(float(loss) - float(rloss)) / abs(float(rloss)),
           "grad_rel_fro": {k: checks.rel_fro(grads[k], rgrads[k])
                            for k in sorted(grads)}}
    print(json.dumps(out), flush=True)
    worst = max(out["grad_rel_fro"].values())
    return 0 if worst <= checks.GRAD_REL_FRO_TOL else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
