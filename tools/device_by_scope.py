"""Device time of a benchmark cell by program op x pass x part: the cell's
trainer built as `benchmark/run.py` builds it, warmed up, then the
program's own `-profile` window of 3 epochs + 1 evaluation, and the report
`python -m roc_tpu.obs report -profile DIR` prints of it.

    chiprun --chips 1 -- python3 tools/device_by_scope.py \
        --workload tconv-reddit.skewed --seed 2147485001

The trainer writes the trace and `roc_scopes.json` under `--out` (default
`.cache/device_by_scope/<workload>`); the map's compiles are this process's
own, uncached (obs/scopes.py), so the call costs about one cold compile of
the train and evaluation steps on top of a run's set-up.  The report is
printed and left in `<out>/report.txt`.  Exits 2 without a TPU;
`--rehearse-cpu` walks it on virtual CPU devices with a rehearsal cell
(`--manifest benchmark/rehearsal/manifest.json`).
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

WARMUP_EPOCHS = 2       # as benchmark/run.py: the first compiles
PROFILED_EPOCHS = 3


def main(argv) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--manifest", default="BENCHMARK.json")
    p.add_argument("--out", default="")
    p.add_argument("--rehearse-cpu", action="store_true")
    args = p.parse_args(argv)
    from benchmark import manifest as mf
    m = mf.load(os.path.join(ROOT, args.manifest))
    cell = mf.cell(m, args.workload)
    chips = int(cell["chips"])
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}")
    import jax

    from benchmark import graphgen
    from benchmark import run as bench_run
    from roc_tpu import cache
    from roc_tpu.models import build_model
    from roc_tpu.obs.report import device_report
    from roc_tpu.train.driver import make_trainer
    devices = jax.devices()
    if not args.rehearse_cpu and (devices[0].platform != "tpu"
                                  or len(devices) < chips):
        print(f"device_by_scope: cell {cell['name']} needs {chips} TPU "
              f"chip(s); JAX found {devices}.  Nothing run.",
              file=sys.stderr)
        return 2
    cache.enable_compile_cache()
    conf = mf.load(os.path.join(ROOT,
                                mf.config_entry(m, cell["config"])["file"]))
    recipe = graphgen.load_recipe(mf.traffic_path(m, cell))
    if "structure_seed" not in recipe:
        os.environ.setdefault("ROC_PLAN_CACHE", "0")
    layers = list(conf["layers"])
    ds = graphgen.generate(recipe, layers[0], layers[-1], args.seed,
                           name=cell["traffic"])
    cfg = bench_run.make_config(conf, recipe, cell, args.seed)
    model = build_model(cfg.model, cfg.layers, cfg.dropout_rate, cfg.aggr,
                        heads=cfg.heads)
    trainer = make_trainer(cfg, ds, model)
    say = lambda line: print(line, flush=True)  # noqa: E731
    cfg.num_epochs = WARMUP_EPOCHS
    trainer.train(print_fn=say)
    out = os.path.join(ROOT, args.out or os.path.join(
        ".cache", "device_by_scope", cell["name"]))
    shutil.rmtree(out, ignore_errors=True)
    # the window: the next three epochs; the middle one is evaluated (the
    # loop stops the trace before it would evaluate the last)
    cfg.profile_dir, cfg.profile_epochs = out, f"0:{PROFILED_EPOCHS}"
    cfg.num_epochs = PROFILED_EPOCHS
    cfg.eval_every = trainer.epoch + 1
    trainer.train(print_fn=say)
    text = device_report(out)
    with open(os.path.join(out, "report.txt"), "w", encoding="utf-8") as f:
        f.write(text + "\n")
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
