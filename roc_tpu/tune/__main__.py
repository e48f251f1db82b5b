"""CLI for the geometry autotuner.

    python -m roc_tpu.tune                      # CI surrogate sweep,
                                                # write tuned.json
    python -m roc_tpu.tune --refit              # + refit rate report
    python -m roc_tpu.tune --selftest           # the preflight gate:
        miniature seeded sweep run TWICE end to end (candidate gen ->
        halving -> tuned.json write, byte-identical across runs), schema
        validation, choose_geometry consumption proof, refit-vs-constants
        tolerance, and the ledger pairing check — all on CPU, no device.
    python -m roc_tpu.tune --device --refit --update    # hardware window:
        real timed trials, tuned.json next to the plan cache, refit rates
        committed into tools/kernel_budgets.json (hw_revalidate step 3h).

The surrogate sweep never touches kernel_budgets.json (rates keep the
measured_calibration refusal contract); its tuned.json IS consumed by
choose_geometry on any backend — tuned entries are a schedule policy,
not a rate claim.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile


def _attach_ledger(obs_dir: str):
    from roc_tpu import obs
    os.makedirs(obs_dir, exist_ok=True)
    reg = obs.MetricsRegistry(
        jsonl_path=os.path.join(obs_dir, "metrics.jsonl"))
    led = obs.get_ledger()
    led.attach(reg.emit)
    return led


def _report(led) -> dict:
    from roc_tpu.obs.ledger import calibration_report
    return calibration_report([{"type": k, **r} for k, r in led.records])


def _run_sweep(args, path: str, log=print):
    from roc_tpu.tune import refit as R
    from roc_tpu.tune import search, store
    shapes = (search.SHAPES_DEVICE if args.shapes == "device"
              else search.SHAPES_CI)
    entries, trials = search.sweep(
        shapes, storage_dtype=args.storage, seed=args.seed, device=args.device,
        screen_keep=args.screen_keep, final_keep=args.final_keep,
        log=log)
    doc = store.merge_entries(path, entries,
                              interpret=not args.device, seed=args.seed)
    rates = R.refit_rates(trials)
    return doc, trials, rates


def _selftest(args) -> int:
    """End-to-end determinism + consumption gate (see module docstring).
    Everything runs in a temp dir; the process env is restored."""
    from roc_tpu.obs.ledger import get_ledger
    from roc_tpu.ops.pallas import binned as B
    from roc_tpu.tune import refit as R
    from roc_tpu.tune import search, store
    ok = True

    def check(name, cond, detail=""):
        nonlocal ok
        print(f"tune-selftest: {name}: "
              f"{'ok' if cond else 'FAIL'}{' ' + detail if detail else ''}")
        ok = ok and bool(cond)

    with tempfile.TemporaryDirectory(prefix="roc_tune_selftest_") as td:
        led = _attach_ledger(os.path.join(td, "obs"))
        try:
            paths = [os.path.join(td, f"tuned_{i}.json") for i in (0, 1)]
            docs = []
            for p in paths:
                a = argparse.Namespace(**vars(args))
                doc, trials, rates = _run_sweep(a, p, log=lambda *_: None)
                docs.append(doc)
            blobs = [open(p, "rb").read() for p in paths]
            check("byte-identical across two runs", blobs[0] == blobs[1],
                  f"({len(blobs[0])} bytes)")
            check("schema valid",
                  not store.validate_store(docs[0]),
                  f"({len(docs[0]['entries'])} entries)")

            # consumption proof: choose_geometry prefers the tuned entry
            # at the swept shape, analytic model elsewhere
            shape = search.synth_shape(*search.SHAPES_CI[0])
            env0 = {k: os.environ.get(k)
                    for k in ("ROC_TUNED_PATH", "ROC_NO_TUNED")}
            os.environ["ROC_TUNED_PATH"] = paths[0]
            os.environ.pop("ROC_NO_TUNED", None)
            store.clear_cache()
            try:
                gkey = store.graph_key(shape.edge_src, shape.edge_dst,
                                       shape.num_rows, shape.table_rows)
                want = tuple(docs[0]["entries"][gkey]
                             [store.variant_key(args.storage)]
                             ["geom"])
                got, _ = B.choose_geometry(
                    shape.edge_src, shape.edge_dst, shape.num_rows,
                    shape.table_rows, force=True,
                    storage_dtype=args.storage)
                check("choose_geometry consumes tuned entry",
                      got is not None and tuple(got) == want,
                      f"(geom {want})")
                other = search.synth_shape("other", 2048, 4096, 7)
                g2, _ = B.choose_geometry(
                    other.edge_src, other.edge_dst, other.num_rows,
                    other.table_rows, force=True)
                check("analytic fallback off-key", g2 is not None)
            finally:
                for k, v in env0.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v
                store.clear_cache()

            bad = {k: r for k, r in rates["vs_constants"].items()
                   if abs(r - 1.0) > 0.05}
            check("refit within 5% of generating constants", not bad,
                  "(" + ", ".join(
                      f"{k}={rates['vs_constants'][k]:.3f}"
                      for k in sorted(rates["vs_constants"])) + ")")

            rep = _report(led)
            for model in ("tune_trial", "tune_confirm", "tune_probe"):
                m = rep["models"].get(model)
                check(f"ledger pairs {model}",
                      m is not None and m["pairs"] > 0
                      and 0.9 <= m["ratio_mean"] <= 1.1,
                      f"({m['pairs']} pairs, mean "
                      f"{m['ratio_mean']:.3f})" if m else "")
        finally:
            led.detach()
            get_ledger().clear()
    print(f"tune-selftest: {'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="python -m roc_tpu.tune",
                                description=__doc__.splitlines()[0])
    p.add_argument("--selftest", action="store_true",
                   help="miniature end-to-end sweep gate (preflight)")
    p.add_argument("--shapes", choices=("ci", "device"), default=None,
                   help="sweep shape set (default: ci; device with "
                        "--device)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default="",
                   help="tuned.json path (default: alongside the plan "
                        "cache / ROC_TUNED_PATH)")
    p.add_argument("--device", action="store_true",
                   help="real timed trials (TPU only; refuses interpret)")
    p.add_argument("--storage", choices=("fp32", "bf16"), default="fp32")
    p.add_argument("--refit", action="store_true",
                   help="re-solve rate constants from the trials")
    p.add_argument("--update", action="store_true",
                   help="with --refit on device: commit the refit table "
                        "into tools/kernel_budgets.json")
    p.add_argument("--screen-keep", type=int, default=16)
    p.add_argument("--final-keep", type=int, default=4)
    args = p.parse_args(argv)
    if args.shapes is None:
        args.shapes = "device" if args.device else "ci"

    if args.selftest:
        return _selftest(args)

    import jax
    from roc_tpu.device import on_tpu
    if args.device and not on_tpu():
        print("tune: --device but no accelerator backend is live; "
              "refusing to record interpret timings", file=sys.stderr)
        return 1

    from roc_tpu.tune import refit as R
    from roc_tpu.tune import store
    path = args.out or store.tuned_store_path()
    if not path:
        print("tune: tuned store disabled (ROC_NO_TUNED/ROC_PLAN_CACHE=0) "
              "and no --out given", file=sys.stderr)
        return 1
    led = _attach_ledger(os.environ.get("ROC_TUNE_OBS_DIR", "roc_obs_tune"))
    try:
        doc, trials, rates = _run_sweep(args, path)
    finally:
        led.detach()
    print(f"tune: wrote {len(doc['entries'])} graph entries -> {path}")
    rep = _report(led)
    for model in sorted(rep["models"]):
        m = rep["models"][model]
        print(f"# calibration {model}: {m['pairs']} pairs, mean ratio "
              f"{m['ratio_mean']:.3g}")
    if args.refit:
        print("tune: refit rates "
              + json.dumps({k: rates[k] for k in
                            (*R.RATE_NAMES, "mm_chunk_s")},
                           sort_keys=True))
        print("tune: refit vs committed constants "
              + json.dumps({k: round(v, 4) for k, v in
                            sorted(rates["vs_constants"].items())}))
        if args.update:
            table = R.to_measured_table(
                trials, interpret=not args.device,
                platform=jax.default_backend(),
                h=int(os.environ.get("KB_H", "128")))
            out = R.update_budgets(table)
            print(f"tune: committed refit measured table -> {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
