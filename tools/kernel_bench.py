#!/usr/bin/env python
"""Per-kernel microbench harness: time each Pallas kernel variant in
isolation across the geometry space and persist the measured table.

Variants, per shape x geometry (only those whose gates admit them):
  twopass    run_binned over the slot-padded two-phase schedule, plus
             phase 1 and phase 2 timed alone (staging round-tripped)
  flat       run_binned over the flat compacted schedule with the fused
             step list stripped — the scan fallback the VMEM gate runs
  fused      run_binned over the fused single-grid pipeline
  matmul     scatter_gather_matmul — the one-hot backend the balance
             cost model's warm-start prior prices

On CPU the kernels run in Pallas interpret mode: the numbers are HARNESS
timings (they validate schema + mechanics in CI), not performance — the
table records ``interpret: true`` and every measured-calibration
consumer (binned.measured_calibration, the balance prior) ignores such
tables.  On hardware (tools/hw_revalidate.sh step 3h) the same command
produces the rates of record.

The table lands under the ``measured`` key of tools/kernel_budgets.json
with --update; check_kernel_budgets.py diffs AROUND that key, so a fresh
hardware table never trips the schedule-drift gate.  Each benched plan
is also written to the content-keyed plan cache (the bench forces
ROC_PLAN_CACHE_MIN_EDGES=0 for its own builds), so a trainer hitting the
same graph content warm-starts its plan build from disk; the measured
matmul per-chunk rate is what binned.measured_calibration feeds back
into _matmul_cost and the balance prior (cost_model.fit seeds it at
MEASURED_PRIOR_WEIGHT).  The binned rows' per-grid-step times are kept
in the table for reading and are not blended into the cost model: its
per-kernel-family rates were fit to ops/pallas/binned_chip_table.json
(PR 24).

The bench attaches the calibration ledger around each choose_geometry
call and measures the winner's wall time under the same plan content
key, pairing the ``geom_time`` predictions nothing else can measure; the
records ride KB_OBS_DIR/metrics.jsonl (default roc_obs_kb) and feed
`python -m roc_tpu.obs calibration`.

    python tools/kernel_bench.py                 # CI shape, interpret
    python tools/kernel_bench.py --update        # + write measured table
    KB_DEVICE=1 python tools/kernel_bench.py --update   # hardware table
    python tools/kernel_bench.py --filter flat/mega_shard_scaled
        # bench only the selected rows: each --filter is an fnmatch
        # pattern against "<variant>/<shape>" (or "<shape>/<variant>",
        # or a bare variant/shape name); repeat or comma-separate to
        # select several.  --update still rewrites the whole measured
        # key, so filtered runs are for iteration, not for the table of
        # record (docs/DESIGN.md §Autotuner).
"""

import dataclasses
import fnmatch
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Bench builds always hit the plan cache (warm-start side effect of
# record); must be set before roc_tpu import.
os.environ.setdefault("ROC_PLAN_CACHE_MIN_EDGES", "0")

import numpy as np  # noqa: E402

BUDGETS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "kernel_budgets.json")

DEVICE = bool(int(os.environ.get("KB_DEVICE", "0")))
H = int(os.environ.get("KB_H", "128"))
REPS = int(os.environ.get("KB_REPS", "5" if DEVICE else "1"))

# CI shape: a one-shard scale where the flat plans' fused schedule
# attaches, so interpret mode exercises EVERY variant.  Device mode adds
# the dense/sparse scales the step-budget table pins
# (check_kernel_budgets.SHAPES).
SHAPES_CI = [("mega_shard_scaled", 1024, 8192, 2)]
SHAPES_DEVICE = SHAPES_CI + [
    ("reddit_scaled", 32768, 4_194_304, 0),
    ("products_scaled", 262_144, 2_097_152, 1),
]

#: --filter patterns (fnmatch); empty = bench everything.
FILTERS = []


def _want(shape: str, variant: str) -> bool:
    """Row selection for --filter: a pattern may name the row as
    variant/shape or shape/variant, or just one side of it."""
    if not FILTERS:
        return True
    keys = (f"{variant}/{shape}", f"{shape}/{variant}", variant, shape)
    return any(fnmatch.fnmatch(k, p) for p in FILTERS for k in keys)


def _geometries():
    import roc_tpu.ops.pallas.binned as B
    geoms = [("default", B._default_geom()),
             ("flat", B.GEOM_FLAT),
             ("flat_bf16", B.GEOM_FLAT_BF16)]
    if DEVICE:
        geoms += [("wide", B.GEOM_WIDE),
                  ("sparse_wide", B.GEOM_SPARSE_WIDE),
                  ("flat_sparse", B.GEOM_FLAT_SPARSE)]
    return geoms


def _timeit(fn):
    """Mean seconds per call over REPS, after a compile+warm call.
    obs.span is the sanctioned clock (raw-timing lint rule)."""
    import jax
    from roc_tpu import obs
    jax.block_until_ready(fn())
    with obs.span("kernel_bench", reps=REPS) as sp:
        for _ in range(REPS):
            out = fn()
        jax.block_until_ready(out)
    return sp.dur_s / REPS


def _strip_fused(plan):
    """The flat scan-fallback variant: same plan, fused step list gone."""
    return dataclasses.replace(
        plan, f_meta=None, f_rows=None, f_blk=None, f_blk2=None,
        f_obi=None, f_dsrc=None, f_ddst=None)


def _phase_times(x, plan, geom, interpret):
    """(p1_s, p2_s): each phase scanned over all groups in isolation."""
    import jax
    import jax.numpy as jnp
    import roc_tpu.ops.pallas.binned as B
    G, C1 = plan.p1_blk.shape
    C2 = plan.p2_obi.shape[1]
    Hp = B._pad_to(x.shape[1], 128)
    xp = jnp.pad(x, ((0, B._pad_to(plan.table_rows, geom.sb) - x.shape[0]),
                     (0, Hp - x.shape[1])))
    stg_rows = C2 * geom.ch2

    @jax.jit
    def p1_all(xp):
        if geom.flat:
            def body(_, gp):
                srcl, blk, blk2, dsrc, ddst = gp
                stg = B._p1_flat_run(xp, blk, blk2, dsrc, ddst, srcl, C1,
                                     stg_rows, interpret, False, geom)
                return None, jnp.sum(stg.astype(jnp.float32))
            xs = (plan.p1_srcl, plan.p1_blk, plan.p1_blk2,
                  plan.p1_dsrc, plan.p1_ddst)
        else:
            def body(_, gp):
                srcl, off, blk = gp
                stg = B._p1_run(xp, blk, off, srcl, C1, stg_rows,
                                interpret, False, geom)
                return None, jnp.sum(stg.astype(jnp.float32))
            xs = (plan.p1_srcl, plan.p1_off, plan.p1_blk)
        _, s = jax.lax.scan(body, None, xs)
        return s

    stg = jnp.zeros((stg_rows, Hp), B.staging_dtype(geom, False))

    @jax.jit
    def p2_all(stg):
        def body(_, gp):
            dstl, obi, first = gp
            out = B._p2_run(stg, obi, first, dstl, C2,
                            plan.bins_per_group * geom.rb, interpret,
                            False, geom)
            return None, jnp.sum(out)
        _, s = jax.lax.scan(body, None,
                            (plan.p2_dstl, plan.p2_obi, plan.p2_first))
        return s

    return _timeit(lambda: p1_all(xp)), _timeit(lambda: p2_all(stg))


def bench_shape(name, n, e, seed, interpret, led):
    import jax
    import jax.numpy as jnp
    import roc_tpu.ops.pallas.binned as B
    from roc_tpu.ops.aggregate import (build_aggregate_plans,
                                       scatter_gather_matmul)
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, size=e).astype(np.int64)
    dst = rng.integers(0, n, size=e).astype(np.int64)
    x = jnp.asarray(rng.standard_normal((n, H)).astype(np.float32))
    entry = {"num_rows": n, "num_edges": e, "seed": seed, "kernels": {}}

    for gname, geom in _geometries():
        if not _want(name, gname):
            continue
        cb, cn, cnt = B._cell_stats(src, dst, geom.sb, geom.rb)
        _, s1, s2 = B._plan_steps(cb, cn, cnt, geom, n, n, e)
        # geom_time pairing: predict under the ledger with THIS geometry
        # forced, then measure the built plan's wall time by content key.
        _, pred_t = B.choose_geometry(src, dst, n, n, candidates=[geom],
                                      force=True)
        # tuned_ok=False: the bench times exactly the geometry it names
        # (a tuned-tier swap here would silently A/B the wrong config)
        plan = B.build_binned_plan(src, dst, n, n, geom=geom,
                                   tuned_ok=False)
        key = B._plan_key(n, n, e, plan.geom)
        row = {"steps_total": int(s1 + s2)}

        if geom.flat:
            flat_plan = (_strip_fused(plan) if plan.f_meta is not None
                         else plan)
            t = _timeit(lambda p=flat_plan: jax.jit(
                lambda xx: B.run_binned(xx, p, interpret))(x))
            row["variant"], row["flat_s"] = "flat", t
            if plan.f_meta is not None:
                tf = _timeit(lambda p=plan: jax.jit(
                    lambda xx: B.run_binned(xx, p, interpret))(x))
                row["fused_s"] = tf
                t = min(t, tf)
        else:
            t = _timeit(lambda p=plan: jax.jit(
                lambda xx: B.run_binned(xx, p, interpret))(x))
            row["variant"], row["total_s"] = "twopass", t
            p1, p2 = _phase_times(x, plan, geom, interpret)
            row["p1_s"], row["p2_s"] = p1, p2
        row["total_s"] = t
        row["per_step_s"] = t / max(s1 + s2, 1)
        if led is not None:
            led.measure("geom_time", key, t, "s")
        entry["kernels"][gname] = row
        print(f"{name}/{gname}: {row['variant']} {t * 1e3:.2f} ms "
              f"({row['steps_total']} steps, modeled {pred_t * 1e3:.2f} ms)")

    # The one-hot matmul backend — the rate the balance prior prices.
    # Its chunk planner requires dst-sorted edges (csr order; the binned
    # planners sort internally).
    if _want(name, "matmul"):
        order = np.argsort(dst, kind="stable")
        plans = build_aggregate_plans(src[order], dst[order], n, n)
        chunks = B._matmul_chunks(e, n)
        tm = _timeit(lambda: jax.jit(
            lambda xx: scatter_gather_matmul(xx, plans, n, n))(x))
        entry["kernels"]["matmul"] = {
            "variant": "matmul", "chunks": int(chunks), "total_s": tm,
            "per_chunk_s": tm / max(chunks, 1)}
        print(f"{name}/matmul: {tm * 1e3:.2f} ms ({chunks} chunks)")
    return entry


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    update = "--update" in argv
    it = iter(argv)
    for a in it:
        if a == "--filter":
            FILTERS.extend(p for p in next(it, "").split(",") if p)
        elif a.startswith("--filter="):
            FILTERS.extend(p for p in a.split("=", 1)[1].split(",") if p)
    import jax
    from roc_tpu import obs
    from roc_tpu.device import on_tpu
    platform = jax.default_backend()
    interpret = not on_tpu()
    if DEVICE and interpret:
        print("KB_DEVICE=1 but no accelerator backend is live; refusing "
              "to write interpret timings as a device table",
              file=sys.stderr)
        return 1

    obs_dir = os.environ.get("KB_OBS_DIR", "roc_obs_kb")
    os.makedirs(obs_dir, exist_ok=True)
    reg = obs.MetricsRegistry(
        jsonl_path=os.path.join(obs_dir, "metrics.jsonl"))
    led = obs.get_ledger()
    led.attach(reg.emit)

    shapes = SHAPES_DEVICE if DEVICE else SHAPES_CI
    t0 = time.time()
    table = {"platform": platform, "interpret": interpret, "h": H,
             "reps": REPS, "shapes": {}}
    try:
        for name, n, e, seed in shapes:
            entry = bench_shape(name, n, e, seed, interpret, led)
            if entry["kernels"]:        # --filter may deselect a shape
                table["shapes"][name] = entry
    finally:
        led.detach()
    table["wall_s"] = round(time.time() - t0, 3)
    rep = obs.ledger.calibration_report(
        [{"type": k, **r} for k, r in led.records])
    gt = rep["models"].get("geom_time")
    if gt:
        print(f"# geom_time calibration: {gt['pairs']} pairs, mean ratio "
              f"{gt['ratio_mean']:.3g} (measured/modeled)")

    if update:
        committed = {}
        if os.path.exists(BUDGETS_PATH):
            with open(BUDGETS_PATH, encoding="utf-8") as f:
                committed = json.load(f)
        committed["measured"] = table
        with open(BUDGETS_PATH, "w", encoding="utf-8") as f:
            json.dump(committed, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"# kernel_bench: wrote measured table -> {BUDGETS_PATH}")
    else:
        print("# kernel_bench: dry run (pass --update to persist)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
