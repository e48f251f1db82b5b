"""Successive-halving search over the kernel-config lattice.

Three stages per (graph direction, variant), each stage cutting the
field roughly 10x before the per-candidate cost rises 10x:

  stage 0 — SCREEN: price the FULL lattice through the analytic model at
    exact ``_plan_steps`` schedules (O(cells) per candidate, cell stats
    cached per window pair).  Keep ``screen_keep``.
  stage 1 — TRIAL: one short measurement per survivor — the seeded CPU
    surrogate in CI, a real timed kernel run on device (surrogate.py).
    Keep ``final_keep``.
  stage 2 — CONFIRM: a longer measurement per finalist (3 draws / more
    reps, both directions of noise), pick the winner.

Every trial is paired through the calibration ledger: the stage's
modeled seconds PREDICT, the trial MEASURES, under a content key naming
(shape, variant, candidate, stage) — so `python -m roc_tpu.obs
calibration` reports the sweep's own model error (``tune_trial`` /
``tune_confirm``) and the watchdog's calibration-drift EWMA covers the
tuner like every other cost model.  A matmul-backend reference trial
rides along per shape: it is both the binned-vs-matmul sanity anchor and
the record refit.py solves the matmul per-chunk rate from.

A PROBE stage rides along too (``REFIT_PROBES``): the halving keeps
whatever geometries happen to win, and winners cluster — their step
counts and DMA counts are nearly collinear, so a rate solve over winners
alone is ill-conditioned (the first selftest run recovered chunk_s at
0.3% of truth and slot_dma_s at 18x).  The probes are a designed
experiment instead, one contrast per rate of binned's ``_cost_terms``:
halved phase-1 chunks at equal phase 2 isolate the per-step rate, halved
phase-2 chunks the per-row rate, slots 16/64/128 at equal chunks the
per-slot-DMA rate, and flat probes at two chunk widths and two window
sizes pull the descriptor walk (steps x KD) from the real copies (cells);
each probe is measured with many averaged draws (CI) or extra reps
(device).  Refit solves from the probes when present and falls back to
trial records otherwise.

The sweep never reads tuned.json (trial plans build with
``tuned_ok=False``) and never writes outside the store handed to
``persist`` — a previous sweep cannot steer this one's measurements.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

from roc_tpu.obs.ledger import content_key, get_ledger
from roc_tpu.ops.pallas import binned as B
from roc_tpu.tune import store as tstore
from roc_tpu.tune import surrogate as S
from roc_tpu.tune.lattice import KernelConfig, candidate_lattice

# Synthetic sweep shapes, mirroring tools/kernel_bench.py: the CI shape
# is a one-shard scale where every candidate's gates admit it; device
# mode adds the dense/sparse scales the step-budget table pins.  The
# second CI shape is there for refit: at one shape the flat probes issue
# nearly the same number of copies (64-70), so the per-copy rate is not
# identified; half the rows and edges halve the copies.
SHAPES_CI = [("mega_shard_scaled", 1024, 8192, 2),
             ("tiny", 512, 4096, 3)]
SHAPES_DEVICE = SHAPES_CI[:1] + [
    ("reddit_scaled", 32768, 4_194_304, 0),
    ("products_scaled", 262_144, 2_097_152, 1),
]


class Shape(NamedTuple):
    name: str
    num_rows: int
    table_rows: int
    edge_src: np.ndarray
    edge_dst: np.ndarray


class TrialRecord(NamedTuple):
    """One measured (or surrogate) trial, carrying the schedule FACTS
    (binned._cost_terms: what the pass does, counted) refit.py needs to
    solve rates without re-deriving plans."""
    shape: str
    variant: str
    label: str
    geom: tuple
    stage: str           # "trial" | "confirm" | "probe" | "matmul"
    steps: int           # s1 + s2 (matmul: chunk count)
    terms: dict          # surrogate.cost_terms (matmul: {})
    default_knobs: bool  # knob priors applied? (refit calibrates w/o)
    modeled_s: float
    trial_s: float


def synth_shape(name: str, num_rows: int, num_edges: int,
                seed: int) -> Shape:
    """Deterministic synthetic graph, same generator discipline as
    kernel_bench (seeded default_rng; dst-major sort for CSR order)."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, num_rows, size=num_edges).astype(np.int64)
    dst = rng.integers(0, num_rows, size=num_edges).astype(np.int64)
    order = np.argsort(dst, kind="stable")
    return Shape(name, num_rows, num_rows, src[order], dst[order])


#: Refit's designed experiment (module docstring).
REFIT_PROBES = (
    B.Geometry(512, 1024, 16, 512, 1024),    # baseline
    B.Geometry(512, 2048, 16, 512, 1024),    # half the phase-1 steps
    B.Geometry(512, 1024, 16, 512, 2048),    # other phase-2 chunk rounding
    B.Geometry(512, 1024, 64, 512, 1024),    # same chunks, 1/4 slot DMAs
    B.Geometry(512, 1024, 128, 512, 1024),   # same chunks, 1/8 slot DMAs
    B.Geometry(512, 2048, 128, 512, 2048),
    B.Geometry(512, 1024, 16, 512, 1024, 0, 0, 1),     # flat, KD 128
    B.Geometry(512, 2048, 16, 512, 2048, 0, 0, 1),     # flat, KD 256
    B.Geometry(1024, 1024, 16, 1024, 1024, 0, 0, 1),   # flat, fewer cells
    B.Geometry(1024, 2048, 16, 1024, 2048, 0, 0, 1),
)


def refit_probes():
    """The probe KernelConfigs (knob-default), VMEM-admissible only."""
    from roc_tpu.tune.lattice import _admissible
    return [KernelConfig(geom=g) for g in REFIT_PROBES if _admissible(g)]


def _default_knobs(cfg: KernelConfig) -> bool:
    return (tuple(cfg.dma_cls) == B._DMA_CLS and cfg.depth == 2
            and cfg.dimension_semantics == "arbitrary")


def _trial_key(shape: Shape, variant: str, cfg_label: str,
               stage: str) -> str:
    return content_key(shape=shape.name, rows=shape.num_rows,
                       edges=len(shape.edge_src), variant=variant,
                       cand=cfg_label, stage=stage)


def _measure(cfg, shape: Shape, modeled: float, stage: str, seed: int,
             device: bool, reps: int) -> float:
    if not device:
        # averaged draws where the stage takes a longer look: 3 for the
        # confirm stage, 96 for the refit probes (the rate solve needs
        # probe noise well under the inter-probe DMA contrast — ~10% of
        # a probe's total at best; draws are hash evaluations, so a long
        # look is free in CI)
        draws = {"confirm": 3, "probe": 96}.get(stage, 1)
        label = cfg.label if hasattr(cfg, "label") else str(cfg)
        if draws == 1:
            return S.surrogate_seconds(modeled, seed, stage, label)
        return sum(S.surrogate_seconds(modeled, seed, f"{stage}{i}", label)
                   for i in range(draws)) / draws
    return S.measure_seconds(cfg, shape.edge_src, shape.edge_dst,
                             shape.num_rows, shape.table_rows,
                             reps=reps)


def sweep(shapes, storage_dtype: str = "fp32", seed: int = 0,
          device: bool = False, screen_keep: int = 16,
          final_keep: int = 4, watchdog=None, log=None):
    """Run the three-stage search over ``shapes`` (Shape tuples or
    (name, rows, edges, seed) specs).  Returns (entries, trials): a
    store.py-shaped ``entries`` dict of winners and the full TrialRecord
    list for refit.  Deterministic for device=False (no clocks, no
    unseeded randomness, sorted candidate order)."""
    led = get_ledger()
    entries: dict = {}
    trials: list = []
    vkey = tstore.variant_key(storage_dtype)
    emit = log or (lambda *_: None)
    for spec in shapes:
        shape = spec if isinstance(spec, Shape) else synth_shape(*spec)
        cfgs = candidate_lattice(storage_dtype)
        stats_cache: dict = {}
        sched_cache: dict = {}

        def _stats(g):
            sk = (g.sb, g.rb)
            if sk not in stats_cache:
                stats_cache[sk] = B._cell_stats(
                    shape.edge_src, shape.edge_dst, g.sb, g.rb)
            return stats_cache[sk]

        def _price(cfg):
            # schedule counts depend on the Geometry alone; knob variants
            # reprice through the factors but never re-derive the O(cells)
            # _plan_steps
            gk = tuple(cfg.geom)
            t, sched = S.modeled_seconds(
                cfg, _stats(cfg.geom), shape.num_rows, shape.table_rows,
                len(shape.edge_src), sched=sched_cache.get(gk))
            sched_cache[gk] = sched
            return t, sched

        # stage 0 — screen the full lattice analytically
        scored = []
        for i, cfg in enumerate(cfgs):
            t, sched = _price(cfg)
            scored.append((t, i, cfg, sched))
        scored.sort(key=lambda r: (r[0], r[1]))
        survivors = scored[:screen_keep]
        emit(f"{shape.name}/{vkey}: screened {len(cfgs)} candidates "
             f"-> {len(survivors)} (best modeled "
             f"{survivors[0][0] * 1e3:.3f} ms)" if survivors else
             f"{shape.name}/{vkey}: no admissible candidates")
        if not survivors:
            continue

        # stage 1 — short trials, ledger-paired
        tried = []
        for t_model, _, cfg, sched in survivors:
            key = _trial_key(shape, vkey, cfg.label, "trial")
            led.predict("tune_trial", key, t_model, "s")
            t_trial = _measure(cfg, shape, t_model, "trial", seed,
                               device, reps=1)
            # schedule FACTS ride the measurement record so refit can
            # re-solve rates straight from the JSONL stream
            terms = S.cost_terms(cfg.geom, _stats(cfg.geom), sched)
            led.measure("tune_trial", key, t_trial, "s",
                        stage="trial", steps=sched[1] + sched[2],
                        flat=int(cfg.geom.flat),
                        default_knobs=_default_knobs(cfg), **terms)
            trials.append(TrialRecord(
                shape.name, vkey, cfg.label, tuple(cfg.geom), "trial",
                sched[1] + sched[2], terms, _default_knobs(cfg),
                t_model, t_trial))
            tried.append((t_trial, t_model, cfg, sched))
        tried.sort(key=lambda r: (r[0], r[2].label))
        finalists = tried[:final_keep]

        # stage 2 — confirmation runs on the finalists
        confirmed = []
        for t_trial, t_model, cfg, sched in finalists:
            key = _trial_key(shape, vkey, cfg.label, "confirm")
            led.predict("tune_confirm", key, t_model, "s")
            t_conf = _measure(cfg, shape, t_model, "confirm", seed,
                              device, reps=5)
            terms = S.cost_terms(cfg.geom, _stats(cfg.geom), sched)
            led.measure("tune_confirm", key, t_conf, "s",
                        stage="confirm", steps=sched[1] + sched[2],
                        flat=int(cfg.geom.flat),
                        default_knobs=_default_knobs(cfg), **terms)
            trials.append(TrialRecord(
                shape.name, vkey, cfg.label, tuple(cfg.geom), "confirm",
                sched[1] + sched[2], terms, _default_knobs(cfg),
                t_model, t_conf))
            confirmed.append((t_conf, t_model, cfg))
        confirmed.sort(key=lambda r: (r[0], r[2].label))
        t_win, t_win_model, win = confirmed[0]
        emit(f"{shape.name}/{vkey}: winner {win.label} "
             f"({t_win * 1e3:.3f} ms confirmed, "
             f"{t_win_model * 1e3:.3f} ms modeled)")

        # probe stage — refit's designed experiment (module docstring)
        for cfg in refit_probes():
            t_model, sched = _price(cfg)
            if not np.isfinite(t_model):
                continue
            key = _trial_key(shape, vkey, cfg.label, "probe")
            led.predict("tune_probe", key, t_model, "s")
            t_probe = _measure(cfg, shape, t_model, "probe", seed,
                               device, reps=5)
            terms = S.cost_terms(cfg.geom, _stats(cfg.geom), sched)
            led.measure("tune_probe", key, t_probe, "s",
                        stage="probe", steps=sched[1] + sched[2],
                        flat=int(cfg.geom.flat),
                        default_knobs=True, **terms)
            trials.append(TrialRecord(
                shape.name, vkey, cfg.label, tuple(cfg.geom), "probe",
                sched[1] + sched[2], terms, True, t_model, t_probe))

        # matmul reference trial: sanity anchor + refit's mm-rate record
        mm_model = S.matmul_seconds(len(shape.edge_src), shape.num_rows)
        mm_key = _trial_key(shape, vkey, "matmul", "matmul")
        led.predict("tune_trial", mm_key, mm_model, "s")
        mm_trial = (S.surrogate_seconds(mm_model, seed, "matmul", "matmul")
                    if not device else mm_model)   # device: modeled only —
        #   kernel_bench's matmul row is the measured source of record
        led.measure("tune_trial", mm_key, mm_trial, "s",
                    stage="matmul",
                    steps=B._matmul_chunks(len(shape.edge_src),
                                           shape.num_rows),
                    flat=0, default_knobs=True, matmul=True)
        trials.append(TrialRecord(
            shape.name, vkey, "matmul", (), "matmul",
            B._matmul_chunks(len(shape.edge_src), shape.num_rows),
            {}, True, mm_model, mm_trial))

        gkey = tstore.graph_key(shape.edge_src, shape.edge_dst,
                                shape.num_rows, shape.table_rows)
        entries.setdefault(gkey, {})[vkey] = {
            "geom": [int(v) for v in tuple(win.geom)],
            "knobs": win.knobs(),
            "modeled_s": float(t_win_model),
            "trial_s": float(t_win),
            "source": "device" if device else "surrogate",
        }

    if watchdog is not None:
        for model, ratio in led.drain_ratios():
            watchdog.observe_calibration(model, ratio)
    return entries, trials


def autotune_graph(edge_src, edge_dst, num_rows: int, table_rows: int,
                   storage_dtype: str = "fp32", seed: int = 0,
                   device: bool = False, path: str = "",
                   watchdog=None, log=None):
    """Tune one REAL graph — both plan directions, since the backward
    plan transposes the roles — and persist the winners into the tuned
    store, where the very next ``choose_geometry``/``build_binned_plan``
    call picks them up (the driver's ``-autotune`` entry point).  Returns
    the winning forward (geom, entry) pair, or (None, None) when the
    sweep produced nothing (e.g. empty graph)."""
    es = np.ascontiguousarray(edge_src, np.int64)
    ed = np.ascontiguousarray(edge_dst, np.int64)
    if len(es) == 0:
        return None, None
    shapes = [Shape("fwd", num_rows, table_rows, es, ed),
              Shape("bwd", table_rows, num_rows, ed, es)]
    entries, _ = sweep(shapes, storage_dtype=storage_dtype, seed=seed,
                       device=device, watchdog=watchdog, log=log)
    p = path or tstore.tuned_store_path()
    if not p:
        return None, None
    tstore.merge_entries(p, entries, interpret=not device, seed=seed)
    return tstore.lookup(es, ed, num_rows, table_rows,
                         storage_dtype=storage_dtype, path=p)
