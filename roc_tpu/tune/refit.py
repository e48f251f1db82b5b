"""Refit: re-solve the cost-model rate constants from trial records.

binned's analytic model prices one aggregation pass as a sum of rate x
count over ``_cost_terms`` (MXU flops, phase-1 steps, phase-2 staging
rows, slot DMAs, flat descriptor slots walked, flat copies), and the
matmul backend as ``chunks * mm_chunk_s``.  Each trial record carries
its measured seconds AND those counts — so recovering the rates is a
small linear least-squares, not a re-derive:

    t_i - mxu_i / peak = sum_k rate_k * count_ik

over the knob-default aggregation trials (knob-variant trials are
excluded: the screen's priors would contaminate the solve; the MXU's
rate is the published peak and is not solved).  When the sweep's PROBE
records are present they are the whole calibration set — the halving's
survivors cluster around the winner, leaving the counts nearly
collinear, while the probes are designed contrasts that pull the
columns apart (search.REFIT_PROBES).  A column no trial exercises (no
flat trial, say) drops out rather than polluting the fit.
``mm_chunk_s`` is the median implied rate of the matmul reference
trials.

On the CI surrogate the recovered rates must land within 5% of the
generating constants (surrogate.CONSTANTS) — the acceptance pin that
proves sweep -> ledger records -> refit closes the loop.  On device the
same solve produces the real constants; the binned rates then go, with
their measured rows, where PR 24's went (ops/pallas/binned.py's
calibration block and binned_chip_table.json), and ``to_measured_table``
/ ``update_budgets`` persist the trials in the kernel_bench ``measured``
format (tools/kernel_budgets.json) that ``measured_calibration`` reads
the matmul rate from and the balance prior warm-starts from — with the
same refusal contract: ``update_budgets`` will not commit an interpret
table as rates.
"""

from __future__ import annotations

import json
import os

import numpy as np

from roc_tpu.ops.pallas.binned import _COST_RATES
from roc_tpu.tune.surrogate import CONSTANTS

#: The solved columns: every cost term but the MXU's.
RATE_NAMES = tuple(k for k in _COST_RATES if k != "mxu")


def _fields(tr):
    """Normalize a TrialRecord or a raw ledger measurement dict to the
    solve's inputs; None when the record lacks the schedule facts."""
    if isinstance(tr, dict):
        if tr.get("model") not in ("tune_trial", "tune_confirm",
                                   "tune_probe") or "steps" not in tr:
            return None
        return {"t": float(tr["value"]), "steps": int(tr["steps"]),
                "terms": {k: float(tr.get(k, 0.0)) for k in _COST_RATES},
                "flat": bool(tr.get("flat", 0)),
                "default_knobs": bool(tr.get("default_knobs", True)),
                "matmul": bool(tr.get("matmul", False)),
                "stage": str(tr.get("stage", "")),
                "variant": str(tr.get("variant", "")),
                "shape": str(tr.get("shape", ""))}
    return {"t": tr.trial_s, "steps": tr.steps,
            "terms": {k: float(tr.terms.get(k, 0.0)) for k in _COST_RATES},
            "flat": bool(tr.geom and tr.geom[7]) if len(tr.geom) > 7
            else False, "default_knobs": tr.default_knobs,
            "matmul": tr.stage == "matmul", "stage": tr.stage,
            "variant": tr.variant, "shape": tr.shape}


def refit_rates(trials) -> dict:
    """Solve the rate constants from trial records (TrialRecords from a
    live sweep, or ledger measurement dicts from the JSONL stream).

    Returns {<RATE_NAMES>, mm_chunk_s, n_agg, n_mm, vs_constants: {name:
    refit/committed ratio}} — a rate is None when no eligible trial
    exercises it."""
    agg, mm = [], []
    for tr in trials:
        f = _fields(tr)
        if f is None:
            continue
        if f["matmul"]:
            if f["steps"] > 0:
                mm.append(f["t"] / f["steps"])
            continue
        if not f["default_knobs"]:
            continue
        agg.append(f)
    # The probe stage is search.py's designed experiment; the halving's
    # own survivors cluster (near-collinear counts), so when probes exist
    # they ARE the calibration set.
    probes = [f for f in agg if f["stage"] == "probe"]
    if probes:
        agg = probes
    out = {**{k: None for k in RATE_NAMES}, "mm_chunk_s": None,
           "n_agg": len(agg), "n_mm": len(mm)}
    if agg:
        # drop all-zero columns so the lstsq stays full-rank and
        # deterministic
        keep = [k for k in RATE_NAMES
                if any(f["terms"][k] != 0 for f in agg)]
        A = np.asarray([[f["terms"][k] for k in keep] for f in agg],
                       dtype=np.float64)
        t = np.asarray([f["t"] for f in agg], dtype=np.float64)
        b = t - np.asarray([f["terms"]["mxu"] for f in agg],
                           dtype=np.float64) * _COST_RATES["mxu"]
        # measurement noise is multiplicative (a fraction of each total),
        # so weight rows by 1/t: otherwise the long trials' absolute
        # noise drowns the small columns' contrast
        w = 1.0 / np.maximum(t, 1e-12)
        sol, *_ = np.linalg.lstsq(A * w[:, None], b * w, rcond=None)
        for k, v in zip(keep, sol):
            out[k] = float(v)
    if mm:
        mm.sort()
        out["mm_chunk_s"] = mm[len(mm) // 2]
    out["vs_constants"] = {
        k: out[k] / CONSTANTS[k]
        for k in CONSTANTS if out.get(k) is not None and CONSTANTS[k]}
    return out


def to_measured_table(trials, interpret: bool, platform: str = "",
                      h: int = 0) -> dict:
    """Trial records -> the kernel_bench ``measured`` table shape
    (binned.measured_calibration's input): per shape, the confirm-stage
    aggregation rows as per_step_s and the matmul reference as
    per_chunk_s.  ``interpret`` rides the table so the refusal contract
    holds end to end — a surrogate table validates schema in CI but is
    never read back as rates."""
    shapes: dict = {}
    for tr in trials:
        f = _fields(tr)
        if f is None or f["steps"] <= 0:
            continue
        stage = tr.get("stage", "") if isinstance(tr, dict) else tr.stage
        label = (tr.get("cand", tr.get("label", "")) if isinstance(tr, dict)
                 else tr.label)
        kernels = shapes.setdefault(f["shape"] or "swept",
                                    {"kernels": {}})["kernels"]
        if f["matmul"]:
            kernels["matmul"] = {
                "variant": "matmul", "chunks": f["steps"],
                "total_s": f["t"], "per_chunk_s": f["t"] / f["steps"]}
        elif stage == "confirm" and f["default_knobs"]:
            kernels[f"tuned/{label}"] = {
                "variant": "flat" if f["flat"] else "twopass",
                "steps_total": f["steps"], "total_s": f["t"],
                "per_step_s": f["t"] / f["steps"]}
    return {"interpret": bool(interpret), "platform": platform, "h": h,
            "source": "roc_tpu.tune refit", "shapes": shapes}


def update_budgets(table: dict, path: str = "") -> str:
    """Commit a refit table under kernel_budgets.json's ``measured`` key
    (the kernel_bench --update discipline: everything AROUND the key is
    preserved).  Refuses interpret tables — CI surrogate timings must
    never become the rates a device run warm-starts from."""
    if table.get("interpret", True):
        raise SystemExit(
            "tune.refit: refusing to commit an interpret/surrogate table "
            "as measured rates (measured_calibration contract)")
    path = path or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "..", "tools", "kernel_budgets.json")
    path = os.path.abspath(path)
    committed = {}
    if os.path.exists(path):
        with open(path, encoding="utf-8") as f:
            committed = json.load(f)
    committed["measured"] = table
    with open(path, "w", encoding="utf-8") as f:
        json.dump(committed, f, indent=1, sort_keys=True)
        f.write("\n")
    return path
