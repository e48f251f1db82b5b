"""Trial pricing: the parameterized analytic model, the seeded CI
surrogate, and the device timing path.

Three layers, one formula:

* ``analytic_seconds`` IS binned's ``_binned_cost_model`` — the same
  ``_cost_terms`` counts over the same exact ``_plan_steps`` schedule —
  with the rates as a PARAMETER instead of the module's ``_COST_RATES``.
  The search screens and the surrogate both price through this closed
  world (the byte-identical-tuned.json pin depends on that), and
  refit.py solves the inverse problem against the same counts it was
  generated from.  ``test_tune.py::test_analytic_seconds_mirrors_cost_model``
  pins the two together.

* ``surrogate_seconds`` is the CI pseudo-measurement: the analytic time
  times ``(1 + eps)`` with eps drawn from sha256 over (seed, salt,
  candidate label) — hashlib, NOT Python's ``hash()``, so the draw is
  independent of PYTHONHASHSEED and identical across processes.  The
  noise band (±2%) is wide enough that the halving stages genuinely
  reorder near-ties (the search can't sleepwalk through) and narrow
  enough that refit's least-squares recovers the generating constants
  inside the 5% acceptance band.

* ``measure_seconds`` is the hardware path: build the real plan
  (``tuned_ok=False`` — a previous sweep must never steer this sweep's
  measurements) and time the kernel through the obs tracer, the same
  clock discipline as tools/kernel_bench.py.  It REFUSES to run under
  interpret — the same contract as ``measured_calibration``: CPU harness
  timings are not rates and must never be recorded as such.
"""

from __future__ import annotations

import hashlib

import numpy as np

from roc_tpu.ops.pallas import binned as B
from roc_tpu.ops.pallas.binned import (Geometry, _COST_RATES, _MM_CHUNK_S,
                                       _MODEL_H)

#: The generating constants, by refit-able name: every rate of binned's
#: cost model but the MXU's (the published peak, not a fit) plus the
#: matmul backend's.  These are the exact values the CI surrogate
#: manufactures its timings from, so the refit acceptance test closes
#: the loop: sweep -> records -> refit -> these.
CONSTANTS = {**{k: v for k, v in _COST_RATES.items() if k != "mxu"},
             "mm_chunk_s": _MM_CHUNK_S}

#: Surrogate noise half-width (fractional).
NOISE = 0.02


def cost_terms(geom: Geometry, stats, sched, H: int = _MODEL_H) -> dict:
    """binned._cost_terms for a candidate at its exact schedule ``sched``
    = (padded, s1, s2), the flat copy count taken from the cell
    statistics as choose_geometry takes it."""
    padded, s1, s2 = sched
    return B._cost_terms(padded, geom, H, s1, s2,
                         copies=B._flat_copies(stats[2], geom))


def analytic_seconds(padded_rows: int, geom: Geometry, steps1: int,
                     steps2: int, H: int = _MODEL_H, copies: int = None,
                     rates: dict = None) -> float:
    """One aggregation pass at this geometry — ``_binned_cost_model``
    with the rates as an explicit parameter (see module docstring)."""
    rates = {**_COST_RATES, **(rates or {})}
    terms = B._cost_terms(padded_rows, geom, H, steps1, steps2, copies)
    return sum(rates[k] * v for k, v in terms.items())


def matmul_seconds(num_edges: int, num_rows: int,
                   mm_chunk_s: float = _MM_CHUNK_S) -> float:
    """The one-hot matmul backend, parameterized like analytic_seconds."""
    return B._matmul_chunks(num_edges, num_rows) * mm_chunk_s


def knob_factors(cfg) -> tuple:
    """(overhead_factor, dma_factor) for a candidate's non-Geometry
    knobs.  These are PRIORS — modest, documented multipliers that let
    the screen rank knob variants at all; the device sweep is what turns
    them into measurements (hw_revalidate step 3h), and refit treats
    knob-default trials as the calibration set so the priors never
    contaminate the recovered constants.

      dma_cls (32, 8, 1): doubled size classes halve the descriptor
        count on dense runs but round thin runs up harder — net prior
        -4% on the staging-DMA terms (slot, descriptor walk, copy).
      depth 3: a third pipeline buffer hides more of the DMA launch
        window behind compute — prior -2% on the per-step and per-row
        terms, paid in VMEM (lattice.py admissibility already charges
        the buffer).
      dimension_semantics "parallel": neutral (1.0) — both phases carry
        cross-step staging dependences, so until a device run proves the
        revolving-window lowering legal AND faster it cannot win a tie.
    """
    ov, dma = 1.0, 1.0
    if cfg.geom.flat and tuple(cfg.dma_cls) != B._DMA_CLS:
        dma *= 0.96
    if cfg.depth == 3:
        ov *= 0.98
    return ov, dma


def modeled_seconds(cfg, stats, num_rows: int, table_rows: int,
                    num_edges: int, rates: dict = None,
                    sched=None) -> tuple:
    """Candidate price at exact schedule counts: (seconds, sched) where
    sched = (padded, s1, s2) feeds the trial records refit solves from.
    ``sched`` short-circuits the O(cells) _plan_steps when the caller
    already derived it for this geometry (knob variants share
    schedules)."""
    cblk, cbin, cnt = stats
    g = cfg.geom
    rates = {**_COST_RATES, **(rates or {})}
    sched = sched if sched is not None else B._plan_steps(
        cblk, cbin, cnt, g, num_rows, table_rows, num_edges)
    padded, s1, s2 = sched
    ovf, dmaf = knob_factors(cfg)
    factor = {"mxu": 1.0, "p1_step": ovf, "p2_row": ovf,
              "slot_dma": dmaf, "flat_slot": dmaf, "flat_copy": dmaf}
    t = sum(rates[k] * factor[k] * v
            for k, v in cost_terms(g, stats, sched).items())
    return t, (padded, s1, s2)


def noise_eps(seed: int, salt: str, label: str,
              width: float = NOISE) -> float:
    """Deterministic noise draw in [-width, +width]: sha256 over the
    (seed, salt, candidate) triple — PYTHONHASHSEED-independent, stable
    across platforms and processes, the root of the byte-identical
    tuned.json pin."""
    h = hashlib.sha256(f"{seed}|{salt}|{label}".encode()).digest()
    u = int.from_bytes(h[:8], "big") / float(1 << 64)
    return (2.0 * u - 1.0) * width


def surrogate_seconds(modeled: float, seed: int, salt: str,
                      label: str) -> float:
    """The CI pseudo-measurement for one trial."""
    return modeled * (1.0 + noise_eps(seed, salt, label))


def measure_seconds(cfg, edge_src, edge_dst, num_rows: int,
                    table_rows: int, H: int = 128, reps: int = 3,
                    precision: str = "fast") -> float:
    """Hardware trial: build the candidate's real plan (tuned_ok=False)
    and time the two-pass (or flat/fused) aggregation on device, median
    of ``reps``, through the obs tracer's clock.  Raises on interpret
    backends — the measured_calibration refusal contract."""
    import jax
    import jax.numpy as jnp
    from roc_tpu import obs
    from roc_tpu.device import on_tpu
    if not on_tpu():
        raise SystemExit(
            "tune.measure_seconds: refusing to record interpret/CPU "
            "timings as kernel rates (measured_calibration contract); "
            "run the surrogate sweep instead")
    plan = B.build_binned_plan(np.asarray(edge_src), np.asarray(edge_dst),
                               num_rows, table_rows, geom=cfg.geom,
                               tuned_ok=False)
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal(H * table_rows)
        .reshape(table_rows, H).astype(np.float32))
    fn = jax.jit(lambda v: B.run_binned(v, plan, precision=precision))
    jax.block_until_ready(fn(x))     # compile outside the timed region
    times = []
    for _ in range(max(reps, 1)):
        with obs.span("tune_trial", label=cfg.label) as sp:
            jax.block_until_ready(fn(x))
        times.append(sp.dur_s)
    times.sort()
    return times[len(times) // 2]
