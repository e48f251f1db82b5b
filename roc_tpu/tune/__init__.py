"""Geometry autotuner (round 12): search the kernel-config space, persist
winners, refit the cost-model constants.

The binned kernels' Geometry constants were hand-picked from a handful of
hardware points (docs/PERF.md rounds 2-5); `choose_geometry` ranks ~10
hand-written presets through an analytic cost model.  This package turns
that into a measured SEARCH:

  lattice.py    the candidate space — every Geometry the invariants and
                the VMEM budget admit (chunk widths, slot, windows, group
                target, flat/unit) crossed with the non-Geometry kernel
                knobs (_DMA_CLS run classes, dimension_semantics,
                double-buffer depth).
  surrogate.py  trial pricing: a parameterized mirror of binned's
                analytic model (exact _plan_steps schedules), plus the
                seeded CI surrogate — deterministic pseudo-measurements
                so the whole loop runs on CPU — and the device timing
                path for hardware windows.
  search.py     successive halving: analytic screen of the full lattice
                -> short trials -> confirmation of finalists, every trial
                paired through the calibration ledger (obs/ledger.py).
  store.py      the content-keyed ``tuned.json`` tier `choose_geometry`
                consults BEFORE its analytic model — same key discipline
                as the ROC_PLAN_CACHE plan cache, stored alongside it.
  refit.py      re-solve the rates of binned's cost terms (per step,
                per staging row, per slot DMA, per flat descriptor slot
                and copy) and the matmul per-chunk rate from trial
                records; on device, emit the kernel_budgets.json
                measured table.

Entry points: ``python -m roc_tpu.tune`` (see __main__.py), the driver's
``-autotune`` / ``ROC_AUTOTUNE=1`` flag, and hw_revalidate step 3h.
Determinism contract: the surrogate sweep is bit-reproducible (seeded
hashlib noise, sorted iteration, no wall clocks), so CI pins
byte-identical tuned.json across runs; device tables keep the
measured_calibration refusal contract (interpret timings never persist
as rates).
"""

from roc_tpu.tune.lattice import KernelConfig, candidate_lattice  # noqa: F401
from roc_tpu.tune.search import autotune_graph, sweep  # noqa: F401
from roc_tpu.tune.store import (  # noqa: F401
    graph_key, load_store, lookup, save_store, tuned_store_path,
    validate_store, variant_key)
