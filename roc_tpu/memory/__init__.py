"""DP activation-memory planner (ROC's memory manager, Algorithm 2 analog).

estimator.py  per-layer activation bytes + recompute time, priced with the
              balance cost-model prior and cross-checked against XLA's own
              buffer sizes via the hlo_audit lowering machinery.
planner.py    exact DP choosing KEEP / REMAT / OFFLOAD-candidate per layer
              under a per-device HBM budget (greedy fallback for deep
              models); deterministic JSON plans (preflight pins this).
policy.py     compiles a plan into one jax.checkpoint a layer, a kept layer's
              with save_only_these_names over the models' checkpoint-name-
              tagged intermediates — the only sanctioned raw-remat site in
              the tree (roclint `remat`).

Driven by -mem-plan {auto,keep,remat} / -mem-budget (ROC_MEM_* env); the
chosen plan joins the structure-keyed step cache so same-plan reshards
still hit the jit caches with zero retraces.
"""

from roc_tpu.memory.estimator import (LayerEstimate, ModelEstimate,
                                      estimate_for_trainer, estimate_model,
                                      fixed_bytes_for, step_arg_bytes,
                                      xla_memory_stats)
from roc_tpu.memory.planner import (KEEP, MemPlan, OFFLOAD, REMAT,
                                    device_budget_bytes, feasible,
                                    measured_peak_bytes, plan_memory,
                                    predict_peak, predict_time, saved_bytes)
from roc_tpu.memory.policy import layer_wrapper, loss_fn, saved_names

__all__ = [
    "KEEP", "REMAT", "OFFLOAD", "LayerEstimate", "ModelEstimate", "MemPlan",
    "estimate_for_trainer", "estimate_model", "fixed_bytes_for",
    "step_arg_bytes", "xla_memory_stats", "device_budget_bytes",
    "measured_peak_bytes", "plan_memory", "predict_peak", "predict_time",
    "feasible", "layer_wrapper", "loss_fn", "saved_names", "saved_bytes",
]
