"""Compile a MemPlan into jax.checkpoint policies over named intermediates.

Models tag every op output with ``jax.ad_checkpoint.checkpoint_name``
(stable ``L<layer>.<kind><id>`` names derived from the op IR, so the same
model always produces the same name set — models/model.py).  An active
plan checkpoints the forward pass a LAYER at a time
(``Model.layer_segments``): a KEPT layer under ``save_only_these_names``
over its tagged outputs, which survive to the backward pass while its
elementwise interior is recomputed (the per-tensor granularity decision,
estimator.py); a REMAT layer under ``nothing_saveable``, recomputed
wholesale from its inputs in the backward pass.  A segment's inputs are
residuals of its checkpoint by construction: the previous layer's
boundary, and a far input such as GCNII's ``H0`` that every layer reads,
stay live under every plan and no segment recomputes another's output.  (One checkpoint around the whole forward pass, as this
module had it before the benchmark held a deep model, recomputes a run of
adjacent REMAT layers in one piece: every residual the plan dropped is live
again at once.)

This module is the ONE place the tree is allowed to call
``jax.checkpoint`` directly — roclint's ``remat`` rule flags it anywhere
else, so ad-hoc remat can't silently bypass the planner's budget
accounting.  An all-KEEP plan compiles to ``None`` (no wrap): the default
autodiff residual behavior, byte-identical to the pre-planner programs.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax

from roc_tpu import ops
from roc_tpu.memory.planner import KEEP, OFFLOAD, MemPlan

try:
    from jax import checkpoint_policies as _cp
    _HAVE_POLICIES = hasattr(_cp, "save_only_these_names")
except ImportError:       # ancient jax: plans degrade to all-KEEP
    _cp = None
    _HAVE_POLICIES = False
# Real host offload for OFFLOAD verdicts (stream executor runs only):
# saved-but-offloaded residuals park in pinned host memory between the
# forward and backward pass instead of staying in HBM.
_HAVE_OFFLOAD = _HAVE_POLICIES and \
    hasattr(_cp, "save_and_offload_only_these_names")


def _tagged_by_layer(model) -> dict:
    """{layer: checkpoint_name tags of its SAVED_KINDS outputs and its
    boundary} (models/model.py stamps ``ckpt_save``)."""
    out: dict = {}
    for op in model.ops:
        if op.attrs.get("ckpt") and op.attrs.get("ckpt_save"):
            out.setdefault(op.attrs.get("layer", 0), []).append(
                op.attrs["ckpt"])
    return out


def saved_names(model, plan: MemPlan) -> Tuple[str, ...]:
    """checkpoint_name tags of what an active plan holds from forward to
    backward, in op order: the tagged outputs of every KEPT layer (under
    its policy), and of every layer the outputs a later one reads (the
    inputs of a later segment: its boundary, a far output like GCNII's
    ``H0``), which no verdict drops."""
    kept = {i for i, d in enumerate(plan.decisions) if d == KEEP}
    pinned = model.pinned_outputs()
    return tuple(op.attrs["ckpt"] for op in model.ops
                 if op.attrs.get("ckpt")
                 and (op.out in pinned
                      or (op.attrs.get("layer") in kept
                          and op.attrs.get("ckpt_save"))))


def layer_wrapper(model, plan: Optional[MemPlan],
                  offload_to_host: bool = False):
    """``wrap_layer`` of ``Model.apply`` for a plan: each layer's function
    under ``jax.checkpoint`` with its verdict's policy.  None = no wrap
    (all-KEEP).

    With ``offload_to_host`` (the stream executor's runs) an OFFLOAD
    verdict compiles to ``save_and_offload_only_these_names``: the
    layer's tagged residuals are saved to pinned host memory and fetched
    back for the backward pass.  Otherwise OFFLOAD degrades to remat —
    the plan records which via ``offload_executes_as``."""
    if plan is None or not plan.any_remat() or not _HAVE_POLICIES:
        return None
    tagged = _tagged_by_layer(model)

    def wrap_layer(layer: int, fn):
        verdict, names = plan.decisions[layer], tagged.get(layer, [])
        if verdict == KEEP:
            policy = _cp.save_only_these_names(*names)
        elif verdict == OFFLOAD and offload_to_host and _HAVE_OFFLOAD:
            policy = _cp.save_and_offload_only_these_names(
                names_which_can_be_saved=[],
                names_which_can_be_offloaded=names,
                offload_src="device", offload_dst="pinned_host")
        else:
            policy = _cp.nothing_saveable
        # the one sanctioned raw-remat site (module docstring); prevent_cse
        # stays on (default): under jit, XLA CSE would otherwise undo the
        # rematerialization this plan was budgeted for
        return jax.checkpoint(fn, policy=policy)

    return wrap_layer


def loss_fn(model, plan: Optional[MemPlan], offload_to_host: bool = False):
    """A drop-in replacement for ``model.loss`` that runs the forward pass
    under the plan's per-layer checkpoints.  Returns ``model.loss`` itself
    when the plan keeps everything, so default runs trace the exact same
    program as before the planner existed."""
    wrap_layer = layer_wrapper(model, plan, offload_to_host)
    if wrap_layer is None:
        return model.loss

    def planned_loss(params, x, labels, mask, gctx, key=None, train=True):
        logits = model.apply(params, x, gctx, key=key, train=train,
                             ckpt_names=True, wrap_layer=wrap_layer)
        return ops.masked_softmax_cross_entropy(logits, labels, mask)

    return planned_loss
