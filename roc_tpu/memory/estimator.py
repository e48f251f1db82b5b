"""Per-layer activation-byte + recompute-time estimates (ROC's DP inputs).

The reference's memory manager (Algorithm 2) plans over measured tensor
sizes and task runtimes; here the analogous inputs come from two sources:

  * **Bytes** are exact: the op IR (models/model.py) carries every
    intermediate's row width, so per-layer activation bytes are
    ``rows * width * itemsize`` sums — the same accounting XLA's buffer
    assigner does for the tensors whose lifetime the planner controls.
    ``step_arg_bytes`` / ``xla_memory_stats`` cross-check this against the
    compiled program's own buffer sizes (per-device, via the lowering
    machinery in analysis/hlo_audit.py); tests pin agreement within 10%.
  * **Recompute time** is priced in the units the balancer already trusts:
    aggregation ops through ``balance.cost_model.prior_times`` (the
    calibrated ``_matmul_cost`` chunk rate, width-scaled), linears through
    a peak-FLOPs/bandwidth roofline with the same constants bench.py
    reports against.  Absolute accuracy matters less than the RATIO of
    recompute cost to step time — that is all the DP compares.

Granularity decision (ROADMAP "per-layer flag vs per-tensor"): decisions
are PER LAYER, but the saved set within a kept layer is PER TENSOR — only
the expensive-to-recompute outputs (linear / aggregate / gat, plus the
layer boundary) are checkpoint-name-tagged for saving; elementwise
outputs (norm / activation / dropout / add) always rematerialize under an
active plan because recomputing them is bandwidth-cheap.  This is why a
planned layer costs ``bytes_saved`` (tagged tensors only) while an
unplanned (no-wrap, all-KEEP) layer costs ``bytes_full``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

# TPU peaks from the single-source roofline module (obs/roofline.py, the
# same constants bench.py reports against); used here only to PRICE
# recompute relative to step time, never as a claim about achieved
# throughput.
from roc_tpu.models.model import attention_heads, attention_score
from roc_tpu.obs.roofline import PEAK_BW, PEAK_FLOPS
# Feature width _MM_CHUNK_S (the aggregation chunk prior) was measured at
# (the reddit bench's in_dim); aggregation recompute scales linearly in
# width from there.
PRIOR_AGG_WIDTH = 602

# Op kinds whose outputs a kept layer SAVES under an active plan (the
# per-tensor half of the granularity decision — see module docstring).
SAVED_KINDS = frozenset({"linear", "aggregate", "gat"})
# Elementwise kinds: cheap to recompute, never saved under an active plan.
CHEAP_KINDS = frozenset({"dropout", "norm", "layernorm", "activation",
                         "add"})


@dataclasses.dataclass(frozen=True)
class LayerEstimate:
    """One layer's planning inputs (all byte figures are per device)."""

    index: int
    name: str                 # "L<i>" — matches the checkpoint-name prefix
    bytes_full: int           # every op output (all-KEEP residual cost)
    bytes_saved: int          # tagged outputs only (KEEP under a plan)
    bytes_boundary: int       # the layer-boundary tensor alone
    recompute_full_s: float   # forward recompute of the whole segment
    recompute_cheap_s: float  # elementwise-only recompute (KEEP under plan)
    # what a later layer reads of this one (its boundary, and a FAR output
    # such as GCNII's H0, read by every layer): a plan checkpoints a layer
    # at a time and a segment's inputs are live from forward to backward,
    # so these bytes stay whatever the layer's verdict; part of bytes_saved
    bytes_pinned: int = 0


@dataclasses.dataclass(frozen=True)
class ModelEstimate:
    """Planner inputs for one (model, shard shape) pair."""

    layers: Tuple[LayerEstimate, ...]
    fixed_bytes: int     # params + opt state + grads + placed node tensors
    base_step_s: float   # predicted all-KEEP step time (fwd + ~2x bwd)
    rows: int
    edges: int

    def total_full_bytes(self) -> int:
        return sum(l.bytes_full for l in self.layers)


def _op_out_dims(model) -> Dict[int, int]:
    """Output width per tensor id, walked from the op IR."""
    dims: Dict[int, int] = {0: model.input.dim}
    for op in model.ops:
        a = dims[op.inputs[0]]
        if op.kind == "linear":
            dims[op.out] = op.attrs["out_dim"]
        elif op.kind == "gat":
            dims[op.out] = op.attrs["head_dim"] * op.attrs["heads"]
        else:
            dims[op.out] = a
    return dims


def _op_forward_s(op, in_dim: int, out_dim: int, rows: int,
                  edges: int) -> float:
    """Forward time of one op at the given shard shape (seconds)."""
    if op.kind == "linear":
        flops = 2.0 * rows * in_dim * out_dim
        bytes_moved = 4.0 * rows * (in_dim + out_dim)
        return max(flops / PEAK_FLOPS, bytes_moved / PEAK_BW)
    if op.kind in ("aggregate", "gat"):
        from roc_tpu.balance.cost_model import prior_times
        import numpy as np
        t = float(prior_times(np.array([[rows, edges, 0, 0, 1.0]]))[0])
        t *= max(out_dim, 1) / PRIOR_AGG_WIDTH
        if op.kind == "gat":
            # projection matmul + per-edge score/softmax passes on top of
            # the aggregation sweep; dot scores project four times (q, k,
            # v at the attention heads' width, the skip at the output's),
            # dynamic ones twice (xl, xr)
            proj, t = out_dim, 2.0 * t
            if attention_score(op) == "dynamic":
                proj = 2 * out_dim
            if attention_score(op) == "dot":
                proj = attention_heads(op) * op.attrs["head_dim"]
                t *= proj / max(out_dim, 1)     # sweeps at the heads' width
                proj = 3 * proj + out_dim
            t += 2.0 * rows * in_dim * proj / PEAK_FLOPS
        return t
    # elementwise: read input, write output (+ one op in between)
    return 4.0 * rows * (in_dim + 2 * out_dim) / PEAK_BW


def estimate_model(model, rows: int, edges: int, itemsize: int = 4,
                   fixed_bytes: int = 0) -> ModelEstimate:
    """Per-layer byte/recompute estimates for ``model`` at a per-device
    shard of ``rows`` node rows and ``edges`` edges.

    ``itemsize`` is the activation element width (4 for fp32, 2 for bf16);
    ``fixed_bytes`` is the plan-independent resident set (params, optimizer
    state, placed node tensors) the caller already knows.
    """
    dims = _op_out_dims(model)
    per_layer: Dict[int, List] = {}
    for op in model.ops:
        per_layer.setdefault(op.attrs.get("layer", 0), []).append(op)
    read_later = model.pinned_outputs()
    layers = []
    total_fwd = 0.0
    for idx in sorted(per_layer):
        full = saved = boundary = pinned = 0
        fwd = cheap = 0.0
        for op in per_layer[idx]:
            in_dim = dims[op.inputs[0]]
            out_dim = dims[op.out]
            out_bytes = rows * out_dim * itemsize
            t = _op_forward_s(op, in_dim, out_dim, rows, edges)
            full += out_bytes + gat_edge_residual_bytes(op, edges, itemsize) \
                + attention_table_bytes(op, rows, itemsize)
            fwd += t
            tagged = op.kind in SAVED_KINDS or op.attrs.get("ckpt_boundary")
            if op.out in read_later:
                pinned += out_bytes
            if tagged or op.out in read_later:
                saved += out_bytes
            if not tagged:
                cheap += t
            if op.attrs.get("ckpt_boundary"):
                boundary = out_bytes
        if not boundary and per_layer[idx]:
            last = per_layer[idx][-1]
            boundary = rows * dims[last.out] * itemsize
        layers.append(LayerEstimate(
            index=idx, name=f"L{idx}", bytes_full=int(full),
            bytes_saved=int(saved), bytes_boundary=int(boundary),
            recompute_full_s=fwd, recompute_cheap_s=cheap,
            bytes_pinned=int(pinned)))
        total_fwd += fwd
    # backward ~ 2x forward (grad-of-linear is two matmuls; grad-of-
    # aggregate is one transposed aggregation + accumulation)
    return ModelEstimate(layers=tuple(layers), fixed_bytes=int(fixed_bytes),
                         base_step_s=3.0 * total_fwd, rows=rows, edges=edges)


def gat_edge_residual_bytes(op, edges: int, itemsize: int = 4) -> int:
    """Per-EDGE bytes a gat op keeps from forward to backward on the plan
    attention path (ops.edge._gat_plan_fwd): the shifted exponentials
    ``e [K, E]`` at the activation width, made from the score its one
    forward scan lands and the broadcast row max, and the score's sign
    ``s >= 0`` as ``[K, E]`` bool, the LeakyReLU's side (a dot or dynamic
    score keeps ``e`` alone: the dynamic score's slope is [K F, E],
    recomputed in the backward's scans, never kept).  Both carry edges on
    the lane axis, so these are the
    bytes the device holds (the old [E, K] layout held 16 x as much at
    K = 8: 128 lanes a row); the attention-dropout mask is redrawn, not
    kept.  They live inside the custom VJP: an all-KEEP step holds them
    (``bytes_full``), a planned layer recomputes them with the layer
    (``bytes_saved`` counts tagged outputs only).  0 for any other op; the
    dense xla path (small graphs) lets autodiff keep more than this."""
    if op.kind != "gat":
        return 0
    # only the additive score keeps a sign (ops.edge._tconv_plan_fwd and
    # _gatv2_plan_fwd keep e alone)
    sign = 1 if attention_score(op) == "additive" else 0
    return attention_heads(op) * int(edges) * (itemsize + sign)


# node tables a pair-score gat op's custom VJP holds: q, k, v (dot);
# xl, xr (dynamic)
PAIR_TABLES = {"dot": 3, "dynamic": 2}


def attention_table_bytes(op, rows: int, itemsize: int = 4) -> int:
    """What a gat op whose score reads both rows holds in its custom VJP
    besides ``e`` and the op's output: its node tables (:data:`PAIR_TABLES`)
    [rows, attention heads x head_dim] each.  An additive op's one table is
    its own projection, as wide as its output and counted with it: 0, as
    for any other op."""
    tables = PAIR_TABLES.get(attention_score(op), 0)
    return tables * int(rows) * attention_heads(op) \
        * int(op.attrs["head_dim"]) * itemsize if tables else 0


def fixed_bytes_for(model, rows: int, in_dim: int, num_classes: int,
                    edges: int, itemsize: int = 4) -> int:
    """Plan-independent per-device residents: replicated params + Adam
    m/v + one grad copy (4x params), placed node tensors (x, one-hot
    labels, mask) and the edge arrays."""
    params = 0
    for op in model.ops:
        if op.kind == "linear":
            params += (op.attrs["in_dim"] + bool(op.attrs.get("bias"))) \
                * op.attrs["out_dim"]
        elif attention_score(op) == "dot":
            # Wq, Wk, Wv, Wr with their biases, and the gate's 3 x out
            out = op.attrs["heads"] * op.attrs["head_dim"]
            kf = attention_heads(op) * op.attrs["head_dim"]
            params += (op.attrs["in_dim"] + 1) * (3 * kf + out) + 3 * out
        elif attention_score(op) == "dynamic":
            # Wl, Wr and a
            kf = op.attrs["heads"] * op.attrs["head_dim"]
            params += 2 * op.attrs["in_dim"] * kf + kf
        elif op.kind == "gat":
            kf = op.attrs["heads"] * op.attrs["head_dim"]
            params += op.attrs["in_dim"] * kf + 2 * kf
        elif op.kind == "layernorm":
            params += 2 * op.attrs["dim"]
    node = rows * (in_dim * itemsize + num_classes * 4 + 4 + 4)
    edge = edges * 2 * 4
    return int(4 * params * 4 + node + edge)


PLAN_FIELDS = ("plans", "ring_plans", "gat_plans")


def plan_bytes(gdata) -> int:
    """Bytes of every aggregation and attention plan set a graph-data
    pytree carries (all parts of a sharded one)."""
    import jax
    return sum(int(a.size) * a.dtype.itemsize
               for name in PLAN_FIELDS
               for a in jax.tree.leaves(getattr(gdata, name, None)))


def estimate_for_trainer(trainer) -> ModelEstimate:
    """Estimates at the trainer's actual per-device shard shape."""
    import numpy as np
    ds = trainer.dataset
    part = getattr(trainer, "part", None)
    k = getattr(trainer, "k", 1)
    if part is not None:
        rows = int(part.shard_nodes) * k
        edges = int(getattr(part, "shard_edges", 0)) * k or \
            -(-ds.graph.num_edges // trainer.config.num_parts)
    else:
        rows = ds.graph.num_nodes
        edges = ds.graph.num_edges
    itemsize = int(np.dtype(trainer.dtype).itemsize)
    fixed = fixed_bytes_for(trainer.model, rows, ds.features.shape[1],
                            ds.num_classes, edges, itemsize)
    # every plan set is a step argument like the edge arrays: one device's
    # share.  At a products-size shard the matmul chunk plans are 0.9 GB
    # of the 1.28 GB of arguments the compiler counts for the train step
    # (tests/test_exchange_obs.py holds this sum to that number); the
    # attention plans are six [C, EB] int32 arrays of ~1.2 E slots.  The
    # exchange's send and receive blocks are temporaries of the step, in
    # the layers' bytes and not here.
    devices = max(int(trainer.config.num_parts) // max(k, 1), 1)
    fixed += plan_bytes(getattr(trainer, "gdata", None)) // devices
    return estimate_model(trainer.model, rows, edges, itemsize=itemsize,
                          fixed_bytes=fixed)


# -- XLA cross-checks (analysis/hlo_audit.py lowering machinery) ----------

def step_arg_bytes(trainer) -> int:
    """Analytic per-device bytes of the train step's arguments: each
    leaf's local-shard size (sharded leaves count one shard, replicated
    leaves count in full) — the quantity XLA reports as argument (+
    donation-aliased) buffer bytes."""
    import jax
    import jax.numpy as jnp
    rng = jax.random.PRNGKey(0)
    alpha = jnp.float32(trainer.optimizer.alpha)
    args = (trainer.params, trainer.opt_state, trainer.x, trainer.labels,
            trainer.mask, trainer.gdata, rng, alpha)
    total = 0
    for leaf in jax.tree_util.tree_leaves(args):
        shards = getattr(leaf, "addressable_shards", None)
        if shards:
            total += shards[0].data.size * leaf.dtype.itemsize
        else:
            total += leaf.size * leaf.dtype.itemsize
    return int(total)


def xla_memory_stats(trainer) -> dict:
    """XLA-reported per-device buffer sizes of the compiled train step
    (argument/output/temp/alias bytes), via the audit subsystem's
    lowering."""
    from roc_tpu.analysis.hlo_audit import lower_steps
    ma = lower_steps(trainer)["train"].compile().memory_analysis()
    if ma is None:   # some backends don't implement memory analysis
        return {}
    return {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
    }
