"""Split the model op IR at aggregation boundaries for shard streaming.

Every op in the IR except ``aggregate``/``gat`` is row-local: row r of the
output depends only on row r of the input, so it can run on one shard's
node slot without seeing any other shard.  The two aggregation kinds are
the only cross-row ops — they read a *source table* indexed by edge
sources, which under streaming is the gathered ``[S + P*K]`` local+halo
table the executor assembles from the host stores (the same table layout
``shard_load.build_halo_local`` gives the perhost SPMD path).

A *segment* is therefore: one optional aggregation head followed by the
row-local ops up to (not including) the next head.  Segment 0 has no head
(the ops before the first aggregation, e.g. dropout+linear for GCN).  The
executor runs each segment as one jitted function per shard, storing the
segment's boundary outputs back to host between sweeps.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from roc_tpu.models.model import (Model, OpNode, attention_drop,
                                  linear_bias, refuse_pair_attention)
from roc_tpu.memory.estimator import _op_out_dims
from roc_tpu import ops

__all__ = ["Segment", "split_segments", "run_segment",
           "predicted_epoch_bytes"]

_HEAD_KINDS = ("aggregate", "gat")


@dataclasses.dataclass(frozen=True)
class Segment:
    """One streamable slice of the op IR.

    ``table_tid`` is the tensor the head reads through the local+halo
    table (-1 when headless); ``own_in_tids`` are earlier-produced
    tensors the body reads row-locally (only this shard's rows are
    needed); ``out_tids`` are tensors produced here that any later
    segment consumes — the executor persists exactly these to host."""

    index: int
    head: Optional[OpNode]
    body: Tuple[OpNode, ...]
    table_tid: int
    own_in_tids: Tuple[int, ...]
    out_tids: Tuple[int, ...]
    is_last: bool
    out_dims: Dict[int, int]


def split_segments(model: Model) -> List[Segment]:
    # a dot-score gat head would need three tables a segment (q by
    # destination, k and v by source) where an additive one has one: say so,
    # do not stream it as the op it is not
    refuse_pair_attention(model, "the streamed executor (-stream, "
                                "stream/segments.py _HEAD_KINDS)")
    ops_list = list(model.ops)
    dims = _op_out_dims(model)
    head_pos = [i for i, op in enumerate(ops_list) if op.kind in _HEAD_KINDS]
    starts = [0] + head_pos
    ends = head_pos + [len(ops_list)]

    raw = []  # (head, body) per segment
    for k, (lo, hi) in enumerate(zip(starts, ends)):
        if k == 0:
            raw.append((None, tuple(ops_list[lo:hi])))
        else:
            raw.append((ops_list[lo], tuple(ops_list[lo + 1:hi])))

    produced = []
    for head, body in raw:
        p = {op.out for op in body}
        if head is not None:
            p.add(head.out)
        produced.append(p)

    # tid -> set of segment indices that consume it (as table or row-local)
    consumers: Dict[int, set] = {}
    for k, (head, body) in enumerate(raw):
        tids = set()
        if head is not None:
            tids.add(head.inputs[0])
        for op in body:
            tids.update(op.inputs)
        for t in tids:
            consumers.setdefault(t, set()).add(k)

    segs = []
    n = len(raw)
    for k, (head, body) in enumerate(raw):
        for op in body:
            assert op.kind not in _HEAD_KINDS, "aggregation op in segment body"
        own_in = sorted(
            t for op in body for t in op.inputs if t not in produced[k])
        outs = sorted(
            t for t in produced[k]
            if any(c > k for c in consumers.get(t, ())))
        touched = produced[k] | set(own_in)
        if head is not None:
            touched.add(head.inputs[0])
        segs.append(Segment(
            index=k,
            head=head,
            body=body,
            table_tid=head.inputs[0] if head is not None else -1,
            own_in_tids=tuple(dict.fromkeys(own_in)),
            out_tids=tuple(outs),
            is_last=(k == n - 1),
            out_dims={t: dims[t] for t in touched},
        ))
    return segs


def predicted_epoch_bytes(segments: List[Segment], parts: int,
                          shard_nodes: int, shard_edges: int, halo_k: int,
                          num_classes: int, *, act_itemsize: int = 4,
                          esrc_itemsize: int = 4,
                          edst_itemsize: int = 4) -> int:
    """Analytic bytes the executor's ``_fetch`` ships in one training
    epoch: the sweep schedule ((nseg-1) fwd + nseg bwd), each sweep
    rotating all ``parts`` shards, priced from the same store shapes
    ``_fetch`` slices.  ``act_itemsize`` is the streamed storage dtype's
    width (2 under -bf16-storage) and covers every float wire — tables,
    own rows, labels, and the cotangent fetch, which the executor casts
    to the storage dtype before shipping; in-degrees stay fp32 and the
    mask int32.  Edge-index widths are passed separately because the
    bf16 layout also narrows them to uint16 when the table fits.  PRNG
    keys (a few device words per fetch) are not counted.  The kernel
    budget gate (tools/check_kernel_budgets.py, ``check_stream_claim``)
    prices both dtypes through this one function, so the committed
    ratio and the runtime's ledger prediction can never drift apart."""
    n = len(segments)
    P, S, E, K = int(parts), int(shard_nodes), int(shard_edges), int(halo_k)
    sweeps = [("fwd", k) for k in range(n - 1)] + \
             [("bwd", k) for k in range(n - 1, -1, -1)]
    total = 0
    for phase, k in sweeps:
        seg = segments[k]
        b = E * (esrc_itemsize + edst_itemsize) + S * 4  # edges + indeg f32
        if seg.head is not None:
            b += (S + P * K) * seg.out_dims[seg.table_tid] * act_itemsize
        for t in seg.own_in_tids:
            b += S * seg.out_dims[t] * act_itemsize
        if seg.is_last:
            b += S * (num_classes * act_itemsize + 4)  # labels + mask i32
        if phase == "bwd" and not seg.is_last:
            for t in seg.out_tids:
                b += S * seg.out_dims[t] * act_itemsize
        total += b * P
    return int(total)


def run_segment(seg: Segment, params, table, own, esrc, edst, indeg, key,
                train: bool, num_nodes: int):
    """Trace one segment for one shard; mirrors ``Model.apply`` dispatch.

    ``table`` is the ``[S + P*K, d]`` gathered source table (None for the
    headless segment 0), ``own`` maps tid -> this shard's ``[S, d]`` rows,
    ``esrc``/``edst`` the table-local edge endpoints, ``indeg`` the
    per-row in-degree.  Returns the full tid -> value map; callers select
    ``seg.out_tids`` (or the logits tid) from it."""
    import jax

    vals = dict(own)
    if seg.head is not None:
        op = seg.head
        if op.kind == "aggregate":
            vals[op.out] = ops.scatter_gather(
                table, esrc, edst, num_nodes, op.attrs["aggr"])
        else:  # gat
            name = op.attrs["param"]
            kk, fd = op.attrs["heads"], op.attrs["head_dim"]
            h_tab = ops.linear(table, params[name + "_w"]).reshape(-1, kk, fd)
            vals[op.out] = ops.gat_attend(
                h_tab[:num_nodes], h_tab, esrc, edst, num_nodes,
                params[name + "_asrc"], params[name + "_adst"],
                op.attrs["slope"],
                attention_drop(op, key, train and key is not None),
            ).reshape(num_nodes, kk * fd)

    for op in seg.body:
        a = vals[op.inputs[0]]
        if op.kind == "dropout":
            k = (jax.random.fold_in(key, op.attrs["slot"])
                 if train and key is not None else None)
            out = ops.dropout(k, a, op.attrs["rate"], train)
        elif op.kind == "linear":
            out = ops.linear(a, params[op.attrs["param"]],
                             op.attrs["activation"], linear_bias(op, params))
        elif op.kind == "norm":
            out = ops.indegree_norm(a, indeg)
        elif op.kind == "activation":
            out = ops.apply_activation(a, op.attrs["mode"])
        elif op.kind == "add":
            out = ops.add(a, vals[op.inputs[1]], op.attrs.get("wa"),
                          op.attrs.get("wb"))
        else:  # pragma: no cover - split_segments asserts heads out of body
            raise ValueError(f"unstreamable op kind {op.kind!r}")
        vals[op.out] = out
    return vals
