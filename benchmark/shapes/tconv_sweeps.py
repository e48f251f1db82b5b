"""Least work of the dot-product attention sweeps (the Graph Transformer
operator, roc_tpu/ops/edge.py `tconv_attend_plan`; in ``shapes["ops"]`` a
`gat` op whose `score` is "dot") for one training epoch on one chip, from
``shapes`` alone.

Such an op with C attention heads of width d (D = C d; C = `heads` output
groups x `mean_heads` averaged in each) over N rows and E in-edges
makes six edge sweeps a training step: forward the score contraction
s = q . k and the weighted sum u = sum a v; backward the contraction
de = du . v and the three weighted row sums dq, dk, dv.

FLOPs: 2 E D a sweep (one multiply-add per edge and feature), six sweeps.

Bytes, the least an algorithm with a perfect cache for node rows needs:
each of q, k, v, the output and their four cotangents read or written ONCE
at width b (2 bytes on `fast`, 4 on `exact`: the program stages float32
rows in both modes since PR 33, so `fast` understates by half here); the edge ids once a sweep (E x 4 bytes); the [C, E]
float32 residual of the softmax (the shifted exponentials) written once and
read once.

Left out, so that the share is understated and never over: every further
[C, E] array a sweep reads or writes (scores, maxima, cotangents: the
program holds five or six in a layer's backward); the plans (1.23 slots an
edge, four int32 arrays each) in place of plain edge ids; a node row read
once per EDGE rather than once (what a gather without a cache does: E D b
against N D b, 100 times as much at 100 in-edges a row); the float32 width
of the score's operands on `fast`; the projections, the gate and LayerNorm
(not in the scans: `dense_ms`).  Imports nothing of `roc_tpu`.
"""

from __future__ import annotations

SWEEPS = 6          # score, u; de, dq, dk, dv
NODE_ARRAYS = 8     # q, k, v, out and their cotangents


def least_work(shapes: dict) -> tuple:
    chips = shapes["chips"]
    n, e = shapes["nodes"] / chips, shapes["in_edges"] / chips
    b = 2 if shapes["precision"] == "fast" else 4
    flops = nbytes = 0.0
    for op in shapes["ops"]:
        if op["kind"] != "gat" or op.get("score") != "dot":
            continue
        heads = op["heads"] * op.get("mean_heads", 1)
        width = heads * op["head_dim"]
        flops += SWEEPS * 2.0 * e * width
        nbytes += NODE_ARRAYS * n * width * b       # node tables, once each
        nbytes += SWEEPS * e * 4                    # edge ids, once a sweep
        nbytes += 2 * heads * e * 4                 # the residual, w + r
    return flops, nbytes
