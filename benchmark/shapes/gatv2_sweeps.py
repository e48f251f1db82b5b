"""Least work of the dynamic attention sweeps (GATv2's operator,
roc_tpu/ops/edge.py `gatv2_attend_plan`; in ``shapes["ops"]`` a `gat` op
whose `score` is "dynamic") for one training epoch on one chip, from
``shapes`` alone.

Such an op with K heads of width F (D = K F) over N rows and E in-edges
makes four edge sweeps a training step: forward the score
s = a . LeakyReLU(xr_i + xl_j) and the weighted sum u = sum a~ xl; backward
one sweep over the in-edges for de, ds, dxr and da, and one over the
out-edges for both terms of dxl.

FLOPs: 2 E D for each per-edge and per-channel product the sweeps need:
the score (a . LeakyReLU) forward and its recomputation in each backward
sweep, u, de, dxr and da, and dxl's two terms: nine.

Bytes, the least an algorithm with a perfect cache for node rows needs:
two node tables of width D read or written a sweep (the score reads xl and
xr, u reads xl and writes the output, the backward sweeps read more) at b
bytes (2 on `fast`, 4 on `exact`: the program stages float32 rows in both
modes, so `fast` understates by half here); the edge ids once a sweep (E x
4 bytes); the [K, E] float32 residual of the softmax (the shifted
exponentials) written once and read once.  Bytes bind.

Left out, so that the share is understated and never over: every further
[K, E] array a sweep reads or writes (scores, maxima, cotangents, the
backward's [2K, E] stack); the plans (1.2 slots an edge, four int32 arrays
each) in place of plain edge ids; a node row read once per EDGE rather than
once (what a gather without a cache does); every node table past two a
sweep; the max and normaliser scans; the projections (not in the scans:
`dense_ms`).  Imports nothing of `roc_tpu`.
"""

from __future__ import annotations

SWEEPS = 4          # score, u; the dst-keyed and the src-keyed backward
PRODUCTS = 9        # score x 3, u, de, dxr, da, dxl x 2
TABLES = 2          # node tables a sweep reads or writes, at least


def least_work(shapes: dict) -> tuple:
    chips = shapes["chips"]
    n, e = shapes["nodes"] / chips, shapes["in_edges"] / chips
    b = 2 if shapes["precision"] == "fast" else 4
    flops = nbytes = 0.0
    for op in shapes["ops"]:
        if op["kind"] != "gat" or op.get("score") != "dynamic":
            continue
        width = op["heads"] * op["head_dim"]
        flops += PRODUCTS * 2.0 * e * width
        nbytes += SWEEPS * TABLES * n * width * b   # node tables
        nbytes += SWEEPS * e * 4                    # edge ids, once a sweep
        nbytes += 2 * op["heads"] * e * 4           # the residual, w + r
    return flops, nbytes
