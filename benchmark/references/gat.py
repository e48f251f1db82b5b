"""Plain reference for the GAT family: float32 `jax.numpy`, `segment_max`,
`segment_sum`, `jnp.take`, matmuls at `highest` precision, no kernels, no
plans, no [K, E] layouts.

Written from Velickovic et al., "Graph Attention Networks", ICLR 2018
(arXiv:1710.10903), equations 1-6 and the transductive settings of section
3.3, independent of `roc_tpu/ops` and `roc_tpu/models`.  For layers =
[d0, F', C] and K heads, with N_i the in-neighbours of i (self-edge
included, as the graph carries it), layer l computes, per head k,

    h       = dropout(x, p) W              reshaped [N, K, F]
    s_ij    = LeakyReLU_0.2(a_dst^k . h_i^k + a_src^k . h_j^k)     j in N_i
    alpha_ij = exp(s_ij - m_i) / sum_j' exp(s_ij' - m_i),  m_i = max_j s_ij
    alpha~_ij = alpha_ij * keep_ij / (1 - p)      (training; not renormalised)
    out_i^k = sum_j alpha~_ij h_j^k

hidden layers concatenate the K heads and apply ELU; the output layer has
one head of C features and no activation.  The loss is the unreduced sum of
softmax cross-entropy over the train rows, as for every model of the
program.  Evaluation drops nothing.

Departures from the paper, all shared with the program under test:
  * a = [a_dst || a_src] is kept as its two halves (the paper's single
    vector applied to the concatenation [W h_i || W h_j] is their sum);
  * no bias (the paper's equations have none; its released code adds one);
  * the output layer is a single head, so its "average over heads" is the
    identity; the softmax of equation 6 lives in the loss;
  * the loss is summed, not averaged, over the train rows;
  * weight decay is the optimiser's, not part of this loss.

Parameters arrive as the trainer's dict: `gat_<i>_w` [d_in, K*F],
`gat_<i>_asrc` and `gat_<i>_adst` [K, F], in recipe order by <i>; the head
count is read from their shapes.

The edge list is walked in fixed blocks of destination rows (in-edge CSR
order: a block of rows owns a contiguous run of edges, so every softmax is
whole inside its block), each padded to the longest block's edge count, so
the gathered [block edges, K, F] rows are the largest temporary: whole,
[E, 8, 8] float32 at the Reddit shape is 6.0 GB.  The block body is
rematerialised under differentiation for the same reason.

Training-mode arithmetic can be compared too: `loss_and_grads` takes the
keep masks (per-edge-and-head for the coefficients, per-feature for the
inputs) that the program drew, and applies them as the equations say.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np

MASK_TRAIN = 0          # gnn.h:98-103
ROW_BLOCK = 4096        # destination rows a block (~4e5 edges at Reddit)
SLOPE = 0.2             # LeakyReLU slope of the score (paper section 2.1)


def edge_blocks(graph, row_block: int = ROW_BLOCK):
    """The in-edge CSR as blocks of ``row_block`` destination rows:
    (src [B, L], dst_local [B, L], edge_start [B]) with L the longest
    block's edge count.  Pad slots have ``dst_local == row_block`` (one past
    the block: dropped by the segment reductions) and source 0."""
    n = graph.num_nodes
    row_ptr = np.asarray(graph.row_ptr, np.int64)
    starts = np.arange(0, n, row_block)
    e0 = row_ptr[starts]
    e1 = row_ptr[np.minimum(starts + row_block, n)]
    longest = max(int((e1 - e0).max()), 1)
    deg = np.diff(row_ptr)
    dst = np.repeat(np.arange(n, dtype=np.int64), deg)
    src = np.zeros((len(starts), longest), np.int32)
    dst_local = np.full((len(starts), longest), row_block, np.int32)
    col = np.asarray(graph.col_idx)
    for b, (a, z, r0) in enumerate(zip(e0, e1, starts)):
        src[b, :z - a] = col[a:z]
        dst_local[b, :z - a] = dst[a:z] - r0
    return src, dst_local, e0.astype(np.int32)


def layer_names(params: dict) -> list:
    """The gat layers' parameter prefixes (`gat_0`, `gat_1`, ...) in
    recipe order."""
    found = set()
    for name in params:
        m = re.fullmatch(r"(gat_(\d+))_(w|asrc|adst)", name)
        if m is None:
            raise ValueError(f"the GAT reference knows no parameter {name!r}")
        found.add((int(m.group(2)), m.group(1)))
    return [name for _, name in sorted(found)]


def ordered_weights(params: dict) -> list:
    """[(W, a_src, a_dst)] per layer, float32."""
    return [tuple(jnp.asarray(params[f"{n}_{s}"], jnp.float32)
                  for s in ("w", "asrc", "adst")) for n in layer_names(params)]


def attend(h, a_src, a_dst, src, dst_local, edge_start, row_block: int,
           edge_keep=None, rate: float = 0.0):
    """Equations 1-4 for one layer: h [N, K, F] -> [N, K, F].
    ``edge_keep``: [K, E] bool keep mask of the coefficients, or None."""
    n, k, f = h.shape
    blocks, longest = src.shape
    rows_padded = blocks * row_block
    as_n = jnp.einsum("nkf,kf->nk", h, a_src)
    ad_n = jnp.einsum("nkf,kf->nk", h, a_dst)
    ad_n = jnp.pad(ad_n, ((0, rows_padded - n), (0, 0)))
    if edge_keep is not None:
        # [E, K] float multiplier, padded so every block slices in bounds
        mult = jnp.pad(edge_keep.T.astype(jnp.float32) / (1.0 - rate),
                       ((0, longest), (0, 0)))

    @jax.checkpoint
    def block(b):
        s_ids, d_loc, e0 = src[b], dst_local[b], edge_start[b]
        d_in = jnp.minimum(d_loc, row_block - 1)      # pads read a live row
        s = jnp.take(ad_n, b * row_block + d_in, axis=0) \
            + jnp.take(as_n, s_ids, axis=0)                       # [L, K]
        s = jnp.where(s >= 0, s, SLOPE * s)
        m = jax.ops.segment_max(s, d_loc, num_segments=row_block,
                                indices_are_sorted=True)
        m = jnp.where(jnp.isfinite(m), m, 0.0)        # rows with no in-edge
        e = jnp.exp(s - jnp.take(m, d_in, axis=0))
        z = jax.ops.segment_sum(e, d_loc, num_segments=row_block,
                                indices_are_sorted=True)
        alpha = e / jnp.take(jnp.where(z > 0, z, 1.0), d_in, axis=0)
        if edge_keep is not None:
            alpha = alpha * jax.lax.dynamic_slice(mult, (e0, 0),
                                                  (longest, k))
        g = jnp.take(h, s_ids, axis=0)                            # [L, K, F]
        return jax.ops.segment_sum(alpha[:, :, None] * g, d_loc,
                                   num_segments=row_block,
                                   indices_are_sorted=True)

    out = jax.lax.map(block, jnp.arange(blocks))      # [B, row_block, K, F]
    return out.reshape(rows_padded, k, f)[:n]


@functools.partial(jax.jit, static_argnames=("row_block", "rate"))
def logits(weights, x, src, dst_local, edge_start, row_block: int = ROW_BLOCK,
           input_keep=None, edge_keep=None, rate: float = 0.0):
    """Logits [N, classes].  Evaluation mode unless keep masks are given:
    ``input_keep[l]`` [N, d_l] and ``edge_keep[l]`` [K_l, E] bool per
    layer, applied at rate ``rate``."""
    t = x.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        for i, (w, a_src, a_dst) in enumerate(weights):
            if input_keep is not None:
                t = jnp.where(input_keep[i], t / (1.0 - rate), 0.0)
            k, f = a_src.shape
            h = (t @ w).reshape(-1, k, f)
            out = attend(h, a_src, a_dst, src, dst_local, edge_start,
                         row_block,
                         None if edge_keep is None else edge_keep[i], rate)
            t = out.reshape(-1, k * f)
            if i != len(weights) - 1:
                t = jnp.where(t > 0, t, jnp.expm1(jnp.minimum(t, 0.0)))  # ELU
    return t


def loss(weights, x, src, dst_local, edge_start, label_ids, mask,
         row_block: int = ROW_BLOCK, input_keep=None, edge_keep=None,
         rate: float = 0.0):
    """Sum of cross-entropy over train rows (softmax_kernel.cu:19-33 gives
    softmax - onehot, masked, unnormalised: the gradient of this sum)."""
    z = logits(weights, x, src, dst_local, edge_start, row_block=row_block,
               input_keep=input_keep, edge_keep=edge_keep, rate=rate)
    logp = jax.nn.log_softmax(z, axis=-1)
    ce = -jnp.take_along_axis(logp, label_ids[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(mask == MASK_TRAIN, ce, 0.0))


def loss_and_grads(params: dict, dataset, layers, row_block: int = ROW_BLOCK,
                   edge_keep=None, input_keep=None, rate: float = 0.0):
    """(loss, {name: gradient}) of the reference on ``dataset`` at the
    trainer's ``params``.  Dropout off unless the program's own keep masks
    are handed in: ``edge_keep`` a list, per layer, of [K, E] bool (the
    coefficients kept, per head and in-edge in CSR order) and
    ``input_keep`` of [N, d] bool (the layer inputs kept), both applied at
    ``rate``; either may be None."""
    names = layer_names(params)
    src, dst_local, e0 = edge_blocks(dataset.graph, row_block)
    val, grads = jax.value_and_grad(loss)(
        ordered_weights(params), jnp.asarray(dataset.features), src,
        dst_local, e0, jnp.asarray(dataset.label_ids, jnp.int32),
        jnp.asarray(dataset.mask, jnp.int32), row_block,
        input_keep, edge_keep, rate)
    out = {}
    for name, (gw, gs, gd) in zip(names, grads):
        out.update({f"{name}_w": gw, f"{name}_asrc": gs, f"{name}_adst": gd})
    return val, out


def reference_logits(params: dict, dataset, layers, device=None,
                     row_block: int = ROW_BLOCK) -> np.ndarray:
    """Host copy of the reference's evaluation-mode logits for the
    trainer's ``params``."""
    src, dst_local, e0 = edge_blocks(dataset.graph, row_block)
    put = functools.partial(jax.device_put, device=device)
    out = logits([tuple(put(a) for a in layer)
                  for layer in ordered_weights(params)],
                 put(dataset.features), put(src), put(dst_local), put(e0),
                 row_block=row_block)
    return np.asarray(out)
